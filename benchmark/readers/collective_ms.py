"""Device time one operation spends in collectives, in milliseconds: the
self time of the device's `all-reduce`, `all-gather`, `reduce-scatter`
and `collective-permute` events (their `-start` and `-done` halves
too) inside the traced window, mean over chips, per operation
(`params.span` names the benchmark span that marks one). 0.0 where the
device ran and none of its operations was a collective; `None` where
there is no device plane, the device did nothing, or there is no such
operation."""

from benchmark.harness import trace as tracing

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")


def opcode(name: str) -> str:
    """The opcode in an event's short name (`%all-reduce.3 all-reduce
    f32[8,128]`), or the name without its `%` where it has no such form."""
    parts = name.split(" ")
    if len(parts) >= 3 and parts[0].startswith("%"):
        return parts[1]
    return parts[0].lstrip("%")


def read(run, params: dict):
    r = run.reduction
    if r is None or not r.busy_by_chip or r.busy_s <= 0:
        return None
    operations = tracing.spans(r.trace, params["span"])
    if not operations:
        return None
    lo, hi = r.window
    per_chip = []
    for events in r.trace.device.values():
        inside = [e for e in events if e.end > lo and e.start < hi]
        per_chip.append(sum(
            ns for name, ns in tracing.self_times(inside).items()
            if opcode(name).startswith(COLLECTIVES)
        ))
    return sum(per_chip) / len(per_chip) / len(operations) / 1e6
