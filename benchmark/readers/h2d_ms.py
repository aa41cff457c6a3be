"""How long a host batch was on its way to the device inside each
operation, and how much of that the device stood still for: median over
operations, in milliseconds.

The program closes its `ks:h2d` span when an upload is ENQUEUED; while a
trace is being taken it also holds a `ks:h2d:transfer` span, on a thread
of its own, from there to the arrival of the upload's last byte
(`keystone_tpu/obs/device.py::watch_transfer`). The union of both kinds
of span, clipped to one operation (the benchmark's span
`bench:<params.span>`), is "a host batch is in flight" for that operation.
`params.what` chooses what is read of it:

- `transfer`: the length of that union: enqueue to arrival, whatever
  else went on ("time busy" of the bus);
- `exposed`: the busiest chip's idle time (as `host_idle_ms` takes it:
  what its busy intervals leave of the operation) that falls inside the
  union: what of the transfer the device waited through ("time work
  waited for it"). Never more than `transfer`, nor than the operation's
  idle time, which is what `host_gap_ms` takes its median of.

The host events are found by NAME: the harness keeps no thread and no
stats of them, and needs neither here. It keeps host events of 0.5 ms or
more, which is why the program watches no upload that would be shorter.

Nothing to read, and `None`: no device plane (a CPU run), no such
operation, or no `ks:h2d:transfer` span at all (a commit from before the
watcher: the enqueue alone says nothing of the copy).
"""

import statistics

from benchmark.harness import trace as tracing

ENQUEUE = "ks:h2d"
TRANSFER = "ks:h2d:transfer"


def in_flight(host):
    """The disjoint, sorted intervals in which an upload was enqueued and
    had not arrived: the union of both kinds of span among `host`."""
    return tracing.union((e.start, e.end) for e in host if e.name in (ENQUEUE, TRANSFER))


def exposed_ns(busy, flight, operation):
    """Nanoseconds of `flight` (disjoint, sorted, inside `operation`) in
    which `busy` (one chip's disjoint, sorted busy intervals) ran nothing."""
    idle = tracing.gaps(busy, operation.start, operation.end)
    return sum(tracing.total(tracing.clip(idle, lo, hi)) for lo, hi in flight)


def read(run, params: dict):
    r = run.reduction
    if r is None or not r.busy_by_chip:
        return None
    operations = tracing.spans(r.trace, params["span"])
    if not operations or not any(e.name == TRANSFER for e in r.trace.host):
        return None
    busiest = max(r.busy_by_chip.values(), key=tracing.total)
    every, what = in_flight(r.trace.host), params["what"]
    values = []
    for operation in operations:
        flight = tracing.clip(every, operation.start, operation.end)
        values.append(
            tracing.total(flight) if what == "transfer" else exposed_ns(busiest, flight, operation)
        )
    value = statistics.median(values) / 1e6
    meaning = {"transfer": "a host batch in flight", "exposed": "the device idle while one was in flight"}[what]
    run.say(f"h2d ({params['span']}): {meaning} median {value:.3f} ms over {len(values)} operations")
    return value
