"""The least time the chip could take for one operation (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from the cell's
shapes by the configuration's cost functions) over the device's busy time
per operation, in percent. `params.cost` names the cost function and
`params.span` the benchmark span that marks one operation."""

from benchmark.harness import trace as tracing
from benchmark.harness.peaks import least_seconds


def read(run, params: dict):
    if run.reduction is None or run.peaks is None:
        return None
    spans = tracing.spans(run.reduction.trace, params["span"])
    busy = sum(run.reduction.busy_inside(s.start, s.end) for s in spans)
    if not spans or busy <= 0:
        return None
    rows = run.completed[0].rows
    cost = getattr(run.cost, params["cost"])(run.config, rows)
    least, bound = least_seconds(cost["flops"], cost["bytes"], run.peaks)
    per_operation = busy / len(spans)
    run.say(
        f"roofline ({params['span']}): {cost['flops']:.3e} FLOPs, {cost['bytes']:.3e} bytes, "
        f"{bound}-bound, least {least * 1e3:.2f} ms against {per_operation * 1e3:.2f} ms busy per operation"
    )
    return 100.0 * least / per_operation
