"""How much longer the busiest chip worked than the chips did on
average: its busy time inside the traced window over the mean over
chips, less one, in percent. 0.0 on one chip; `None` where there is no
device plane or the device did nothing."""

from benchmark.harness import trace as tracing


def read(run, params: dict):
    r = run.reduction
    if r is None or not r.busy_by_chip:
        return None
    busy = [tracing.total(intervals) for intervals in r.busy_by_chip.values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) / mean - 1.0)
