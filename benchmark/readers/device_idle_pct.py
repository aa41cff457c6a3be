"""1 - (union of the device's operation intervals over the traced
window), in percent, averaged over the chips used."""


def read(run, params: dict):
    r = run.reduction
    if r is None or not r.busy_by_chip or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
