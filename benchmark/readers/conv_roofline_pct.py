"""A featurizer's share of its roofline: the least time the chip could
take for its work (the larger of operations over peak FLOP/s and bytes
over peak bytes/s, by `params.cost` of the configuration's cost file)
over the device self time under `params.scope` per operation, in percent.
The time is the one `scope_ms.py` reads from its probe trace (taken once
a run, whichever of the two readers asks first). Nothing to read, and
`None`, wherever that reader has nothing."""

from benchmark.harness.peaks import least_seconds


def read(run, params: dict):
    if run.peaks is None:
        return None
    scope_ms = run.bench.load_module("readers", "scope_ms.py")
    ms = scope_ms.read(run, {"span": params["span"], "scope": params["scope"]})
    if not ms:
        return None
    cost = getattr(run.cost, params["cost"])(run.config, run.completed[0].rows)
    least, bound = least_seconds(cost["flops"], cost["bytes"], run.peaks)
    run.say(
        f"roofline of {params['scope']} ({params['span']}): {cost['flops']:.3e} FLOPs, {cost['bytes']:.3e} bytes, "
        f"{bound}-bound, least {least * 1e3:.2f} ms against {ms:.2f} ms under the scope per operation"
    )
    return 100.0 * least / (ms / 1e3)
