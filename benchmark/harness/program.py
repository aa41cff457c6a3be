"""What every configuration's adapter asks of the program in the same
way. With the adapters (`configs/<config>_sut.py`) and the runner's
compile-cache set-up, the only code of the benchmark that imports
keystone_tpu."""

from __future__ import annotations


def block_mapper(pipeline):
    """The fitted BlockLinearMapper inside a fitted pipeline's graph
    (fusion may have folded it into a fused chain's members)."""
    from keystone_tpu.ops.learning.block import BlockLinearMapper

    found = [
        m
        for op in pipeline.graph.operators.values()
        for m in getattr(op, "members", (op,))
        if isinstance(m, BlockLinearMapper)
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one BlockLinearMapper, found {len(found)}")
    return found[0]


def fit_health(pipeline) -> list[str]:
    """Ways a fit can go wrong without raising: the solver stepped down
    its degradation ladder, or the reliability layer recovered from
    something."""
    from keystone_tpu import reliability

    problems = []
    degradation = getattr(block_mapper(pipeline), "degradation", None)
    if degradation is not None:
        problems.append(f"the mapper reports degradation: {degradation}")
    events = reliability.get_recovery_log().summary()["events"]
    if events:
        problems.append(f"the recovery log is not empty: {events}")
    return problems
