"""Per-operator execution tracing, now backed by the unified span layer.

The reference's observability is (1) per-rule DOT logging
(reference: workflow/RuleExecutor.scala:42-49) and (2) the AutoCacheRule
profiler that eagerly executes scaled samples under ``System.nanoTime``
(reference: workflow/AutoCacheRule.scala:153-465). This module adds the
per-op timeline the reference lacked — and since the observability PR it
is a thin compatibility shim over :mod:`keystone_tpu.obs.spans`:
``trace()`` opens a real :class:`~keystone_tpu.obs.spans.TraceSession`
with a ``pipeline`` root span, each forced operator becomes a
``node:<label>`` child span (exportable as a Chrome trace via
``obs.export``), and node wall times land in the
``keystone_executor_node_seconds`` histogram. The legacy
:class:`PipelineTrace` view (``timings`` / ``report()``) is preserved so
existing callers and tests keep working unchanged.

Timing forces each operator's lazy result (and on accelerators blocks on a
scalar fetch) — tracing is a profiling mode, not a zero-cost observer;
laziness across operators is preserved apart from the forcing. The same
forcing applies under an ``obs.spans`` session that declares
``sync_timings=True`` (the default, e.g. the ``keystone-tpu profile``
CLI) even when no ``trace()`` shim is active; a ``sync_timings=False``
session — and a metrics-registry-only run with no session at all —
skips the per-node sync entirely, preserving async dispatch between
nodes (spans then carry ``synced=False``).

A plain run still has its ``node:<label>`` spans: the thunks of
``workflow/operators.py`` open one around each node's own work, and the
span layer's bridge (obs/spans.py) puts it into whatever profiler trace
is being taken. Where ``timed_execute`` opens the node's span itself (a
session, the cost observatory) the thunk's stands down, so a node appears
once in a trace either way.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, List, Optional

from ..obs import cost as _cost
from ..obs import names as _names
from ..obs import spans as _spans
from .operators import holding_node_span


@dataclass
class OpTiming:
    label: str
    seconds: float


@dataclass
class PipelineTrace:
    """Back-compat flat view of one traced run; ``session`` carries the
    underlying span session for callers that want the hierarchy."""

    timings: List[OpTiming] = field(default_factory=list)
    session: Optional[Any] = None  # obs.spans.TraceSession

    def record(self, label: str, seconds: float) -> None:
        self.timings.append(OpTiming(label, seconds))

    @property
    def total_seconds(self) -> float:
        return sum(t.seconds for t in self.timings)

    def report(self) -> str:
        """Pretty table, slowest first."""
        rows = sorted(self.timings, key=lambda t: -t.seconds)
        width = max([len("operator"), len("TOTAL")] + [len(t.label) for t in rows])
        lines = [f"{'operator':<{width}}  seconds"]
        for t in rows:
            lines.append(f"{t.label:<{width}}  {t.seconds:8.4f}")
        lines.append(f"{'TOTAL':<{width}}  {self.total_seconds:8.4f}")
        return "\n".join(lines)


_local = threading.local()


def current_trace() -> Optional[PipelineTrace]:
    return getattr(_local, "trace", None)


@contextmanager
def trace():
    """Context manager: trace all pipeline executions in this thread.

    >>> with trace() as t:
    ...     pipeline(data).get()
    >>> print(t.report())

    Also opens (or joins) an ``obs.spans`` tracing session with a
    ``pipeline`` root span, so ``t.session`` can be exported with
    ``obs.export.write_chrome_trace`` after the block.
    """
    prev = current_trace()
    tr = PipelineTrace()
    _local.trace = tr
    try:
        with _spans.tracing_session("pipeline") as session:
            tr.session = session
            with _spans.span("pipeline"):
                yield tr
    finally:
        _local.trace = prev


def _force(value: Any) -> None:
    """Force lazy/async results so timings measure real work.

    Datasets are unwrapped to their array pytree; device arrays are
    synced with block_until_ready."""
    data = getattr(value, "data", value)  # ArrayDataset → pytree
    try:
        import jax

        leaves = [
            l for l in jax.tree_util.tree_leaves(data) if hasattr(l, "dtype")
        ]
        # This IS the sync primitive: every call site gates it behind
        # the session's sync_timings (timed_execute's `if sync:`).
        jax.block_until_ready(leaves)  # keystone: allow-sync
    except Exception:
        pass


def _node_seconds_hist():
    return _names.metric(_names.NODE_SECONDS)


def timed_execute(op, deps, execute=None):
    """Execute ``op`` under the active trace/span session (or plainly if
    neither is active). ``execute`` stands in for ``op.execute`` where
    the executor has something to decide before the operator's own thunk
    runs (a row chain: workflow/executor.py); the span, the timing and
    the ledger entry are ``op``'s either way.

    The blocking device sync (:func:`_force`) runs only when someone
    actually needs real per-node timings — an active ``trace()`` shim or
    a span session with ``sync_timings`` (the default). A metrics-only
    run (no session) or a ``sync_timings=False`` session keeps async
    dispatch between nodes: spans/histograms then record dispatch time,
    flagged ``synced=False`` so a reader never mistakes it for work time.

    A fused chain (workflow/fusion.py) appears as ONE ``node:Fused[...]``
    span carrying the member labels as an attribute — the per-member
    spans collapse along with the dispatches.

    With the cost observatory enabled (obs/cost.py,
    ``KEYSTONE_COST_OBS``) each forcing additionally runs inside a
    harvest frame: operators note their jitted computations into it and
    the frame finalizes into a perf-ledger entry — predicted cost,
    measured wall, flop/byte facts, roofline placement — AFTER the wall
    measurement, so first-shape harvesting never inflates node timings.
    The entry's lowering digest lands on the span
    (``lowering_digest``), joining spans to ProfileStore keys
    deterministically — the fused-member-names attribute alone never
    identified the executable.
    """
    tr = current_trace()
    session = _spans.active_session()
    expression = (execute or op.execute)(deps)
    cost_on = _cost.cost_observatory_enabled()
    if tr is None and session is None and not cost_on:
        return expression
    # Ledger-only runs (observatory on, no trace/session) keep async
    # dispatch: seconds then measures dispatch, marked synced=False so a
    # reader never mistakes it for work time.
    sync = tr is not None or (
        session is not None and getattr(session, "sync_timings", True)
    )
    label = str(getattr(op, "label", type(op).__name__))
    members = getattr(op, "member_labels", None)
    partition = getattr(op, "partition", None)
    frame = _cost.push_frame(label) if cost_on else None
    with _spans.span(f"node:{label}", op=type(op).__name__) as sp:
        if members is not None:
            sp.set_attribute("fused_members", ",".join(members))
        if partition is not None and getattr(partition, "eligible", False):
            # The partitioner's pinned decision, on the node's own span:
            # a sharded fit is identifiable in any trace without
            # cross-referencing the plan report (docs/PARTITIONING.md).
            sp.set_attribute(
                "mesh_shape", "x".join(str(s) for s in partition.mesh_shape)
            )
            sp.set_attribute("partition_spec", partition.spec)
            sp.set_attribute(
                "model_shards",
                int(getattr(partition, "model_shards", 1) or 1),
            )
        try:
            if frame is not None:
                # Compile events during the forcing mark the wall as
                # cold: compile-inflated timings never anchor or score
                # the drift sentinel (obs/cost.py).
                from ..utils.compilation_cache import compile_count

                compiles_before = compile_count()
            # This span is the node's: the thunk's own `node_span` stands
            # down while it is open (one span a node, session or not).
            with holding_node_span(op):
                start = time.perf_counter()
                value = expression.get()
                if sync:
                    _force(value)
                seconds = time.perf_counter() - start
        finally:
            if frame is not None:
                frame.compiles = compile_count() - compiles_before
                _cost.pop_frame(frame)
        sp.set_attribute("seconds", round(seconds, 6))
        if not sync:
            sp.set_attribute("synced", False)
    if frame is not None:
        # Post-measurement: resolves noted computations to flop/byte
        # facts (jit trace-cache hits — zero backend compiles), joins
        # the plan's prediction, drift-scores, lands the ledger entry,
        # and back-fills the span's cost attributes.
        _cost.finalize_node(label, seconds, sync, op=op, span=sp, frame=frame)
    if tr is not None:
        tr.record(label, seconds)
    if tr is not None or session is not None:
        _node_seconds_hist().observe(seconds, op=label)
    return expression
