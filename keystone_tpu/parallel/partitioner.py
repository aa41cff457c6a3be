"""First-class multi-device partitioning: the runtime face of the mesh.

The reference derives its data layout from the Spark cluster view —
``getExecutorStorageStatus`` machine counts decide partition counts and
every solver treeReduces per-partition Grams (reference:
nodes/learning/LeastSquaresEstimator.scala:70-75, SURVEY §2.10). The TPU
equivalent lived in two disconnected places: the in-core solvers shard
through ``parallel/linalg.py`` over the ambient :func:`~keystone_tpu.
parallel.mesh.get_mesh`, while the streaming engine and the serving
layer stayed single-device and the multichip evidence came from bespoke
dryrun scripts (``__graft_entry__.dryrun_multichip``).

This module promotes that rehearsal into a planned, explainable runtime
layer:

- :class:`Partitioner` decides, per plan node, whether and how the
  example (row) dimension shards over the active mesh's row axes
  (``data``, plus ``replica`` on hybrid meshes — mesh.py conventions).
  Every decision — eligible or not — is a :class:`PartitionDecision`
  carrying the mesh shape, the rendered row ``PartitionSpec``, and a
  stable reason key, recorded into the plan and surfaced by
  ``keystone-tpu check --pipeline``, the BENCH json, and the
  ``keystone_partition_*`` metrics.
- The optimizer consults it as the LAST rule batch
  (``workflow/optimize.py::PartitionPlanRule``): eligible estimator fits
  pin the decided mesh, eligible ``StreamingFitOperator`` nodes run the
  sharded chunk plan (each device ingests its row slice; the O(d²)
  sufficient statistics are reduced across the mesh once, at finish),
  and serving's bucketed ``compiled_apply`` places batch rows
  ``NamedSharding``-sharded onto the warmed executables.
- Identical pipeline code runs unchanged on 1 and N devices: a
  single-shard mesh (or any failed gate) is a recorded fallback to the
  existing single-device path, never an error.

Env knobs (all via envknobs.py — no raw env reads, KV501):

- ``KEYSTONE_PARTITION=off`` disables planning (decisions record
  ``disabled``); :func:`set_partition_enabled` / :func:`partition_disabled`
  are the programmatic/tri-state equivalents (mirrors fusion/streaming).
- ``KEYSTONE_PARTITION_MIN_ROWS`` — minimum LOGICAL rows per shard for a
  fit to be worth partition-managing (default 2; raise it to keep small
  fits off the partition-managed path).
- ``KEYSTONE_PARTITION_MODEL_SHARDS`` — feature-axis (``model``) shards
  for wide Gram/BCD/sketch fits (0 = auto from the ambient mesh's model
  axis; >1 reshapes the mesh into (devices/p, p)).
- ``KEYSTONE_PARTITION_MIN_WIDTH`` — minimum featurized columns per
  model shard (default 512) below which a requested model axis records
  ``below-width-floor`` and the layout stays row-only.

See docs/PARTITIONING.md for the axis conventions, the full eligibility
and fallback matrix, and the collective-bytes accounting model.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..envknobs import env_disabled, env_int
from .mesh import (
    MODEL_AXIS,
    REPLICA_AXIS,
    Mesh,
    get_mesh,
    model_axis_size,
    model_mesh,
    row_axes,
    row_shard_count,
)

# Stable reason keys (the fallback matrix in docs/PARTITIONING.md; the
# verifier's KV203 diagnostics carry these verbatim).
SHARDED = "sharded"
R_DISABLED = "disabled"
R_SINGLE_SHARD = "single-shard-mesh"
R_UNKNOWN_ROWS = "unknown-rows"
R_BELOW_FLOOR = "below-rows-floor"
R_CHUNK_TOO_NARROW = "chunk-below-shard-count"
R_BUCKETS_INDIVISIBLE = "buckets-indivisible"
R_OPT_OUT = "operator-opt-out"
# Model-axis (feature-sharding) refusals: the decision may still shard
# rows — these land in ``PartitionDecision.model_fallback`` and the
# keystone_partition_fallbacks metric, never in ``reason`` unless the
# whole decision is ineligible.
R_MODEL_INDIVISIBLE = "model-axis-indivisible"
R_BELOW_WIDTH_FLOOR = "below-width-floor"

#: Every reason key a decision (or its model axis) can carry — the
#: docs-sync surface: each must appear in docs/PARTITIONING.md's
#: eligibility matrix (tests/workflow/test_verify.py docs-sync).
ALL_REASON_KEYS = (
    SHARDED,
    R_DISABLED,
    R_SINGLE_SHARD,
    R_UNKNOWN_ROWS,
    R_BELOW_FLOOR,
    R_CHUNK_TOO_NARROW,
    R_BUCKETS_INDIVISIBLE,
    R_OPT_OUT,
    R_MODEL_INDIVISIBLE,
    R_BELOW_WIDTH_FLOOR,
)


# ------------------------------------------------------------------ enablement

_enabled: Optional[bool] = None
_enabled_lock = threading.Lock()


def partition_enabled() -> bool:
    if _enabled is not None:
        return _enabled
    return not env_disabled("KEYSTONE_PARTITION")


def set_partition_enabled(value: Optional[bool]) -> None:
    """Force partitioning on/off process-wide; ``None`` restores the env
    default (same tri-state contract as fusion/streaming)."""
    global _enabled
    with _enabled_lock:
        _enabled = value


@contextlib.contextmanager
def partition_disabled():
    """Scoped off-switch — parity tests build the single-device reference
    here, exactly like ``streaming_disabled()``."""
    global _enabled
    with _enabled_lock:
        prev = _enabled
        _enabled = False
    try:
        yield
    finally:
        with _enabled_lock:
            _enabled = prev


def partition_min_rows_per_shard() -> int:
    """Minimum logical rows each shard must receive for a fit/stream plan
    to shard (``KEYSTONE_PARTITION_MIN_ROWS``, default 2). Collective
    latency is per-dispatch; a shard holding one row pays it for nothing."""
    return max(1, env_int("KEYSTONE_PARTITION_MIN_ROWS", 2))


def partition_model_shards() -> int:
    """Requested feature-axis (``model``) shards for wide Gram/BCD/sketch
    fits (``KEYSTONE_PARTITION_MODEL_SHARDS``). 0 (the default) = auto:
    adopt the ambient mesh's ``model`` axis when it has one, else stay
    row-only. Values > 1 ask the partitioner to RESHAPE the mesh into
    (devices/p, p) — refused per node with ``model-axis-indivisible`` /
    ``below-width-floor`` when the device count or featurized width
    doesn't cooperate (docs/PARTITIONING.md "2-D layouts")."""
    return max(0, env_int("KEYSTONE_PARTITION_MODEL_SHARDS", 0))


def partition_min_width_per_shard() -> int:
    """Minimum featurized columns each model shard must receive
    (``KEYSTONE_PARTITION_MIN_WIDTH``, default 512). Below this the
    feature blocks are too small for the sharded state to matter and the
    finish-time concat overhead dominates — the decision records
    ``below-width-floor`` and keeps the row-only layout."""
    return max(1, env_int("KEYSTONE_PARTITION_MIN_WIDTH", 512))


# -------------------------------------------------------------------- decision


@dataclass
class PartitionDecision:
    """One node's partitioning outcome — the explainable record the plan,
    ``check --pipeline``, and BENCH json all surface.

    ``eligible`` decisions carry the mesh they shard over; fallbacks
    carry the reason key from the matrix above. Never an error: an
    ineligible node simply runs the existing single-device path.
    """

    kind: str  # "fit" | "fit_stream" | "serve"
    node: str  # operator label
    eligible: bool
    reason: str  # SHARDED, or the fallback reason key
    shards: int = 1  # ROW shards (data × replica axes)
    model_shards: int = 1  # feature-axis shards (1 = row-only layout)
    mesh_axes: Tuple[str, ...] = ()  # row axes — the chunk/batch spec
    mesh_shape: Tuple[int, ...] = ()
    spec: str = ""  # rendered row (× feature) PartitionSpec
    detail: str = ""
    model_fallback: str = ""  # why the MODEL axis was refused/demoted
    chunk_rows: Optional[int] = None  # fit_stream: rounded to row shards
    mesh: Optional[Mesh] = field(default=None, repr=False)

    @property
    def carry_axes(self) -> Tuple[str, ...]:
        """Axes the stacked streaming carry shards over: row axes, plus
        ``model`` when the layout is 2-D (the carry's leading block axis
        enumerates all ``shards × model_shards`` devices row-major)."""
        if self.model_shards > 1:
            return self.mesh_axes + (MODEL_AXIS,)
        return self.mesh_axes

    @property
    def total_shards(self) -> int:
        """Device blocks in the stacked carry: row × feature shards."""
        return self.shards * self.model_shards

    def to_json(self) -> Dict[str, Any]:
        out = {
            "kind": self.kind,
            "node": self.node,
            "eligible": self.eligible,
            "reason": self.reason,
            "shards": self.shards,
            "model_shards": self.model_shards,
            "mesh_axes": list(self.mesh_axes),
            "mesh_shape": list(self.mesh_shape),
            "spec": self.spec,
        }
        if self.detail:
            out["detail"] = self.detail
        if self.model_fallback:
            out["model_fallback"] = self.model_fallback
        if self.chunk_rows is not None:
            out["chunk_rows"] = self.chunk_rows
        return out


# -------------------------------------------------------------------- report

_report_lock = threading.Lock()
_last_report: List[PartitionDecision] = []
_report_generation = 0


def reset_partition_report() -> None:
    """Start a fresh decision list (PartitionPlanRule calls this per
    optimizer run, so the report always describes the LAST plan). Bumps
    the generation counter so per-plan consumers (GraphExecutor) can
    tell whether THEIR optimize actually ran a partition batch."""
    global _last_report, _report_generation
    with _report_lock:
        _last_report = []
        _report_generation += 1


def partition_report_generation() -> int:
    """Monotonic counter of report resets — compare before/after an
    optimizer run to know whether the current report belongs to it."""
    with _report_lock:
        return _report_generation


def record_decision(
    decision: PartitionDecision, to_report: bool = True
) -> PartitionDecision:
    """Publish the metric family and (by default) append to the plan
    report. Serving attaches pass ``to_report=False``: the report is
    documented as "the last plan's decisions" and only the planner's
    batch resets it, so out-of-plan decisions must not leak into it."""
    if to_report:
        with _report_lock:
            _last_report.append(decision)
    from ..obs import names as _names

    _names.metric(_names.PARTITION_DECISIONS).inc(
        kind=decision.kind, eligible="1" if decision.eligible else "0"
    )
    if decision.eligible:
        _names.metric(_names.PARTITION_SHARDS).set(
            decision.shards, kind=decision.kind, axis="data"
        )
        if decision.model_shards > 1:
            _names.metric(_names.PARTITION_SHARDS).set(
                decision.model_shards, kind=decision.kind, axis="model"
            )
    else:
        _names.metric(_names.PARTITION_FALLBACKS).inc(reason=decision.reason)
    if decision.model_fallback and decision.model_fallback != decision.reason:
        # A row-sharded decision whose MODEL axis was refused still counts
        # a fallback under the model reason — the observable trace of "why
        # is this wide fit not feature-sharded".
        _names.metric(_names.PARTITION_FALLBACKS).inc(
            reason=decision.model_fallback
        )
    return decision


def last_partition_report() -> List[PartitionDecision]:
    """Decisions of the most recent partition-planned optimizer run."""
    with _report_lock:
        return list(_last_report)


def record_collective_bytes(nbytes: int, axis: str = "data") -> None:
    """Account payload bytes entering a partitioner-managed cross-device
    reduction (the finish-time reductions of streamed sufficient stats),
    labelled by the mesh axis they cross. Counted as per-device-payload ×
    (axis shards−1): the bytes that must cross at least one device
    boundary in any reduction topology on that axis — ``data`` carries
    the row-partial sums, ``model`` the feature-block gather.
    Deterministic for a pinned plan, so bench-diff exact-gates both."""
    if nbytes <= 0:
        return
    from ..obs import names as _names

    _names.metric(_names.PARTITION_COLLECTIVE_BYTES).inc(int(nbytes), axis=axis)


def reduction_collective_bytes(
    leaf_nbytes: Sequence[int],
    layout: Sequence[Optional[int]],
    shards: int,
    model_shards: int,
) -> Tuple[int, int]:
    """``(data, model)`` payload bytes of the finish-time reduction of a
    stacked streaming carry, plan-pure: from the REDUCED leaves' byte
    counts, their layout (the axis a leaf's feature blocks split along,
    None for a leaf every block holds whole) and the shard counts. With
    the bytes split into feature (B_f, sharded over model) and remainder
    (B_r, replicated), each device block holds B_f/p_m + B_r. The
    data-axis sum moves one block per non-root row shard per model
    column; the model-axis reassembly moves one block per non-root model
    column. At p_m = 1 the data term is the historical
    bytes × (shards − 1)."""
    b_f = sum(n for n, ax in zip(leaf_nbytes, layout) if ax is not None)
    b_r = sum(leaf_nbytes) - b_f
    data = (b_f + model_shards * b_r) * (shards - 1)
    model = (b_f // model_shards + b_r) * (model_shards - 1)
    return data, model


def record_imbalance(kind: str, logical_rows: int, padded_rows: int) -> None:
    """Per-device imbalance: the fraction of sharded rows that are pad
    (devices holding pad rows do the same FLOPs for no useful output)."""
    if padded_rows <= 0:
        return
    from ..obs import names as _names

    frac = max(0.0, 1.0 - logical_rows / padded_rows)
    _names.metric(_names.PARTITION_IMBALANCE).set(frac, kind=kind)


# ----------------------------------------------------------------- partitioner


class Partitioner:
    """Decides row-sharding over the active mesh for fit, fit_stream,
    and serving plans. One instance per planning pass; all decisions go
    through :func:`record_decision` so the plan stays explainable."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        min_rows_per_shard: Optional[int] = None,
        model_shards: Optional[int] = None,
    ):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.min_rows = (
            min_rows_per_shard
            if min_rows_per_shard is not None
            else partition_min_rows_per_shard()
        )
        self.axes = row_axes(self.mesh)
        self.shards = row_shard_count(self.mesh)
        req = model_shards if model_shards is not None else partition_model_shards()
        if req == 0:  # auto: adopt the ambient mesh's model axis
            req = model_axis_size(self.mesh)
        self.requested_model = max(1, int(req))
        self.min_width = partition_min_width_per_shard()

    # ------------------------------------------------------------- rendering
    def spec_str(self, axes: Tuple[str, ...], model_shards: int = 1) -> str:
        row = f"P(({', '.join(repr(a) for a in axes)},), …)"
        if model_shards > 1:
            return row + f" × P(…, ({MODEL_AXIS!r},))"
        return row

    def _base(
        self,
        kind: str,
        node: str,
        eligible: bool,
        reason: str,
        mesh: Optional[Mesh] = None,
        axes: Optional[Tuple[str, ...]] = None,
        shards: Optional[int] = None,
        model_shards: int = 1,
        **kw,
    ):
        mesh = mesh if mesh is not None else self.mesh
        axes = axes if axes is not None else self.axes
        shards = shards if shards is not None else self.shards
        return PartitionDecision(
            kind=kind,
            node=node,
            eligible=eligible,
            reason=reason,
            shards=shards if eligible else 1,
            model_shards=model_shards if eligible else 1,
            mesh_axes=axes if eligible else (),
            mesh_shape=tuple(mesh.shape[a] for a in mesh.shape)
            if eligible
            else (),
            spec=self.spec_str(axes, model_shards) if eligible else "",
            mesh=mesh if eligible else None,
            **kw,
        )

    def _gate(self, kind: str, node: str) -> Optional[PartitionDecision]:
        if not partition_enabled():
            return self._base(kind, node, False, R_DISABLED)
        if self.shards <= 1:
            return self._base(
                kind, node, False, R_SINGLE_SHARD,
                detail=f"mesh has {self.shards} row shard",
            )
        return None

    # ------------------------------------------------------------ model axis
    def _model_plan(
        self, width: Optional[int], model_ok: bool, optimistic: bool
    ) -> Tuple[int, str, str]:
        """How many feature-axis shards this node gets: ``(model_shards,
        fallback_reason, detail)``. ``model_shards == 1`` with an empty
        reason means "nothing requested / operator can't ride it" — not
        a recorded fallback. ``optimistic`` (streams) grants the request
        on unknown width; the fold re-validates against the real
        featurized width and demotes via :func:`demote_model_axis`."""
        req = self.requested_model
        if req <= 1 or not model_ok:
            return 1, "", ""
        total = int(self.mesh.devices.size)
        if req > total or total % req != 0:
            return 1, R_MODEL_INDIVISIBLE, (
                f"{req} model shards do not divide {total} devices"
            )
        if REPLICA_AXIS in self.mesh.shape and model_axis_size(self.mesh) != req:
            return 1, R_MODEL_INDIVISIBLE, (
                "hybrid (replica) mesh carries no model axis to reshape"
            )
        if width is None or width < 0:
            if optimistic:
                return req, "", ""
            return 1, R_BELOW_WIDTH_FLOOR, (
                "featurized width unknown at plan time"
            )
        if width % req != 0:
            return 1, R_MODEL_INDIVISIBLE, (
                f"width {width} not divisible by {req} model shards"
            )
        if width < req * self.min_width:
            return 1, R_BELOW_WIDTH_FLOOR, (
                f"width {width} < {req} shards × {self.min_width} "
                "min cols/shard"
            )
        return req, "", ""

    def _layout(
        self, width: Optional[int], model_ok: bool, optimistic: bool
    ) -> Tuple[Mesh, Tuple[str, ...], int, int, str, str]:
        """The (mesh, row_axes, row_shards, model_shards, model_fallback,
        model_detail) layout for a fit/stream decision. A granted model
        plan reshapes the ambient devices into the cached ``(data,
        model)`` mesh (identity-stable — jit caches key on mesh id)."""
        p_m, mfall, mdetail = self._model_plan(width, model_ok, optimistic)
        if p_m > 1:
            mesh = (
                self.mesh
                if model_axis_size(self.mesh) == p_m
                else model_mesh(self.mesh, p_m)
            )
            return mesh, row_axes(mesh), row_shard_count(mesh), p_m, mfall, mdetail
        return self.mesh, self.axes, self.shards, 1, mfall, mdetail

    @staticmethod
    def _emit(record: bool, decision: PartitionDecision) -> PartitionDecision:
        """Record into the plan report + metrics (the planning path), or
        return the decision un-recorded (the verifier derives diagnostics
        without mutating the last plan's report)."""
        return record_decision(decision) if record else decision

    # -------------------------------------------------------------- decisions
    def decide_fit(
        self,
        node: str,
        rows: Optional[int],
        record: bool = True,
        opt_out: bool = False,
        width: Optional[int] = None,
        model_ok: bool = False,
    ) -> PartitionDecision:
        """In-core estimator fit: rows shard over the row axes, Gram/AᵀA
        partials psummed across shards (parallel/linalg.py); when the
        operator rides the model axis (``model_ok``) and the featurized
        ``width`` clears the floor, the feature dimension additionally
        blocks across ``model`` (block_coordinate_descent_2d). Needs a
        known row count with at least ``min_rows`` logical rows/shard."""
        if not partition_enabled():
            return self._emit(record, self._base("fit", node, False, R_DISABLED))
        if opt_out:
            return self._emit(
                record, self._base("fit", node, False, R_OPT_OUT)
            )
        mesh, axes, p_d, p_m, mfall, mdetail = self._layout(
            width, model_ok, optimistic=False
        )
        if p_d <= 1 and p_m <= 1:
            return self._emit(record,
                self._base(
                    "fit", node, False, R_SINGLE_SHARD,
                    detail=f"mesh has {self.shards} row shard",
                    model_fallback=mfall,
                )
            )
        if rows is None or rows < 0:
            return self._emit(record,
                self._base("fit", node, False, R_UNKNOWN_ROWS,
                           model_fallback=mfall)
            )
        if rows < p_d * self.min_rows:
            return self._emit(record,
                self._base(
                    "fit", node, False, R_BELOW_FLOOR,
                    detail=f"{rows} rows < {p_d} shards × "
                    f"{self.min_rows} min rows/shard",
                    model_fallback=mfall,
                )
            )
        return self._emit(record,
            self._base(
                "fit", node, True, SHARDED,
                mesh=mesh, axes=axes, shards=p_d, model_shards=p_m,
                model_fallback=mfall, detail=mdetail,
            )
        )

    def decide_stream(
        self,
        node: str,
        chunk_rows: int,
        rows: Optional[int] = None,
        record: bool = True,
        opt_out: bool = False,
        width: Optional[int] = None,
        model_ok: bool = False,
    ) -> PartitionDecision:
        """Streamed fit: every chunk splits data-parallel across the row
        axes (chunk_rows rounds UP to a row-shard multiple so the one
        compiled chunk shape divides evenly); per-device carries hold
        unreduced partial statistics, reduced once at finish — rows
        summed across ``data``, feature blocks concatenated across
        ``model`` when the layout is 2-D. Unknown width grants the model
        axis optimistically; the fold demotes against the real
        featurized width (:func:`demote_model_axis`)."""
        if not partition_enabled():
            return self._emit(
                record, self._base("fit_stream", node, False, R_DISABLED)
            )
        if opt_out:
            return self._emit(
                record, self._base("fit_stream", node, False, R_OPT_OUT)
            )
        mesh, axes, p_d, p_m, mfall, mdetail = self._layout(
            width, model_ok, optimistic=True
        )
        if p_d <= 1 and p_m <= 1:
            return self._emit(record,
                self._base(
                    "fit_stream", node, False, R_SINGLE_SHARD,
                    detail=f"mesh has {self.shards} row shard",
                    model_fallback=mfall,
                )
            )
        if chunk_rows < p_d:
            return self._emit(record,
                self._base(
                    "fit_stream", node, False, R_CHUNK_TOO_NARROW,
                    detail=f"chunk_rows {chunk_rows} < {p_d} shards",
                    model_fallback=mfall,
                )
            )
        if rows is not None and 0 <= rows < p_d * self.min_rows:
            return self._emit(record,
                self._base(
                    "fit_stream", node, False, R_BELOW_FLOOR,
                    detail=f"{rows} rows < {p_d} shards × "
                    f"{self.min_rows} min rows/shard",
                    model_fallback=mfall,
                )
            )
        rounded = -(-chunk_rows // p_d) * p_d
        return self._emit(record,
            self._base(
                "fit_stream", node, True, SHARDED, chunk_rows=rounded,
                mesh=mesh, axes=axes, shards=p_d, model_shards=p_m,
                model_fallback=mfall, detail=mdetail,
            )
        )

    def decide_serve(
        self, node: str, buckets: Sequence[int], record: bool = True
    ) -> PartitionDecision:
        """Bucketed serving: a batch padded to bucket b shards its rows
        across the mesh when b divides evenly; smaller/indivisible
        buckets keep default placement (each bucket's layout is fixed,
        so warmup covers exactly the layouts steady state replays —
        zero steady-state compiles preserved). Eligible when at least
        one bucket shards."""
        gated = self._gate("serve", node)
        if gated is not None:
            return self._emit(record, gated)
        divisible = sorted(
            {int(b) for b in buckets if int(b) >= self.shards and int(b) % self.shards == 0}
        )
        if not divisible:
            return self._emit(record, 
                self._base(
                    "serve", node, False, R_BUCKETS_INDIVISIBLE,
                    detail=f"no bucket in {sorted(set(map(int, buckets)))} is a "
                    f"multiple of {self.shards} shards",
                )
            )
        return self._emit(record, 
            self._base(
                "serve", node, True, SHARDED,
                detail=f"sharded buckets: {divisible}",
            )
        )


# ------------------------------------------------------------------ consumers


def demote_model_axis(
    decision: PartitionDecision, reason: str, detail: str = ""
) -> PartitionDecision:
    """Runtime demotion of an optimistically-granted model axis (the fold
    discovers the REAL featurized width, or a step function without the
    blocked protocol). Keeps the 2-D mesh — ``P(('data',), …)`` on it
    simply replicates over ``model``, so the chunk geometry and the armed
    durable cursor stay valid — and drops ``model_shards`` to 1. If the
    row axis alone cannot shard (a 1×N mesh), the decision turns
    ineligible and the stream runs the single-device path. Counted in
    keystone_partition_fallbacks under the model reason either way."""
    from ..obs import names as _names

    _names.metric(_names.PARTITION_FALLBACKS).inc(reason=reason)
    demoted = dataclasses.replace(
        decision,
        model_shards=1,
        model_fallback=reason,
        spec=f"P(({', '.join(repr(a) for a in decision.mesh_axes)},), …)",
        detail=detail or decision.detail,
    )
    if demoted.shards <= 1:
        demoted = dataclasses.replace(
            demoted,
            eligible=False,
            reason=reason,
            shards=1,
            mesh_axes=(),
            mesh_shape=(),
            spec="",
            mesh=None,
        )
    return demoted


def fit_mesh(op: Any) -> Mesh:
    """The mesh an estimator fit should shard over: the partitioner's
    pinned decision when the plan carries one, else the ambient mesh.
    An in-core fit WITHOUT an eligible pin (direct est.fit() outside a
    plan, a fallback decision, KEYSTONE_PARTITION=off) keeps the legacy
    ambient-mesh behavior the solvers have always had — a fit fallback
    means "not partition-managed", NOT "single-device" (the stream and
    serve kinds, whose sharding the partitioner fully owns, genuinely
    run single-device on fallback)."""
    decision = getattr(op, "partition", None)
    if (
        decision is not None
        and getattr(decision, "eligible", False)
        and decision.mesh is not None
    ):
        return decision.mesh
    return get_mesh()


def shard_rows(decision: Optional[PartitionDecision], tree: Any) -> Any:
    """Place a pytree of host/device arrays with dim 0 sharded per the
    decision — the serving-batch placement primitive. Leaves whose row
    count does not divide the shard count come back untouched (bucket
    layouts must be deterministic, never half-sharded)."""
    if decision is None or not decision.eligible or decision.mesh is None:
        return tree
    import jax

    sharding = NamedShardingCache.get(decision.mesh, decision.mesh_axes)

    def place(a):
        rows = getattr(a, "shape", (0,))[0] if getattr(a, "ndim", 0) else 0
        if rows < decision.shards or rows % decision.shards != 0:
            return a
        return jax.device_put(a, sharding)

    return jax.tree_util.tree_map(place, tree)


def attach_serving_partition(
    model: Any, buckets: Sequence[int], name: str = "serve"
) -> Optional[PartitionDecision]:
    """Decide and install row-sharding for a served model's bucketed
    ``compiled_apply`` path (serving/server.py warmup and
    serving/registry.py both call this, so warmed and steady-state
    layouts are decided ONCE and identically — the zero-steady-state-
    compile guarantee extends to the sharded path).

    Returns the recorded decision; ``None`` when the model has no
    ``compiled_apply`` handle (checkpointed bare transformers serve
    through ``batch_transform`` on default placement)."""
    compiled = getattr(model, "compiled_apply", None)
    if not callable(compiled):
        return None
    label = str(getattr(model, "label", name))
    decision = Partitioner().decide_serve(label, buckets, record=False)
    handle = compiled()
    installed = handle.partition
    previous = getattr(handle, "_serve_decision", None)
    if installed is not None and (
        installed.shards != decision.shards
        or installed.mesh is not decision.mesh
    ):
        # First attach wins: the handle is shared by every server over
        # this pipeline ("all servers applying this fitted pipeline
        # share one handle"), and its installed layout is what earlier
        # warmups compiled. Re-deciding differently here (another
        # bucket set, another mesh) would hand steady-state batches
        # layouts nobody warmed — the steady-state-recompile hazard.
        import logging

        logging.getLogger(__name__).warning(
            "serving partition for %s already installed (%s shards); "
            "keeping it over the conflicting new decision (%s, %s shards)",
            label, installed.shards, decision.reason, decision.shards,
        )
        return installed
    if (
        previous is None
        or previous.eligible != decision.eligible
        or previous.shards != decision.shards
        or previous.mesh is not decision.mesh
    ):
        # Count DECISIONS, not attaches: an idempotent re-attach (every
        # warmup re-derives the same contract) must not drift the
        # keystone_partition_* counters away from decision-count.
        record_decision(decision, to_report=False)
    handle._serve_decision = decision
    if decision.eligible:
        handle.partition = decision
    return decision


class NamedShardingCache:
    """One NamedSharding per (mesh, axes) — device_put sharding objects
    compare by identity fast-path, so reusing them keeps the serving hot
    path cheap. LRU-bounded: each entry strongly references its mesh
    (so a cached id can never be a stale reuse), and processes that
    rebuild meshes per reconfiguration must not pin them all forever."""

    _MAX = 32
    _cache = None  # OrderedDict[(id(mesh), axes) -> NamedSharding]
    _lock = threading.Lock()

    @classmethod
    def get(cls, mesh: Mesh, axes: Tuple[str, ...]):
        from collections import OrderedDict

        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (id(mesh), tuple(axes))
        with cls._lock:
            if cls._cache is None:
                cls._cache = OrderedDict()
            hit = cls._cache.get(key)
            if hit is None:
                hit = NamedSharding(mesh, P(tuple(axes)))
                cls._cache[key] = hit
            cls._cache.move_to_end(key)
            while len(cls._cache) > cls._MAX:
                cls._cache.popitem(last=False)
            return hit
