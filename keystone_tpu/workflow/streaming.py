"""Streaming chunked execution: overlap ingest, transfer, and fused compute.

The Pipeline API materializes every stage's output dataset — correct and
optimizer-visible, but the reason featurization-heavy fits die at scale:
the full feature matrix must exist before the solver sees a single row.
The reference never pays that cost — featurization stays lazy per
partition and feeds the solver incrementally (reference:
ImageNetSiftLcsFV.scala:96-136) — and our hand-rolled flagship module
(pipelines/imagenet_streaming.py) proved the TPU shape of the same idea:
uint8 uploads double-buffered against fused per-chunk dispatches.

This module generalizes that shape into the workflow layer:

- :class:`StreamingPlanRule` (the LAST optimizer batch, after auto-cache
  and fusion) rewrites eligible ``ingest/featurize-chain → estimator``
  graphs: the featurize chain between the data source and a
  ``fit_stream``-capable estimator is absorbed into a
  :class:`StreamingFitOperator` that consumes the RAW dataset directly.
- At fit time the operator drives a chunked plan: a bounded-prefetch
  host pipeline (:class:`~keystone_tpu.data.ingest.PrefetchQueue` —
  multi-worker decode/stack feeding a depth-limited queue), host→device
  uploads that cross at the NARROWEST dtype
  (:func:`~keystone_tpu.data.dataset.transfer_dtype`; uint8 images stay
  uint8, 4× less traffic) and cast on device, and ONE fused XLA dispatch
  per chunk composing cast → featurize chain → the estimator's
  Gram-accumulation step, with the carry donated ping-pong style
  (parallel/linalg.py streaming idiom).
- Upload of chunk i+1 is issued before compute of chunk i completes
  (double-buffering, asserted by scripts/streaming_smoke.sh), and the
  full feature matrix never exists on host or device — only O(chunk)
  host buffers and O(d²) device statistics.

Estimator protocol: operators advertising ``supports_fit_stream = True``
implement ``fit_stream(stream)`` where ``stream`` is a
:class:`ChunkStream`; ``stream.fold(init_fn, step_fn)`` runs the engine
loop with ``step_fn`` traced INTO the per-chunk dispatch. See
``LeastSquaresEstimator`` / ``BlockLeastSquaresEstimator`` /
``LinearMapEstimator`` and docs/STREAMING.md.

Boundaries (mirror fusion's, docs/OPTIMIZER.md): Cacher nodes, saveable
prefixes, multi-consumer intermediates, and bespoke-``apply_batch``
transformers all cut the streamed chain — a cut chain streams from the
boundary's materialized output instead (the Cacher-boundary parity case
in tests/workflow/test_streaming.py).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, List, Optional, Sequence, Tuple

from ..envknobs import env_disabled, env_int
from ..data.dataset import (
    ArrayDataset,
    Dataset,
    ObjectDataset,
    default_ingest_workers,
    transfer_dtype,
)
from ..obs import cost as _cost
from ..obs import device as _device
from ..obs import names as _names
from ..obs import spans as _spans
from ..obs import store as _store
from ..reliability.durable import ShardLossError, shard_loss_index
from ..reliability.faultinject import probe
from .graph import Graph, NodeId, SourceId
from .operators import DatasetOperator, EstimatorOperator, TransformerOperator
from .rules import PrefixMap, Rule

logger = logging.getLogger(__name__)


# ------------------------------------------------------------------ enablement

# Tri-state like fusion's: None → env default (on unless
# KEYSTONE_STREAMING=off/0/disabled).
_enabled: Optional[bool] = None
_enabled_lock = threading.Lock()


def streaming_enabled() -> bool:
    if _enabled is not None:
        return _enabled
    return not env_disabled("KEYSTONE_STREAMING")


def set_streaming_enabled(value: Optional[bool]) -> None:
    """Force streaming on/off process-wide; ``None`` restores the env
    default."""
    global _enabled
    with _enabled_lock:
        _enabled = value


@contextmanager
def streaming_disabled():
    """Scoped off-switch (parity tests build the materialized reference
    here, exactly like fusion_disabled())."""
    global _enabled
    with _enabled_lock:
        prev = _enabled
        _enabled = False
    try:
        yield
    finally:
        with _enabled_lock:
            _enabled = prev


def stream_chunk_rows() -> int:
    """Rows per streamed chunk (``KEYSTONE_STREAM_CHUNK_ROWS``, default
    4096 — large enough to amortize dispatch, small enough that two host
    chunk buffers stay far below any realistic feature matrix)."""
    return max(1, env_int("KEYSTONE_STREAM_CHUNK_ROWS", 4096))


def stream_min_rows() -> int:
    """Plan-time eligibility floor for known-size datasets: below
    max(2·chunk, this) the materialized path wins (one dispatch, no
    pipeline overhead). ``KEYSTONE_STREAM_MIN_ROWS`` raises it."""
    return env_int("KEYSTONE_STREAM_MIN_ROWS", 0)


def stream_prefetch_depth() -> int:
    """Host prefetch-queue depth (``KEYSTONE_STREAM_PREFETCH``, default
    1). The engine holds at most depth+1 host chunk buffers live — depth
    queued plus one in hand being uploaded — so the default keeps peak
    host residency at 2× chunk while still hiding decode behind compute."""
    return max(1, env_int("KEYSTONE_STREAM_PREFETCH", 1))


def chain_class(members: Sequence[Any]) -> str:
    """Process-stable identity of a featurize chain for knob keys: the
    member type sequence, hashed. Deliberately coarser than the autocache
    structural digest — a chunk-size observation transfers across fits
    whose chains have the same op sequence even when weights differ."""
    import hashlib

    token = "|".join(
        f"{type(m).__module__}.{type(m).__qualname__}" for m in members
    )
    return hashlib.sha1(token.encode()).hexdigest()[:16]


class StreamingFallback(Exception):
    """Raised (internally, before any chunk is consumed) when a planned
    streaming fit turns out ineligible at run time — the operator falls
    back to the materialized path. Never used for mid-stream failures:
    those propagate to the reliability layer."""


class FoldPreempted(Exception):
    """Raised inside ``ChunkStream.fold``'s dispatch loop when the armed
    scheduler lease yields at a chunk boundary (sustained SLO pressure).
    Caught by the fold itself — it returns normally with the partial
    prefix carry and ``report.preempted_at_chunk`` set; the durable
    cursor was committed before the raise, so the deferred fold resumes
    from the boundary instead of restarting (docs/SCHEDULING.md)."""

    def __init__(self, chunk_index: int):
        self.chunk_index = int(chunk_index)
        super().__init__(f"fold preempted at chunk {chunk_index}")


# ------------------------------------------------------------- pipelined loop


def stream_pipelined(
    items: Iterable[Any],
    stage: Callable[[Any], Any],
    compute: Callable[[Any, Any], Any],
    consume: Callable[[Any, Any], None],
    prefetch: int = 2,
) -> int:
    """The shared double-buffered dispatch loop.

    ``stage(item)`` issues the (async) host→device upload; ``compute``
    dispatches device work on the staged value; ``consume`` forces and
    drains a result ONE item behind the dispatch frontier — so staging
    of item i+1 is always issued before the loop blocks on item i, and
    transfer, device compute, and host copies overlap. This is the
    engine under both the streaming fit path below and the ImageNet
    flagship's per-bucket encode loop
    (pipelines/imagenet_streaming.py), which used to hand-roll it.
    Returns the number of items processed.
    """
    staged: List[Tuple[Any, Any]] = []
    pending: List[Tuple[Any, Any]] = []
    it = iter(items)
    done = 0

    def stage_next() -> bool:
        try:
            item = next(it)
        except StopIteration:
            return False
        staged.append((stage(item), item))
        return True

    for _ in range(max(1, prefetch)):
        stage_next()
    while staged:
        s, item = staged.pop(0)
        pending.append((compute(s, item), item))
        stage_next()
        if len(pending) > 1:
            r, r_item = pending.pop(0)
            consume(r, r_item)
            done += 1
    while pending:
        r, r_item = pending.pop(0)
        consume(r, r_item)
        done += 1
    return done


def _stalls_spanned(queue):
    """``queue``'s items, each wait for the next one under a
    ``stream:stall`` span: where the fold's consumer stands still because
    the host has not prepared the next chunk yet."""
    while True:
        with _spans.span("stream:stall"):
            try:
                item = next(queue)
            except StopIteration:
                return
        yield item


# ------------------------------------------------------------------- reporting


@dataclass
class StreamReport:
    """What the last streaming fit actually did — the evidence the
    smoke script and tests assert on (overlap, compiles, memory)."""

    chunks: int = 0
    chunk_rows: int = 0
    num_examples: int = 0
    bytes_transferred: int = 0
    prefetch_depth: int = 0
    host_buffer_peak_bytes: int = 0
    stall_s: float = 0.0
    compiles_first_chunk: int = 0
    compiles_steady_state: int = 0
    #: Partitioned (multi-device) chunk plan: row shards the chunk rows
    #: split across, feature-block (model) shards of a 2-D layout, the
    #: mesh shape, and the payload bytes of the finish-time statistics
    #: reductions (docs/PARTITIONING.md; 1/()/0 = single-device).
    #: ``collective_bytes`` totals both axes; the per-axis split and the
    #: per-device carry bytes are what bench-diff exact-gates.
    shards: int = 1
    model_shards: int = 1
    mesh_shape: Tuple[int, ...] = ()
    collective_bytes: int = 0
    collective_bytes_data: int = 0
    collective_bytes_model: int = 0
    state_bytes_per_device: int = 0
    #: Durable-fit evidence (docs/RELIABILITY.md "Durable fits"):
    #: mid-stream checkpoints committed, the absolute chunk a crashed
    #: fit resumed from (None = fresh), chunks re-ingested by resume or
    #: shard-loss recovery, and device losses absorbed mid-stream.
    checkpoints: int = 0
    resumed_from_chunk: Optional[int] = None
    reingested_chunks: int = 0
    shard_losses: int = 0
    #: Scheduler preemption (docs/SCHEDULING.md): the absolute chunk a
    #: leased fold yielded at under sustained SLO pressure (None = ran
    #: to completion). The durable cursor committed at this boundary —
    #: the deferred fold resumes from it instead of restarting.
    preempted_at_chunk: Optional[int] = None
    #: perf_counter at fold start — the event lists below are offsets
    #: from this, so exporters can place chunk slices on a session
    #: timeline (obs/export.py Perfetto view).
    t0_s: float = 0.0
    upload_issued_t: List[float] = field(default_factory=list)
    dispatch_t: List[float] = field(default_factory=list)
    compute_done_t: List[float] = field(default_factory=list)

    @property
    def measured_steady_state(self) -> bool:
        """Not resumed, not preempted, no shard loss: the fold's wall is a
        steady-state measurement of all its rows, not one of recovery or
        of a prefix."""
        return (
            self.resumed_from_chunk is None
            and self.preempted_at_chunk is None
            and not self.shard_losses
        )

    def overlap_ok(self) -> bool:
        """True when the upload of chunk i+1 was issued before compute
        of chunk i was observed complete — the double-buffer invariant."""
        return self.overlap_efficiency() == 1.0

    def overlap_efficiency(self) -> float:
        """Fraction of chunk boundaries where the next upload was in
        flight before the previous compute finished — 1.0 is perfect
        double-buffering, the number the profile store remembers per
        shape class."""
        if self.chunks < 2:
            return 1.0
        good = sum(
            1
            for i in range(self.chunks - 1)
            if self.upload_issued_t[i + 1] <= self.compute_done_t[i]
        )
        return good / (self.chunks - 1)


_last_report: Optional[StreamReport] = None
_report_lock = threading.Lock()


def last_stream_report() -> Optional[StreamReport]:
    """The :class:`StreamReport` of the most recent streaming fit in
    this process (None if none ran)."""
    return _last_report


def _publish_report(report: StreamReport) -> None:
    global _last_report
    with _report_lock:
        _last_report = report
    _names.metric(_names.STREAM_HOST_BUFFER_PEAK).set(
        report.host_buffer_peak_bytes
    )


# ----------------------------------------------------------- fused chunk step

# One jitted (cast → chain → re-zero → estimator step) callable per chain
# STRUCTURE, shared across folds and across pipelines. Every fit of an
# unfitted pipeline builds fresh members and a fresh StreamingFitOperator
# (``keystone-tpu timit``, each round of the refit daemon), so a cache on
# member identity retraced and rebuilt the identical program on every fit,
# with the members' weights baked in as constants, and pinned each retired
# chain until it aged out. The key is what the trace depends on (member
# types, static parameters, the shapes and dtypes of their arrays, the
# step function, the partition); the arrays themselves are ARGUMENTS of
# the jitted step, placed once per fold. Entries hold templates (members
# with their arrays taken out), never weights. A member that cannot be
# split this way is keyed on itself and closed over, as before.
_STEP_JIT_CACHE = None  # type: ignore
_STEP_JIT_MAX = 32
_step_cache_lock = threading.Lock()


def _cast_tree(x):
    import jax
    import jax.numpy as jnp

    def cast(a):
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(jnp.float32)  # uint8/int/bool → f32 ON DEVICE

    return jax.tree_util.tree_map(cast, x)


def _apply_chain(members, x, mask):
    import jax
    import jax.numpy as jnp

    from .pipeline import feat_scope

    with jax.named_scope("stream/cast"):
        x = _cast_tree(x)
    for m in members:
        with feat_scope(m):  # the name its operations carry in a device trace
            x = m.apply_arrays(x)

    # Re-zero pad rows once at the end of the chain (valid because
    # apply_arrays is row-independent by the BatchTransformer contract)
    # so the estimator's accumulation sees exact zeros — same discipline
    # as BatchTransformer.apply_batch.
    def zero_pad(a):
        m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m > 0, a, jnp.zeros((), dtype=a.dtype))

    return jax.tree_util.tree_map(zero_pad, x)


def _lift_member(m):
    """``(key, template, arrays)`` of one chain member. ``arrays`` are its
    attributes that are jax arrays, ``template`` a shallow copy without
    them, and ``key`` what a trace of ``apply_arrays`` can depend on
    besides their values: the type, every other attribute, the arrays'
    shapes and dtypes. A member with an unhashable attribute (or none to
    read) stays whole: its own key, its own template, nothing lifted."""
    import copy

    import jax

    try:
        attrs = vars(m)
        arrays = {k: v for k, v in attrs.items() if isinstance(v, jax.Array)}
        static = tuple(sorted(
            (k, type(v), v) for k, v in attrs.items() if k not in arrays
        ))
        hash(static)
    except TypeError:
        return _whole_member(m)
    template = copy.copy(m)
    vars(template).update(dict.fromkeys(arrays))
    shapes = tuple((k, v.shape, str(v.dtype)) for k, v in sorted(arrays.items()))
    return (type(m), static, shapes), template, arrays


def _whole_member(m):
    """``_lift_member``'s result for a member that stays whole: keyed on
    itself (the cache entry's closure holds it, so its id is not reused
    meanwhile), its own template, nothing lifted."""
    return ("whole", id(m)), m, {}


def _lift_chain(members, lift: bool = True):
    """``(key, templates, arrays)`` of a featurize chain: tuples with one
    entry per member. ``lift=False`` keeps every member whole (a chain
    whose ``apply_arrays`` reads an array's VALUE while it is traced)."""
    parts = [(_lift_member if lift else _whole_member)(m) for m in members]
    return tuple(zip(*parts)) if parts else ((), (), ())


def _bind_chain(templates, arrays):
    """The members to trace: each template with its arrays (tracers,
    inside the jitted step) put back."""
    import copy

    bound = []
    for template, own in zip(templates, arrays):
        if own:
            template = copy.copy(template)
            vars(template).update(own)
        bound.append(template)
    return bound


class _BoundStep:
    """The shared jitted step with one fold's member arrays bound:
    ``step(carry, x_raw, y, mask)``. ``jitted`` takes them as a fifth
    argument, ``arrays``."""

    def __init__(self, jitted, arrays):
        self.jitted = jitted
        self.arrays = arrays

    def __call__(self, carry, x_raw, y, mask):
        return self.jitted(carry, x_raw, y, mask, self.arrays)


def _shared_step_jit(members: tuple, step_fn, partition=None, lift: bool = True):
    """jit of (carry, x_raw, y, mask, member arrays) → (carry', probe),
    cached on (chain structure, step_fn, partition). Returns
    (step, trace_counter_list): ``step(carry, x_raw, y, mask)`` has this
    chain's arrays bound; the counter appends at trace time only and is
    shared by every fold that hits the entry, so a fold counts ITS traces
    from the length it found.

    With an eligible ``partition`` decision the fused step runs inside
    ``shard_map`` over the decision's mesh: each device featurizes its
    row slice of the chunk and accumulates into its OWN carry block (the
    carry grows a leading ``(shards,)`` axis sharded over the row axes),
    so no collective runs per chunk — the partial statistics are summed
    across shards once, at fold finish (docs/PARTITIONING.md). The member
    arrays are replicated over the mesh."""
    global _STEP_JIT_CACHE
    import jax

    chain_key, templates, arrays = _lift_chain(members, lift)
    key = (chain_key, step_fn)
    if partition is not None:
        key += (
            "sharded", partition.mesh, tuple(partition.mesh_axes),
            tuple(getattr(partition, "carry_axes", partition.mesh_axes)),
            partition.shards, getattr(partition, "model_shards", 1),
        )
        from jax.sharding import NamedSharding, PartitionSpec

        everywhere = NamedSharding(partition.mesh, PartitionSpec())
        arrays = jax.device_put(arrays, everywhere)
    with _step_cache_lock:
        if _STEP_JIT_CACHE is None:
            from collections import OrderedDict

            _STEP_JIT_CACHE = OrderedDict()
        hit = _STEP_JIT_CACHE.get(key)
        if hit is not None:
            _STEP_JIT_CACHE.move_to_end(key)
            return _BoundStep(hit[0], arrays), hit[1]

    traces: List[tuple] = []

    # Index-keyed folds (sketch/core.py) declare needs_mask: the step
    # receives the chunk's pad mask — whose lane holds absolute row
    # indices — as a fourth argument. Gram-family steps keep the 3-arg
    # signature untouched.
    needs_mask = bool(getattr(step_fn, "needs_mask", False))

    def accumulate(step, carry, x, y, mask, *block):
        if needs_mask:
            return step(carry, x, y, mask, *block)
        return step(carry, x, y, *block)

    def probed(new_carry):
        leaf = jax.tree_util.tree_leaves(new_carry)[0]
        return new_carry, leaf.ravel()[:1]  # tiny, NOT donated: safe to block on

    if partition is None:

        def fused(carry, x_raw, y, mask, arrays=()):
            traces.append(())  # trace-time side effect: once per new shape
            x = _apply_chain(_bind_chain(templates, arrays), x_raw, mask)
            return probed(accumulate(step_fn, carry, x, y, mask))

    else:
        from jax.sharding import PartitionSpec as P

        from ..parallel.collectives import shard_map as _smap
        from ..parallel.mesh import MODEL_AXIS

        mesh = partition.mesh
        model_shards = getattr(partition, "model_shards", 1)
        # Chunks shard rows over the ROW axes only (replicated over a
        # model axis if present); the stacked carry's leading block axis
        # additionally shards over ``model`` in a 2-D layout.
        spec = P(tuple(partition.mesh_axes))
        carry_spec = P(
            tuple(getattr(partition, "carry_axes", partition.mesh_axes))
        )
        block_step = getattr(step_fn, "model_block_step", None)

        def fused(carry, x_raw, y, mask, arrays=()):
            traces.append(())

            def local(c, x, yb, m, own):
                # One device's view: carry block (1, …) squeezed, the
                # chunk's row slice featurized and accumulated locally —
                # apply_arrays is row-independent (the BatchTransformer
                # contract), so per-shard application is exact.
                c0 = jax.tree_util.tree_map(lambda a: a[0], c)
                feats = _apply_chain(_bind_chain(templates, own), x, m)
                # m is this device's row slice of the mask, so an
                # index-keyed step sees exactly its rows' absolute
                # indices — per-shard sketch partials stay exact.
                if model_shards > 1:
                    # 2-D layout: this device accumulates only its
                    # feature block — the step's blocked protocol takes
                    # the (traced) model-axis position and slices its own
                    # columns out of the full-width featurized chunk.
                    j = jax.lax.axis_index(MODEL_AXIS)
                    c1 = accumulate(block_step, c0, feats, yb, m, j)
                else:
                    c1 = accumulate(step_fn, c0, feats, yb, m)
                return jax.tree_util.tree_map(lambda a: a[None], c1)

            return probed(_smap(
                local, mesh=mesh,
                in_specs=(carry_spec, spec, spec, spec, P()),
                out_specs=carry_spec,
            )(carry, x_raw, y, mask, arrays))

    # carry is owned by the fold loop: created by gram_stream_init (or a
    # refit state seed) and threaded only through this step.
    # keystone: owns-donated
    jitted = jax.jit(fused, donate_argnums=(0,))
    with _step_cache_lock:
        _STEP_JIT_CACHE[key] = (jitted, traces)
        _STEP_JIT_CACHE.move_to_end(key)
        while len(_STEP_JIT_CACHE) > _STEP_JIT_MAX:
            _STEP_JIT_CACHE.popitem(last=False)
    return _BoundStep(jitted, arrays), traces


# ------------------------------------------------------------------ the stream


def _tree_nbytes(tree) -> int:
    import jax

    return sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(tree)
    )


def _stacked_from_blocks(shape, dtype, sharding, seed_block):
    """A global ``shape`` array, leading axis sharded one block per
    device, assembled from blocks built ON the device that holds them:
    ``seed_block(i)`` is leading block i's initial value (shape
    ``shape[1:]``) or None for zeros. Nothing of global size ever exists
    on one device — at TIMIT width on four chips the old
    zeros-then-``.at[0].set``-then-reshard route put 4 GiB plus a 4 GiB
    copy on chip 0."""
    import jax
    import jax.numpy as jnp

    blocks = []
    for dev, index in sharding.addressable_devices_indices_map(shape).items():
        seed = seed_block(index[0].start or 0)
        if seed is None:
            # default_device, not zeros(device=dev): jax 0.9.0 builds the
            # latter on the DEFAULT device and copies it over, and with
            # async dispatch those staging copies pile up on chip 0
            # (measured on four v5e chips: 5 carries on chip 0, 3 elsewhere).
            with jax.default_device(dev):
                blocks.append(jnp.zeros((1,) + tuple(shape[1:]), dtype))
        else:
            blocks.append(jax.device_put(seed[None], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, blocks)


def _carry_layout(step_fn, carry) -> Optional[Tuple[Optional[int], ...]]:
    """The blocked-carry protocol's per-leaf feature axes, validated
    against the actual carry structure — ``None`` when the step doesn't
    declare the protocol or the declaration doesn't match the carry."""
    import jax

    layout = getattr(step_fn, "model_layout", None)
    if layout is None or getattr(step_fn, "model_block_step", None) is None:
        return None
    leaves = jax.tree_util.tree_leaves(carry)
    if len(leaves) != len(layout):
        return None
    return tuple(layout)


@dataclass(frozen=True)
class _FoldLayout:
    """Where one attempt of a fold keeps its carry and sends its chunks:
    built in one place (``ChunkStream._layout``) from a partition
    decision, the step and the estimator's carry. ``part`` None is the
    single-device value: one shard, no shardings, nothing stacked."""

    part: Any  # eligible PartitionDecision, or None
    shards: int  # row shards the chunk rows split across
    model_shards: int  # feature blocks of a 2-D layout (1 = row-only)
    mesh_shape: Tuple[int, ...]
    chunk_sharding: Any  # rows over the row axes
    carry_sharding: Any  # leading block axis over (row axes[, model])
    #: Per carry leaf, the axis its feature blocks split along, or None
    #: for a leaf every block holds whole (all of them in a 1-D layout).
    carry_layout: Tuple[Optional[int], ...]
    step: _BoundStep
    #: The step's trace list, shared by every fold over this chain
    #: structure: a fold's compiles are what it appends from here on.
    traces: List[tuple]
    chunk_rows: int

    @property
    def sharded(self) -> bool:
        return self.part is not None

    @property
    def total_shards(self) -> int:
        return self.shards * self.model_shards


def _stack_carry(carry, layout: _FoldLayout):
    """``carry`` as device arrays, one block per device where the layout
    is sharded: a leading ``row_shards × model_shards`` axis over ``(row
    axes[, model])`` — flat block index ``data_idx·model_shards +
    model_idx``, row-major. Feature leaves (``carry_layout`` axis int)
    split into model blocks; the SEED therefore lands spread over blocks
    0..model_shards−1 (data row 0). Feature-free leaves (``carry_layout``
    None) keep full shape per block and seed only block 0, the rest start
    zero — exact for the additive accumulation the fit_stream protocol is
    (final carry = seed + Σ partials, summed once at finish, leaf-wise)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if not layout.sharded:
        return jax.tree_util.tree_map(jnp.asarray, carry)
    total, p_m = layout.total_shards, layout.model_shards
    leaves, treedef = jax.tree_util.tree_flatten(carry)

    def stack(a, ax):
        a = jnp.asarray(a)
        if ax is None:
            return _stacked_from_blocks(
                (total,) + a.shape, a.dtype, layout.carry_sharding,
                lambda i: a if i == 0 else None,
            )
        b = a.shape[ax] // p_m
        return _stacked_from_blocks(
            (total,) + a.shape[:ax] + (b,) + a.shape[ax + 1:],
            a.dtype, layout.carry_sharding,
            lambda i: (
                lax.slice_in_dim(a, i * b, (i + 1) * b, axis=ax)
                if i < p_m
                else None
            ),
        )

    return jax.tree_util.tree_unflatten(
        treedef, [stack(a, ax) for a, ax in zip(leaves, layout.carry_layout)]
    )


def _merge_blocks(
    carry, row_shards: int, model_shards: int, layout, np_mod, drop_row=None
):
    """THE additive contract of a stacked ``(row_shards·model_shards, …)``
    carry, back in the estimator's single-device shape: partials SUM
    across the data axis; feature leaves then CONCATENATE their model
    blocks along the layout axis, feature-free leaves sum (only model
    block 0 accumulated them). ``np_mod`` is numpy for host merges
    (checkpoints, salvage) or jax.numpy for the on-device finish reduce.
    ``drop_row`` leaves one data row-group out of the sum: what survives
    a device lost from that group (all of it dropped gives zeros)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(carry)
    if layout is None:
        layout = (None,) * len(leaves)

    def merge(a, ax):
        a = np_mod.asarray(a)
        a = a.reshape((row_shards, model_shards) + a.shape[1:])
        if drop_row is not None:
            a = np_mod.delete(a, drop_row, axis=0)
        a = a.sum(axis=0)
        if ax is None or model_shards == 1:
            return a.sum(axis=0) if ax is None else a[0]
        return np_mod.concatenate(
            [a[j] for j in range(model_shards)], axis=ax
        )

    return jax.tree_util.tree_unflatten(
        treedef, [merge(a, ax) for a, ax in zip(leaves, layout)]
    )


@functools.lru_cache(maxsize=None)
def _reduce_fn(row_shards: int, model_shards: int, layout):
    """The finish-time reduction of a stacked carry as ONE program (its
    collectives under the scope ``gram/reduce`` in a device trace), per
    (shard counts, layout); jit's own cache holds one executable per
    carry shape. The result is replicated over the mesh."""
    import jax
    import jax.numpy as jnp

    def reduce(carry):
        with jax.named_scope("gram/reduce"):
            return _merge_blocks(carry, row_shards, model_shards, layout, jnp)

    return jax.jit(reduce)


def _labels_host(labels: Dataset):
    """Labels as one host (n, k) float-ready matrix. Labels are O(n·k) —
    'the full feature matrix never materializes' is about features; a
    label matrix is the estimator's RHS and is small by construction."""
    import numpy as np

    if isinstance(labels, ObjectDataset):
        labels = labels.to_arrays()
    if not isinstance(labels, ArrayDataset):
        raise StreamingFallback(f"labels of type {type(labels).__name__}")
    # One-time fit setup, before the chunk loop starts.  # keystone: allow-sync
    y = np.asarray(labels.data)[: labels.num_examples]
    if y.ndim == 1:
        y = y[:, None]
    if y.ndim != 2:
        raise StreamingFallback(f"labels must be rank ≤ 2, got {y.shape}")
    # Not made contiguous here: a label matrix that the executor left on
    # the device comes back with its rows padded to the device's tiles,
    # and a contiguous copy of all of it cost 0.67 s of every TIMIT fit
    # with the device idle (308 MB; PERF.md section 6, PR 30). Each
    # chunk's rows are made contiguous where the chunk is prepared.
    return y.astype(transfer_dtype(y.dtype), copy=False)


def _chunk_boundary(durable, lease, dispatched: int, snapshot, report) -> None:
    """The one call a fold with a durability plan or a lease makes before
    it dispatches a chunk, ``dispatched`` chunks into its attempt. The
    lease says whether the fold yields here (sched/scheduler.py), the plan
    whether this boundary commits ``snapshot()`` (reliability/durable.py;
    a yield asks it to). The order is the preemption contract: commit the
    durable cursor FIRST — a deferred fold must resume from here, not
    restart — then mark the lease, then unwind. The prefix carry stays
    valid statistics; the caller reads ``report.preempted_at_chunk`` and
    re-leases later."""
    yielding = lease is not None and dispatched > 0 and lease.should_yield()
    if durable is not None and durable.at_boundary(
        dispatched, snapshot, force=yielding
    ):
        report.checkpoints += 1
    if yielding:
        at = dispatched + (durable.start_chunk if durable is not None else 0)
        report.preempted_at_chunk = at
        lease.mark_preempted(at)
        raise FoldPreempted(at)


class _FoldRun:
    """What one fold threads through its chunks. ``stage`` / ``compute``
    / ``consume`` are ``stream_pipelined``'s three callbacks and
    ``prepare`` the prefetch workers' job; ``begin`` starts an attempt —
    the fold's first, or the one salvage made of a shard loss, which
    replaces layout, carry and windows together."""

    def __init__(self, stream, report, y_host, start_chunk, rows_folded):
        self.stream, self.report, self.y_host = stream, report, y_host
        self.guarded = stream.durable is not None or stream.lease is not None
        #: Absolute index of the fold's first window (a resumed fold
        #: starts at its cursor's).
        self.start_chunk = start_chunk
        #: ABSOLUTE logical rows fully dispatched (a resumed fold starts
        #: at the cursor's count) — what a committed cursor records.
        self.rows_folded = rows_folded
        self.in_hand_peak = 0
        self.chunks_c = _names.metric(_names.STREAM_CHUNKS)
        self.bytes_c = _names.metric(_names.STREAM_BYTES)

    def begin(self, layout: _FoldLayout, carry, windows) -> None:
        report = self.report
        self.layout, self.windows = layout, windows
        report.shards, report.model_shards = layout.shards, layout.model_shards
        report.mesh_shape = layout.mesh_shape
        # Shard-loss recovery must be able to re-add the attempt's seed
        # when the device holding carry block 0 dies: keep the PRE-STACK
        # carry alive (stacking copies, nothing donates it) — on the
        # device where it is there — and fetch it to host only if that
        # loss actually happens.
        self.seed = carry if layout.sharded else None
        self.carry = _stack_carry(carry, layout)
        #: Chunks of ``windows`` dispatched, in order.
        self.dispatched = 0
        # A fold's compiles are what its attempts append to their steps'
        # trace lists, past the fold's first chunk.
        self._trace_base = len(layout.traces)

    def new_traces(self) -> int:
        """Traces of the attempt's step since the last call (or since the
        attempt began)."""
        base, self._trace_base = self._trace_base, len(self.layout.traces)
        return self._trace_base - base

    def prepare(self, window):
        import jax
        import numpy as np

        start, stop = window
        padded_rows = self.layout.chunk_rows
        # fetch_rows runs inside the prefetch workers — this is the
        # decode/stack work being overlapped with device compute.
        x = self.stream.data.fetch_rows(start, stop)
        x = jax.tree_util.tree_map(lambda a: _pad_narrow(a, padded_rows), x)
        # The labels' rows are made contiguous here, not all at once
        # (`_labels_host`), and the tail chunk padded to the compiled shape.
        y = _pad_narrow(self.y_host[start:stop], padded_rows)
        rows = stop - start
        # The pad-mask lane carries each row's ABSOLUTE dataset index + 1
        # (0 = pad). The chain only tests m > 0, so this is
        # backward-compatible; index-keyed folds (the sketch tier) read
        # the value itself, which stays exact in float32 up to 2^24 rows
        # (sketch/core.py refuses longer streams).
        mask = np.zeros((padded_rows, 1), np.float32)
        mask[:rows, 0] = np.arange(start + 1, stop + 1, dtype=np.float32)
        return x, y, mask, rows

    def stage(self, chunk):
        """Enqueue one prepared chunk's upload (never waited for here).
        While somebody is recording, the chunk's device arrays also go to
        the transfer watcher, whose ``h2d:transfer`` span
        (``site="ChunkStream"``) ends when they have arrived
        (obs/device.py::watch_transfer); otherwise one flag check."""
        import jax

        x, y, mask, rows = chunk
        report, sharding = self.report, self.layout.chunk_sharding
        nbytes = _tree_nbytes(x) + y.nbytes + mask.nbytes
        self.in_hand_peak = max(self.in_hand_peak, nbytes)
        report.upload_issued_t.append(time.perf_counter() - report.t0_s)

        # Async uploads at transfer (narrow) width; cast happens on device
        # inside the fused step. Under a partition decision every leaf
        # lands row-sharded over the mesh — each device receives only its
        # slice of the chunk.
        def put(a):
            return jax.device_put(a, sharding)

        dev = (jax.tree_util.tree_map(put, x), put(y), put(mask), rows)
        if _device.watching(nbytes):
            _device.watch_transfer(dev[:3], "ChunkStream", nbytes)
        report.bytes_transferred += nbytes
        self.bytes_c.inc(nbytes)
        return dev

    def snapshot(self):
        """``DurableFold.commit``'s arguments for the carry as it stands:
        a mesh-INDEPENDENT host snapshot and the cursor's geometry."""
        import jax
        import numpy as np

        lay = self.layout
        # Commit-before-continue barrier: the carry is host-fetched
        # (device_get blocks until the last dispatch retired) and the
        # atomic store write completes BEFORE the next chunk's dispatch
        # donates the buffer — a persisted carry is never stale.
        # keystone: allow-sync
        host = jax.device_get(self.carry)
        if lay.sharded:
            # Per-shard partials merge via the additive contract (rows
            # summed, feature blocks reassembled): resume may re-plan on
            # any mesh shape, 1-D or 2-D. Operates on the already-fetched
            # HOST tree, never a device array.  # keystone: allow-sync
            host = _merge_blocks(
                host, lay.shards, lay.model_shards, lay.carry_layout, np
            )
        return dict(
            host_carry=tuple(
                np.asarray(a)  # host leaves  # keystone: allow-sync
                for a in jax.tree_util.tree_leaves(host)
            ),
            rows_consumed=self.rows_folded,
            chunk_rows=lay.chunk_rows,
            mesh_shape=lay.mesh_shape,
            shards=lay.shards,
            model_shards=lay.model_shards,
        )

    def compute(self, staged_chunk, _chunk):
        import jax

        x_dev, y_dev, mask_dev, rows = staged_chunk
        lay, report = self.layout, self.report
        if self.guarded:
            _chunk_boundary(
                self.stream.durable, self.stream.lease, self.dispatched,
                self.snapshot, report,
            )
        if lay.sharded:
            try:
                probe("parallel.shard_loss")
            except Exception as exc:
                # Any injected fault at this site models the runtime
                # observing a device gone from the mesh before this chunk
                # could dispatch — the fold's elastic recovery owns it.
                # Indexed over ALL carry blocks (row × model shards, flat
                # row-major) so a seeded fault can land on either axis of
                # a 2-D layout.
                raise ShardLossError(
                    shard_loss_index(lay.total_shards),
                    self.start_chunk + self.dispatched,
                    lay.total_shards,
                ) from exc
        probe("streaming.chunk")
        if not report.chunks and _cost.current_frame() is not None:
            # Cost-observatory note, once per fold: avals (not the arrays
            # — the carry is donated into the step) so the per-chunk
            # program's flop/byte facts harvest at node finalize through
            # the jit trace cache (obs/cost.py).
            avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (self.carry, x_dev, y_dev, mask_dev, lay.step.arrays),
            )
            _cost.note_jit_call("stream_step", lay.step.jitted, avals=avals)
        report.dispatch_t.append(time.perf_counter() - report.t0_s)
        with _spans.span(
            "stream:chunk", index=self.start_chunk + report.chunks, rows=rows
        ):
            self.carry, probe_out = lay.step(self.carry, x_dev, y_dev, mask_dev)
        self.chunks_c.inc()
        report.chunks += 1
        if report.chunks == 1:
            report.compiles_first_chunk = self.new_traces()
        self.dispatched += 1
        self.rows_folded += rows
        return probe_out

    def consume(self, probe_out, _chunk):
        # The overlap engine's completion barrier for chunk i — a
        # one-element un-donated probe leaf, waited on so chunk timings
        # and backpressure are real: consume() drains one item behind the
        # dispatch frontier, so the upload of chunk i+1 (stage) is always
        # issued before the loop blocks on chunk i — the double-buffer
        # invariant the smoke script asserts via the event log.
        # keystone: allow-sync
        probe_out.block_until_ready()
        self.report.compute_done_t.append(
            time.perf_counter() - self.report.t0_s
        )


class ChunkStream:
    """The engine-side handle handed to ``Estimator.fit_stream``.

    ``fold(init_fn, step_fn)`` drives the chunked plan:

    - ``init_fn(feat_aval, y_aval)`` receives jax ShapeDtypeStructs of
      the FEATURIZED chunk (post-chain, computed via ``jax.eval_shape``
      without touching data) and the label chunk, and returns the
      initial carry pytree. Raise :class:`StreamingFallback` here to
      reject the shape (nothing has been prefetched yet).
    - ``step_fn(carry, x_feat, y) -> carry`` is traced INTO the single
      per-chunk dispatch, after the featurize chain, with the carry
      donated — the Gram-accumulation protocol.

    Returns ``(carry, info)`` where info has ``num_examples``, ``d``
    (featurized width) and the :class:`StreamReport`.
    """

    def __init__(
        self,
        data: Dataset,
        labels: Optional[Dataset],
        members: Sequence[TransformerOperator],
        chunk_rows: Optional[int] = None,
        prefetch: Optional[int] = None,
        workers: Optional[int] = None,
        partition=None,
    ):
        self.data = data
        self.labels = labels
        self.members = tuple(members)
        self.chunk_rows = chunk_rows or stream_chunk_rows()
        self.prefetch = prefetch or stream_prefetch_depth()
        self.workers = workers or min(default_ingest_workers(), 4)
        self.num_examples = len(data)
        self._feat_aval = None
        #: Whether the fused step takes the members' arrays as arguments
        #: (one compiled step per chain STRUCTURE); `feature_aval` turns
        #: it off for a chain that does not trace that way.
        self._lift = True
        # An eligible PartitionDecision (parallel/partitioner.py) runs the
        # sharded chunk plan; the compiled chunk shape must divide evenly
        # across the shards, so round chunk_rows up to a shard multiple.
        self.partition = (
            partition
            if partition is not None and getattr(partition, "eligible", False)
            else None
        )
        if self.partition is not None:
            s = self.partition.shards
            self.chunk_rows = -(-self.chunk_rows // s) * s
        #: Durability plan (reliability/durable.py DurableFold), armed by
        #: the streaming operator when a checkpoint store is attached.
        #: None = today's fold, byte for byte.
        self.durable = None
        #: Mesh-scheduler lease (sched/scheduler.py), armed by scheduled
        #: callers (the refit daemon under a MeshScheduler): consulted at
        #: every chunk boundary; sustained SLO pressure preempts the fold
        #: there, committing the durable cursor first. None = unscheduled
        #: fold, byte for byte.
        self.lease = None

    def feature_aval(self):
        """Shape/dtype of one FEATURIZED chunk (shape-only trace of the
        chain, no data touched). Raises :class:`StreamingFallback` when
        the chain can't shape-trace or the dataset isn't chunkable."""
        if self._feat_aval is None:
            import jax
            import numpy as np

            x_spec = _chunk_spec(self.data, self.chunk_rows)
            mask_spec = jax.ShapeDtypeStruct((self.chunk_rows, 1), np.float32)

            def shape_trace():
                # As the fused step will trace it: the members' arrays
                # are arguments (abstract here), unless `_lift` is off.
                _, templates, arrays = _lift_chain(self.members, self._lift)
                return jax.eval_shape(
                    lambda own, x, m: _apply_chain(
                        _bind_chain(templates, own), x, m
                    ),
                    arrays, x_spec, mask_spec,
                )

            try:
                try:
                    self._feat_aval = shape_trace()
                except (
                    jax.errors.JAXTypeError, jax.errors.UnexpectedTracerError
                ):
                    # A member reads an array's VALUE while it is traced
                    # (concretization): close over this chain instead.
                    self._lift = False
                    self._feat_aval = shape_trace()
            except StreamingFallback:
                raise
            except Exception as e:
                raise StreamingFallback(
                    f"chain not shape-traceable: {e}"
                ) from e
        return self._feat_aval

    # ---------------------------------------------------------------- fold
    def fold(self, init_fn, step_fn):
        import jax

        from ..data.ingest import PrefetchQueue
        from ..parallel.linalg import _quiet_unused_donation_warnings
        from ..parallel.partitioner import (
            record_collective_bytes,
            record_imbalance,
            reduction_collective_bytes,
        )
        data, chunk_rows, n = self.data, self.chunk_rows, self.num_examples
        if self.labels is None:
            raise StreamingFallback("no labels bound for a supervised fit")
        y_host = _labels_host(self.labels)
        if y_host.shape[0] < n:
            raise StreamingFallback(
                f"labels rows {y_host.shape[0]} < data rows {n}"
            )
        if getattr(type(data), "fetch_rows", None) in (None, Dataset.fetch_rows):
            raise StreamingFallback(f"{type(data).__name__} is not chunkable")

        # Shape-only pass: featurized aval without touching data.
        feat_aval = self.feature_aval()
        y_spec = jax.ShapeDtypeStruct((chunk_rows, y_host.shape[1]), y_host.dtype)
        carry = init_fn(feat_aval, y_spec)
        _quiet_unused_donation_warnings()  # carries are donated each step
        layout = self._layout(self.partition, step_fn, carry, chunk_rows)
        # A step may say something about the fold it is about to run (the
        # Gram step: the panels of its symmetric product), for a counter of
        # its own and as attributes of `stream:fold`. The model-axis block
        # step is another function and says nothing.
        note_fold = getattr(step_fn, "note_fold", None)
        fold_attrs = (
            note_fold(carry) if note_fold and layout.model_shards == 1 else {}
        )

        durable = self.durable
        windows = [
            (s, min(s + chunk_rows, n)) for s in range(0, n, chunk_rows)
        ]
        start_chunk = resume_rows = 0
        if durable is not None:
            start_chunk = min(durable.start_chunk, len(windows))
            resume_rows = durable.resume_rows
        report = StreamReport(
            chunk_rows=chunk_rows, num_examples=n, prefetch_depth=self.prefetch
        )
        run = _FoldRun(self, report, y_host, start_chunk, resume_rows)
        run.begin(layout, carry, windows[start_chunk:])
        # The acceptance number for 2-D layouts: bytes of streamed solver
        # state each device actually holds — shrinks with model shards
        # while the row-only plan replicates it.
        report.state_bytes_per_device = (
            _tree_nbytes(run.carry) // layout.total_shards
        )
        if start_chunk:
            # Crash-resume: chunks before the cursor live in the seeded
            # carry already — only the suffix is re-ingested.
            report.resumed_from_chunk = start_chunk
            report.reingested_chunks = len(windows) - start_chunk
            _names.metric(_names.DURABLE_REINGESTED_CHUNKS).inc(
                report.reingested_chunks
            )
        report.t0_s = time.perf_counter()
        queue_peak = 0
        try:
            with _spans.span(
                "stream:fold", chunks=len(windows), chunk_rows=chunk_rows,
                shards=report.shards, **fold_attrs,
            ):
                while True:
                    queue = PrefetchQueue(
                        iter(run.windows),
                        run.prepare,
                        depth=self.prefetch,
                        workers=min(self.workers, self.prefetch),
                        size_of=lambda c: _tree_nbytes(c[0]) + c[1].nbytes,
                    )
                    try:
                        # The attempt IS stream_pipelined — the same engine
                        # that runs the flagship's per-bucket encode — with
                        # the carry threaded and the report timestamps
                        # recorded through the run's three callbacks.
                        stream_pipelined(
                            _stalls_spanned(queue), stage=run.stage,
                            compute=run.compute, consume=run.consume,
                            prefetch=1,
                        )
                        break
                    except FoldPreempted:
                        # Graceful yield: on to the finish merge with the
                        # prefix carry — the cursor is already committed,
                        # the report already marked.
                        break
                    except ShardLossError as lost:
                        loss = lost
                    finally:
                        # Joins this attempt's prefetch workers BEFORE
                        # salvage, and before ANY exception leaves the
                        # fold: an abandoned fold must never leak decode
                        # threads.
                        queue.close()
                        report.stall_s += queue.stall_s
                        queue_peak = max(queue_peak, queue.peak_live_bytes)
                        report.compiles_steady_state += run.new_traces()
                    if durable is not None:
                        durable.suspend()
                    run.begin(*self._salvage_shard_loss(loss, run, step_fn))
                lay, carry = run.layout, run.carry
                if lay.sharded:
                    # THE cross-shard reduction of the whole fit, once at
                    # finish — O(d²) payload independent of how many
                    # chunks streamed (docs/PARTITIONING.md): the additive
                    # contract of `_merge_blocks`, on the device.
                    # Unconditional on chunk count: the stacked carry must
                    # ALWAYS come back to the estimator's single-device
                    # shape (a zero-chunk fold reduces to the seeded init
                    # carry).
                    with _spans.span(
                        "stream:reduce", shards=lay.shards,
                        model_shards=lay.model_shards,
                    ):
                        carry = _reduce_fn(
                            lay.shards, lay.model_shards, lay.carry_layout
                        )(carry)
                    if report.chunks:
                        data_b, model_b = reduction_collective_bytes(
                            [a.nbytes for a in jax.tree_util.tree_leaves(carry)],
                            lay.carry_layout, lay.shards, lay.model_shards,
                        )
                        report.collective_bytes_data = data_b
                        report.collective_bytes_model = model_b
                        report.collective_bytes = data_b + model_b
                        record_collective_bytes(data_b, axis="data")
                        record_collective_bytes(model_b, axis="model")
                        record_imbalance(
                            "fit_stream", n, len(windows) * lay.chunk_rows
                        )
        finally:
            report.host_buffer_peak_bytes = queue_peak + run.in_hand_peak
            _publish_report(report)

        if durable is not None and report.preempted_at_chunk is None:
            # The fit completed: a resume entry pointing into its middle
            # must not outlive it. A PREEMPTED fold is the opposite case
            # — its cursor IS the resume point the next lease needs.
            durable.complete()

        # A failed fold recorded nothing — its throughput would be a lie;
        # a resumed or shard-loss-recovered fold measured recovery, and a
        # preempted one a partial wall against full num_examples: either
        # would inflate rows/s (the PR-15 suffix-wall guard, extended to
        # deferrals).
        if report.measured_steady_state and report.compute_done_t:
            if report.chunks == len(windows):
                # A COMPLETED fold is a knob observation: remember what
                # this chunk size achieved on this data shape, so
                # MeasuredKnobRule can prefer the best recorded chunk_rows
                # next plan.
                self._record_observation(
                    report, _store.dataset_shape_class(data)
                )
            # Achieved throughput to the enclosing harvest frame: a
            # rows/s-denominated prediction (the measured-knob chunk
            # winner) is drift-scored in its own unit (obs/cost.py).
            wall = max(report.compute_done_t[-1], 1e-9)
            _cost.note_stream_result(report.num_examples / wall, n)

        info = {
            # Rows THIS fold absorbed: a resumed fold re-ingests only the
            # suffix past the cursor — the cursor's rows already live in
            # the seeding state, and estimators add state.num_examples.
            # A preempted fold absorbed only the dispatched prefix.
            "num_examples": (
                run.rows_folded - resume_rows
                if report.preempted_at_chunk is not None
                else n - resume_rows
            ),
            "chunks": report.chunks,
            "report": report,
        }
        return carry, info

    def _layout(self, part, step_fn, carry, chunk_rows: int) -> _FoldLayout:
        """The layout of a fold of ``carry`` under ``part`` (an eligible
        partition decision, or None), with the fused step bound for it."""
        import jax

        if part is not None and part.model_shards > 1:
            # The plan granted the model axis optimistically (raw-width
            # proxy); re-validate against the REAL carry the estimator
            # built — the step's blocked protocol, the featurized width's
            # divisibility, and the width floor — and demote to row-only
            # (same mesh, replicated over model) when any fail.
            part = self._validate_model_axis(part, step_fn, carry)
        step, traces = _shared_step_jit(
            self.members, step_fn, part, lift=self._lift
        )
        whole = (None,) * len(jax.tree_util.tree_leaves(carry))
        if part is None:
            return _FoldLayout(
                None, 1, 1, (), None, None, whole, step, traces, chunk_rows
            )
        from ..parallel.partitioner import NamedShardingCache

        blocked = part.model_shards > 1
        return _FoldLayout(
            part, part.shards, part.model_shards, tuple(part.mesh_shape),
            NamedShardingCache.get(part.mesh, part.mesh_axes),
            NamedShardingCache.get(part.mesh, part.carry_axes),
            _carry_layout(step_fn, carry) if blocked else whole,
            step, traces, chunk_rows,
        )

    def _validate_model_axis(self, part, step_fn, carry):
        """Fold-time re-validation of an optimistically-granted model
        axis against ground truth the planner lacked: the step function's
        blocked-carry protocol and the REAL featurized width sitting in
        the estimator's init carry. Any failure demotes to the row-only
        layout on the SAME mesh (``demote_model_axis`` — chunk geometry
        and the armed durable cursor stay valid); a demotion that leaves
        no row axis to shard returns ``None`` (single-device fold)."""
        import jax

        from ..parallel.partitioner import (
            R_BELOW_WIDTH_FLOOR,
            R_MODEL_INDIVISIBLE,
            demote_model_axis,
            partition_min_width_per_shard,
        )

        p_m, floor = part.model_shards, partition_min_width_per_shard()
        widths = {
            leaf.shape[ax]
            for leaf, ax in zip(
                jax.tree_util.tree_leaves(carry),
                _carry_layout(step_fn, carry) or (),
            )
            if ax is not None
        }
        if not widths:
            reason = R_MODEL_INDIVISIBLE
            detail = (
                f"step {getattr(step_fn, '__name__', type(step_fn).__name__)}"
                " declares no blocked-carry protocol"
            )
        elif any(w % p_m for w in widths):
            reason = R_MODEL_INDIVISIBLE
            detail = (
                f"featurized width {sorted(widths)} not divisible by "
                f"{p_m} model shards"
            )
        elif max(widths) < p_m * floor:
            reason = R_BELOW_WIDTH_FLOOR
            detail = (
                f"featurized width {max(widths)} < {p_m} shards × "
                f"{floor} min cols/shard"
            )
        else:
            return part
        demoted = demote_model_axis(part, reason, detail)
        return demoted if demoted.eligible else None

    def _salvage_shard_loss(self, loss, run: "_FoldRun", step_fn):
        """Absorb a mid-stream device loss: the layout, the (unstacked)
        carry and the windows of the next fold attempt.

        The lost device's carry block is gone; everything else survives:
        the other shards' partials merge via the additive state contract
        into one host carry, and — when the dead shard was block 0, which
        carries the attempt's SEED (the estimator's init, a resume state
        or an earlier salvage) — the seed is added back. The rows only the
        lost shard had folded (its row slice of every chunk dispatched
        this attempt) become recovery windows, re-ingested ahead of the
        untouched remainder. The Partitioner is re-consulted on the
        shrunken mesh; an ineligible decision (down to one device)
        continues single-device — elasticity is never an error
        (docs/RELIABILITY.md "Durable fits").
        """
        import jax
        import numpy as np

        from ..parallel.mesh import mesh_without
        from ..parallel.partitioner import Partitioner, record_decision
        from ..reliability.recovery import get_recovery_log

        lay, report = run.layout, run.report
        label = f"fit_stream[{len(self.members)}ops]"
        # The flat block index is row-major over (data, model): a loss on
        # EITHER axis maps to one data row-group, and the whole group is
        # dropped — with feature-sharded blocks no single column holds a
        # complete partial, so group-mates of a lost device contribute
        # nothing usable on their own. Their rows are re-ingested below.
        lost_row = loss.lost_shard // lay.model_shards
        get_recovery_log().record(
            "shard_loss",
            label,
            lost_shard=loss.lost_shard,
            shards=lay.total_shards,
            chunk_index=loss.chunk_index,
        )
        _names.metric(_names.DURABLE_SHARD_LOSSES).inc()
        report.shard_losses += 1

        # Surviving per-shard partials, merged once on host (O(d²) — the
        # same additive algebra the finish-time reduce runs).
        # keystone: allow-sync
        surviving = _merge_blocks(
            jax.device_get(run.carry), lay.shards, lay.model_shards,
            lay.carry_layout, np, drop_row=lost_row,
        )
        if lost_row == 0:
            # Data row-group 0 carried the attempt's seed (spread over its
            # feature blocks in a 2-D layout) and the whole group was
            # dropped; the seed itself was kept.  # keystone: allow-sync
            surviving = jax.tree_util.tree_map(
                lambda s, a: np.asarray(s) + np.asarray(a),
                surviving,
                jax.device_get(run.seed),
            )

        # Rows only the lost row-group had absorbed: group i held padded
        # rows [i·rps, (i+1)·rps) of each chunk, so the lost LOGICAL rows
        # of a window (s, e) are the contiguous
        # [s+lost_row·rps, min(s+(lost_row+1)·rps, e)). The ordered
        # PrefetchQueue dispatched the attempt's windows in source order.
        rps = lay.chunk_rows // lay.shards
        recovery: List[Tuple[int, int]] = []
        for s, e in run.windows[:run.dispatched]:
            lo = s + lost_row * rps
            hi = min(s + (lost_row + 1) * rps, e)
            if lo < hi:
                recovery.append((lo, hi))
        remaining = list(run.windows[run.dispatched:])

        decision = Partitioner(
            mesh=mesh_without(lay.part.mesh, loss.lost_shard)
        ).decide_stream(
            label, lay.chunk_rows, rows=self.num_examples, record=False
        )
        # Metrics yes, plan report no: the report is documented as "the
        # last PLAN's decisions" and a mid-fold re-decision is runtime.
        record_decision(decision, to_report=False)
        chunk_rows = lay.chunk_rows
        if decision.eligible:
            chunk_rows = decision.chunk_rows or chunk_rows
        layout = self._layout(
            decision if decision.eligible else None, step_fn, surviving,
            chunk_rows,
        )

        report.reingested_chunks += len(recovery)
        _names.metric(_names.DURABLE_REINGESTED_CHUNKS).inc(len(recovery))
        _names.metric(_names.DURABLE_RESUMES).inc(kind="shard")
        get_recovery_log().record(
            "shard_resume",
            label,
            shards=layout.shards,
            recovery_chunks=len(recovery),
            remaining_chunks=len(remaining),
        )
        return layout, surviving, recovery + remaining

    def _record_observation(self, report: StreamReport, data_shape: str) -> None:
        store = _store.get_store()
        if store is None or not report.compute_done_t:
            return
        wall = max(report.compute_done_t[-1], 1e-9)
        store.record(
            f"stream:{chain_class(self.members)}:cr{report.chunk_rows}",
            data_shape,
            chunk_rows=report.chunk_rows,
            rows_per_s=report.num_examples / wall,
            overlap_efficiency=report.overlap_efficiency(),
            stall_s=round(report.stall_s, 6),
            prefetch_depth=report.prefetch_depth,
            host_buffer_peak_bytes=report.host_buffer_peak_bytes,
        )


def _chunk_spec(data: Dataset, chunk_rows: int):
    """ShapeDtypeStructs of one padded chunk at TRANSFER dtype."""
    import jax
    import numpy as np

    if isinstance(data, ArrayDataset):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (chunk_rows,) + tuple(a.shape[1:]),
                transfer_dtype(getattr(a, "dtype", np.float32)),
            ),
            data.data,
        )
    if isinstance(data, ObjectDataset):
        if not len(data):
            raise StreamingFallback("empty dataset")
        first = data.take(1)[0]
        return jax.tree_util.tree_map(
            # Plan-time spec probe on ONE decoded host item, before any
            # chunk flows.  # keystone: allow-sync
            lambda leaf: jax.ShapeDtypeStruct(
                (chunk_rows,) + np.asarray(leaf).shape,
                transfer_dtype(np.asarray(leaf).dtype),
            ),
            first,
        )
    raise StreamingFallback(f"{type(data).__name__} is not chunkable")


def _pad_narrow(a, chunk_rows: int):
    """Narrow a host leaf to its transfer dtype and zero-pad the tail
    chunk to the compiled chunk shape (one shape → one compile)."""
    import numpy as np

    # Operates on the decoded HOST chunk buffer (pre-upload), never a
    # device array.  # keystone: allow-sync
    a = np.asarray(a)
    narrow = transfer_dtype(a.dtype)
    if narrow != a.dtype:
        a = a.astype(narrow)
    rows = a.shape[0]
    if rows < chunk_rows:
        a = np.concatenate(
            [a, np.zeros((chunk_rows - rows,) + a.shape[1:], a.dtype)]
        )
    return np.ascontiguousarray(a)


# ------------------------------------------------------------------- operator


class StreamingFitOperator(EstimatorOperator):
    """An estimator node rewritten onto the streaming engine.

    Wraps the original estimator plus the featurize-chain members that
    were between it and the data source; depends directly on the RAW
    data (plus labels). At force time it streams chunks through ONE
    fused dispatch per chunk into ``estimator.fit_stream``; if run-time
    eligibility fails (small data, unchunkable dataset, untraceable
    chain) it reproduces the materialized path exactly — member-by-member
    batch application then ``fit_datasets`` — so a planned-but-infeasible
    stream can never change results.
    """

    #: PartitionDecision pinned by workflow/optimize.py::PartitionPlanRule
    #: (None = single-device chunk plan; the class default keeps copies
    #: built by MeasuredKnobRule before the partition batch unpinned).
    partition = None

    def __init__(
        self,
        estimator: EstimatorOperator,
        members: Sequence[TransformerOperator],
        chunk_rows: Optional[int] = None,
        prefetch: Optional[int] = None,
    ):
        self.estimator = estimator
        self.members = tuple(members)
        self.chunk_rows = chunk_rows
        self.prefetch = prefetch

    @property
    def label(self) -> str:
        est = getattr(self.estimator, "label", type(self.estimator).__name__)
        return f"StreamFit[{est}+{len(self.members)}ops]"

    @property
    def solver_precision(self):
        """The wrapped estimator's measured precision pin, surfaced so the
        inherited ``EstimatorOperator.execute`` scopes the whole fit
        (stream and materialized-fallback paths alike) under it."""
        return getattr(self.estimator, "solver_precision", None)

    def fit_datasets(self, datasets: List[Dataset]) -> TransformerOperator:
        data = datasets[0]
        labels = datasets[1] if len(datasets) > 1 else None
        chunk_rows = self.chunk_rows or stream_chunk_rows()
        with _spans.span(
            "stream:fit",
            estimator=str(getattr(self.estimator, "label", "")),
            members=len(self.members),
            chunk_rows=chunk_rows,
        ) as span:
            # A planned-but-unknowable head (Cacher etc.) may yield a
            # Dataset subclass without even a length — that is a
            # fallback, not a crash (the materialized path handles it).
            try:
                n_rows = len(data)
            except Exception:
                n_rows = -1
            if streaming_enabled() and n_rows >= max(
                2 * chunk_rows, stream_min_rows()
            ):
                try:
                    stream = ChunkStream(
                        data,
                        labels,
                        self.members,
                        chunk_rows=chunk_rows,
                        prefetch=self.prefetch,
                        partition=self.partition,
                    )
                    # Durable fits (docs/RELIABILITY.md): with a
                    # checkpoint store attached, arm mid-fit cursor
                    # checkpoints and look for a resume entry a killed
                    # predecessor left behind. A valid entry seeds the
                    # fold (fit_stream's state contract) and the fold
                    # re-ingests only chunks past the cursor; a stale
                    # one is refused (KV306 — VerificationError in
                    # strict mode, which must propagate, not fall back).
                    resume_state = None
                    from .executor import PipelineEnv

                    store = PipelineEnv.get_or_create().checkpoint
                    if store is not None:
                        from ..reliability.durable import arm_durable_fold

                        stream.durable, resume_state = arm_durable_fold(
                            stream, self.estimator, store
                        )
                    if resume_state is not None:
                        span.set_attribute(
                            "resumed_from_chunk", stream.durable.start_chunk
                        )
                        return self.estimator.fit_stream(
                            stream, state=resume_state
                        )
                    return self.estimator.fit_stream(stream)
                except StreamingFallback as e:
                    logger.info(
                        "streaming fit of %s fell back to the materialized "
                        "path: %s", self.label, e,
                    )
                    span.set_attribute("fallback", str(e))
            else:
                span.set_attribute("fallback", "below row floor or disabled")
            featurized = data
            for m in self.members:
                featurized = m.batch_transform([featurized])
            rest = [labels] if labels is not None else []
            return self.estimator.fit_datasets([featurized] + rest)


# ----------------------------------------------------------------- the rule


def _streamable_member(op) -> bool:
    from .fusion import FusedTransformerOperator, is_fusable

    return isinstance(op, FusedTransformerOperator) or is_fusable(op)


class StreamingPlanRule(Rule):
    """Rewrite eligible ``data → featurize-chain → estimator`` shapes
    onto the streaming engine.

    Runs LAST (after auto-cache and fusion, docs/OPTIMIZER.md): the
    chain it absorbs is usually already one FusedTransformerOperator,
    whose members it flattens into the per-chunk dispatch. A chain
    member is absorbable under exactly the fusion rules (array-in/
    array-out, single consumer, unary, outside the prefix map); the
    walk stops at Cacher nodes, saveable prefixes, and fan-out — the
    stream then starts from that boundary's materialized output.

    Plan-time gates: the estimator advertises ``supports_fit_stream``;
    a known-size head (a bound ``DatasetOperator``) must hold at least
    max(2·chunk, ``KEYSTONE_STREAM_MIN_ROWS``) rows; an unknown-size
    head (e.g. a Cacher) is rewritten only when there is a featurize
    chain to fuse into the chunk dispatches, and the operator's own
    run-time gate makes the final call.
    """

    def __init__(self, chunk_rows: Optional[int] = None):
        self.chunk_rows = chunk_rows

    def apply(self, graph: Graph, prefixes: PrefixMap) -> Tuple[Graph, PrefixMap]:
        if not streaming_enabled():
            return graph, prefixes
        chunk_rows = self.chunk_rows or stream_chunk_rows()
        rewrites = 0
        for node in sorted(graph.nodes):
            if node not in graph.operators:
                continue  # absorbed into an earlier rewrite
            op = graph.get_operator(node)
            if isinstance(op, StreamingFitOperator):
                continue
            if not isinstance(op, EstimatorOperator):
                continue
            if not getattr(op, "supports_fit_stream", False):
                continue
            deps = graph.get_dependencies(node)
            if not deps:
                continue
            dependents = graph.dependents()
            chain: List[NodeId] = []
            cur = deps[0]
            while isinstance(cur, NodeId):
                consumers = dependents.get(cur, [])
                if (
                    len(consumers) == 1
                    and cur not in prefixes
                    and len(graph.get_dependencies(cur)) == 1
                    and _streamable_member(graph.get_operator(cur))
                ):
                    chain.append(cur)
                    cur = graph.get_dependencies(cur)[0]
                else:
                    break
            head = cur
            if isinstance(head, SourceId):
                continue  # unbound input: nothing to chunk at plan time
            head_op = graph.get_operator(head)
            if isinstance(head_op, DatasetOperator):
                ds = head_op.dataset
                if not isinstance(ds, (ArrayDataset, ObjectDataset)):
                    continue
                if len(ds) < max(2 * chunk_rows, stream_min_rows()):
                    continue
            elif not chain:
                # Unknown size AND nothing to fuse per chunk: the
                # rewrite could only reproduce the materialized fit.
                continue

            from .fusion import FusedTransformerOperator

            members: List[TransformerOperator] = []
            for cn in reversed(chain):  # head-first application order
                m = graph.get_operator(cn)
                if isinstance(m, FusedTransformerOperator):
                    members.extend(m.members)
                else:
                    members.append(m)
            streaming_op = StreamingFitOperator(
                op, members, chunk_rows=self.chunk_rows
            )
            graph = graph.set_operator(node, streaming_op)
            graph = graph.set_dependencies(node, (head,) + tuple(deps[1:]))
            for cn in chain:  # estimator-adjacent first: now unreferenced
                graph = graph.remove_node(cn)
            rewrites += 1
        if rewrites:
            _names.metric(_names.STREAM_PLANS).inc(rewrites)
        return graph, prefixes
