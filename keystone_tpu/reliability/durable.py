"""Durable elastic fits: the mid-stream resume contract.

The reference got mid-job durability from Spark lineage — a killed job
re-ran and already-materialized blocks short-circuited. Our equivalents
so far cover the *edges* of a fit: completed prefixes restore from the
:class:`~keystone_tpu.reliability.checkpoint.CheckpointStore`, and the
refit state contract (refit/state.py) persists sufficient statistics
*between* folds. What neither covers is the inside of one long
``fit_stream``: a SIGKILL at chunk 4000 of 5000 used to discard every
chunk already folded, and a device lost from the mesh mid-fit had no
recovery path at all.

This module is the contract both recoveries share (docs/RELIABILITY.md
"Durable fits"):

- :class:`StreamCursor` — WHERE a streamed fit was: absolute chunk
  index, rows consumed, the compiled chunk geometry, and the identity
  fingerprints (dataset/labels content digests, featurize-chain digest,
  featurized width/dtype) that make resuming safe.
- :class:`ResumeEntry` — cursor + the mesh-independent
  :class:`~keystone_tpu.refit.state.StreamState` snapshot (per-shard
  partials already merged via the additive contract), persisted in the
  CheckpointStore under :func:`resume_key`.
- :func:`resume_key` is deliberately COARSER than the cursor's
  fingerprints: it names the logical fit (estimator × chain class ×
  row count) so a fresh process re-planning the same pipeline *finds*
  the entry — and the verifier (``verify_stream_resume``, KV306) then
  refuses it when any content fingerprint disagrees. Stale resume must
  be a loud refusal, never silent corruption.
- :class:`ShardLossError` — the mid-stream signal that a device left
  the mesh (raised by the ``parallel.shard_loss`` probe site); the
  streaming engine catches it, salvages surviving per-shard partials,
  and re-plans on the shrunken mesh (workflow/streaming.py).

The contract is solver-agnostic on purpose: envelopes carry an opaque
host-numpy carry (whatever ``kind`` the estimator accumulates), so the
sketch-state tier the ROADMAP names inherits durability for free.

Import discipline: stdlib + numpy only at module scope (same rule as
refit/state.py) — the control plane imports this without paying for a
backend.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..envknobs import env_int
from ..refit.state import FORMAT_VERSION, StreamState
from .checkpoint import _MISS
from .recovery import get_recovery_log

#: Resume-entry layout version — bumped independently of the StreamState
#: format; loads refuse unknown versions (a miss, never a mis-resume).
RESUME_FORMAT_VERSION = 1


class ShardLossError(RuntimeError):
    """A device left the mesh mid-stream. Raised at the
    ``parallel.shard_loss`` probe site (one call per sharded chunk
    dispatch) and caught by ``ChunkStream.fold``, which salvages the
    surviving shards' partials and continues on the shrunken mesh."""

    def __init__(self, lost_shard: int, chunk_index: int, shards: int):
        self.lost_shard = int(lost_shard)
        self.chunk_index = int(chunk_index)
        self.shards = int(shards)
        super().__init__(
            f"shard {lost_shard}/{shards} lost at chunk {chunk_index}"
        )


# ----------------------------------------------------------------- knobs


def stream_ckpt_chunks(n_rows: int) -> int:
    """Chunks between mid-fit checkpoint commits; 0 = off.

    ``KEYSTONE_STREAM_CKPT_CHUNKS`` set explicitly wins (0 disables even
    for huge fits). Unset, checkpointing auto-arms at every
    ``KEYSTONE_STREAM_CKPT_AUTO_EVERY`` (default 32) chunks once the
    dataset holds at least ``KEYSTONE_STREAM_CKPT_AUTO_ROWS`` rows
    (default 1e6) — small fits are cheaper to redo than to checkpoint.
    """
    explicit = env_int("KEYSTONE_STREAM_CKPT_CHUNKS", -1)
    if explicit >= 0:
        return explicit
    if n_rows >= env_int("KEYSTONE_STREAM_CKPT_AUTO_ROWS", 1_000_000):
        return max(1, env_int("KEYSTONE_STREAM_CKPT_AUTO_EVERY", 32))
    return 0


def shard_loss_index(shards: int) -> int:
    """Which shard a *simulated* loss removes (default: the last).
    ``KEYSTONE_SHARD_LOSS_INDEX`` overrides so tests can exercise the
    seed-bearing shard-0 path. Real device loss would carry the failed
    device's identity instead of this knob."""
    idx = env_int("KEYSTONE_SHARD_LOSS_INDEX", shards - 1)
    return min(max(idx, 0), shards - 1)


# ------------------------------------------------------------- identity


def content_digest(value: Any) -> str:
    """Process-stable content digest of a dataset/operator attribute —
    the checkpoint layer's ``_value_token`` hashed, so the rules (array
    content, dataset payload + length, scalar config) stay in one place."""
    from .checkpoint import _value_token

    return hashlib.sha1(repr(_value_token(value)).encode()).hexdigest()


#: Above this, array leaves fingerprint by shape/dtype + a deterministic
#: strided row sample instead of a full-content pass — the fits where
#: durability auto-arms are exactly the ones where an O(n·d) host hash
#: at plan time would betray the streaming path's no-full-pass design.
FULL_HASH_MAX_BYTES = 64 << 20
#: Rows sampled (first + last always included) for oversized leaves.
FINGERPRINT_SAMPLE_ROWS = 257


def dataset_fingerprint(ds: Any) -> str:
    """Process-stable fingerprint of a dataset for resume validation.

    Small payloads hash in full (identical to :func:`content_digest`
    semantics); array leaves past :data:`FULL_HASH_MAX_BYTES` hash their
    shape/dtype plus a deterministic evenly-strided row sample — bounded
    work at plan time, at the cost of missing a drift confined entirely
    to unsampled rows (a deliberate trade: KV306 is a stale-RESUME
    guard, not a data-integrity audit; the full-content prefix digests
    still govern completed-fit checkpoints)."""
    data = getattr(ds, "data", None)
    n = getattr(ds, "num_examples", None)
    if data is None or n is None:
        return content_digest(ds)
    h = hashlib.sha1(f"ds:n{int(n)}".encode())
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(data)
    except Exception:
        leaves = [data]
    for leaf in leaves:
        if not isinstance(leaf, np.ndarray):
            h.update(content_digest(leaf).encode())
            continue
        h.update(f"{leaf.dtype}{leaf.shape}".encode())
        if leaf.nbytes <= FULL_HASH_MAX_BYTES or leaf.ndim == 0:
            h.update(np.ascontiguousarray(leaf))
        else:
            rows = np.unique(
                np.linspace(
                    0, leaf.shape[0] - 1, FINGERPRINT_SAMPLE_ROWS
                ).astype(np.int64)
            )
            h.update(rows.tobytes())
            h.update(np.ascontiguousarray(leaf[rows]))
    return h.hexdigest()


def chain_digest(members: Tuple[Any, ...]) -> str:
    """Content digest of the featurize chain BETWEEN the data source and
    the estimator — operator class identity plus content-hashed state
    (weights included: a chain with different weights produces different
    features, so resuming across it would corrupt the fit)."""
    from .checkpoint import _op_token

    return hashlib.sha1(
        repr([_op_token(m) for m in members]).encode()
    ).hexdigest()


def resume_key(estimator: Any, members: Tuple[Any, ...], n_rows: int) -> str:
    """Checkpoint-store digest naming the LOGICAL fit. Coarser than the
    cursor's validation fingerprints by design (module docstring): same
    estimator class, same chain op sequence, same row count → same key,
    so a re-planned pipeline finds the entry and the KV306 validation
    gets to rule on whether the contents still agree."""
    from ..workflow.streaming import chain_class

    est = f"{type(estimator).__module__}.{type(estimator).__qualname__}"
    token = f"keystone-stream-resume:{est}:{chain_class(members)}:n{n_rows}"
    return hashlib.sha1(token.encode()).hexdigest()


# -------------------------------------------------------------- envelope


@dataclass
class StreamCursor:
    """Where a streamed fit stood when its state was committed."""

    chunk_index: int          # absolute chunks fully folded
    rows_consumed: int        # logical dataset rows those chunks held
    chunk_rows: int           # compiled chunk geometry (must match to resume)
    dataset_digest: str
    labels_digest: str
    chain_digest: str
    feature_width: int
    feature_dtype: str
    mesh_shape: Tuple[int, ...] = ()
    shards: int = 1
    # Layout METADATA only (like mesh_shape/shards): the snapshot carry
    # itself is always merged to the mesh-independent single-device
    # shape, so resume re-plans freely across 1-D and 2-D meshes.
    model_shards: int = 1


@dataclass
class ResumeEntry:
    """One persisted mid-fit snapshot: cursor + mesh-independent state."""

    cursor: StreamCursor
    state: StreamState
    #: rows the fold's SEED state held that did not come from this
    #: dataset (a refit-seeded fold); the resume arithmetic needs them
    #: separated from ``rows_consumed`` so totals stay exact.
    seed_rows: int = 0
    format_version: int = RESUME_FORMAT_VERSION


def save_resume_entry(store: Any, key: str, entry: ResumeEntry) -> bool:
    return store.save(None, entry, digest=key)


def load_resume_entry(store: Any, key: str) -> Optional[ResumeEntry]:
    """The persisted entry, or None (missing/torn/foreign versions are
    misses — the checkpoint-store contract)."""
    value = store.lookup(None, digest=key)
    if value is _MISS or not isinstance(value, ResumeEntry):
        return None
    if value.format_version != RESUME_FORMAT_VERSION:
        return None
    if value.state.format_version != FORMAT_VERSION:
        return None
    return value


def clear_resume_entry(store: Any, key: str) -> None:
    store.delete(key)


# --------------------------------------------------------- fold-side plan


@dataclass
class DurableFold:
    """The durability plan ``ChunkStream.fold`` executes (built by the
    streaming operator's arm step; ``None`` on a stream = today's
    behavior, byte for byte)."""

    store: Any                      # reliability CheckpointStore
    key: str                        # resume-entry digest
    kind: str                       # stream-state kind ("gram", ...)
    estimator: str                  # estimator qualname for the envelope
    ckpt_every: int                 # chunks between commits (0 = never)
    #: Extra envelope meta the committed StreamState must carry (e.g. the
    #: sketch tier's {sketch_variant, sketch_seed} — what a resumed fold
    #: needs to keep accumulating under the SAME sketch map).
    state_meta: Dict[str, Any] = field(default_factory=dict)
    fingerprints: Dict[str, Any] = field(default_factory=dict)
    start_chunk: int = 0            # chunks to skip (resumed fold)
    resume_rows: int = 0            # rows those skipped chunks held
    seed_rows: int = 0              # non-dataset rows in the seed state
    suspended: bool = False         # no more commits this fold (shard loss)
    _committed_at: int = field(default=-1, repr=False)

    def suspend(self) -> None:
        """The fold lost a shard: recovery windows break the canonical
        chunk-prefix ordering a cursor describes, so checkpoints suspend
        for the remainder of the fold."""
        self.suspended = True

    def at_boundary(self, dispatched: int, snapshot, force: bool = False) -> bool:
        """The chunk-boundary decision, and the commit where it says so:
        the fold is about to dispatch chunk ``dispatched`` of its attempt
        (``start_chunk + dispatched`` of the fit). Commits every
        ``ckpt_every`` chunks, or at any boundary when ``force`` (the
        lease is yielding here); never before the first chunk, twice at
        one index, or while suspended. ``snapshot()`` is called only for
        a commit and returns :meth:`commit`'s other arguments — it holds
        the commit-before-continue barrier. True when a cursor was
        written."""
        due = force or (
            self.ckpt_every > 0 and dispatched % self.ckpt_every == 0
        )
        if (
            not due
            or self.suspended
            or dispatched <= 0
            or dispatched == self._committed_at
        ):
            return False
        self._committed_at = dispatched
        return self.commit(
            chunk_index=self.start_chunk + dispatched, **snapshot()
        )

    def cursor(
        self,
        chunk_index: int,
        rows_consumed: int,
        chunk_rows: int,
        mesh_shape: Tuple[int, ...],
        shards: int,
        model_shards: int = 1,
    ) -> StreamCursor:
        return StreamCursor(
            chunk_index=chunk_index,
            rows_consumed=rows_consumed,
            chunk_rows=chunk_rows,
            mesh_shape=tuple(mesh_shape),
            shards=shards,
            model_shards=model_shards,
            **self.fingerprints,
        )

    def commit(
        self,
        host_carry: Tuple[np.ndarray, ...],
        chunk_index: int,
        rows_consumed: int,
        chunk_rows: int,
        mesh_shape: Tuple[int, ...] = (),
        shards: int = 1,
        model_shards: int = 1,
    ) -> bool:
        """Persist one mid-fit snapshot (atomic tmp+rename underneath).
        Called (by :meth:`at_boundary`) with the carry ALREADY
        host-fetched and shard-merged — the commit-before-continue barrier
        is the fold's snapshot; this is just the write. Best-effort: a
        failed write is ledgered and the fit continues (durability must
        never fail a fit that would have succeeded)."""
        state = StreamState(
            kind=self.kind,
            estimator=self.estimator,
            num_examples=int(self.seed_rows + rows_consumed),
            carry=tuple(np.asarray(a) for a in host_carry),
            meta={**self.state_meta, "durable": True},
        )
        entry = ResumeEntry(
            cursor=self.cursor(
                chunk_index, rows_consumed, chunk_rows, mesh_shape, shards,
                model_shards,
            ),
            state=state,
            seed_rows=self.seed_rows,
        )
        ok = save_resume_entry(self.store, self.key, entry)
        if ok:
            from ..obs import names as _names

            _names.metric(_names.DURABLE_CHECKPOINTS).inc()
            get_recovery_log().record(
                "stream_checkpoint",
                self.estimator,
                chunk_index=chunk_index,
                rows_consumed=rows_consumed,
                key=self.key[:12],
            )
        else:
            get_recovery_log().record(
                "stream_checkpoint_failed",
                self.estimator,
                chunk_index=chunk_index,
                key=self.key[:12],
            )
        return ok

    def complete(self) -> None:
        """The fit finished: a resume entry pointing into its middle
        must not outlive it (a later identical fit would 'resume' work
        that is already done and persisted whole by the prefix store)."""
        clear_resume_entry(self.store, self.key)


# -------------------------------------------------------------------- arming


def arm_durable_fold(
    stream: Any, estimator: Any, store: Any,
    ckpt_every: Optional[int] = None,
):
    """Build a stream's durability plan and, when a valid resume entry
    exists, the :class:`StreamState` that seeds the fold.

    Returns ``(durable, resume_state)`` — ``(None, None)`` when
    durability stays off (no store, checkpointing off for this size and
    no entry to resume). Called by ``StreamingFitOperator`` after the
    chunk geometry is final (partition rounding included).

    ``ckpt_every`` overrides the size-based :func:`stream_ckpt_chunks`
    cadence — the mesh scheduler arms checkpoints on folds far below the
    auto-arm row threshold because its preemption contract (yield at a
    chunk boundary, resume from the cursor) needs a committable cursor
    regardless of fold size (docs/SCHEDULING.md).

    Refusal ladder for an existing entry:

    - geometry drift (a re-planned/tuned ``chunk_rows`` that no longer
      matches the cursor's) — the entry is *discarded* with a
      ``resume_discard`` ledger event: chunk boundaries can't realign,
      but nothing is corrupt;
    - fingerprint drift (dataset/labels/chain content, featurized
      width/dtype) — the entry is *refused* via ``verify_stream_resume``
      (KV306): warn mode re-ingests from scratch, ``KEYSTONE_VERIFY=
      strict`` raises :class:`~keystone_tpu.workflow.verify.
      VerificationError` — stale resume is corruption, not a knob.
    """
    from ..obs import names as _names
    from ..workflow.verify import (
        VerificationError,
        verification_mode,
        verify_stream_resume,
    )

    members = stream.members
    n = stream.num_examples
    every = ckpt_every if ckpt_every is not None else stream_ckpt_chunks(n)
    key = resume_key(estimator, members, n)
    entry = load_resume_entry(store, key)
    if every <= 0 and entry is None:
        return None, None

    # Content fingerprints — the KV306 validation surface. feature_aval
    # raises StreamingFallback for unchunkable shapes, which the caller
    # already treats as "stream ineligible".
    import jax

    leaves = jax.tree_util.tree_leaves(stream.feature_aval())
    if len(leaves) == 1 and len(leaves[0].shape) == 2:
        width, dtype = int(leaves[0].shape[1]), str(leaves[0].dtype)
    else:
        width, dtype = -1, "|".join(str(l.dtype) for l in leaves)
    fingerprints = {
        "dataset_digest": dataset_fingerprint(stream.data),
        "labels_digest": dataset_fingerprint(stream.labels),
        "chain_digest": chain_digest(members),
        "feature_width": width,
        "feature_dtype": dtype,
    }
    # Meta-estimators pick their concrete rung per stream (width-based
    # ladder), so the committed state's kind/meta must come from the
    # CHOSEN rung, not the class default — the optional *_for(stream)
    # protocol resolves both after the geometry is final.
    kind_for = getattr(estimator, "stream_state_kind_for", None)
    kind = (
        kind_for(stream) if callable(kind_for)
        else getattr(estimator, "stream_state_kind", "gram")
    )
    meta_for = getattr(estimator, "stream_state_meta_for", None)
    if callable(meta_for):
        state_meta = dict(meta_for(stream) or {})
    else:
        state_meta = dict(getattr(estimator, "stream_state_meta", {}) or {})
    durable = DurableFold(
        store=store,
        key=key,
        kind=kind,
        estimator=f"{type(estimator).__module__}.{type(estimator).__qualname__}",
        ckpt_every=every,
        state_meta=state_meta,
        fingerprints=fingerprints,
    )
    if entry is None:
        return durable, None

    if entry.cursor.chunk_rows != stream.chunk_rows:
        get_recovery_log().record(
            "resume_discard",
            durable.estimator,
            reason="chunk-geometry-drift",
            entry_chunk_rows=entry.cursor.chunk_rows,
            planned_chunk_rows=stream.chunk_rows,
        )
        _names.metric(_names.DURABLE_RESUME_REFUSED).inc(reason="geometry")
        clear_resume_entry(store, key)
        return durable, None

    report = verify_stream_resume(entry.cursor, fingerprints)
    if not report.ok:
        get_recovery_log().record(
            "resume_refused",
            durable.estimator,
            codes=sorted({d.code for d in report.errors()}),
            fields=sorted(
                {str(d.details.get("field")) for d in report.errors()}
            ),
        )
        _names.metric(_names.DURABLE_RESUME_REFUSED).inc(reason="kv306")
        if verification_mode() == "strict":
            # Strict refuses the FIT, not the entry: the mismatch may be
            # THIS run's mistake (wrong dataset), and deleting here would
            # destroy the legitimate job's checkpoint work. Only the warn
            # path — which proceeds to a from-scratch refit that will
            # overwrite the entry anyway — retires it.
            raise VerificationError(report)
        clear_resume_entry(store, key)
        return durable, None

    durable.start_chunk = int(entry.cursor.chunk_index)
    durable.resume_rows = int(entry.cursor.rows_consumed)
    durable.seed_rows = int(entry.seed_rows)
    _names.metric(_names.DURABLE_RESUMES).inc(kind="crash")
    get_recovery_log().record(
        "stream_resume",
        durable.estimator,
        chunk_index=entry.cursor.chunk_index,
        rows_consumed=entry.cursor.rows_consumed,
        key=key[:12],
    )
    return durable, entry.state
