"""The solver-agnostic stream-state contract.

``fit_stream`` estimators accumulate *mergeable* state: for the Gram
family that is the ``(AᵀA, AᵀY, Σx, Σy)`` carry ``parallel/linalg.py``
threads through the chunk plan — O(d²), additive over row chunks, and
sufficient to finish a fit with zero data passes. This module freezes
that property into a portable envelope so the statistics captured at fit
time can be persisted, shipped, merged with later traffic, and finished
into a NEW fitted transformer without ever refitting from scratch — the
heart of the continuous-refit loop (docs/REFIT.md).

The contract is deliberately NOT Gram-specific: an envelope names its
accumulation ``kind`` and carries an opaque host-numpy carry pytree plus
the example count. ``merge_stream_states`` applies the kind's merge rule
(``additive`` today; a future sketch tier registers its own), so the
Panther-style sketched solvers (PAPERS.md) ride the same loop by
exporting a different kind with O(s·d) carries.

Estimator surface (the three ``supports_fit_stream`` estimators —
``LinearMapEstimator``, ``BlockLeastSquaresEstimator``, and the
``LeastSquaresEstimator`` meta-solver — all implement it):

- ``fit_stream(stream, state=None)`` — ``state`` seeds the fold carry
  with previously captured statistics, so new chunks EXTEND the old fit.
- ``export_stream_state()`` — the envelope captured by this instance's
  most recent ``fit_stream`` (host numpy; safe to pickle), or ``None``.
- ``merge_stream_state(a, b)`` — combine two envelopes (disjoint data).
- ``finish_from_state(state)`` — a fitted transformer from statistics
  alone: no stream, no data, one device round for the solve.

Persistence rides the reliability checkpoint store
(:class:`~keystone_tpu.reliability.checkpoint.CheckpointStore`): the
same atomic-write ``<digest>.pkl`` directory training checkpoints and
serving artifacts already share, keyed by :func:`stream_state_key`.

Import discipline: stdlib + numpy only at module scope (jax loads
lazily inside the few device touch points), so the serving/refit control
plane can import this without paying a backend import.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

#: Envelope format — bump when the layout changes; loads refuse unknown
#: versions loudly rather than mis-merging silently.
FORMAT_VERSION = 1

#: kind → merge rule. "additive" is the Gram family's algebra (leafwise
#: sum of carries, sum of example counts). The sketch tier's carry
#: (keystone_tpu/sketch) is additive by construction — every row's
#: contribution is a deterministic function of its absolute index — so
#: it registers the SAME rule and inherits merge/scaled()/resume whole.
MERGE_RULES: Dict[str, str] = {"gram": "additive", "sketch": "additive"}

#: Per-kind meta keys that must AGREE for two envelopes to combine
#: (lenient when either side never recorded them — old envelopes).
#: Sketch carries are sums of hash-seeded row contributions: adding
#: sketches drawn from different (variant, seed) maps is algebra on
#: unrelated projections and must fail loudly.
MERGE_META_KEYS: Dict[str, Tuple[str, ...]] = {
    "sketch": ("sketch_variant", "sketch_seed"),
}


class StateMismatch(ValueError):
    """Two envelopes (or an envelope and a stream) that can never be
    combined: different kinds, shapes, or format versions. Raised BEFORE
    any accumulation happens — a mismatched merge must fail loudly, not
    produce statistics that solve to garbage."""


@dataclass
class StreamState:
    """One estimator's exported sufficient statistics.

    ``carry`` is a tuple of host numpy arrays (the estimator's fold
    carry, device-fetched), ``num_examples`` the rows it has absorbed,
    ``meta`` whatever the estimator needs to finish (d, k, reg...).
    """

    kind: str
    estimator: str
    num_examples: int
    carry: Tuple[np.ndarray, ...]
    meta: Dict[str, Any] = field(default_factory=dict)
    format_version: int = FORMAT_VERSION

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.carry))

    def scaled(self, decay: float) -> "StreamState":
        """Exponential forgetting for additive kinds: every statistic
        (and the effective example count) scaled by ``decay`` ∈ (0, 1].
        Folding new rows on a decayed state is a recency-weighted fit —
        the knob that lets a drifting workload's OLD distribution stop
        dominating the Gram (docs/REFIT.md). ``decay=1`` is a no-op;
        the algebra stays exact because the centering identity uses the
        same effective count the sums were scaled by."""
        if not 0.0 < decay <= 1.0:
            raise StateMismatch(f"decay must be in (0, 1], got {decay}")
        if decay == 1.0:
            return self
        return StreamState(
            kind=self.kind,
            estimator=self.estimator,
            num_examples=max(int(round(self.num_examples * decay)), 1),
            carry=tuple(np.asarray(a) * decay for a in self.carry),
            meta=dict(self.meta),
            format_version=self.format_version,
        )

    def describe(self) -> Dict[str, Any]:
        """Telemetry/ledger view — shapes and counts, never payloads."""
        return {
            "kind": self.kind,
            "estimator": self.estimator,
            "num_examples": int(self.num_examples),
            "carry_shapes": [tuple(a.shape) for a in self.carry],
            "nbytes": self.nbytes(),
            "format_version": self.format_version,
        }


def _check_compatible(a: StreamState, b: StreamState) -> None:
    if a.format_version != b.format_version:
        raise StateMismatch(
            f"format versions differ: {a.format_version} vs {b.format_version}"
        )
    if a.kind != b.kind:
        raise StateMismatch(f"state kinds differ: {a.kind!r} vs {b.kind!r}")
    shapes_a = [tuple(x.shape) for x in a.carry]
    shapes_b = [tuple(x.shape) for x in b.carry]
    if shapes_a != shapes_b:
        raise StateMismatch(
            f"carry shapes differ: {shapes_a} vs {shapes_b} — these "
            "statistics were captured over different feature spaces"
        )
    for key in MERGE_META_KEYS.get(a.kind, ()):
        va, vb = a.meta.get(key), b.meta.get(key)
        if va is not None and vb is not None and va != vb:
            raise StateMismatch(
                f"{a.kind!r} states disagree on {key}: {va!r} vs {vb!r} — "
                "carries under different sketch maps cannot be summed"
            )


def merge_stream_states(a: StreamState, b: StreamState) -> StreamState:
    """Combine two envelopes captured over DISJOINT data. For additive
    kinds the merged statistics are exactly what one pass over the union
    would have produced — the property the round-trip tests pin."""
    _check_compatible(a, b)
    rule = MERGE_RULES.get(a.kind)
    if rule != "additive":
        raise StateMismatch(
            f"no merge rule for state kind {a.kind!r} "
            f"(known: {sorted(MERGE_RULES)})"
        )
    return StreamState(
        kind=a.kind,
        estimator=a.estimator,
        num_examples=int(a.num_examples) + int(b.num_examples),
        carry=tuple(
            np.asarray(x) + np.asarray(y) for x, y in zip(a.carry, b.carry)
        ),
        meta=dict(a.meta),
        format_version=a.format_version,
    )


# --------------------------------------------------------------- persistence


def stream_state_key(name: str) -> str:
    """Stable checkpoint-store digest for a named refit state. Namespaced
    so refit states can never collide with prefix-digest fit entries in
    a shared store directory."""
    return hashlib.sha1(f"keystone-refit-state:{name}".encode()).hexdigest()


def save_stream_state(store: Any, name: str, state: StreamState) -> bool:
    """Persist ``state`` under ``name`` in a reliability
    :class:`CheckpointStore` (atomic tmp+rename write). Returns False
    when the store refused (unpicklable — should never happen for numpy
    carries)."""
    return store.save(None, state, digest=stream_state_key(name))


def load_stream_state(store: Any, name: str) -> Optional[StreamState]:
    """The persisted state for ``name``, or None (missing/torn entries
    are misses, the checkpoint-store contract)."""
    from ..reliability.checkpoint import _MISS

    value = store.lookup(None, digest=stream_state_key(name))
    if value is _MISS or not isinstance(value, StreamState):
        return None
    if value.format_version != FORMAT_VERSION:
        return None  # refuse to extend a layout this build doesn't speak
    return value


class _HostFetch:
    """Device arrays that cross to the host when somebody asks for them,
    once. Until then they stay where the fold left them: an estimator
    lives as long as the pipeline that holds it (the prefix table lets a
    fit go with its pipeline: workflow/prefix.py), so a fit whose state
    nobody exports pays for no copy, and one whose pipeline is kept holds
    the carry on the device until it is asked for or dropped."""

    def __init__(self, arrays):
        self._arrays = tuple(arrays)
        self._host: Optional[Tuple[np.ndarray, ...]] = None

    def result(self) -> Tuple[np.ndarray, ...]:
        if self._host is None:
            import jax

            # Export crosses to host by definition: the envelope must
            # pickle into the checkpoint store.  # keystone: allow-sync
            self._host = tuple(np.asarray(jax.device_get(a)) for a in self._arrays)
            self._arrays = ()  # the device buffers go with the copy
        return self._host

    def __getstate__(self):
        # An estimator that is copied or pickled between its fit and its
        # export takes the fetched arrays with it, not the device's.
        self.result()
        return dict(vars(self))


# ------------------------------------------------------------ the Gram mixin


class GramStreamStateMixin:
    """State-contract plumbing shared by the Gram-family estimators.

    Concrete estimators implement ``_finish_from_stats(carry, n)`` —
    fitted transformer from the (device) carry and total row count — and
    get ``export_stream_state`` / ``merge_stream_state`` /
    ``finish_from_state`` plus the fold-side helpers for free. The
    captured envelope lands on ``self._stream_state`` (underscored on
    purpose: excluded from checkpoint digests, so capturing state never
    changes an estimator's structural identity).
    """

    stream_state_kind = "gram"

    def export_stream_state(self) -> Optional[StreamState]:
        """The envelope of this instance's last streamed fit, its
        statistics on the host (fetched here, the first time it is
        asked for: ``_capture_state`` left them on the device)."""
        fetch = vars(self).pop("_stream_fetch", None)
        if fetch is not None:
            self._stream_state.carry = fetch.result()
        return getattr(self, "_stream_state", None)

    def merge_stream_state(self, a: StreamState, b: StreamState) -> StreamState:
        return merge_stream_states(a, b)

    def finish_from_state(self, state: StreamState):
        """A fitted transformer from statistics alone (no data pass).

        The finish is a standalone mesh reduction (the Gram/sketch
        solve), so it opts into the co-scheduler when one is installed
        (docs/SCHEDULING.md): admitted into an idle gap it is priced,
        spanned, and harvested; under pressure the deferral is ledgered
        but the solve still runs — callers (publish, rollback, boot)
        need the model synchronously."""
        import jax.numpy as jnp

        from ..sched.scheduler import maybe_lease

        self._check_state_kind(state)
        carry = tuple(jnp.asarray(a) for a in state.carry)
        width, classes = (
            (int(carry[1].shape[0]), int(carry[1].shape[-1]))
            if len(carry) > 1 and getattr(carry[1], "ndim", 0) >= 1
            else (0, 0)
        )
        with maybe_lease(
            f"{type(self).__name__}:finish", "finish",
            rows=int(state.num_examples), width=width, classes=classes,
        ):
            return self._finish_from_stats(carry, int(state.num_examples))

    # ------------------------------------------------------- fold-side hooks
    def _check_state_kind(self, state: StreamState) -> None:
        if state.format_version != FORMAT_VERSION:
            raise StateMismatch(
                f"state format v{state.format_version} != v{FORMAT_VERSION}"
            )
        if state.kind != self.stream_state_kind:
            raise StateMismatch(
                f"{type(self).__name__} accumulates {self.stream_state_kind!r} "
                f"state, got {state.kind!r}"
            )

    def _seed_carry(self, state: Optional[StreamState], d: int, k: int):
        """The fold's initial carry: fresh zeros, or ``state``'s
        statistics (shape-checked against the stream's featurized
        width) so new chunks extend the old fit."""
        from ..parallel import linalg

        if state is None:
            return linalg.gram_stream_init(d, k)
        self._check_state_kind(state)
        want = [(d, d), (d, k), (d,), (k,)]
        got = [tuple(a.shape) for a in state.carry]
        if got != want:
            raise StateMismatch(
                f"resume state shaped {got} cannot seed a (d={d}, k={k}) "
                f"stream (want {want})"
            )
        import jax
        import jax.numpy as jnp

        carry = tuple(jnp.asarray(a, jnp.float32) for a in state.carry)
        # One-time fold setup, and load-bearing: the fold's step jit
        # DONATES the carry, and with a warm compilation cache the first
        # chunk dispatches immediately — donating a buffer whose async
        # host→device transfer has not committed corrupts the seed
        # (observed as nondeterministic garbage fits). Commit the O(d²)
        # transfer before the donating dispatch can race it.
        # keystone: allow-sync
        return jax.block_until_ready(carry)

    def _capture_state(self, carry, n_total: int, **meta: Any) -> StreamState:
        """Remember the post-fold carry and its portable envelope on the
        instance for ``export_stream_state``, which fetches it. The
        fetch is O(d²) (1.08 GB and a third of a second at TIMIT's
        width: PERF.md section 6, PR 30) and nothing in the fit reads
        its result, so no fit pays for it: whoever exports does (on a
        thread until PR 34, for every fit, with 1.08 GB of host memory
        to allocate and free each time: PERF.md section 6, PR 34)."""
        state = StreamState(
            kind=self.stream_state_kind,
            estimator=f"{type(self).__module__}.{type(self).__qualname__}",
            num_examples=int(n_total),
            carry=(),  # the host arrays, once `export_stream_state` has them
            meta=dict(meta),
        )
        self._stream_fetch = _HostFetch(carry)
        self._stream_state = state
        return state


# ---------------------------------------------------------- the sketch mixin


class SketchStreamStateMixin(GramStreamStateMixin):
    """State-contract plumbing for the sketch tier (keystone_tpu/sketch).

    Identical protocol to the Gram mixin — the carry is additive, so
    export/merge/``scaled()``/resume are inherited verbatim — with a
    different kind tag, a 5-leaf ``(SA, SY, s1, Σx, Σy)`` carry whose
    leading dimension is the sketch size s (not d), and a meta
    compatibility check: a resumed fold must keep accumulating under the
    SAME (variant, seed) sketch map or the sum is meaningless.
    """

    stream_state_kind = "sketch"

    def _check_state_kind(self, state: StreamState) -> None:
        super()._check_state_kind(state)
        mine = getattr(self, "stream_state_meta", {}) or {}
        for key in MERGE_META_KEYS["sketch"]:
            va, vb = state.meta.get(key), mine.get(key)
            if va is not None and vb is not None and va != vb:
                raise StateMismatch(
                    f"resume state's {key}={va!r} != estimator's {vb!r} — "
                    "a fold cannot extend a sketch drawn from a different map"
                )

    def _seed_carry(self, state: Optional[StreamState], s: int, d: int, k: int):
        """Fresh zeros, or ``state``'s sketch seeded onto device —
        shape-checked so a fold never extends statistics captured over a
        different (s, d, k) geometry."""
        if state is None:
            from ..sketch.core import sketch_stream_init

            return sketch_stream_init(s, d, k)
        self._check_state_kind(state)
        want = [(s, d), (s, k), (s,), (d,), (k,)]
        got = [tuple(a.shape) for a in state.carry]
        if got != want:
            raise StateMismatch(
                f"resume state shaped {got} cannot seed a (s={s}, d={d}, "
                f"k={k}) sketch stream (want {want})"
            )
        import jax
        import jax.numpy as jnp

        carry = tuple(jnp.asarray(a, jnp.float32) for a in state.carry)
        # Same commit-before-donate discipline as the Gram seed: the fold
        # step donates this buffer on the first dispatch.
        # keystone: allow-sync
        return jax.block_until_ready(carry)
