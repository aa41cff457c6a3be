"""SketchedLeastSquaresEstimator: sketched-vs-exact parity in both
finish regimes, the sketch-and-precondition in-core path (divergence
guard included), and the kind="sketch" state contract — merge/scaled/
resume round-trips under GLOBAL row-index semantics (docs/SOLVERS.md)."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.refit.state import (
    StateMismatch,
    StreamState,
    merge_stream_states,
)
from keystone_tpu.sketch.core import (
    MASK_INDEX_EXACT_ROWS,
    sketch_stream_init,
    sketch_stream_step,
)
from keystone_tpu.sketch.solvers import (
    SketchedLeastSquaresEstimator,
    default_sketch_size,
)
from keystone_tpu.workflow.streaming import ChunkStream, StreamingFallback

pytestmark = pytest.mark.sketch

N, D, K, CHUNK = 512, 32, 3, 64


def _stream(x, y, chunk=CHUNK):
    return ChunkStream(ArrayDataset(x), ArrayDataset(y), (), chunk_rows=chunk)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _realizable(n=N, d=D, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, K)).astype(np.float32)
    return x, (x @ w).astype(np.float32)


# ------------------------------------------------------------ parity bounds


@pytest.mark.parametrize("variant", ["countsketch", "srht"])
def test_streamed_primal_matches_exact_small_d(variant):
    """s ≥ d regime: on noiseless realizable data rank(SA) = d pins the
    sketched solution to the exact one — streamed sketch-and-solve vs
    the exact Gram rung, parity ≤ 1e-4 on predictions."""
    x, y = _realizable()
    exact = LinearMapEstimator(reg=1e-6).fit_stream(_stream(x, y))
    ep = np.asarray(exact.apply_arrays(x))
    est = SketchedLeastSquaresEstimator(
        reg=1e-6, sketch_size=2 * D, variant=variant, seed=1
    )
    preds = np.asarray(est.fit_stream(_stream(x, y)).apply_arrays(x))
    assert _rel(preds, ep) <= 1e-4
    state = est.export_stream_state()
    assert state.kind == "sketch" and state.num_examples == N
    assert state.meta["sketch_variant"] == variant


def test_streamed_dual_bounded_on_low_rank_rows():
    """s < d regime (the tier's point — no d×d state): a row-space
    sketch recovers predictions up to the row-space energy it captures,
    so with effective rank ≪ s the train error stays small."""
    rng = np.random.default_rng(2)
    n, d, r, s = 512, 128, 16, 64
    z = rng.normal(size=(n, r)).astype(np.float32)
    basis = rng.normal(size=(r, d)).astype(np.float32) / np.sqrt(r)
    x = (z @ basis + 0.01 * rng.normal(size=(n, d))).astype(np.float32)
    w = rng.normal(size=(d, K)).astype(np.float32) / np.sqrt(d)
    y = (x @ w).astype(np.float32)
    est = SketchedLeastSquaresEstimator(reg=1e-4, sketch_size=s, seed=1)
    preds = np.asarray(est.fit_stream(_stream(x, y)).apply_arrays(x))
    assert np.isfinite(preds).all()
    assert _rel(preds, y) < 0.05


def test_incore_precondition_matches_exact():
    """Sketch-and-precondition on materialized data: PCG refinement on
    the full normal operator reaches solver-grade parity with the exact
    ridge even at modest s."""
    rng = np.random.default_rng(3)
    x, y0 = _realizable(seed=3)
    y = y0 + 0.05 * rng.normal(size=y0.shape).astype(np.float32)
    exact = LinearMapEstimator(reg=1e-3).fit(ArrayDataset(x), ArrayDataset(y))
    ep = np.asarray(exact.apply_arrays(x))
    est = SketchedLeastSquaresEstimator(reg=1e-3, sketch_size=2 * D, seed=1)
    preds = np.asarray(est.fit(ArrayDataset(x), ArrayDataset(y)).apply_arrays(x))
    assert _rel(preds, ep) <= 1e-3


def test_incore_divergence_guard_stays_finite():
    """When s undersamples the row space (underdetermined fit, s well
    below rank) PCG can run away; the residual guard falls back to the
    bounded sketch-only solve — never NaN, never inf."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(64, 256)).astype(np.float32)
    y = rng.normal(size=(64, 2)).astype(np.float32)
    for iters in (0, 16):
        est = SketchedLeastSquaresEstimator(
            reg=1e-3, sketch_size=32, seed=0, refine_iters=iters
        )
        preds = np.asarray(
            est.fit(ArrayDataset(x), ArrayDataset(y)).apply_arrays(x)
        )
        assert np.isfinite(preds).all(), f"iters={iters}"


# -------------------------------------------------------- state contract


def _manual_state(x, y, s, seed, index_base, est):
    """A kind="sketch" envelope folded with GLOBAL row indices starting
    at index_base — what the sharded / durable-cursor paths produce for
    a row range (a fresh ChunkStream restarts indexing at 0, so disjoint
    halves of one dataset are sketched at their true offsets here)."""
    import jax.numpy as jnp

    step = sketch_stream_step(est.variant, seed)
    n, d = x.shape
    carry = sketch_stream_init(s, d, y.shape[1])
    mask = jnp.arange(
        index_base + 1, index_base + n + 1, dtype=jnp.float32
    )[:, None]
    carry = step(carry, jnp.asarray(x), jnp.asarray(y), mask)
    return StreamState(
        kind="sketch",
        estimator="manual",
        num_examples=n,
        carry=tuple(np.asarray(c) for c in carry),
        meta={"sketch_variant": est.variant, "sketch_seed": seed},
    )


def test_merge_at_global_offsets_matches_oneshot():
    """Halves sketched at their true global offsets merge to the one-shot
    streamed carry — the additivity the sharded reduce and shard-loss
    salvage rest on."""
    x, y = _realizable(seed=5)
    s = 2 * D
    est = SketchedLeastSquaresEstimator(reg=1e-3, sketch_size=s, seed=7)
    ref = est.fit_stream(_stream(x, y))
    ref_out = np.asarray(ref.apply_arrays(x))

    half = N // 2
    a = _manual_state(x[:half], y[:half], s, 7, 0, est)
    b = _manual_state(x[half:], y[half:], s, 7, half, est)
    merged = merge_stream_states(a, b)
    assert merged.num_examples == N
    fitted = SketchedLeastSquaresEstimator(
        reg=1e-3, sketch_size=s, seed=7
    ).finish_from_state(merged)
    # Same terms, different order: the one-shot carry adds 8 chunks of 64
    # rows in sequence, the merge adds two 256-row halves, so every
    # float32 sketch entry is a sum of up to N terms taken in another
    # order. Reordering a float32 sum of N terms moves it by about
    # sqrt(N)·eps (2.7e-6 here; the seed's 1e-6 was a guess the CPU
    # backend of jax 0.9.0 misses at 1.06e-6).
    tol = np.sqrt(N) * np.finfo(np.float32).eps
    assert _rel(np.asarray(fitted.apply_arrays(x)), ref_out) <= tol


def test_scaled_state_finishes_to_same_model():
    """scaled(γ) is exponential forgetting: every leaf and the count
    scale together, so the decayed state still solves to the same map.
    reg=None (the scale-aware floor, λ ∝ tr(K)/s) keeps the algebra
    EXACTLY homogeneous — a fixed absolute λ would shift ~1e-5 under γ
    because the ridge no longer tracks the shrunken statistics."""
    x, y = _realizable(seed=6)
    est = SketchedLeastSquaresEstimator(reg=None, sketch_size=2 * D, seed=0)
    est.fit_stream(_stream(x, y))
    state = est.export_stream_state()
    half = state.scaled(0.5)
    assert half.num_examples == state.num_examples // 2
    np.testing.assert_allclose(half.carry[0], state.carry[0] * 0.5)
    a = np.asarray(est.finish_from_state(state).apply_arrays(x))
    b = np.asarray(est.finish_from_state(half).apply_arrays(x))
    assert _rel(b, a) <= 1e-5


def test_mismatched_sketch_maps_refused():
    """Sums across different (variant, seed) maps are algebra on
    unrelated projections: merge AND resume must fail loudly."""
    x, y = _realizable(seed=7)
    est = SketchedLeastSquaresEstimator(reg=1e-3, sketch_size=2 * D, seed=0)
    a = _manual_state(x, y, 2 * D, 0, 0, est)
    b_seed = _manual_state(x, y, 2 * D, 1, 0, est)
    with pytest.raises(StateMismatch, match="sketch_seed"):
        merge_stream_states(a, b_seed)
    b_var = StreamState(
        kind="sketch", estimator="manual", num_examples=N, carry=a.carry,
        meta={"sketch_variant": "srht", "sketch_seed": 0},
    )
    with pytest.raises(StateMismatch, match="sketch_variant"):
        merge_stream_states(a, b_var)
    # A Gram-kind state never seeds a sketched fold.
    gram = StreamState(
        kind="gram", estimator="manual", num_examples=N, carry=a.carry
    )
    with pytest.raises(StateMismatch, match="kind|gram|sketch"):
        est.fit_stream(_stream(x, y), state=gram)


def test_resume_adopts_state_map():
    """fit_stream(state=…) adopts the state's (variant, seed): the
    combined sketch stays ONE coherent linear map even when the resuming
    estimator was constructed with different defaults."""
    x, y = _realizable(seed=8)
    est = SketchedLeastSquaresEstimator(
        reg=1e-3, sketch_size=2 * D, variant="countsketch", seed=0
    )
    state = _manual_state(x, y, 2 * D, 5, 0, est)
    resumed = SketchedLeastSquaresEstimator(
        reg=1e-3, sketch_size=2 * D, variant="countsketch", seed=0
    )
    resumed.fit_stream(_stream(x, y), state=state)
    assert resumed.seed == 5
    assert resumed.export_stream_state().num_examples == 2 * N


def test_row_index_cap_falls_back():
    """Streams longer than the float32-exact index range refuse loudly
    (StreamingFallback) instead of silently colliding hash inputs."""

    class HugeStream:
        num_examples = MASK_INDEX_EXACT_ROWS + 1

    est = SketchedLeastSquaresEstimator(reg=1e-3)
    with pytest.raises(StreamingFallback, match="float32-exact"):
        est.fit_stream(HugeStream())


def test_default_sketch_size_bounds():
    assert default_sketch_size(10) == 128
    assert default_sketch_size(1000) == 1000
    assert default_sketch_size(100_000) == 4096
