"""The stream-state contract: export → checkpoint → merge/resume →
finish ≡ one-shot fit, for every ``supports_fit_stream`` estimator,
single-device and sharded (docs/REFIT.md)."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.least_squares import LeastSquaresEstimator
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.refit.state import (
    StateMismatch,
    StreamState,
    load_stream_state,
    merge_stream_states,
    save_stream_state,
)
from keystone_tpu.reliability.checkpoint import CheckpointStore
from keystone_tpu.workflow.streaming import ChunkStream

pytestmark = pytest.mark.refit

N, D, K, CHUNK = 384, 10, 3, 64


def _problem(seed=0, n=N):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, D)).astype(np.float32)
    w = rng.normal(size=(D, K)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(size=(n, K))).astype(np.float32)
    return x, y


def _stream(x, y, chunk=CHUNK, partition=None):
    return ChunkStream(
        ArrayDataset(x), ArrayDataset(y), (), chunk_rows=chunk,
        partition=partition,
    )


def _rel(a, b):
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


ESTIMATORS = [
    ("linear_map", lambda: LinearMapEstimator(reg=1e-3)),
    ("block_ls", lambda: BlockLeastSquaresEstimator(8, num_iter=2, reg=1e-3)),
    ("least_squares_meta", lambda: LeastSquaresEstimator(reg=1e-3, block_size=8)),
]


@pytest.mark.parametrize("name,make", ESTIMATORS, ids=[e[0] for e in ESTIMATORS])
def test_roundtrip_export_checkpoint_merge_finish(name, make, tmp_path):
    """Split fit → export both halves → persist through the checkpoint
    store → load → merge → finish_from_state ≡ the one-shot streamed fit
    (parity ≤ 1e-6), for all three fit_stream estimators."""
    x, y = _problem()
    reference = make().fit_stream(_stream(x, y))
    ref_out = np.asarray(reference.apply_arrays(x))

    store = CheckpointStore(str(tmp_path))
    half = N // 2
    for i, sl in enumerate((slice(None, half), slice(half, None))):
        est = make()
        est.fit_stream(_stream(x[sl], y[sl]))
        assert save_stream_state(store, f"part{i}", est.export_stream_state())

    a = load_stream_state(store, "part0")
    b = load_stream_state(store, "part1")
    assert a is not None and b is not None
    assert a.num_examples + b.num_examples == N
    merged = merge_stream_states(a, b)
    fitted = make().finish_from_state(merged)
    assert _rel(np.asarray(fitted.apply_arrays(x)), ref_out) <= 1e-6


@pytest.mark.parametrize("name,make", ESTIMATORS, ids=[e[0] for e in ESTIMATORS])
def test_resume_fold_extends_state(name, make):
    """fit_stream(state=…) seeds the carry: first-half fit + resumed
    second-half fold ≡ one fit over everything (parity ≤ 1e-6)."""
    x, y = _problem(seed=1)
    reference = make().fit_stream(_stream(x, y))
    ref_out = np.asarray(reference.apply_arrays(x))

    first = make()
    first.fit_stream(_stream(x[: N // 2], y[: N // 2]))
    resumed_est = make()
    resumed = resumed_est.fit_stream(
        _stream(x[N // 2 :], y[N // 2 :]), state=first.export_stream_state()
    )
    assert _rel(np.asarray(resumed.apply_arrays(x)), ref_out) <= 1e-6
    # The re-exported state covers the union.
    assert resumed_est.export_stream_state().num_examples == N


@pytest.mark.parametrize("name,make", ESTIMATORS, ids=[e[0] for e in ESTIMATORS])
def test_sharded_fold_state_parity(name, make):
    """The same contract through the PARTITIONED chunk plan: a sharded
    resumed fold matches the 1-device one-shot fit ≤ 1e-6 (per-device
    partial stats, one reduce at finish — docs/PARTITIONING.md)."""
    import jax

    from keystone_tpu.parallel.partitioner import Partitioner

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    x, y = _problem(seed=2)
    reference = make().fit_stream(_stream(x, y))
    ref_out = np.asarray(reference.apply_arrays(x))

    decision = Partitioner().decide_stream("refit-test", CHUNK, record=False)
    assert decision.eligible
    first = make()
    first.fit_stream(_stream(x[: N // 2], y[: N // 2], partition=decision))
    est = make()
    resumed = est.fit_stream(
        _stream(x[N // 2 :], y[N // 2 :], partition=decision),
        state=first.export_stream_state(),
    )
    assert _rel(np.asarray(resumed.apply_arrays(x)), ref_out) <= 1e-6


def test_state_decay_scales_statistics():
    x, y = _problem(seed=3, n=128)
    est = LinearMapEstimator(reg=1e-3)
    est.fit_stream(_stream(x, y))
    state = est.export_stream_state()
    assert state.scaled(1.0) is state
    half = state.scaled(0.5)
    assert half.num_examples == state.num_examples // 2
    assert np.allclose(half.carry[0], state.carry[0] * 0.5)
    # The decayed state still finishes to the SAME model (every
    # statistic and the count scale together — the centering identity
    # is homogeneous).
    a = np.asarray(est.finish_from_state(state).apply_arrays(x))
    b = np.asarray(est.finish_from_state(half).apply_arrays(x))
    assert _rel(b, a) <= 1e-5
    with pytest.raises(StateMismatch):
        state.scaled(0.0)


def test_mismatched_states_fail_loudly():
    x, y = _problem(seed=4, n=128)
    est = LinearMapEstimator(reg=1e-3)
    est.fit_stream(_stream(x, y))
    state = est.export_stream_state()
    wrong_kind = StreamState(
        kind="sketch", estimator="x", num_examples=1, carry=state.carry
    )
    with pytest.raises(StateMismatch):
        merge_stream_states(state, wrong_kind)
    narrow = LinearMapEstimator(reg=1e-3)
    narrow.fit_stream(_stream(x[:, :4], y, chunk=32))
    with pytest.raises(StateMismatch):
        merge_stream_states(state, narrow.export_stream_state())
    # Seeding a stream of the wrong width refuses before any chunk flows.
    with pytest.raises(StateMismatch):
        LinearMapEstimator(reg=1e-3).fit_stream(
            _stream(x[:, :4], y, chunk=32), state=state
        )


def test_unknown_format_version_is_a_miss(tmp_path):
    x, y = _problem(seed=5, n=128)
    est = LinearMapEstimator(reg=1e-3)
    est.fit_stream(_stream(x, y))
    state = est.export_stream_state()
    state.format_version = 99
    store = CheckpointStore(str(tmp_path))
    save_stream_state(store, "future", state)
    assert load_stream_state(store, "future") is None


def test_seeded_fold_correct_under_warm_cache(tmp_path):
    """The streaming step donates its carry with a persistent compilation
    cache active, and a seeded fold through an executable DESERIALIZED
    from that cache is exact. (On jax 0.4.37's CPU backend such
    executables misapplied input→output aliasing and a donated seeded
    carry accumulated garbage, so donation was gated off there; on jax
    0.9.0 the hazard is gone and CPU tests donate exactly as the chip
    does. This test is what would catch it coming back.)"""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.utils.compilation_cache import (
        cache_hit_count,
        install_compile_counter,
    )
    from keystone_tpu.workflow import streaming as streaming_mod

    install_compile_counter()
    rng = np.random.default_rng(11)
    seed = rng.normal(size=(D, D)).astype(np.float32)
    chunks = [rng.normal(size=(8, D)).astype(np.float32) for _ in range(4)]
    want = seed + sum(c.T @ c for c in chunks)

    def step(carry, x_feat, y_b):  # fresh fn: bypass the step cache
        (g,) = carry
        return (g + x_feat.T @ x_feat,)

    def fold():
        jitted, _ = streaming_mod._shared_step_jit((), step)
        carry = (jnp.asarray(seed),)
        for c in chunks:
            spent = carry
            carry, _probe = jitted(
                carry, jnp.asarray(c), jnp.ones((8, K)), jnp.ones((8, 1))
            )
            jax.block_until_ready(carry)
            assert spent[0].is_deleted(), "carry was not donated"
        return np.asarray(carry[0])

    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cold = fold()
        jax.clear_caches()  # drop in-memory executables: the next fold loads
        hits = cache_hit_count()
        warm = fold()
        assert cache_hit_count() > hits, "second fold did not load from the cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", saved[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[2])
    np.testing.assert_allclose(cold, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(warm, want, rtol=1e-5, atol=1e-5)
