"""Stats ops vs numpy golden values."""

import threading

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import spans
from keystone_tpu.ops.stats import core as stats_core
from keystone_tpu.ops.stats.core import (
    CosineRandomFeatures,
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
    SignedHellingerMapper,
    StandardScaler,
    Sampler,
)


def test_random_sign_node():
    node = RandomSignNode.create(16, seed=0)
    signs = np.asarray(node.signs)
    assert set(np.unique(signs)) <= {-1.0, 1.0}
    x = np.random.default_rng(1).normal(size=(4, 16)).astype(np.float32)
    out = np.asarray(node.apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out, x * signs, rtol=1e-6)


def test_padded_fft_matches_numpy():
    x = np.random.default_rng(0).normal(size=(3, 20)).astype(np.float32)
    out = np.asarray(PaddedFFT().apply_batch(ArrayDataset(x)).data)
    # pad 20 -> 32, full fft, real part of first 16
    padded = np.pad(x, ((0, 0), (0, 12)))
    expected = np.fft.fft(padded, axis=-1).real[:, :16]
    assert out.shape == (3, 16)
    np.testing.assert_allclose(out, expected, rtol=1e-3, atol=1e-4)


def test_padded_fft_power_of_two_input():
    x = np.random.default_rng(0).normal(size=(2, 16)).astype(np.float32)
    out = np.asarray(PaddedFFT().apply_batch(ArrayDataset(x)).data)
    assert out.shape == (2, 8)


def test_linear_rectifier():
    x = np.array([[-1.0, 0.5, 2.0]], dtype=np.float32)
    out = np.asarray(LinearRectifier(0.0, 1.0).apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out, [[0.0, 0.0, 1.0]])


def test_normalize_rows():
    x = np.array([[3.0, 4.0], [0.0, 0.0]], dtype=np.float32)
    out = np.asarray(NormalizeRows().apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out, [[0.6, 0.8], [0.0, 0.0]], rtol=1e-6)


def test_signed_hellinger():
    x = np.array([[-4.0, 9.0]], dtype=np.float32)
    out = np.asarray(SignedHellingerMapper().apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out, [[-2.0, 3.0]], rtol=1e-6)


def test_standard_scaler_mean_and_std():
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(200, 5)) * [1, 2, 3, 4, 5] + [10, 0, -5, 1, 2]).astype(np.float32)
    model = StandardScaler().fit(ArrayDataset(x))
    out = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-4)
    np.testing.assert_allclose(out.std(axis=0, ddof=1), 1.0, atol=1e-3)


def test_standard_scaler_mean_only():
    x = np.random.default_rng(0).normal(size=(50, 3)).astype(np.float32)
    model = StandardScaler(normalize_std_dev=False).fit(ArrayDataset(x))
    assert model.std is None
    out = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-5)


def test_standard_scaler_constant_column_guard():
    x = np.ones((10, 2), dtype=np.float32)
    model = StandardScaler().fit(ArrayDataset(x))
    np.testing.assert_allclose(np.asarray(model.std), 1.0)


def test_standard_scaler_respects_padding_mask():
    x = np.random.default_rng(0).normal(size=(10, 3)).astype(np.float32)
    padded = np.concatenate([x, np.zeros((6, 3), dtype=np.float32)])
    model_pad = StandardScaler().fit(ArrayDataset(padded, num_examples=10))
    model_raw = StandardScaler().fit(ArrayDataset(x))
    np.testing.assert_allclose(np.asarray(model_pad.mean), np.asarray(model_raw.mean), atol=1e-5)
    np.testing.assert_allclose(np.asarray(model_pad.std), np.asarray(model_raw.std), atol=1e-5)


def test_sampler():
    x = np.arange(100, dtype=np.float32).reshape(100, 1)
    out = Sampler(10, seed=0).apply_batch(ArrayDataset(x))
    assert len(out) == 10


def test_cosine_random_features_matches_numpy():
    """cos(xWᵀ + b) vs numpy golden values
    (reference: nodes/stats/CosineRandomFeaturesSuite)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 5)).astype(np.float32)
    w = rng.normal(size=(7, 5))
    b = rng.uniform(0, 2 * np.pi, size=7)
    out = CosineRandomFeatures(w, b).apply_batch(ArrayDataset(x))
    expected = np.cos(x @ w.T.astype(np.float32) + b.astype(np.float32))
    np.testing.assert_allclose(np.asarray(out.data), expected, atol=1e-5)


def test_cosine_random_features_create_shapes_and_dists():
    t = CosineRandomFeatures.create(5, 16, gamma=0.5, dist="gaussian", seed=1)
    assert t.w.shape == (16, 5) and t.b.shape == (16,)
    c = CosineRandomFeatures.create(5, 16, gamma=0.5, dist="cauchy", seed=1)
    assert c.w.shape == (16, 5)
    # Cauchy tails are heavier: max |w| should exceed the gaussian's
    assert float(abs(np.asarray(c.w)).max()) > float(abs(np.asarray(t.w)).max())
    with pytest.raises(ValueError):
        CosineRandomFeatures.create(5, 16, 0.5, dist="laplace")


def test_cosine_random_features_mismatched_b():
    with pytest.raises(ValueError):
        CosineRandomFeatures(np.ones((4, 3)), np.ones(5))


# ------------------------------------------- a bank of branches, side by side

def _draw_threads():
    return [t for t in threading.enumerate() if t.name.startswith("keystone-draw")]


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("dist", ["gaussian", "cauchy"])
def test_draw_branches_is_draw_a_seed_rounded_in_branch_order(monkeypatch, dist, workers):
    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: workers)
    seeds = [2_900_000_011 + i for i in range(5)]
    pairs = CosineRandomFeatures.draw_branches(6, 33, 0.25, dist, seeds)
    assert len(pairs) == len(seeds)
    for seed, (w, b) in zip(seeds, pairs):
        w64, b64 = CosineRandomFeatures.draw(6, 33, 0.25, dist, seed)
        assert w.dtype == b.dtype == np.float32
        assert np.array_equal(w, w64.astype(np.float32))
        assert np.array_equal(b, b64.astype(np.float32))
        made = CosineRandomFeatures.create(6, 33, 0.25, dist, seed)
        assert np.array_equal(np.asarray(made.w), w) and np.array_equal(np.asarray(made.b), b)


def test_draw_branches_draws_four_at_once(monkeypatch):
    """Without a clock: every `draw` waits at a barrier of four, which
    opens only if four are inside `draw` together."""
    barrier = threading.Barrier(4)
    real = CosineRandomFeatures.draw

    def waits(*args):
        barrier.wait(timeout=30)
        return real(*args)

    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: 8)
    monkeypatch.setattr(CosineRandomFeatures, "draw", staticmethod(waits))
    pairs = CosineRandomFeatures.draw_branches(3, 5, 1.0, "gaussian", [7, 8, 9, 10])
    assert [w.shape for w, _ in pairs] == [(5, 3)] * 4
    assert not barrier.broken
    assert not _draw_threads()  # the pool died with the call


@pytest.mark.parametrize("seeds,host_workers", [([3], 8), ([3, 4, 5], 1)], ids=["one-branch", "one-worker"])
def test_one_branch_or_one_worker_draws_inline(monkeypatch, seeds, host_workers):
    def no_pool(*args, **kwargs):
        raise AssertionError("a pool was made")

    seen = []
    real = CosineRandomFeatures.draw

    def records(*args):
        seen.append(threading.get_ident())
        return real(*args)

    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: host_workers)
    monkeypatch.setattr(stats_core, "ThreadPoolExecutor", no_pool)
    monkeypatch.setattr(CosineRandomFeatures, "draw", staticmethod(records))
    with spans.tracing_session("t") as session:
        pairs = CosineRandomFeatures.draw_branches(3, 5, 1.0, "gaussian", seeds)
    assert len(pairs) == len(seeds)
    assert seen == [threading.get_ident()] * len(seeds)
    (span,) = session.spans()
    assert span.attributes == {"branches": len(seeds), "workers": 1}


@pytest.mark.parametrize("workers", [1, 4])
def test_a_workers_error_reaches_the_caller_as_it_is(monkeypatch, workers):
    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: workers)
    with pytest.raises(ValueError, match="unknown distribution 'laplace'"):
        CosineRandomFeatures.draw_branches(3, 5, 1.0, "laplace", [1, 2, 3, 4])
    assert not _draw_threads()
