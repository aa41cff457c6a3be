"""The exact kernel suite at the shapes the TIMIT kernel cell forced
(PR 34): the mapper scans the train rows a block at a time, uploads a
request once and places its model once; the solver slices a block out of
the shard that holds it; what a fit or a request did is in the spans and
the counters."""

import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import names, spans
from keystone_tpu.ops.learning import kernel as K
from keystone_tpu.ops.learning.kernel import (
    GaussianKernelGenerator,
    KernelBlockLinearMapper,
    KernelRidgeRegression,
)

GAMMA = 0.02


def np_kernel(a, b, gamma=GAMMA):
    sq = ((a[:, None, :].astype(np.float64) - b[None, :, :]) ** 2).sum(-1)
    return np.exp(-gamma * sq)


def _model(n, d=12, k=5, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(n, d)).astype(np.float32),
        rng.normal(size=(n, k)).astype(np.float32),
    )


@pytest.mark.parametrize("n,block", [(256, 32), (200, 32), (64, 64), (50, 16), (40, 64)])
def test_the_mapper_blocked_over_train_rows_equals_the_unblocked_product(n, block):
    """Any block size gives K(test, train) W: the scan over train blocks
    is another summation order of the same product, and the padding it
    brings (whole blocks on each of eight shards) has zero duals."""
    train, duals = _model(n)
    test = np.random.default_rng(1).normal(size=(37, 12)).astype(np.float32)
    expected = np_kernel(test, train) @ duals.astype(np.float64)
    blocked = KernelBlockLinearMapper(train, duals, GAMMA, num_train=n, block_size=block)
    out = np.asarray(blocked.apply_arrays(test))
    assert out.shape == (37, 5)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)
    one_block = KernelBlockLinearMapper(train, duals, GAMMA, num_train=n, block_size=max(n, block))
    np.testing.assert_allclose(out, np.asarray(one_block.apply_arrays(test)), rtol=2e-5, atol=2e-5)
    shards = len(jax.devices())
    assert blocked.train.shape[0] % (block * shards) == 0
    assert np.abs(np.asarray(blocked.duals)[n:]).max(initial=0.0) == 0.0


def test_a_requests_panel_is_rows_by_block_never_rows_by_the_train_shard():
    """The compiled program holds no array of test rows x train rows."""
    n, block, m = 2048, 64, 128
    train, duals = _model(n, d=8, k=3)
    mapper = KernelBlockLinearMapper(train, duals, GAMMA, num_train=n, block_size=block)
    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import get_mesh, row_shard_count

    mesh = get_mesh()
    shards = row_shard_count(mesh)
    xt = linalg.prepare_row_sharded(jnp.zeros((m, 8), jnp.float32), mesh)
    text = K._ring_kernel_apply(mesh, block).lower(
        xt, mapper.train, mapper.duals, jnp.float32(GAMMA)
    ).compile().as_text()
    assert f"f32[{m // shards},{block}]" in text  # the live panel
    assert f"f32[{m // shards},{n // shards}]" not in text and f"f32[{m},{n}]" not in text


def test_the_model_is_placed_once_and_a_request_is_uploaded_once():
    n = 128
    train, duals = _model(n)
    mapper = KernelBlockLinearMapper(train, duals, GAMMA, num_train=n, block_size=16)
    placed = (mapper.train, mapper.duals)
    test = np.random.default_rng(2).normal(size=(24, 12)).astype(np.float32)
    bytes_c, transfers_c = names.metric(names.H2D_BYTES), names.metric(names.H2D_TRANSFERS)
    site = "KernelBlockLinearMapper"
    before = (bytes_c.value(site=site), transfers_c.value(site=site))
    a = mapper.apply_batch(ArrayDataset(test))  # the batch path uploads for the mapper
    b = mapper.apply_arrays(test)  # handed a host array directly, it uploads itself
    c = mapper.apply_arrays(jnp.asarray(test))  # device rows: nothing to upload
    assert bytes_c.value(site=site) - before[0] == 2 * test.nbytes
    assert transfers_c.value(site=site) - before[1] == 2
    assert mapper.train is placed[0] and mapper.duals is placed[1]  # not placed again by a request
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_a_pickled_mapper_places_its_model_again_and_answers_the_same():
    n = 96
    train, duals = _model(n)
    mapper = KernelBlockLinearMapper(train, duals, GAMMA, num_train=n, block_size=16)
    test = np.random.default_rng(3).normal(size=(10, 12)).astype(np.float32)
    loaded = pickle.loads(pickle.dumps(mapper))
    assert loaded._mesh is None and loaded.block_size == 16
    np.testing.assert_allclose(
        np.asarray(loaded.apply_arrays(test)), np.asarray(mapper.apply_arrays(test)), rtol=1e-6, atol=1e-6
    )
    assert loaded._mesh is not None


@pytest.mark.parametrize("n,block,epochs", [(96, 16, 3), (50, 16, 2), (30, 64, 1)])
def test_the_solver_matches_numpy_block_gauss_seidel(n, block, epochs):
    """The same sweeps in float64 numpy: every block lies in one of the
    eight shards, padded blocks are never visited, padded rows solve to
    zero duals whatever lambda is."""
    x, y = _model(n, d=6, k=3, seed=4)
    lam, seed = 0.3, 11
    est = KernelRidgeRegression(GaussianKernelGenerator(GAMMA), lam, block, epochs, block_permuter=seed)
    model = est.fit(ArrayDataset(x), ArrayDataset(y))
    bs = min(block, n)
    kmat = np_kernel(x, x)
    w = np.zeros((n, 3))
    rng = np.random.default_rng(seed)
    blocks = -(-n // bs)
    for _ in range(epochs):
        order = np.arange(blocks)
        rng.shuffle(order)
        for i in order:
            rows = slice(i * bs, min((i + 1) * bs, n))
            rhs = y[rows] - kmat[:, rows].T @ w + kmat[rows, rows] @ w[rows]
            w[rows] = np.linalg.solve(kmat[rows, rows] + lam * np.eye(rows.stop - rows.start), rhs)
    duals = np.asarray(model.duals)
    np.testing.assert_allclose(duals[:n], w, rtol=2e-4, atol=2e-5)
    assert np.abs(duals[n:]).max(initial=0.0) == 0.0
    assert duals.shape[0] % (bs * len(jax.devices())) == 0


def test_lambda_zero_with_a_padded_last_block_stays_finite():
    """A padded row's system is 1 * w = 0: the factorization does not
    depend on lambda to be positive definite there."""
    x, y = _model(40, d=6, k=2, seed=5)
    model = KernelRidgeRegression(GaussianKernelGenerator(0.5), 0.0, 16, 1).fit(ArrayDataset(x), ArrayDataset(y))
    duals = np.asarray(model.duals)
    assert np.isfinite(duals).all() and np.abs(duals[40:]).max() == 0.0 and np.abs(duals[:40]).max() > 0


def test_a_fit_and_a_request_say_what_they_did():
    n, block, epochs = 100, 16, 2
    x, y = _model(n, d=6, k=3, seed=6)
    panels = names.metric(names.KERNEL_PANELS)
    gauge = names.metric(names.KERNEL_PANEL_BYTES)
    fit_site, apply_site = "KernelRidgeRegression", "KernelBlockLinearMapper"
    before = (panels.value(site=fit_site), panels.value(site=apply_site))
    with spans.tracing_session("kernel", sync_timings=False) as session:
        model = KernelRidgeRegression(GaussianKernelGenerator(GAMMA), 0.5, block, epochs, block_permuter=3).fit(
            ArrayDataset(x), ArrayDataset(y)
        )
        model.apply_arrays(x[:10])
    shards = len(jax.devices())
    n_pad = -(-n // (block * shards)) * block * shards
    by_name = {s.name: s for s in session.spans()}
    assert {"solver:fit", "solver:iteration", "kernel:fit", "kernel:prepare", "kernel:solve", "kernel:apply"} <= set(by_name)
    fit = by_name["kernel:fit"]
    assert fit.attributes == {
        "n": n, "block": block, "blocks": 7, "epochs": epochs,
        "panel_bytes": 4 * (n_pad // shards) * block, "shards": shards,
    }
    assert fit.parent_id == by_name["solver:iteration"].span_id
    assert by_name["kernel:prepare"].parent_id == by_name["kernel:solve"].parent_id == fit.span_id
    uploads = [s for s in session.spans() if s.name == "h2d"]
    assert {s.attributes["site"] for s in uploads} == {fit_site, apply_site}
    assert any(s.parent_id == by_name["kernel:prepare"].span_id for s in uploads)
    assert any(s.parent_id == by_name["kernel:apply"].span_id for s in uploads)
    assert by_name["kernel:apply"].attributes == {"rows": 10, "train_rows": n, "block": block}
    # column panels: blocks x epochs a fit (blocks of padding alone are not visited), train blocks a request
    assert panels.value(site=fit_site) - before[0] == 7 * epochs
    assert panels.value(site=apply_site) - before[1] == n_pad // block
    assert gauge.value(site=fit_site) == 4 * (n_pad // shards) * block
    assert gauge.value(site=apply_site) == 4 * (-(-10 // shards)) * block


def test_the_device_scopes_are_in_the_programs():
    """`jax.named_scope` reaches the compiled program's metadata, where a
    profiler trace reads it (PERF.md section 5)."""
    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import get_mesh

    mesh = get_mesh()
    x = linalg.prepare_row_sharded(jnp.zeros((128, 4), jnp.float32), mesh)
    y = linalg.prepare_row_sharded(jnp.zeros((128, 2), jnp.float32), mesh)
    workspace = linalg.prepare_row_sharded(jnp.zeros((128, 16), jnp.float32), mesh)
    fit = K._krr_fit(mesh, 16).lower(
        x, y, jnp.zeros((8,), jnp.int32), jnp.float32(1.0), jnp.float32(1.0), jnp.int32(128), workspace
    ).as_text(debug_info=True)
    for scope in ("krr/gather", "krr/panel", "krr/residual", "krr/cholesky", "krr/update"):
        assert scope in fit, scope
    apply = K._ring_kernel_apply(mesh, 16).lower(x, x, y, jnp.float32(1.0)).as_text(debug_info=True)
    assert "kernel/panel" in apply and "kernel/apply" in apply
