"""Distributed linalg vs local numpy golden values, on an 8-device CPU mesh
(the reference's local-partitions-stand-in-for-cluster strategy)."""

import re

import numpy as np
import pytest

import jax

from keystone_tpu.parallel import linalg
from keystone_tpu.parallel.mesh import make_mesh, use_mesh
from keystone_tpu.utils.testing import assert_about_eq


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def test_mesh_has_8_devices(mesh):
    assert len(jax.devices()) == 8
    assert mesh.shape["data"] == 8


def test_gram(mesh):
    a = rand((64, 12))
    b = rand((64, 3), seed=1)
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        B = linalg.prepare_row_sharded(b)
        ata, atb = linalg.gram(A, B)
    np.testing.assert_allclose(np.asarray(ata), a.T @ a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(atb), a.T @ b, rtol=1e-4, atol=1e-4)


def test_gram_with_padding(mesh):
    a = rand((61, 5))  # 61 not divisible by 8 → zero-padded
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        assert A.shape[0] == 64
        ata, _ = linalg.gram(A)
    np.testing.assert_allclose(np.asarray(ata), a.T @ a, rtol=1e-4, atol=1e-4)


def test_normal_equations_solve(mesh):
    a = rand((128, 10))
    x_true = rand((10, 4), seed=2)
    b = a @ x_true
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        B = linalg.prepare_row_sharded(b)
        x = linalg.normal_equations_solve(A, B, reg=0.0)
    np.testing.assert_allclose(np.asarray(x), x_true, rtol=1e-2, atol=1e-3)


def test_ridge_matches_closed_form(mesh):
    a = rand((96, 8))
    b = rand((96, 2), seed=3)
    lam = 0.5
    expected = np.linalg.solve(a.T @ a + lam * np.eye(8), a.T @ b)
    with use_mesh(mesh):
        x = linalg.normal_equations_solve(
            linalg.prepare_row_sharded(a), linalg.prepare_row_sharded(b), reg=lam
        )
    np.testing.assert_allclose(np.asarray(x), expected, rtol=1e-3, atol=1e-3)


def test_tsqr_r_gram_identity(mesh):
    """RᵀR must equal AᵀA (QR correctness without fixing R's sign)."""
    a = rand((80, 6))
    with use_mesh(mesh):
        r = linalg.tsqr_r(linalg.prepare_row_sharded(a))
    np.testing.assert_allclose(np.asarray(r.T @ r), a.T @ a, rtol=1e-3, atol=1e-3)


def test_tsqr_svd_matches_local(mesh):
    a = rand((120, 7))
    _, s_expected, vt_expected = np.linalg.svd(a, full_matrices=False)
    with use_mesh(mesh):
        s, vt = linalg.tsqr_svd(linalg.prepare_row_sharded(a))
    np.testing.assert_allclose(np.asarray(s), s_expected, rtol=1e-3, atol=1e-3)
    # columns defined up to sign
    for i in range(7):
        vi, wi = np.asarray(vt)[i], vt_expected[i]
        assert min(np.linalg.norm(vi - wi), np.linalg.norm(vi + wi)) < 1e-2


def test_bcd_converges_to_ridge_solution(mesh):
    a = rand((160, 12))
    x_true = rand((12, 3), seed=5)
    y = a @ x_true
    lam = 0.1
    expected = np.linalg.solve(a.T @ a + lam * np.eye(12), a.T @ y)
    with use_mesh(mesh):
        w = linalg.block_coordinate_descent(
            linalg.prepare_row_sharded(a),
            linalg.prepare_row_sharded(y),
            reg=lam,
            num_epochs=30,
            block_size=4,
        )
    np.testing.assert_allclose(np.asarray(w), expected, rtol=5e-2, atol=5e-3)


def test_bcd_single_block_equals_exact(mesh):
    """One epoch, one block == exact normal-equation solve."""
    a = rand((64, 6))
    y = rand((64, 2), seed=7)
    lam = 0.3
    expected = np.linalg.solve(a.T @ a + lam * np.eye(6), a.T @ y)
    with use_mesh(mesh):
        w = linalg.block_coordinate_descent(
            linalg.prepare_row_sharded(a),
            linalg.prepare_row_sharded(y),
            reg=lam,
            num_epochs=1,
            block_size=6,
        )
    np.testing.assert_allclose(np.asarray(w), expected, rtol=1e-3, atol=1e-3)


def _gauss_seidel(a, y, reg, num_epochs, block_size):
    """Plain block Gauss-Seidel in float64: the step-by-step form, with
    the Gram formed and solved inside every step."""
    a, y = a.astype(np.float64), y.astype(np.float64)
    w = np.zeros((a.shape[1], y.shape[1]))
    p = np.zeros_like(y)
    for _ in range(num_epochs):
        for start in range(0, a.shape[1], block_size):
            cols = slice(start, start + block_size)
            a_b = a[:, cols]
            r = y - p + a_b @ w[cols]
            w_b = np.linalg.solve(a_b.T @ a_b + reg * np.eye(block_size), a_b.T @ r)
            p += a_b @ (w_b - w[cols])
            w[cols] = w_b
    return w


@pytest.mark.parametrize("rows", [96, 93], ids=["rows_whole", "rows_padded"])
@pytest.mark.parametrize("reg", [0.0, 1e-3], ids=["reg_floor", "reg_1e-3"])
@pytest.mark.parametrize("num_blocks", [1, 4])
@pytest.mark.parametrize("num_epochs", [1, 2, 5])
def test_bcd_matches_plain_gauss_seidel(mesh, num_epochs, num_blocks, reg, rows):
    """The factor pass changes how often a block is factored, not the
    iterates: every (epochs, blocks) form agrees with the plain loop."""
    from keystone_tpu.ops.learning.block import _scale_aware_reg_floor

    block_size = 8
    a = rand((rows, num_blocks * block_size), seed=11)
    y = rand((rows, 3), seed=12)
    if reg == 0.0:  # what the estimator passes in for reg = 0
        reg = _scale_aware_reg_floor(a, rows)
    with use_mesh(mesh):
        w = linalg.block_coordinate_descent(
            linalg.prepare_row_sharded(a),
            linalg.prepare_row_sharded(y),
            reg=reg,
            num_epochs=num_epochs,
            block_size=block_size,
        )
    expected = _gauss_seidel(a, y, reg, num_epochs, block_size)
    np.testing.assert_allclose(np.asarray(w), expected, rtol=1e-4, atol=1e-5)


def _lowered_bcd_functions(mesh, num_epochs, num_blocks=4, block_size=8, k=3):
    """The lowered in-core program as (whole text, {function name: body})."""
    f32 = np.float32
    a = jax.ShapeDtypeStruct((64, num_blocks * block_size), f32)
    y = jax.ShapeDtypeStruct((64, k), f32)
    reg = jax.ShapeDtypeStruct((), f32)
    text = linalg._bcd_fn(mesh, num_epochs, block_size, False).lower(a, y, reg).as_text()
    parts = re.split(r"\n  func\.func ", text)
    return text, {re.match(r"(?:public |private )?@(\w+)", p).group(1): p for p in parts[1:]}


# shapes of _lowered_bcd_functions: the Gram is the one product that is
# block x block; the factor stack is (num_blocks, block, block)
_GRAM_DOT = r"dot_general[^\n]*-> tensor<8x8xf32>"
_FACTOR_STACK = "tensor<4x8x8xf32>"


def test_bcd_factors_in_a_factor_pass_ahead_of_the_epoch_scan(mesh):
    text, functions = _lowered_bcd_functions(mesh, num_epochs=5)
    assert len(re.findall(r"stablehlo\.while", text)) == 2
    assert _FACTOR_STACK in text
    (epoch_body,) = [f for f in functions.values() if "call @_cho_solve" in f]
    (factor_body,) = [f for f in functions.values() if "call @_cholesky" in f]
    assert epoch_body is not factor_body
    assert len(re.findall(_GRAM_DOT, factor_body)) == 1
    assert not re.search(_GRAM_DOT, epoch_body)
    assert "dot_general" in epoch_body and _FACTOR_STACK in epoch_body
    assert len(re.findall(_GRAM_DOT, text)) == 1  # one Gram site in the whole program
    # the factor pass runs once a block, the epoch scan epochs x blocks times
    assert re.search(r"constant dense<4> : tensor<i32>[^\n]*\n[^\n]*compare  LT", text)
    assert re.search(r"constant dense<20> : tensor<i32>[^\n]*\n[^\n]*compare  LT", text)


def test_bcd_single_pass_is_one_loop_and_holds_no_factor_stack(mesh):
    text, functions = _lowered_bcd_functions(mesh, num_epochs=1)
    assert len(re.findall(r"stablehlo\.while", text)) == 1
    assert not re.search(r"tensor<\d+x8x8xf32>", text)
    (step,) = [f for f in functions.values() if "call @_cho_solve" in f]
    assert "call @_cholesky" in step and re.search(_GRAM_DOT, step)


@pytest.mark.parametrize("num_epochs,mode", [(1, "single_pass"), (2, "reused"), (5, "reused")])
def test_bcd_factor_mode_follows_the_static_epoch_count(num_epochs, mode):
    assert linalg.bcd_factor_mode(num_epochs) == mode


# ------------------------------------------------- symmetric Gram product


@pytest.mark.parametrize(
    "rows,width,panels",
    [
        (48, 32, 2), (48, 32, 4), (48, 32, 8),  # the forced panel counts
        (48, 30, 4),  # a width the panels do not divide: 8, 7, 8, 7
        (48, 17, 8),  # 3, 2, 2, 2, 2, 2, 2, 2
        (48, 3, 8),  # fewer columns than panels: 1, 1, 1
        (1, 32, 4),  # one row
        (48, 32, 1),  # one block: the full product, mirrored
    ],
)
@pytest.mark.parametrize("pad_rows", [0, 16])
def test_gram_sym_is_the_full_product_from_its_upper_block_triangle(rows, width, panels, pad_rows):
    x = np.concatenate([rand((rows, width), seed=rows + width), np.zeros((pad_rows, width), np.float32)])
    got = np.asarray(linalg._gram_sym_panels(x, panels))
    assert got.dtype == np.float32 and got.shape == (width, width)
    assert np.array_equal(got, got.T)  # to the bit
    full = np.asarray(linalg.mm(x.T, x))  # HIGHEST, float32
    exact = x.astype(np.float64).T @ x.astype(np.float64)
    if panels == 1:
        assert np.array_equal(np.triu(got), np.triu(full))
    # each entry is the same dot product over the same rows: as close to
    # the exact product as the full product is, and within rounding of it
    tol = 8 * np.finfo(np.float32).eps * np.sqrt(rows) * np.abs(exact).max()
    assert np.abs(got - exact).max() <= tol
    assert np.abs(got - full).max() <= tol


def test_gram_sym_takes_its_panels_from_the_width_alone():
    assert linalg.gram_panels(64) == 1  # the tests' widths, MNIST-sized fits
    x = rand((8, 64), seed=9)
    assert np.array_equal(np.asarray(linalg.gram_sym(x)), np.asarray(linalg.mm(x.T, x)))
    assert linalg.gram_panels(16384) > 1  # the streamed cell's width
    wide = rand((4, 2 * linalg._GRAM_SYM_PANEL), seed=10)
    got = np.asarray(linalg.gram_sym(wide))
    assert np.array_equal(got, got.T)
    np.testing.assert_allclose(got, wide.T @ wide, rtol=0, atol=1e-5)


@pytest.mark.parametrize("num_epochs", [1, 5], ids=["single_pass", "factor_pass"])
def test_bcd_with_a_panelled_gram_matches_plain_gauss_seidel(mesh, monkeypatch, num_epochs):
    """The in-core program with `gram_sym` engaged (inside `shard_map`,
    under the factor pass's `lax.map`, ahead of the `psum`)."""
    # a block size no other test has: `_bcd_fn` keeps what it traced under this rule
    block_size = 20
    monkeypatch.setattr(linalg, "_GRAM_SYM_PANEL", 5)
    assert linalg.gram_panels(block_size) == 4
    a = rand((93, 3 * block_size), seed=13)
    y = rand((93, 3), seed=14)
    with use_mesh(mesh):
        w = linalg.block_coordinate_descent(
            linalg.prepare_row_sharded(a),
            linalg.prepare_row_sharded(y),
            reg=1e-3,
            num_epochs=num_epochs,
            block_size=block_size,
        )
    expected = _gauss_seidel(a, y, 1e-3, num_epochs, block_size)
    np.testing.assert_allclose(np.asarray(w), expected, rtol=1e-4, atol=1e-5)


def _dot_flops(jaxpr):
    """2 x multiply-adds over every `dot_general` of a jaxpr, nested ones too."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            k = int(np.prod([lhs[i] for i in contract]))
            total += 2 * k * int(np.prod(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _dot_flops(sub)
    return total


@pytest.mark.parametrize(
    "rows,width",
    [(16384, 16384), (32768, 4096)],
    ids=["streamed_chunk_a_chip", "in_core_block"],
)
def test_the_gram_at_the_cells_shapes_is_not_the_full_product(rows, width):
    """Trace only, no compute: the guard against the full product coming
    back quietly. The width rule engages at both shapes, and the matmuls
    of `gram_stream_step` do at most 0.65 of 2nd² (+ the cross product)."""
    classes = 147
    f32 = np.float32
    carry = tuple(
        jax.ShapeDtypeStruct(shape, f32)
        for shape in [(width, width), (width, classes), (width,), (classes,)]
    )
    x = jax.ShapeDtypeStruct((rows, width), f32)
    y = jax.ShapeDtypeStruct((rows, classes), f32)
    flops = _dot_flops(jax.make_jaxpr(linalg.gram_stream_step)(carry, x, y).jaxpr)
    full, cross = 2 * rows * width * width, 2 * rows * width * classes
    m = linalg.gram_panels(width)
    assert m > 1 and flops == cross + full * (m + 1) // (2 * m)
    assert flops <= 0.65 * full + cross


# --------------------------------------------------------- hybrid (DCN) mesh


def test_hybrid_mesh_hierarchical_gram():
    """A (replica, data) mesh reduces over both tiers — the multi-slice
    (ICI + DCN) layout of SURVEY §2.10 on virtual devices."""
    import jax
    import numpy as np

    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import (
        REPLICA_AXIS,
        make_hybrid_mesh,
        row_axes,
        row_shard_count,
    )

    mesh = make_hybrid_mesh(num_replicas=2, devices=jax.devices()[:8])
    assert mesh.shape[REPLICA_AXIS] == 2
    assert row_axes(mesh) == (REPLICA_AXIS, "data")
    assert row_shard_count(mesh) == 8

    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 12)).astype(np.float32)
    b = rng.standard_normal((64, 3)).astype(np.float32)
    asd = linalg.prepare_row_sharded(a, mesh)
    bsd = linalg.prepare_row_sharded(b, mesh)
    ata, atb = linalg.gram(asd, bsd, mesh=mesh)
    np.testing.assert_allclose(np.asarray(ata), a.T @ a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(atb), a.T @ b, rtol=1e-4, atol=1e-4)


def test_hybrid_mesh_bcd_matches_closed_form():
    import jax
    import numpy as np

    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import make_hybrid_mesh

    mesh = make_hybrid_mesh(num_replicas=2, devices=jax.devices()[:8])
    rng = np.random.default_rng(1)
    a = rng.standard_normal((64, 8)).astype(np.float32)
    y = rng.standard_normal((64, 2)).astype(np.float32)
    asd = linalg.prepare_row_sharded(a, mesh)
    ysd = linalg.prepare_row_sharded(y, mesh)
    w = np.asarray(
        linalg.block_coordinate_descent(
            asd, ysd, reg=0.1, num_epochs=30, block_size=4, mesh=mesh
        )
    )
    want = np.linalg.solve(a.T @ a + 0.1 * np.eye(8), a.T @ y)
    np.testing.assert_allclose(w, want, rtol=1e-3, atol=1e-3)


def test_hybrid_mesh_tsqr():
    import jax
    import numpy as np

    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import make_hybrid_mesh

    mesh = make_hybrid_mesh(num_replicas=2, devices=jax.devices()[:8])
    rng = np.random.default_rng(2)
    a = rng.standard_normal((64, 6)).astype(np.float32)
    r = np.asarray(linalg.tsqr_r(linalg.prepare_row_sharded(a, mesh), mesh=mesh))
    # RᵀR == AᵀA exactly (QR sign ambiguity cancels in the product)
    np.testing.assert_allclose(r.T @ r, a.T @ a, rtol=1e-3, atol=1e-3)


def test_all_to_all_shard_transpose():
    """all_to_all = the Spark shuffle analog (SURVEY §2.10)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from keystone_tpu.parallel.collectives import all_to_all, shard_map
    from keystone_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=jax.devices()[:4])
    x = np.arange(16, dtype=np.float32).reshape(16, 1)

    def f(x_local):  # (4, 1) per device
        return all_to_all(x_local, split_axis=0, concat_axis=0)

    out = jax.jit(
        shard_map(f, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))
    )(x)
    # device i ends with rows [i, 4+i, 8+i, 12+i] — a (4,4) shard transpose
    got = np.asarray(out).reshape(4, 4)
    want = np.arange(16, dtype=np.float32).reshape(4, 4).T
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ 2-D (data, model) mesh


@pytest.fixture(scope="module")
def mesh2d():
    from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    return make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS), devices=jax.devices()[:8])


def test_bcd_2d_matches_closed_form(mesh2d):
    """Column-sharded A + model-sharded W converge to the same ridge
    solution as the closed form — the VERDICT item 4 acceptance test."""
    a = rand((64, 32), seed=11)
    x_true = rand((32, 3), seed=12)
    y = a @ x_true
    lam = 0.1
    expected = np.linalg.solve(a.T @ a + lam * np.eye(32), a.T @ y)
    asd = linalg.prepare_block_sharded(a, mesh2d)
    ysd = linalg.prepare_block_sharded(y, mesh2d, fine_rows=True)
    w = np.asarray(
        linalg.block_coordinate_descent_2d(
            asd, ysd, reg=lam, num_epochs=40, block_size=8, mesh=mesh2d
        )
    )
    assert_about_eq(w, expected, thresh=5e-2)


def test_bcd_2d_w_is_model_sharded(mesh2d):
    from jax.sharding import PartitionSpec as P

    from keystone_tpu.parallel.mesh import MODEL_AXIS

    a = rand((32, 16), seed=13)
    y = rand((32, 2), seed=14)
    asd = linalg.prepare_block_sharded(a, mesh2d)
    ysd = linalg.prepare_block_sharded(y, mesh2d, fine_rows=True)
    w = linalg.block_coordinate_descent_2d(
        asd, ysd, reg=0.2, num_epochs=5, block_size=4, mesh=mesh2d
    )
    assert w.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh2d, P(MODEL_AXIS, None)), w.ndim
    )


def test_bcd_2d_single_pass_matches_1d_order(mesh2d):
    """With one block per model group the 2-D update order degenerates to
    the sequential order, so a single epoch must match the 1-D solver
    bit-for-tolerance."""
    a = rand((64, 8), seed=15)
    y = rand((64, 2), seed=16)
    lam = 0.3
    mesh1d = make_mesh(devices=jax.devices()[:8])
    w1 = np.asarray(
        linalg.block_coordinate_descent(
            linalg.prepare_row_sharded(a, mesh1d),
            linalg.prepare_row_sharded(y, mesh1d),
            reg=lam, num_epochs=1, block_size=4, mesh=mesh1d,
        )
    )
    w2 = np.asarray(
        linalg.block_coordinate_descent_2d(
            linalg.prepare_block_sharded(a, mesh2d),
            linalg.prepare_block_sharded(y, mesh2d, fine_rows=True),
            reg=lam, num_epochs=1, block_size=4, mesh=mesh2d,
        )
    )
    assert_about_eq(w2, w1, thresh=1e-3)


def test_block_sharded_apply_matches_matmul(mesh2d):
    a = rand((48, 16), seed=17)
    w = rand((16, 5), seed=18)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import MODEL_AXIS

    asd = linalg.prepare_block_sharded(a, mesh2d)
    wsd = jax.device_put(w, NamedSharding(mesh2d, P(MODEL_AXIS, None)))
    got = np.asarray(linalg.block_sharded_apply(asd, wsd, mesh=mesh2d))
    assert_about_eq(got, a @ w)


def test_block_estimator_on_2d_mesh(mesh2d):
    """BlockLeastSquaresEstimator transparently uses the 2-D path when the
    active mesh has a model axis, and matches the centered closed form."""
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator

    a = rand((64, 16), seed=19)
    x_true = rand((16, 3), seed=20)
    y = a @ x_true
    with use_mesh(mesh2d):
        model = BlockLeastSquaresEstimator(8, num_iter=30, reg=0.1).fit(
            ArrayDataset(a), ArrayDataset(y)
        )
        preds = np.asarray(model.apply_arrays(a))
    ac = a - a.mean(axis=0)
    yc = y - y.mean(axis=0)
    w_want = np.linalg.solve(ac.T @ ac + 0.1 * np.eye(16), ac.T @ yc)
    want = ac @ w_want + y.mean(axis=0)
    np.testing.assert_allclose(preds, want, rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------ streaming BCD


def test_streaming_bcd_matches_in_core():
    """Host-streamed feature blocks (beyond-HBM path) solve to the same
    weights as the in-core compiled BCD, including centering and a short
    last block."""
    import jax.numpy as jnp

    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator

    rng = np.random.default_rng(0)
    n, d, k = 200, 50, 4  # d=50, block 16 -> short last block
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    mesh = make_mesh(devices=jax.devices()[:8])
    with use_mesh(mesh):
        m_core = BlockLeastSquaresEstimator(
            16, num_iter=3, reg=0.1, host_streaming=False
        ).fit(ArrayDataset(x), ArrayDataset(y))
        m_stream = BlockLeastSquaresEstimator(
            16, num_iter=3, reg=0.1, host_streaming=True
        ).fit(ArrayDataset(x), ArrayDataset(y))
        p1 = np.asarray(m_core.apply_arrays(jnp.asarray(x)))
        p2 = np.asarray(m_stream.apply_arrays(jnp.asarray(x)))
    np.testing.assert_allclose(p1, p2, atol=1e-5)


def test_streaming_bcd_improves_residual_over_epochs():
    from keystone_tpu.parallel import linalg

    rng = np.random.default_rng(1)
    n, d, k = 160, 24, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    y = x @ w_true
    mesh = make_mesh(devices=jax.devices()[:8])
    with use_mesh(mesh):
        w1, mu_a, mu_b = linalg.block_coordinate_descent_streaming(
            x, y, reg=1e-6, num_epochs=1, block_size=8, mesh=mesh
        )
        w5, _, _ = linalg.block_coordinate_descent_streaming(
            x, y, reg=1e-6, num_epochs=5, block_size=8, mesh=mesh
        )
    xc = x - np.asarray(mu_a)
    yc = y - np.asarray(mu_b)
    r1 = np.linalg.norm(xc @ np.asarray(w1) - yc)
    r5 = np.linalg.norm(xc @ np.asarray(w5) - yc)
    assert r5 < r1
    assert r5 < 1e-2 * np.linalg.norm(yc)


def test_centered_solve_refined_matches_unrefined_when_well_conditioned(mesh):
    a = rand((120, 10))
    b = rand((120, 3), seed=4)
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        B = linalg.prepare_row_sharded(b)
        w0, mu_a, mu_b = linalg.centered_solve_refined(A, B, 120, 0.1)
        w2, _, _ = linalg.centered_solve_refined(A, B, 120, 0.1, refine_steps=2)
    # float64 centered ridge reference
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    ac, bc = a64 - a64.mean(0), b64 - b64.mean(0)
    expect = np.linalg.solve(ac.T @ ac + 0.1 * np.eye(10), ac.T @ bc)
    np.testing.assert_allclose(np.asarray(w0), expect, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(w2), expect, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mu_a), a.mean(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mu_b), b.mean(0), rtol=1e-4, atol=1e-5)


def test_refinement_recovers_ill_conditioned_accuracy(mesh):
    """The mixed-precision IR mechanism: with an ill-conditioned A, the
    fp32 Cholesky's forward error is large; two refinement steps (residual
    recomputed from A itself) must shrink it by orders of magnitude —
    the same mechanism that recovers the fast-Gram error on TPU."""
    rng = np.random.default_rng(0)
    n, d, k = 512, 32, 4
    u, _ = np.linalg.qr(rng.normal(size=(n, d)))
    v, _ = np.linalg.qr(rng.normal(size=(d, d)))
    a = ((u * np.logspace(0, -3, d)) @ v.T).astype(np.float32)
    b = (a @ rng.normal(size=(d, k)) + 0.01 * rng.normal(size=(n, k))).astype(
        np.float32
    )
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    ac, bc = a64 - a64.mean(0), b64 - b64.mean(0)
    lam = 1e-8
    w64 = np.linalg.solve(ac.T @ ac + lam * np.eye(d), ac.T @ bc)
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        B = linalg.prepare_row_sharded(b)
        w0, _, _ = linalg.centered_solve_refined(A, B, n, lam, refine_steps=0)
        w2, _, _ = linalg.centered_solve_refined(A, B, n, lam, refine_steps=2)
    e0 = np.linalg.norm(np.asarray(w0) - w64) / np.linalg.norm(w64)
    e2 = np.linalg.norm(np.asarray(w2) - w64) / np.linalg.norm(w64)
    assert e2 < 0.05 * e0, (e0, e2)
    assert e2 < 1e-4


def test_rematerialized_bcd_matches_materialized(mesh):
    """block_coordinate_descent_rematerialized with a seeded generator
    must equal ordinary BCD on the materialized matrix the generator
    describes (the full-n TIMIT-wide path: features never exist)."""
    import jax.numpy as jnp

    n, d, k, bs = 64, 24, 3, 8
    num_blocks = d // bs
    key = jax.random.PRNGKey(5)

    def block_fn(b, row_offset, rows):
        # Row-offset-keyed generation so every shard produces its own
        # rows of the same global matrix.
        def one_row(r):
            kk = jax.random.fold_in(jax.random.fold_in(key, b), r)
            return jax.random.normal(kk, (bs,), jnp.float32)

        return jax.vmap(one_row)(row_offset + jnp.arange(rows))

    # Materialize the identical matrix on host for the oracle run.
    blocks = [
        np.asarray(block_fn(b, jnp.int32(0), n)) for b in range(num_blocks)
    ]
    a = np.concatenate(blocks, axis=1)
    y = rand((n, k), seed=9)

    with use_mesh(mesh):
        ys = linalg.prepare_row_sharded(y)
        w_remat = linalg.block_coordinate_descent_rematerialized(
            block_fn, ys, reg=0.1, num_epochs=2, block_size=bs,
            num_blocks=num_blocks,
        )
        a_s = linalg.prepare_row_sharded(a)
        w_mat = linalg.block_coordinate_descent(
            a_s, ys, reg=0.1, num_epochs=2, block_size=bs
        )
    np.testing.assert_allclose(
        np.asarray(w_remat), np.asarray(w_mat), rtol=1e-5, atol=1e-6
    )


def test_refine_guard_falls_back_to_highest_on_stalled_refinement(mesh):
    """ADVICE r3 (medium): IR with a bad fast-Gram factor can stall and
    silently return weights worse than a HIGHEST solve. The guard tracks
    the true residual norm and redoes the solve from a HIGHEST-precision
    Gram (same compiled program, lax.cond) when refinement fails to halve
    it. Host CPU ignores matmul precision flags, so the fast Gram is
    corrupted through the _TEST_GRAM_PERTURB seam instead."""
    a = rand((160, 10))
    b = rand((160, 3), seed=9)
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    ac, bc = a64 - a64.mean(0), b64 - b64.mean(0)
    expect = np.linalg.solve(ac.T @ ac + 0.1 * np.eye(10), ac.T @ bc)
    try:
        linalg._TEST_GRAM_PERTURB = 100.0
        with use_mesh(mesh):
            A = linalg.prepare_row_sharded(a)
            B = linalg.prepare_row_sharded(b)
            # Control: the corrupted Gram with no refinement produces
            # garbage (proves the seam corrupts), no guard to rescue it.
            w_bad, _, _ = linalg.centered_solve_refined(
                A, B, 160, 0.1, gram_precision=jax.lax.Precision.DEFAULT,
                refine_steps=0,
            )
            # Guarded refine path: IR stalls against the corrupted factor,
            # the guard must detect it and return the HIGHEST-Gram solve.
            w, _, _ = linalg.centered_solve_refined(
                A, B, 160, 0.1, gram_precision=jax.lax.Precision.DEFAULT,
                refine_steps=2,
            )
    finally:
        linalg._TEST_GRAM_PERTURB = 0.0
    bad_err = np.linalg.norm(np.asarray(w_bad) - expect) / np.linalg.norm(expect)
    guard_err = np.linalg.norm(np.asarray(w) - expect) / np.linalg.norm(expect)
    assert bad_err > 0.2, bad_err  # seam really corrupted the fast solve
    np.testing.assert_allclose(np.asarray(w), expect, rtol=1e-4, atol=1e-5)
    assert guard_err < 1e-3 * bad_err, (bad_err, guard_err)


def test_centered_solve_refined_with_row_padding(mesh):
    a = rand((61, 6))  # 61 not divisible by 8 → zero-padded rows
    b = rand((61, 2), seed=5)
    with use_mesh(mesh):
        A = linalg.prepare_row_sharded(a)
        B = linalg.prepare_row_sharded(b)
        w, mu_a, mu_b = linalg.centered_solve_refined(
            A, B, 61, 0.05, refine_steps=2
        )
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    ac, bc = a64 - a64.mean(0), b64 - b64.mean(0)
    expect = np.linalg.solve(ac.T @ ac + 0.05 * np.eye(6), ac.T @ bc)
    np.testing.assert_allclose(np.asarray(w), expect, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mu_a), a.mean(0), rtol=1e-5, atol=1e-6)
