"""Image feature extractors: dense SIFT, Fisher Vector, LCS.

Mirrors the reference's tolerance-based golden testing strategy
(reference: utils/external/VLFeatSuite.scala, EncEvalSuite.scala,
nodes/images/FisherVectorSuite) with numpy-golden checks and structural
invariants instead of MATLAB fixtures.
"""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.images.fisher import FisherVector, GMMFisherVectorEstimator
from keystone_tpu.ops.images.lcs import LCSExtractor
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel


# ------------------------------------------------------------------- SIFT


def test_sift_shapes_match_grid_counts():
    ext = SIFTExtractor(step_size=4, bin_size=4, scales=2, scale_step=1)
    x = np.random.default_rng(0).uniform(size=(2, 48, 40)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    assert out.shape == (2, sum(ext.grid_counts(48, 40)), 128)


def test_sift_quantized_range():
    ext = SIFTExtractor(step_size=4, bin_size=4, scales=2)
    x = np.random.default_rng(1).uniform(size=(1, 48, 48)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    assert out.min() >= 0 and out.max() <= 255
    np.testing.assert_array_equal(out, np.floor(out))  # integer quantization
    assert out.max() > 0  # random texture → real descriptors


def test_sift_flat_image_zeroed_by_contrast_threshold():
    ext = SIFTExtractor(step_size=4, bin_size=4, scales=1)
    x = np.full((1, 40, 40), 0.5, dtype=np.float32)
    out = np.asarray(ext.apply_arrays(x))
    np.testing.assert_array_equal(out, 0.0)


def test_sift_translation_equivariance():
    """Shifting the image by one step moves descriptors one grid cell."""
    step = 4
    ext = SIFTExtractor(step_size=step, bin_size=4, scales=1)
    rng = np.random.default_rng(2)
    base = rng.uniform(size=(56, 48)).astype(np.float32)
    shifted = np.roll(base, -step, axis=0)
    d0 = np.asarray(ext.apply_arrays(base[None]))[0]
    d1 = np.asarray(ext.apply_arrays(shifted[None]))[0]
    off = 1 + 2 * ext.scales
    span = 3 * ext.bin_size
    nx = (56 - 1 - off - span) // step + 1
    ny = (48 - 1 - off - span) // step + 1
    g0 = d0.reshape(nx, ny, 128)
    g1 = d1.reshape(nx, ny, 128)
    # interior rows (away from roll wraparound and border padding)
    a, b = g0[2:-1], g1[1:-2]
    match = np.mean(np.abs(a - b) <= 1.0)
    assert match > 0.95, f"only {match:.2%} of entries within 1"


def test_sift_gray_channel_axis_accepted():
    ext = SIFTExtractor(scales=1)
    x = np.random.default_rng(3).uniform(size=(1, 40, 40, 1)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    assert out.ndim == 3 and out.shape[-1] == 128


# ---------------------------------------------------------------- FisherVector


def _toy_gmm(d=4, k=3, seed=0):
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(d, k))
    variances = rng.uniform(0.5, 1.5, size=(d, k))
    weights = rng.uniform(0.2, 1.0, size=k)
    weights /= weights.sum()
    return GaussianMixtureModel(means, variances, weights)


def test_fisher_vector_matches_reference_formulas():
    """FV algebra vs direct numpy evaluation of the Sanchez et al. formulas
    (reference: FisherVector.scala:38-52)."""
    gmm = _toy_gmm()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 10, 4)).astype(np.float32)
    fv = np.asarray(FisherVector(gmm).apply_arrays(x))

    means = np.asarray(gmm.means, dtype=np.float64)
    variances = np.asarray(gmm.variances, dtype=np.float64)
    weights = np.asarray(gmm.weights, dtype=np.float64)
    for i in range(2):
        q = np.asarray(gmm.apply_arrays(x[i]))  # (n, K) posteriors
        n = x.shape[1]
        s0 = q.mean(axis=0)
        s1 = x[i].T.astype(np.float64) @ q / n
        s2 = (x[i].T.astype(np.float64) ** 2) @ q / n
        fv1 = (s1 - means * s0) / (np.sqrt(variances) * np.sqrt(weights))
        fv2 = (s2 - 2 * means * s1 + (means**2 - variances) * s0) / (
            variances * np.sqrt(2 * weights)
        )
        expected = np.concatenate([fv1, fv2], axis=1)
        np.testing.assert_allclose(fv[i], expected, rtol=1e-4, atol=1e-4)


def test_fisher_vector_shape():
    gmm = _toy_gmm(d=5, k=4)
    x = np.random.default_rng(2).normal(size=(3, 7, 5)).astype(np.float32)
    assert np.asarray(FisherVector(gmm).apply_arrays(x)).shape == (3, 5, 8)


def test_gmm_fisher_vector_estimator_end_to_end():
    rng = np.random.default_rng(3)
    # two well-separated descriptor clusters
    a = rng.normal(size=(4, 20, 3)) + 5.0
    b = rng.normal(size=(4, 20, 3)) - 5.0
    data = ArrayDataset(np.concatenate([a, b]).astype(np.float32))
    est = GMMFisherVectorEstimator(k=2)
    fv = est.fit(data)
    assert isinstance(fv, FisherVector)
    out = np.asarray(fv.apply_arrays(np.asarray(data.data)))
    assert out.shape == (8, 3, 4)
    assert np.isfinite(out).all()


def _fisher_by_the_docstring(x, means, variances, weights, threshold):
    """One image's (n, D) descriptors in float64, from the formulas at
    the head of ``ops/images/fisher.py`` and the thresholded posteriors
    of ``ops/learning/gmm.py``, a centre at a time. Returns the (D, 2K)
    encoding and the posteriors before and after the threshold."""
    n, d = x.shape
    k = len(weights)
    llh = np.empty((n, k))
    for j in range(k):
        llh[:, j] = (
            np.log(weights[j])
            - 0.5 * d * np.log(2 * np.pi)
            - 0.5 * np.log(variances[:, j]).sum()
            - 0.5 * (((x - means[:, j]) ** 2) / variances[:, j]).sum(axis=1)
        )
    raw = np.exp(llh - llh.max(axis=1, keepdims=True))
    raw /= raw.sum(axis=1, keepdims=True)
    q = np.where(raw > threshold, raw, 0.0)
    q /= q.sum(axis=1, keepdims=True)
    s0 = q.mean(axis=0)
    s1 = x.T @ q / n
    s2 = (x * x).T @ q / n
    fv1 = (s1 - means * s0) / (np.sqrt(variances) * np.sqrt(weights))
    fv2 = (s2 - 2 * means * s1 + (means**2 - variances) * s0) / (variances * np.sqrt(2 * weights))
    return np.concatenate([fv1, fv2], axis=1), raw, q


def test_fisher_encode_at_a_descriptor_count_no_tile_divides():
    """173 descriptors an image (no multiple of 8 or 128: what the chip
    tiles by) and a threshold that zeroes posteriors, against a float64
    loop over images."""
    import jax.numpy as jnp

    from keystone_tpu.ops.images.fisher import _fisher_encode

    gmm = _toy_gmm(d=8, k=5, seed=4)
    x = (1.5 * np.random.default_rng(5).normal(size=(3, 173, 8))).astype(np.float32)
    threshold = 0.0517
    got = np.asarray(_fisher_encode(x, gmm.means, gmm.variances, gmm.weights, jnp.float32(threshold)))
    assert got.shape == (3, 8, 10)

    means, variances, weights = (
        np.asarray(a, np.float64) for a in (gmm.means, gmm.variances, gmm.weights)
    )
    zeroed = 0
    for i in range(3):
        want, raw, q = _fisher_by_the_docstring(
            x[i].astype(np.float64), means, variances, weights, threshold
        )
        # no posterior so near the threshold that float32 could fall on its other side
        assert np.abs(raw - threshold).min() > 1e-5
        zeroed += int(((raw > 0) & (q == 0)).sum())
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-5)
    assert zeroed > 50  # the threshold did something


def _log_likelihood_over_axis_one(x, means, variances, weights):
    """``gmm._gmm_log_likelihood`` as it stood while it took
    two-dimensional input only (before PR 39), written out: the width
    from ``shape[1]``."""
    import jax.numpy as jnp

    from keystone_tpu.parallel import linalg

    d = x.shape[1]
    xsq = x * x
    inv_var = 1.0 / variances
    sq_mahal = (
        linalg.mm(xsq, (0.5 * inv_var).T)
        - linalg.mm(x, (means * inv_var).T)
        + 0.5 * jnp.sum(means * means * inv_var, axis=1)
    )
    log_norm = (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
    )
    return log_norm - sq_mahal


def _posteriors_over_axis_one(x, means, variances, weights, weight_threshold):
    """``gmm._gmm_posteriors`` of the same time: the reductions over the
    centres at ``axis=1``."""
    import jax.numpy as jnp

    llh = _log_likelihood_over_axis_one(x, means, variances, weights)
    llh = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(llh)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    return q / jnp.maximum(jnp.sum(q, axis=1, keepdims=True), 1e-30)


def _posterior_case(seed=6, shape=(3, 173, 8), k=5, threshold=0.05):
    """A mixture, descriptors of `shape`, and the mixture as the helpers
    take it: (means (k, d), variances (k, d), weights, threshold)."""
    import jax.numpy as jnp

    gmm = _toy_gmm(d=shape[-1], k=k, seed=seed)
    gmm.weight_threshold = threshold
    x = (1.5 * np.random.default_rng(seed + 1).normal(size=shape)).astype(np.float32)
    params = (
        jnp.asarray(gmm.means, jnp.float32).T, jnp.asarray(gmm.variances, jnp.float32).T,
        jnp.asarray(gmm.weights, jnp.float32), jnp.float32(threshold),
    )
    return gmm, x, params


def test_gmm_posteriors_of_a_batch_are_its_rows_posteriors():
    """(N, n, D) -> (N, n, K) with no flat intermediate, equal to the
    same rows flattened."""
    from keystone_tpu.ops.learning.gmm import _gmm_posteriors

    _, x, params = _posterior_case()
    batch = np.asarray(_gmm_posteriors(x, *params))
    rows = np.asarray(_gmm_posteriors(x.reshape(-1, x.shape[-1]), *params))
    assert batch.shape == (3, 173, 5)
    assert (rows == 0).any() and np.allclose(batch.sum(axis=-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(batch.reshape(rows.shape), rows, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("what", ["posteriors", "log_likelihood", "apply_arrays", "em"])
def test_gmm_on_two_dimensional_input_is_the_program_it_was(what, monkeypatch):
    """The helpers work over the LAST axis since PR 39; for (n, D) input
    that is axis 1, and everything that hands them such input
    (``GaussianMixtureModel.apply_arrays``, the EM loop: the flagship's
    set-up fit) comes out to the bit."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops.learning import gmm as gmm_module

    model, x, params = _posterior_case(shape=(519, 8))
    if what == "posteriors":
        got = gmm_module._gmm_posteriors(x, *params)
        want = jax.jit(_posteriors_over_axis_one)(x, *params)
    elif what == "log_likelihood":
        got = gmm_module._gmm_log_likelihood(x, *params[:3])
        want = jax.jit(_log_likelihood_over_axis_one)(x, *params[:3])
    elif what == "apply_arrays":
        got = model.apply_arrays(x)
        want = jax.jit(_posteriors_over_axis_one)(x, *params)
    else:
        def em():
            # a fresh callable a call: `jax.jit(f)` twice over one f shares
            # one trace, and the second call would replay the first's
            return jax.jit(lambda *a: gmm_module._gmm_em.__wrapped__(*a[:5], 7, *a[5:]))(
                jnp.asarray(x), *params[:3], jnp.full((x.shape[1],), 1e-3, jnp.float32),
                jnp.float32(1e-6), params[3], jnp.float32(2.0),
            )

        got = em()
        assert int(got[4]) >= 2  # the loop updated the mixture, and again
        monkeypatch.setattr(gmm_module, "_gmm_log_likelihood", _log_likelihood_over_axis_one)
        want = em()
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ----------------------------------------------------------------------- LCS


def test_lcs_shape_and_values_vs_numpy():
    """Box means/stds + grid reads vs a direct numpy evaluation
    (reference: LCSExtractorSuite checks dims on a real image)."""
    ext = LCSExtractor(stride=4, stride_start=16, sub_patch_size=6)
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(1, 48, 48, 3)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    kx = np.arange(16, 48 - 16, 4)
    assert out.shape == (1, len(kx) ** 2, 4 * 4 * 3 * 2)

    # numpy golden for one keypoint / channel / neighbor
    s = 6
    pad_lo = (s - 1) // 2
    padded = np.zeros((48 + s - 1, 48 + s - 1))
    padded[pad_lo : pad_lo + 48, pad_lo : pad_lo + 48] = x[0, :, :, 0]
    win = np.lib.stride_tricks.sliding_window_view(padded, (s, s))
    mean_img = win.mean(axis=(2, 3))
    sq_img = (win**2).mean(axis=(2, 3))
    std_img = np.sqrt(np.maximum(sq_img - mean_img**2, 0))

    offs = ext._neighbor_offsets()
    kp = (16, 16)  # first keypoint
    expected_first_pair = (
        mean_img[kp[0] + offs[0], kp[1] + offs[0]],
        std_img[kp[0] + offs[0], kp[1] + offs[0]],
    )
    np.testing.assert_allclose(out[0, 0, 0], expected_first_pair[0], atol=1e-4)
    np.testing.assert_allclose(out[0, 0, 1], expected_first_pair[1], atol=1e-4)


def test_lcs_out_of_bounds_raises():
    ext = LCSExtractor(stride=4, stride_start=4, sub_patch_size=6)
    x = np.zeros((1, 32, 32, 3), dtype=np.float32)
    with pytest.raises(ValueError):
        ext.apply_arrays(x)


# ----------------------------------------------------------------------- HOG


def test_hog_shape_and_layout():
    from keystone_tpu.ops.images.hog import HogExtractor

    ext = HogExtractor(bin_size=8)
    x = np.random.default_rng(0).uniform(size=(2, 64, 48, 3)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    nxc, nyc = 8, 6
    assert out.shape == (2, (nxc - 2) * (nyc - 2), 32)
    np.testing.assert_array_equal(out[..., 31], 0.0)  # truncation feature
    assert (out >= 0).all()
    assert out.max() > 0


def test_hog_flat_image_is_zero():
    from keystone_tpu.ops.images.hog import HogExtractor

    x = np.full((1, 32, 32, 3), 0.7, dtype=np.float32)
    out = np.asarray(HogExtractor(bin_size=8).apply_arrays(x))
    np.testing.assert_allclose(out, 0.0)


def test_hog_interp_matrix_partition_of_unity():
    from keystone_tpu.ops.images.hog import _interp_matrix

    m = _interp_matrix(30, 4, 8)
    sums = m.sum(axis=1)
    # interior pixels distribute all their mass; border pixels lose the
    # out-of-bounds share exactly as the reference's bounds checks do
    assert (sums <= 1.0 + 1e-6).all()
    assert (sums[4:-4] > 0.999).all()


def test_hog_gradient_orientation_selective():
    """A pure vertical edge puts its mass in a different orientation bin
    than a horizontal edge."""
    from keystone_tpu.ops.images.hog import HogExtractor

    ext = HogExtractor(bin_size=4)
    v = np.zeros((1, 32, 32, 1), dtype=np.float32)
    v[:, 16:, :, :] = 1.0  # edge along y (gradient in x)
    h = np.transpose(v, (0, 2, 1, 3))
    fv = np.asarray(ext.apply_arrays(v)).sum(axis=(0, 1))
    fh = np.asarray(ext.apply_arrays(h)).sum(axis=(0, 1))
    assert np.argmax(fv[:18]) != np.argmax(fh[:18])


# --------------------------------------------------------------------- DAISY


def test_daisy_shape_and_normalized_histograms():
    from keystone_tpu.ops.images.daisy import DaisyExtractor

    ext = DaisyExtractor()
    x = np.random.default_rng(1).uniform(size=(1, 48, 48)).astype(np.float32)
    out = np.asarray(ext.apply_arrays(x))
    kx = np.arange(16, 48 - 16, 4)
    assert out.shape == (1, len(kx) ** 2, ext.feature_size)
    # every H-bin block is L2-normalized (or zeroed)
    blocks = out.reshape(out.shape[0], out.shape[1], -1, ext.daisy_h)
    norms = np.linalg.norm(blocks, axis=-1)
    assert np.all((np.abs(norms - 1.0) < 1e-4) | (norms < 1e-6))


def test_daisy_flat_image_interior_zero():
    """A constant image has zero gradients, so interior keypoints (outside
    the reach of the zero-padding border artifact the reference's conv2D
    shares) produce zero histograms."""
    from keystone_tpu.ops.images.daisy import DaisyExtractor

    ext = DaisyExtractor()
    x = np.full((1, 96, 96), 0.25, dtype=np.float32)
    out = np.asarray(ext.apply_arrays(x))
    kx = np.arange(16, 96 - 16, 4)
    nk = len(kx)
    grid = out.reshape(nk, nk, -1)
    interior = (kx >= 40) & (kx <= 55)
    sub = grid[np.ix_(interior, interior)]
    np.testing.assert_allclose(sub, 0.0, atol=1e-6)


def test_daisy_border_guard():
    from keystone_tpu.ops.images.daisy import DaisyExtractor

    x = np.zeros((1, 48, 48), dtype=np.float32)
    with pytest.raises(ValueError):
        DaisyExtractor(pixel_border=4).apply_arrays(x)
