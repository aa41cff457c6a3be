"""imagenet-siftlcs-fv: the plain reference, a block of images at a time.

ImageNet by dense SIFT + local colour statistics + Fisher vectors and a
per-class mixture-weighted least-squares solve (the reference system's
`ImageNetSiftLcsFV.scala`, after Sanchez, Perronnin, Mensink, Verbeek,
"Image Classification with the Fisher Vector", IJCV 2013). The same
mathematics as the program's pipeline, written out again from the
published descriptions in straightforward `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`, on one device, importing
nothing from keystone_tpu:

  gray     PixelScaler (x / 255), NTSC luminance of a BGR image
  SIFT     the flat-window dense SIFT of VLFeat (`VLFeat.cxx:45-260`,
           `vl_dsift`): per scale s a bin of b = bin + 2 s pixels, Gaussian
           smoothing at sigma = b / 6 with the border replicated, central
           differences (one-sided at the border), 8 orientation planes with
           linear interpolation, triangular spatial bins of half-width b
           (zero outside the image), a 4 x 4 grid of bins every `step + s`
           pixels from the offset (1 + 2 scales) - 3 s, then L2 / clamp 0.2 /
           L2, zero under the contrast threshold 0.005, min(floor(512 v), 255)
  LCS      (Clinchant et al. 2007) mean and standard deviation of each
           colour channel over `patch` x `patch` boxes (zero outside the image)
           at a 4 x 4 neighbourhood of every keypoint of a regular grid
  branch   signed square root (SIFT only), the sampler's columns, PCA to
           `desc_dim` by the centred data's right singular vectors with the
           sign convention (largest coefficient of a component positive), a
           diagonal-covariance mixture of `vocab_size` Gaussians by EM, the
           Fisher vector's two gradients, L2, signed square root, L2
  solver   `weighted.py`'s header equations, closed form a class (d = 4,096
           is one block, one pass from W = 0: no Gauss-Seidel):
               jointXTX_c = (1-w) popCov + w classCov_c + w (1-w) d_c d_c^T
               jointXTR_c = (1-w) popXTR[:, c] + w classXTR_c - jointMean_c meanMix_c
               W_c = (jointXTX_c + lambda I)^-1 jointXTR_c
               b_c = jlm_c - jointMean_c . W_c,   jlm_c = 2 w + 2 (1-w) n_c / n - 1

Computed in blocks of `BLOCK` images, so that nothing larger than one
block's SIFT descriptors is ever held.

`correct` is decided by three comparisons, all here except the last's
limit and all required. The reference computes every number it compares
from the images, the labels and the configuration, with one exception
that is stated and checked (the basis, below); nothing it featurizes,
fits, solves or scores with is a table the program fitted.

  (a) `fit_codebook`, the set-up fit's codebooks. The reference makes its
      own descriptors and takes the sampler's columns of them. The
      program's PCA basis is held to the reference's own covariance of
      those samples: orthonormal (`pca_not_orthonormal`), capturing what
      the best 64 directions capture (`pca_variance_missed`, second order
      in an angle), and DIAGONALISING it: the largest off-diagonal entry
      of P^T C P over the largest eigenvalue (`pca_not_diagonal`), which
      is first order in a rotation and weighs it by the gap between the
      two eigenvalues it mixes, so that it is blind exactly where the data
      are: inside a stretch of equal eigenvalues any rotation is a
      principal basis. (These synthetic images have such stretches, a gap
      of 7e-6 of the largest eigenvalue among the leading 64: the
      reference's own eigenvectors and the program's differ by 3e-3 there
      in float32 and by 0.5 once the smoothing is rounded to bfloat16,
      `PERF.md`; two right answers cannot be compared entry by entry.)
      A basis that passes is one of the right answers, and the reference
      then works in it, as it works with the sampler's columns: it is the
      one thing of the program's it computes with. In those coordinates it
      fits its OWN mixture by EM, from the given start for the given
      number of updates, and the program's mixture is held to it by the
      samples' mean log-likelihood (`gmm_log_likelihood_apart`).
  (b) `encodings_apart`, what the cell's requests compute: the held-out
      images' two 2,048-wide encodings (descriptors, projection, Fisher
      vector, normalisations) by the reference, with its own mixtures,
      against the program's (`given["heldout_encodings"]`, which is
      compared and never computed with): the worst image's
      |program - reference|_2 / |reference|_2 over either branch, within
      `encodings_row_l2_apart`. No division by lambda stands between a
      rounding and this number: it is the limit that tells the precisions
      apart (the configuration's `tolerance.why` has both readings).
  (c) the class scores, from the reference's own encodings of the
      training images and its own solve, within the harness's
      `scores_max_abs_over_ref_max_abs`. At 2,048 rows the 4,096-wide
      per-class system has a 2,048-dimensional null space that lambda
      6e-5 alone holds, a held-out image has mass there, and rounding
      over lambda is what this number reads: its limit is wide, and
      guards the solver against being wrong, not against a precision.

A number over its limit raises after all of (a) and (b) are printed,
which the harness reports as not correct.

What this form cannot see: a basis that is principal for the
reference's samples but was reached in lower precision (it diagonalises
the covariance within `pca_not_diagonal` or it fails; inside a stretch
of equal eigenvalues nothing can tell); a start of EM or a number of
updates other than the program's rule would give (they are given); and
a solver that loses no more than the wide limit of (c).

What is random in the program and no part of the model crosses in
`given` (arrays only): the sampler's columns of either branch
(`sift_columns`, `lcs_columns`: (images, samples an image)), the
mixtures' starting points from k-means++ (`*_gmm_means0`, `_vars0`,
`_weights0`) and the number of EM updates the program applied
(`*_gmm_updates`). What the program fitted and computed crosses there
to be compared: `*_components` (d, 64), checked and then the
coordinates; `*_gmm_means`, `_gmm_variances` (16, 64), `_gmm_weights`
(16,); `heldout_encodings` (held-out rows, 4,096).

The one knob is the configuration's stated `smoothing_input_dtype`,
written out as a cast: what the inputs of the Gaussian smoothing (the
gray image and the kernel's taps) are rounded to before their products
are summed in float32. "float32" as shipped; a test and the tolerance's
second reading state "bfloat16", the nearest precision below, which is
what a TPU's default precision does to a float32 convolution.

Departures from the published descriptions, each for a stated reason:
- Every convolution is a sum of shifted copies, one a tap: plain, exact
  float32 on any backend, and not the program's `lax.conv_general_dilated`.
- PCA's right singular vectors come from the eigenvectors of the centred
  samples' d x d covariance (the same vectors in exact arithmetic): a
  singular value decomposition of a 1,000,000 x 128 matrix is minutes on
  a TPU, and the covariance is one product.
- The descriptor's layout is the program's (orientation fastest, then
  x bin, then y bin; keypoints x-major; scales one after another), not
  VLFeat's MATLAB-transposed one: the same set of numbers.
- EM runs for the program's number of updates and does not test for
  convergence itself; its thresholds (posteriors under 1e-4 dropped and
  renormalised, variances floored at 1e-2 of the global variance) are the
  enceval ones the program documents.
- Labels are -1/+1 indicators (the program's ClassLabelIndicators).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 64  # images featurized at a time
ORIENTATIONS, BINS = 8, 4


# ----------------------------------------------------------------- convolution


def _correlate(x, taps, axis: int, low: int, mode: str):
    """sum_k taps[k] x[.. i + k - low ..] along `axis`, same size; outside
    the array `x` is its border value (`edge`) or zero (`zero`)."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (low, len(taps) - 1 - low)
    padded = jnp.pad(x, pad, mode="edge" if mode == "edge" else "constant")
    out = jnp.zeros_like(x)
    for k, tap in enumerate(taps):
        out = out + tap * jax.lax.slice_in_dim(padded, k, k + x.shape[axis], axis=axis)
    return out


def _gaussian_taps(sigma: float) -> np.ndarray:
    radius = max(1, int(math.ceil(4.0 * sigma)))
    taps = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
    return (taps / taps.sum()).astype(np.float32)


def _triangle_taps(b: int) -> np.ndarray:
    return np.maximum(0.0, 1.0 - np.abs(np.arange(-(b - 1), b)) / b).astype(np.float32)


# ------------------------------------------------------------------------ SIFT


def sift_grid(config: dict, s: int, x_dim: int, y_dim: int):
    """Bin size, and the descriptor origins along x and y, at scale `s`."""
    b = config["sift_bin_size"] + 2 * s
    step = config["sift_step_size"] + s * config["sift_scale_step"]
    off = max(0, (1 + 2 * config["sift_scales"]) - 3 * s)
    span = (BINS - 1) * b
    ox = off + step * np.arange(max(0, (x_dim - 1 - off - span) // step + 1))
    oy = off + step * np.arange(max(0, (y_dim - 1 - off - span) // step + 1))
    return b, ox, oy


def _sift_scale(gray, b: int, ox, oy, input_dtype: str):
    """(N, X, Y) gray in [0, 1] -> (N, len(ox) * len(oy), 128) at one scale."""
    n = gray.shape[0]
    taps = _gaussian_taps(b / 6.0)
    # the stated precision of the smoothing's inputs, as a cast
    image = gray.astype(input_dtype).astype(jnp.float32)
    taps = [float(t) for t in taps.astype(jnp.dtype(input_dtype)).astype(np.float32)]
    low = (len(taps) - 1) // 2
    smooth = _correlate(_correlate(image, taps, 1, low, "edge"), taps, 2, low, "edge")

    def gradient(a, axis):
        ahead = jax.lax.slice_in_dim(a, 2, None, axis=axis)
        behind = jax.lax.slice_in_dim(a, 0, -2, axis=axis)
        first = jax.lax.slice_in_dim(a, 1, 2, axis=axis) - jax.lax.slice_in_dim(a, 0, 1, axis=axis)
        last = jax.lax.slice_in_dim(a, -1, None, axis=axis) - jax.lax.slice_in_dim(a, -2, -1, axis=axis)
        return jnp.concatenate([first, 0.5 * (ahead - behind), last], axis=axis)

    gx, gy = gradient(smooth, 1), gradient(smooth, 2)
    magnitude = jnp.sqrt(gx * gx + gy * gy)
    angle = jnp.mod(jnp.arctan2(gy, gx), 2.0 * jnp.pi) * (ORIENTATIONS / (2.0 * jnp.pi))
    # linear interpolation between the two nearest of 8 orientations
    away = jnp.abs(angle[..., None] - jnp.arange(ORIENTATIONS, dtype=jnp.float32))
    away = jnp.minimum(away, ORIENTATIONS - away)
    planes = magnitude[..., None] * jnp.maximum(0.0, 1.0 - away)  # (N, X, Y, 8)

    triangle = [float(t) for t in _triangle_taps(b)]
    binned = _correlate(_correlate(planes, triangle, 1, b - 1, "zero"), triangle, 2, b - 1, "zero")

    bx = (ox[:, None] + b * np.arange(BINS)).reshape(-1)
    by = (oy[:, None] + b * np.arange(BINS)).reshape(-1)
    grid = binned[:, bx][:, :, by]  # (N, nx * 4, ny * 4, 8)
    grid = grid.reshape(n, len(ox), BINS, len(oy), BINS, ORIENTATIONS)
    raw = jnp.transpose(grid, (0, 1, 3, 4, 2, 5)).reshape(n, len(ox) * len(oy), -1)

    norm = jnp.linalg.norm(raw, axis=-1, keepdims=True)
    d = jnp.minimum(raw / jnp.maximum(norm, 1e-10), 0.2)
    d = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True), 1e-10)
    d = jnp.where(norm > 0.005, d, 0.0)
    return jnp.minimum(jnp.floor(512.0 * d), 255.0)


def sift(config: dict, images):
    """(N, X, Y, 3) images in 0-255, BGR -> (N, descriptors, 128)."""
    scaled = images.astype(jnp.float32) / 255.0
    gray = 0.2989 * scaled[..., 2] + 0.5870 * scaled[..., 1] + 0.1140 * scaled[..., 0]
    per_scale = []
    for s in range(config["sift_scales"]):
        b, ox, oy = sift_grid(config, s, gray.shape[1], gray.shape[2])
        if len(ox) and len(oy):
            per_scale.append(_sift_scale(gray, b, ox, oy, config["smoothing_input_dtype"]))
    return jnp.concatenate(per_scale, axis=1)


# ------------------------------------------------------------------------- LCS


def lcs(config: dict, images):
    """(N, X, Y, C) -> (N, keypoints, 4 * 4 * C * 2): per keypoint and
    channel a 4 x 4 neighbourhood of (mean, standard deviation) pairs."""
    x = images.astype(jnp.float32)
    n, x_dim, y_dim, c = x.shape
    patch, stride, start = config["lcs_patch"], config["lcs_stride"], config["lcs_border"]
    taps = [1.0 / patch] * patch
    low = (patch - 1) // 2

    def box(a):
        return _correlate(_correlate(a, taps, 1, low, "zero"), taps, 2, low, "zero")

    mean = box(x)
    deviation = jnp.sqrt(jnp.maximum(box(x * x) - mean * mean, 0.0))
    kx = np.arange(start, x_dim - start, stride)
    ky = np.arange(start, y_dim - start, stride)
    around = np.arange(-2 * patch + patch // 2 - 1, patch + patch // 2, patch)  # 4 offsets
    ax = (kx[:, None] + around).reshape(-1)
    ay = (ky[:, None] + around).reshape(-1)

    def read(a):
        g = a[:, ax][:, :, ay].reshape(n, len(kx), len(around), len(ky), len(around), c)
        return jnp.transpose(g, (0, 1, 3, 5, 2, 4))  # (N, kx, ky, C, 4, 4)

    pairs = jnp.stack([read(mean), read(deviation)], axis=-1)
    return pairs.reshape(n, len(kx) * len(ky), -1)


# ------------------------------------------------------------ codebooks (fit)


def signed_root(x):
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


def pca(samples, dims: int):
    """(m, d) -> (d, dims): the centred samples' leading right singular
    vectors, each with its largest coefficient positive."""
    centred = samples - jnp.mean(samples, axis=0)
    _, vectors = jnp.linalg.eigh(centred.T @ centred)
    components = vectors[:, ::-1][:, :dims]
    largest = jnp.max(jnp.abs(components), axis=0)
    return components * jnp.where(jnp.max(components, axis=0) == largest, 1.0, -1.0)


def _log_likelihood(x, means, variances, weights):
    """(m, d) samples, (k, d) means and variances, (k,) weights -> (m, k)."""
    d = x.shape[1]
    mahalanobis = (
        (x * x) @ (0.5 / variances).T - x @ (means / variances).T
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    constant = (
        -0.5 * d * jnp.log(2 * jnp.pi) - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
    )
    return constant - mahalanobis


def posteriors(x, means, variances, weights, threshold: float):
    llh = _log_likelihood(x, means, variances, weights)
    q = jnp.exp(llh - jnp.max(llh, axis=1, keepdims=True))
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > threshold, q, 0.0)
    return q / jnp.maximum(jnp.sum(q, axis=1, keepdims=True), 1e-30)


@functools.partial(jax.jit, static_argnames=("threshold",))
def _em_update(x, means, variances, weights, floor, threshold: float):
    q = posteriors(x, means, variances, weights, threshold)
    mass = jnp.sum(q, axis=0)
    safe = jnp.maximum(mass, 1e-12)[:, None]
    new_means = (q.T @ x) / safe
    new_variances = jnp.maximum((q.T @ (x * x)) / safe - new_means**2, floor)
    return new_means, new_variances, mass / x.shape[0]


def gmm(config: dict, samples, means, variances, weights, updates: int):
    """`updates` EM steps from the given start; (k, d), (k, d), (k,)."""
    g = config["gmm"]
    floor = jnp.maximum(
        g["small_variance_threshold"] * jnp.var(samples, axis=0), g["absolute_variance_threshold"]
    )
    means, weights = jnp.asarray(means), jnp.asarray(weights)
    variances = jnp.maximum(jnp.asarray(variances), floor)
    for _ in range(updates):
        means, variances, weights = _em_update(
            samples, means, variances, weights, floor, threshold=g["weight_threshold"]
        )
    return means, variances, weights


# -------------------------------------------------------------- Fisher vector


def fisher(config: dict, x, means, variances, weights):
    """(N, n, D) descriptors, a mixture of K -> (N, D * 2 K): the gradients
    with respect to the means and the variances (Sanchez et al., eq. 16-17),
    L2, signed square root, L2."""
    n_images, n, d = x.shape
    q = posteriors(
        x.reshape(-1, d), means, variances, weights, config["gmm"]["weight_threshold"]
    ).reshape(n_images, n, -1)
    mu, var = means.T, variances.T  # (D, K)
    s0 = jnp.mean(q, axis=1)[:, None, :]
    s1 = jnp.einsum("bnd,bnk->bdk", x, q) / n
    s2 = jnp.einsum("bnd,bnk->bdk", x * x, q) / n
    by_mean = (s1 - mu * s0) / (jnp.sqrt(var) * jnp.sqrt(weights))
    by_variance = (s2 - 2.0 * mu * s1 + (mu * mu - var) * s0) / (var * jnp.sqrt(2.0 * weights))
    fv = jnp.concatenate([by_mean, by_variance], axis=2).reshape(n_images, -1)

    def unit(a):
        norms = jnp.linalg.norm(a, axis=-1, keepdims=True)
        return a / jnp.where(norms == 0, 1.0, norms)

    return unit(signed_root(unit(fv)))


# ---------------------------------------------------------------- the branches


def _descriptors(config: dict, branch: str, images):
    if branch == "sift":
        return signed_root(sift(config, images))
    return lcs(config, images)


_descriptors_jit = jax.jit(_descriptors, static_argnums=(0, 1))


class _Frozen(dict):
    """A configuration as a static argument of a jitted function."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=str)))


def _blocks(rows: int):
    return [(start, min(start + BLOCK, rows)) for start in range(0, rows, BLOCK)]


def sample_columns(config: dict, branch: str, images: np.ndarray, columns: np.ndarray):
    """The given columns of every image's descriptors: (images * samples, d)."""
    frozen = _Frozen(_hashable(config))
    out = []
    for start, stop in _blocks(len(images)):
        desc = _descriptors_jit(frozen, branch, jnp.asarray(images[start:stop]))
        out.append(jnp.take_along_axis(desc, jnp.asarray(columns[start:stop])[:, :, None], axis=1))
    return jnp.concatenate(out, axis=0).reshape(-1, out[0].shape[-1])


def _hashable(config: dict) -> dict:
    return {k: (_Frozen(v) if isinstance(v, dict) else tuple(v) if isinstance(v, list) else v)
            for k, v in config.items()}


def mean_log_likelihood(x, means, variances, weights):
    return jnp.mean(jax.scipy.special.logsumexp(_log_likelihood(x, means, variances, weights), axis=1))


def fit_codebook(config: dict, branch: str, images: np.ndarray, given: dict):
    """Part (a) for one branch: (the codebook the reference goes on with,
    its readings of the program's). The program draws the same columns for
    the PCA's samples and the mixture's (one seed, one count), so they are
    computed once."""
    samples = sample_columns(config, branch, images, given[f"{branch}_columns"])
    basis = jnp.asarray(given[f"{branch}_components"])
    dims = basis.shape[1]
    centred = samples - jnp.mean(samples, axis=0)
    covariance = centred.T @ centred / samples.shape[0]
    values = jnp.linalg.eigvalsh(covariance)
    rotated = basis.T @ covariance @ basis
    reduced = samples @ basis  # the checked basis: the reference's coordinates from here on
    means, variances, weights = gmm(
        config, reduced,
        given[f"{branch}_gmm_means0"], given[f"{branch}_gmm_vars0"],
        given[f"{branch}_gmm_weights0"], int(given[f"{branch}_gmm_updates"]),
    )
    theirs = (given[f"{branch}_gmm_{k}"] for k in ("means", "variances", "weights"))
    readings = {
        "pca_not_orthonormal": float(jnp.max(jnp.abs(basis.T @ basis - jnp.eye(dims)))),
        "pca_variance_missed": float(1.0 - jnp.trace(rotated) / jnp.sum(values[-dims:])),
        "pca_not_diagonal": float(jnp.max(jnp.abs(rotated - jnp.diag(jnp.diag(rotated)))) / values[-1]),
        "gmm_log_likelihood_apart": float(jnp.abs(
            mean_log_likelihood(reduced, *(jnp.asarray(t) for t in theirs))
            - mean_log_likelihood(reduced, means, variances, weights)
        )),
        # for the record, no limit: the reference's own eigenvectors against the basis (any
        # size inside a stretch of equal eigenvalues), and how far EM carried the two apart
        "pca_least_cosine": float(jnp.min(jnp.linalg.svd(pca(samples, dims).T @ basis, compute_uv=False))),
        "gmm_means_apart_in_deviations": float(jnp.max(
            jnp.abs(means - jnp.asarray(given[f"{branch}_gmm_means"])) / jnp.sqrt(variances)
        )),
    }
    codebook = {"components": basis, "means": means, "variances": variances, "weights": weights}
    return codebook, readings


LIMITED = ("pca_not_orthonormal", "pca_variance_missed", "pca_not_diagonal", "gmm_log_likelihood_apart")


def encodings_apart(config: dict, program: np.ndarray, reference) -> dict:
    """Part (b): {branch: the worst row's |program - reference|_2 /
    |reference|_2} over the held-out encodings, SIFT's half then LCS's."""
    reference = np.asarray(reference)
    if program.shape != reference.shape:
        raise ValueError(f"the program's held-out encodings are {program.shape}, the reference's {reference.shape}")
    half = reference.shape[1] // 2
    return {
        branch: float(np.max(
            np.linalg.norm(program[:, part] - reference[:, part], axis=1)
            / np.maximum(np.linalg.norm(reference[:, part], axis=1), 1e-30)
        ))
        for branch, part in (("sift", slice(0, half)), ("lcs", slice(half, None)))
    }


def over_their_limits(config: dict, readings: dict) -> list:
    """["<branch> <reading> <value>, over the tolerance <limit>"] for
    every reading of parts (a) and (b) that has a limit and passes it (a
    reading that is not a number passes every limit)."""
    limits = config["tolerance"]
    return [
        f"{branch} {key} {value:.3e}, over the tolerance {limits[key]:.1e}"
        for branch, of_branch in readings.items()
        for key, value in of_branch.items()
        if key in limits and not value <= limits[key]
    ]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _encode_block(config, branch, images, components, means, variances, weights):
    reduced = jnp.einsum("ncd,dk->nck", _descriptors(config, branch, images), components)
    return fisher(config, reduced, means, variances, weights)


def features(config: dict, images: np.ndarray, codebooks: dict):
    """(N, 4096): the two branches' encodings side by side, SIFT first."""
    frozen = _Frozen(_hashable(config))
    rows = []
    for start, stop in _blocks(len(images)):
        block = jnp.asarray(images[start:stop])
        rows.append(jnp.concatenate(
            [_encode_block(frozen, b, block, *(codebooks[b][k] for k in ("components", "means", "variances", "weights")))
             for b in ("sift", "lcs")], axis=1,
        ))
    return jnp.concatenate(rows, axis=0)


# ------------------------------------------------------------------ the solver


def solve(config: dict, x, labels: np.ndarray):
    """The per-class mixture-weighted ridge solve; W (d, classes), b (classes,)."""
    n, d = x.shape
    classes, w, lam = config["num_classes"], config["mixture_weight"], config["reg"]
    counts = np.bincount(labels, minlength=classes)
    most = int(counts.max())
    members = np.zeros((classes, most), np.int32)  # a class's rows, padded
    for c in range(classes):
        found = np.flatnonzero(labels == c)
        members[c, : len(found)] = found
    y = -np.ones((n, classes), np.float32)
    y[np.arange(n), labels] = 1.0
    nc = jnp.asarray(counts, jnp.float32)
    jlm = jnp.where(nc > 0, 2.0 * w + 2.0 * (1.0 - w) * nc / n - 1.0, -1.0)
    residual = jnp.asarray(y) - jlm
    pop_mean = jnp.mean(x, axis=0)
    pop_cov = x.T @ x / n - jnp.outer(pop_mean, pop_mean)
    pop_xtr = x.T @ residual / n
    residual_mean = jnp.mean(residual, axis=0)
    eye = jnp.eye(d, dtype=x.dtype)

    def one_class(args):
        c, rows, count = args
        there = (jnp.arange(most) < count).astype(x.dtype)
        safe = jnp.maximum(count, 1.0)
        x_c = x[rows] * there[:, None]
        r_c = residual[rows, c] * there
        class_mean = jnp.sum(x_c, axis=0) / safe
        class_cov = x_c.T @ x_c / safe - jnp.outer(class_mean, class_mean)
        delta = class_mean - pop_mean
        joint_mean = w * class_mean + (1.0 - w) * pop_mean
        mean_mix = (1.0 - w) * residual_mean[c] + w * jnp.sum(r_c) / safe
        joint_xtx = (1.0 - w) * pop_cov + w * class_cov + w * (1.0 - w) * jnp.outer(delta, delta)
        joint_xtr = (1.0 - w) * pop_xtr[:, c] + w * (x_c.T @ r_c) / safe - joint_mean * mean_mix
        factor = jax.scipy.linalg.cho_factor(joint_xtx + lam * eye, lower=True)
        w_c = jax.scipy.linalg.cho_solve(factor, joint_xtr) * (count > 0)
        return w_c, jlm[c] - joint_mean @ w_c

    weights, intercept = jax.lax.map(
        one_class, (jnp.arange(classes), jnp.asarray(members), nc)
    )
    return weights.T, intercept


# ---------------------------------------------------------------------- entry


def compared(config: dict, train: dict, heldout_x: np.ndarray, given: dict):
    """Everything the three comparisons need, nothing judged: ({branch:
    readings of parts (a) and (b)}, the reference's class scores)."""
    with jax.default_matmul_precision("highest"):
        fitted = {b: fit_codebook(config, b, train["x"], given) for b in ("sift", "lcs")}
        codebooks = {b: codebook for b, (codebook, _) in fitted.items()}
        readings = {b: dict(r) for b, (_, r) in fitted.items()}
        held = features(config, heldout_x, codebooks)
        for branch, apart in encodings_apart(config, np.asarray(given["heldout_encodings"]), held).items():
            readings[branch]["encodings_row_l2_apart"] = apart
        for branch, r in readings.items():
            print(f"reference[{branch}]: " + ", ".join(f"{k} {v:.3e}" for k, v in r.items()), flush=True)
        weights, intercept = solve(
            config, features(config, train["x"], codebooks), np.asarray(train["y"])
        )
        return readings, np.asarray(held @ weights + intercept)


def reference_scores(
    config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict
) -> np.ndarray:
    """Fit on `train` ({"x": images, "y": labels}, host arrays) and score
    `heldout_x`: real-valued class scores, (rows, classes), on the host.
    Raises where the program's codebooks or held-out encodings are outside
    the configuration's written tolerances (parts (a) and (b))."""
    readings, scores = compared(config, train, heldout_x, given)
    over = over_their_limits(config, readings)
    if over:
        raise ValueError("the program is not the reference: " + "; ".join(over))
    return scores
