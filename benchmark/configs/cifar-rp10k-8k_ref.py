"""cifar-rp10k-8k: the plain reference, staged.

CIFAR RandomPatch (the reference system's `RandomPatchCifar.scala`, after
Coates and Ng, "Learning Feature Representations with K-means", 2012):
patches, per-patch normalisation, whitened filters, symmetric rectifier,
sum pooling, standardisation and one pass of block coordinate descent.
Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")` on an explicit patch matrix (no
convolution primitive), on one device, importing nothing from
keystone_tpu. The filter bank and the whitener's means are given (the
configuration's file says why).

`correct` is decided by three comparisons and a check of the learned
filters, all here except (c)'s limit and all required:

  (d) `whitening_apart` and `filters_apart`: the filter learning, held to
      the sampled, row-normalised patches that the program fitted its
      whitener on (`given["patches"]`, input data: their sampling cannot
      be repeated independently). With C the patches' covariance about
      the program's whitener means, plus epsilon on the diagonal, in
      float64: the ZCA whitener is C^(-1/2), and every filter f = u W
      with |u| = 1 has f C f' = 1. The readings are |W - C^(-1/2)|_F /
      |C^(-1/2)|_F (the reference's own whitener, by an eigendecomposition
      in float64) and max |f C f' - 1| over the filters. This sees the ZCA
      fit (its means, its SVD and the scale of each direction: the
      patches' near-null direction, where epsilon 1e-5 sets the scale, is
      where a bfloat16 SVD goes wrong), and the filters' whitening and
      normalisation.

  (a) `features_row_l2_apart`: the held-out images' features, from the
      given filters and whitener, against the program's
      (`given["heldout_features"]`, compared and never computed with):
      the worst row's |program - reference|_2 / |reference|_2. The
      patch's mean and standard deviation come from the patch itself in
      two passes (mean, then the squared deviations), exact float32 on any
      backend. This is the limit that sees the patch statistics' precision:
      on the data's low-contrast square the variance is a few levels
      squared beside the constant 10, and squared pixels rounded to
      bfloat16 move it by about as much.
  (b) `solve_scores_apart`: the SOLVE alone. The reference standardises
      the program's own training features (`given["train_features"]`),
      runs its one-pass BCD on them and scores the program's own held-out
      features; max |program - reference| over max |reference| of the
      scores. No featurizer stands between the two solves, so this is the
      limit that sees the solver's precision (a solver at the MXU default
      rounds every product's inputs to bfloat16).
  (c) the class scores of the reference's OWN pipeline (its features of
      the training and held-out images, its standardisation, its solve)
      within the harness's `scores_max_abs_over_ref_max_abs`: a wide
      limit, since n = 8,192 < d = 80,000 with lambda 3,000 carries the
      featurizer's float32 rounding into the weights.

A number over its limit raises after all of (d), (a) and (b) are printed,
which the harness reports as not correct.

Staged: each comparison's arrays are let go of before the next starts,
the features are made `IMAGES_PER_STEP` images at a time, and (b) and (c)
each hold one feature matrix, its standardised and its centred copy
(7.9 GB at 8,192 rows), never two at once.

The knobs are the configuration's stated precisions, each written out as
a rounding of float32 values (on their bits: `_rounded`):
`conv_input_dtype`, what the patches and the filters are rounded to
before the one large product (bfloat16 as shipped: the MXU default of
a float32 convolution); `patch_stats_dtype`, "float32" as stated, or
"bfloat16": the mean and variance as a float32 convolution at the MXU
default computes them, from box sums of the patch and of its squares
rounded to bfloat16, the variance as their difference; `solver_input_dtype`,
"float32" as stated, or "bfloat16": the inputs of every product of the
solve rounded, as a solver at the MXU default multiplies;
`whitener_dtype`, "float32" as stated (the program's whitener and
filters are checked), or "bfloat16": the reference's own whitener, from
an SVD of the centred patches rounded to bfloat16, and filters made with
it, are checked in their place. The three "bfloat16" settings are the
nearest precisions below the stated ones, which (a), (b) and (d) have to
fail.

Departures from the published descriptions, each for a stated reason:
- The patch matrix is made explicitly, `IMAGES_PER_STEP` images a step, and
  not per image as the reference's `Convolver.scala`: the same numbers.
- The filters and the whitener are given, not learned (the
  configuration's `assumed.filters`), and held to the given patches (d).
- Labels are -1/+1 indicators (the program's ClassLabelIndicators).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMAGES_PER_STEP = 32  # bounds the (images x 729, filters) response matrix: 0.93 GB
LIMITED = ("whitening_apart", "filters_apart", "features_row_l2_apart", "solve_scores_apart")


def _patches(x, s: int):
    """(n, X, Y, C) images -> (n, X-s+1, Y-s+1, s*s*C) patches, laid out
    as the filters' rows are: index = c + dx*C + dy*C*s."""
    n, size_x, size_y, c = x.shape
    rx, ry = size_x - s + 1, size_y - s + 1
    cols = [x[:, dx:dx + rx, dy:dy + ry, :] for dy in range(s) for dx in range(s)]
    return jnp.stack(cols, axis=3).reshape(n, rx, ry, s * s * c)


def _pool_sum(v, size: int, stride: int):
    """Sum over square pools centred at size//2, size//2 + stride, ...,
    each [centre - size//2, centre + size//2), clipped to the map."""
    half = size // 2
    centres = range(half, v.shape[1], stride)
    rows = [
        jnp.stack(
            [
                v[:, max(cx - half, 0):cx + half, max(cy - half, 0):cy + half, :].sum(axis=(1, 2))
                for cy in centres
            ],
            axis=1,
        )
        for cx in centres
    ]
    return jnp.stack(rows, axis=1)  # (n, pools_x, pools_y, channels)


def _rounded(a, dtype: str):
    """float32 `a` rounded to `dtype` ("float32": as it is; "bfloat16": to
    the nearest of its 8 significant bits, ties to even), as float32. Done
    on the bits: a TPU's compiler may keep a float32 value where a convert
    to bfloat16 and back asks it to round (its excess precision), and did
    so for the squared pixels here (PERF.md section 6, PR 40)."""
    if dtype == "float32":
        return a
    if dtype != "bfloat16":
        raise ValueError(f"no rounding to {dtype!r}")
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("s", "var_constant", "alpha", "pool", "stride", "conv_dtype", "stats_dtype")
)
def _features_step(images, filters, offsets, s, var_constant, alpha, pool, stride, conv_dtype, stats_dtype):
    """The features of one step of images: (m, 8 * filters)."""
    d = filters.shape[1]
    p = _patches(images, s)
    if stats_dtype == "float32":
        mean = p.mean(axis=-1, keepdims=True)
        var = jnp.sum((p - mean) ** 2, axis=-1, keepdims=True) / (d - 1)
    else:  # box sums of the patch and of its squares, inputs rounded: the MXU default
        mean = _rounded(p, stats_dtype).sum(axis=-1, keepdims=True) / d
        var = jnp.maximum(_rounded(p * p, stats_dtype).sum(axis=-1, keepdims=True) - d * mean * mean, 0.0) / (d - 1)
    sd = jnp.sqrt(var + var_constant)
    # filter . ((patch - mean) / sd - whitener mean), expanded so that the
    # stated rounding sits on the one large product's inputs
    raw = _rounded(p, conv_dtype) @ _rounded(filters, conv_dtype).T
    out = (raw - mean * filters.sum(axis=1)) / sd - offsets
    both = jnp.concatenate([jnp.maximum(0.0, out - alpha), jnp.maximum(0.0, -out - alpha)], axis=-1)
    pooled = _pool_sum(both, pool, stride)  # (m, px, py, 2F)
    return jnp.transpose(pooled, (0, 2, 1, 3)).reshape(images.shape[0], -1)


def features(config: dict, images: np.ndarray, given: dict, stats_dtype=None) -> jax.Array:
    """(N, 8 * filters) on the device, `IMAGES_PER_STEP` images a step."""
    if len(images) % IMAGES_PER_STEP:
        raise ValueError(f"rows must be a multiple of {IMAGES_PER_STEP}")
    filters = jnp.asarray(given["filters"])
    offsets = jnp.asarray(given["whitener_means"]) @ filters.T
    step = functools.partial(
        _features_step, filters=filters, offsets=offsets,
        s=config["patch_size"], var_constant=float(config["patch_var_constant"]),
        alpha=float(config["alpha"]), pool=config["pool_size"], stride=config["pool_stride"],
        conv_dtype=config["conv_input_dtype"], stats_dtype=stats_dtype or config["patch_stats_dtype"],
    )
    return jnp.concatenate(
        [step(jnp.asarray(images[i:i + IMAGES_PER_STEP])) for i in range(0, len(images), IMAGES_PER_STEP)]
    )


@functools.partial(jax.jit, static_argnames=("block", "epochs", "reg", "dtype"), donate_argnums=(0,))
def _fit(feats, y, block: int, epochs: int, reg: float, dtype: str):
    n, d = feats.shape
    block = min(block, d)  # as the program's estimator does
    mean = jnp.sum(feats, axis=0) / n
    var = (jnp.sum(feats * feats, axis=0) - n * mean * mean) / max(n - 1, 1)
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    std = jnp.where(jnp.isnan(std) | jnp.isinf(std) | (std < 1e-12), 1.0, std)
    scaled = (feats - mean) / std
    mu_a = jnp.sum(scaled, axis=0) / n
    mu_b = jnp.sum(y, axis=0) / n
    d_pad = -(-d // block) * block
    xc = jnp.pad(scaled - mu_a, ((0, 0), (0, d_pad - d)))  # zero columns are inert
    yc = y - mu_b
    eye = jnp.eye(block, dtype=jnp.float32)
    w = jnp.zeros((d_pad, y.shape[1]), jnp.float32)
    p = jnp.zeros_like(yc)

    def mm(a, b):
        return _rounded(a, dtype) @ _rounded(b, dtype)

    def step(carry, b):
        w, p = carry
        a_b = jax.lax.dynamic_slice(xc, (0, b * block), (n, block))
        w_b = jax.lax.dynamic_slice(w, (b * block, 0), (block, y.shape[1]))
        r = yc - p + mm(a_b, w_b)
        factor = jax.scipy.linalg.cho_factor(mm(a_b.T, a_b) + reg * eye, lower=True)
        w_new = jax.scipy.linalg.cho_solve(factor, mm(a_b.T, r))
        p = p + mm(a_b, w_new - w_b)
        return (jax.lax.dynamic_update_slice(w, w_new, (b * block, 0)), p), None

    order = jnp.tile(jnp.arange(d_pad // block), epochs)
    (w, _), _ = jax.lax.scan(step, (w, p), order)
    return w[:d], mean, std, mu_a, mu_b


@jax.jit
def _scores(feats, w, mean, std, mu_a, mu_b):
    return ((feats - mean) / std - mu_a) @ w + mu_b


def solve_and_score(config: dict, train_features, labels: np.ndarray, heldout_features, dtype=None) -> np.ndarray:
    """Standardise, one-pass BCD, and the held-out rows' class scores, on
    the host. `train_features` is donated: the caller lets go of it."""
    y = -jnp.ones((len(labels), config["num_classes"]), jnp.float32)
    y = y.at[jnp.arange(len(labels)), jnp.asarray(labels)].set(1.0)
    model = _fit(
        train_features, y, block=config["block_size"], epochs=config["num_epochs"],
        reg=float(config["reg"]), dtype=dtype or config["solver_input_dtype"],
    )
    return np.asarray(_scores(jnp.asarray(heldout_features), *model))


def _zca(patches: np.ndarray, eps: float, dtype: str):
    """The ZCA whitener of `patches` and their means, float32, from an SVD
    of the centred patches rounded to `dtype`."""
    p = jnp.asarray(patches, jnp.float32)
    means = p.mean(axis=0)
    _, s, vt = jnp.linalg.svd(_rounded(p - means, dtype), full_matrices=False)
    scale = (s * s / (p.shape[0] - 1) + eps) ** -0.5
    return np.asarray((vt.T * scale) @ vt), np.asarray(means)


def whitening(config: dict, given: dict) -> dict:
    """(d): {"whitening_apart": |W - C^(-1/2)|_F / |C^(-1/2)|_F,
    "filters_apart": max over the filters of |f C f' - 1|}, C the given
    patches' covariance about the whitener's means plus epsilon I, all in
    float64 on the host."""
    patches = np.asarray(given["patches"], np.float64)
    eps = float(config["whitening_epsilon"])
    if config["whitener_dtype"] == "float32":
        w, means, filters = (np.asarray(given[k], np.float64) for k in ("whitener", "whitener_means", "filters"))
    else:  # the reference's own whitener at the precision below, its filters drawn from the first rows
        w, means = (np.asarray(a, np.float64) for a in _zca(given["patches"], eps, config["whitener_dtype"]))
        u = (patches[: len(given["filters"])] - means) @ w
        filters = u / np.linalg.norm(u, axis=1, keepdims=True) @ w.T
    centred = patches - means
    cov = centred.T @ centred / (len(patches) - 1) + eps * np.eye(len(means))
    lam, vec = np.linalg.eigh(cov)
    own = (vec / np.sqrt(lam)) @ vec.T
    return {
        "whitening_apart": float(np.linalg.norm(w - own) / np.linalg.norm(own)),
        "filters_apart": float(np.max(np.abs(np.einsum("ij,jk,ik->i", filters, cov, filters) - 1.0))),
    }


def rows_apart(program: np.ndarray, reference) -> float:
    """The worst row's |program - reference|_2 / |reference|_2."""
    reference = np.asarray(reference)
    if program.shape != reference.shape:
        raise ValueError(f"the program's rows are {program.shape}, the reference's {reference.shape}")
    return float(np.max(
        np.linalg.norm(program - reference, axis=1) / np.maximum(np.linalg.norm(reference, axis=1), 1e-30)
    ))


def scores_apart(program: np.ndarray, reference: np.ndarray) -> float:
    """max |program - reference| over max |reference|."""
    return float(np.max(np.abs(program - reference)) / max(float(np.max(np.abs(reference))), 1e-30))


def compared(config: dict, train: dict, heldout_x: np.ndarray, given: dict):
    """Everything the comparisons need, nothing judged: ({reading: value}
    of parts (d), (a) and (b), the reference's own class scores)."""
    if config["reg"] <= 0:
        raise ValueError("the reference takes the configuration's lambda as it stands")
    with jax.default_matmul_precision("highest"):
        readings = whitening(config, given)
        readings["features_row_l2_apart"] = rows_apart(
            np.asarray(given["heldout_features"]), features(config, heldout_x, given)
        )
        program_solved = solve_and_score(
            config, jnp.asarray(given["train_features"]), train["y"], given["heldout_features"]
        )
        readings["solve_scores_apart"] = scores_apart(np.asarray(given["heldout_scores"]), program_solved)
        print("reference: " + ", ".join(f"{k} {v:.3e}" for k, v in readings.items()), flush=True)
        own = solve_and_score(config, features(config, train["x"], given), train["y"], features(config, heldout_x, given))
    return readings, own


def over_their_limits(config: dict, readings: dict) -> list:
    """["<reading> <value>, over the tolerance <limit>"] for every reading
    over its limit (a reading that is not a number is over every limit)."""
    limits = config["tolerance"]
    return [
        f"{key} {value:.3e}, over the tolerance {limits[key]:.1e}"
        for key, value in readings.items()
        if not value <= limits[key]
    ]


def reference_scores(config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict) -> np.ndarray:
    """Fit on `train` ({"x": images, "y": labels}, host arrays) with the
    given filters and whitener means, and score `heldout_x`: real-valued
    class scores, (rows, classes), on the host. Raises where the program's
    held-out features or its solve are outside the configuration's written
    tolerances (parts (d), (a) and (b))."""
    readings, scores = compared(config, train, heldout_x, given)
    over = over_their_limits(config, readings)
    if over:
        raise ValueError("the program is not the reference: " + "; ".join(over))
    return scores
