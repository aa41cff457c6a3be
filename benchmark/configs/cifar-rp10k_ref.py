"""cifar-rp10k: the plain reference.

Patches, per-patch normalisation, whitened filters, symmetric rectifier,
sum pooling, standardisation and one pass of block coordinate descent:
straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, on an explicit patch matrix
(no convolution primitive). It imports nothing from keystone_tpu. The
filter bank and the whitener's means are given (the configuration's file
says why). The one departure from plain float32 is the configuration's
`conv_input_dtype`, written out as casts: the pixels, the squared pixels
and the filters are rounded to it before they are multiplied and summed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMAGES_PER_STEP = 64  # bounds the (images x 729, filters) response matrix


def _patches(x, s: int):
    """(n, X, Y, C) images -> (n, X-s+1, Y-s+1, s*s*C) patches, laid out
    as the filters' rows are: index = c + dx*C + dy*C*s."""
    n, size_x, size_y, c = x.shape
    rx, ry = size_x - s + 1, size_y - s + 1
    cols = [
        x[:, dx:dx + rx, dy:dy + ry, :] for dy in range(s) for dx in range(s)
    ]
    return jnp.stack(cols, axis=3).reshape(n, rx, ry, s * s * c)


def _pool_sum(v, size: int, stride: int):
    """Sum over square pools centred at size//2, size//2 + stride, ...,
    each [centre - size//2, centre + size//2), clipped to the map."""
    half = size // 2
    extent = v.shape[1]
    centres = range(half, extent, stride)
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


@functools.partial(jax.jit, static_argnames=("s", "var_constant", "alpha", "pool", "stride", "dtype"))
def _features(x, filters, whitener_means, s, var_constant, alpha, pool, stride, dtype):
    def rounded(a):
        return a.astype(dtype).astype(jnp.float32)

    d = filters.shape[1]
    filter_sums = filters.sum(axis=1)
    offsets = whitener_means @ filters.T

    def step(images):
        p = _patches(images, s)
        mean = rounded(p).sum(axis=-1, keepdims=True) / d
        var = jnp.maximum(rounded(p * p).sum(axis=-1, keepdims=True) - d * mean * mean, 0.0) / (d - 1)
        sd = jnp.sqrt(var + var_constant)
        # filter . ((patch - mean) / sd - whitener mean), expanded so that
        # the stated rounding sits on the one large product's inputs
        out = (rounded(p) @ rounded(filters).T - mean * filter_sums) / sd - offsets
        both = jnp.concatenate(
            [jnp.maximum(0.0, out - alpha), jnp.maximum(0.0, -out - alpha)], axis=-1
        )
        pooled = _pool_sum(both, pool, stride)  # (m, px, py, 2F)
        return jnp.transpose(pooled, (0, 2, 1, 3)).reshape(images.shape[0], -1)

    steps = x.reshape((-1, IMAGES_PER_STEP) + x.shape[1:])
    return jax.lax.map(step, steps).reshape(x.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("block", "epochs", "reg"), donate_argnums=(0,))
def _fit(feats, y, block: int, epochs: int, reg: float):
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

    def step(carry, b):
        w, p = carry
        a_b = jax.lax.dynamic_slice(xc, (0, b * block), (n, block))
        w_b = jax.lax.dynamic_slice(w, (b * block, 0), (block, y.shape[1]))
        r = yc - p + a_b @ w_b
        factor = jax.scipy.linalg.cho_factor(a_b.T @ a_b + reg * eye, lower=True)
        w_new = jax.scipy.linalg.cho_solve(factor, a_b.T @ r)
        p = p + a_b @ (w_new - w_b)
        return (jax.lax.dynamic_update_slice(w, w_new, (b * block, 0)), p), None

    order = jnp.tile(jnp.arange(d_pad // block), epochs)
    (w, _), _ = jax.lax.scan(step, (w, p), order)
    return w[:d], mean, std, mu_a, mu_b


@jax.jit
def _scores(feats, w, mean, std, mu_a, mu_b):
    return ((feats - mean) / std - mu_a) @ w + mu_b


def reference_scores(
    config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict
) -> np.ndarray:
    """Fit on `train` ({"x": images, "y": labels}, host arrays) with the
    given filters and whitener means, and score `heldout_x`: real-valued
    class scores, (rows, classes), on the host."""
    if config["reg"] <= 0:
        raise ValueError("the reference takes the configuration's lambda as it stands")
    for rows in (len(train["x"]), len(heldout_x)):
        if rows % IMAGES_PER_STEP:
            raise ValueError(f"rows must be a multiple of {IMAGES_PER_STEP}")
    featurize = functools.partial(
        _features,
        filters=jnp.asarray(given["filters"]),
        whitener_means=jnp.asarray(given["whitener_means"]),
        s=config["patch_size"], var_constant=float(config["patch_var_constant"]),
        alpha=float(config["alpha"]), pool=config["pool_size"],
        stride=config["pool_stride"], dtype=config["conv_input_dtype"],
    )
    with jax.default_matmul_precision("highest"):
        y = -jnp.ones((len(train["y"]), config["num_classes"]), jnp.float32)
        y = y.at[jnp.arange(len(train["y"])), jnp.asarray(train["y"])].set(1.0)
        model = _fit(
            featurize(jnp.asarray(train["x"])), y, block=config["block_size"],
            epochs=config["num_epochs"], reg=float(config["reg"]),
        )
        out = _scores(featurize(jnp.asarray(heldout_x)), *model)
    return np.asarray(out)
