"""timit-rf16k: the plain reference.

Cosine random features, centring, block coordinate descent with the same
block order and epochs, a Cholesky solve per block: straightforward
`jax.numpy` in float32 under `jax.default_matmul_precision("highest")`.
It imports nothing from keystone_tpu. The one departure from plain
float32 is stated in the configuration and written out here as a cast:
the featurizer's matmul sees its inputs rounded to bfloat16, as the MXU
default does to them in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def weights(config: dict, seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """(W_i, b_i) per branch: W = gamma * N(0, 1), b ~ U[0, 2 pi), from
    `default_rng(seed + i)`, the rule the configuration states."""
    if config["rf_type"] != "gaussian":
        raise ValueError("the reference knows the Gaussian variant only")
    out = []
    for i in range(config["num_cosines"]):
        rng = np.random.default_rng(seed + i)
        w = rng.normal(size=(config["num_cosine_features"], config["input_dim"]))
        b = rng.uniform(0.0, 2.0 * np.pi, size=config["num_cosine_features"])
        out.append(((w * config["gamma"]).astype(np.float32), b.astype(np.float32)))
    return out


@functools.partial(jax.jit, static_argnames=("input_dtype",))
def _features(x, ws, bs, input_dtype: str):
    # ws: (branches, features, inputs); one matmul per branch, concatenated.
    # The cast is the configuration's stated rounding of the matmul's inputs.
    def rounded(a):
        return a.astype(input_dtype).astype(jnp.float32)

    parts = [jnp.cos(rounded(x) @ rounded(w).T + b) for w, b in zip(ws, bs)]
    return jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnames=("block", "epochs", "reg0"), donate_argnums=(0,))
def _fit(feats, y, block: int, epochs: int, reg0: float):
    n, d = feats.shape
    mu_a = jnp.sum(feats, axis=0) / n
    mu_b = jnp.sum(y, axis=0) / n
    xc = feats - mu_a
    yc = y - mu_b
    # reg 0 means the program's floor: 1e-6 of the mean Gram diagonal
    reg = reg0 if reg0 > 0 else jnp.maximum(1e-6 * n * jnp.mean(jnp.square(xc)), 1e-6)
    eye = jnp.eye(block, dtype=jnp.float32)
    w = jnp.zeros((d, y.shape[1]), jnp.float32)
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

    order = jnp.tile(jnp.arange(d // block), epochs)
    (w, _), _ = jax.lax.scan(step, (w, p), order)
    return w, mu_a, mu_b


@jax.jit
def _scores(feats, w, mu_a, mu_b):
    return (feats - mu_a) @ w + mu_b


def reference_scores(
    config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict
) -> np.ndarray:
    """Fit on `train` ({"x", "y"} host arrays) and score `heldout_x`:
    real-valued class scores, (rows, classes), on the host."""
    block = config["block_size"]
    d = config["num_cosines"] * config["num_cosine_features"]
    if d % block:
        raise ValueError("the reference needs whole blocks")
    dtype = config["featurizer_input_dtype"]
    pairs = weights(config, seed)
    ws = jnp.stack([w for w, _ in pairs])
    bs = jnp.stack([b for _, b in pairs])
    with jax.default_matmul_precision("highest"):
        y = -jnp.ones((len(train["y"]), config["num_classes"]), jnp.float32)
        y = y.at[jnp.arange(len(train["y"])), jnp.asarray(train["y"])].set(1.0)
        feats = _features(jnp.asarray(train["x"]), ws, bs, dtype)
        w, mu_a, mu_b = _fit(
            feats, y, block=block, epochs=config["num_epochs"], reg0=float(config["reg"])
        )
        out = _scores(_features(jnp.asarray(heldout_x), ws, bs, dtype), w, mu_a, mu_b)
    return np.asarray(out)
