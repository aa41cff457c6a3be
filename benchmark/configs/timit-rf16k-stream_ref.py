"""timit-rf16k-stream: the plain reference, in row blocks so that it fits.

The same mathematics as timit-rf16k's reference (cosine random features,
centring, block coordinate descent with the same block order and epochs,
a Cholesky solve per block), written for a feature matrix that is never
held: the features of one row block at a time are made, centred with the
column means of a first pass, and folded into the centred Gram
(Xc^T Xc) and cross product (Xc^T Yc); the block steps then read those.
A block step of coordinate descent needs of the data only
A_b^T (Yc - P + A_b W_b) = (Xc^T Yc)_b - (Xc^T Xc W)_b + (Xc^T Xc)_bb W_b.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, on one device, importing
nothing from keystone_tpu. Two passes over the rows (means, then the
centred products), where the program makes one and centres algebraically.
The one departure from plain float32 is stated in the configuration and
written out here as a cast: the featurizer's matmul sees its inputs
rounded to bfloat16, as the MXU default does to them in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 16384  # rows of features held at a time: 1 GiB at 16,384 features


def weights(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(W, b) of all branches, stacked in branch order: W_i = gamma *
    N(0, 1), b_i ~ U[0, 2 pi), from `default_rng(seed + i)`, the rule the
    configuration states."""
    if config["rf_type"] != "gaussian":
        raise ValueError("the reference knows the Gaussian variant only")
    ws, bs = [], []
    for i in range(config["num_cosines"]):
        rng = np.random.default_rng(seed + i)
        w = rng.normal(size=(config["num_cosine_features"], config["input_dim"]))
        b = rng.uniform(0.0, 2.0 * np.pi, size=config["num_cosine_features"])
        ws.append((w * config["gamma"]).astype(np.float32))
        bs.append(b.astype(np.float32))
    return np.concatenate(ws), np.concatenate(bs)


@functools.partial(jax.jit, static_argnames=("input_dtype",))
def _features(x, w, b, input_dtype: str):
    # The cast is the configuration's stated rounding of the matmul's inputs.
    def rounded(a):
        return a.astype(input_dtype).astype(jnp.float32)

    return jnp.cos(rounded(x) @ rounded(w).T + b)


@functools.partial(jax.jit, static_argnames=("input_dtype",), donate_argnums=(0, 1))
def _add_sums(sum_a, sum_b, x, y, w, b, input_dtype: str):
    return sum_a + jnp.sum(_features(x, w, b, input_dtype), axis=0), sum_b + jnp.sum(y, axis=0)


@functools.partial(jax.jit, static_argnames=("input_dtype",), donate_argnums=(0, 1))
def _add_products(gram, cross, x, y, w, b, mu_a, mu_b, input_dtype: str):
    xc = _features(x, w, b, input_dtype) - mu_a
    yc = y - mu_b
    return gram + xc.T @ xc, cross + xc.T @ yc


@functools.partial(jax.jit, static_argnames=("block", "epochs", "reg0"))
def _solve(gram, cross, block: int, epochs: int, reg0: float):
    d, k = cross.shape
    # reg 0 means the program's floor: 1e-6 of the mean Gram diagonal
    # (n * mean(xc^2) = trace(Xc^T Xc) / d)
    reg = reg0 if reg0 > 0 else jnp.maximum(1e-6 * jnp.trace(gram) / d, 1e-6)
    eye = jnp.eye(block, dtype=jnp.float32)

    def step(w, i):
        g_rows = jax.lax.dynamic_slice(gram, (i * block, 0), (block, d))
        g_bb = jax.lax.dynamic_slice(g_rows, (0, i * block), (block, block))
        c_b = jax.lax.dynamic_slice(cross, (i * block, 0), (block, k))
        w_b = jax.lax.dynamic_slice(w, (i * block, 0), (block, k))
        rhs = c_b - g_rows @ w + g_bb @ w_b
        factor = jax.scipy.linalg.cho_factor(g_bb + reg * eye, lower=True)
        w_new = jax.scipy.linalg.cho_solve(factor, rhs)
        return jax.lax.dynamic_update_slice(w, w_new, (i * block, 0)), None

    order = jnp.tile(jnp.arange(d // block), epochs)
    w, _ = jax.lax.scan(step, jnp.zeros((d, k), jnp.float32), order)
    return w


@jax.jit
def _scores(feats, w, mu_a, mu_b):
    return (feats - mu_a) @ w + mu_b


def _row_blocks(train: dict, num_classes: int):
    """(x, y) of one row block after another, y as -1/+1 indicators."""
    n = len(train["y"])
    for start in range(0, n, ROW_BLOCK):
        labels = np.asarray(train["y"][start:start + ROW_BLOCK])
        y = -np.ones((len(labels), num_classes), np.float32)
        y[np.arange(len(labels)), labels] = 1.0
        yield jnp.asarray(train["x"][start:start + ROW_BLOCK]), jnp.asarray(y)


def reference_scores(
    config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict
) -> np.ndarray:
    """Fit on `train` ({"x", "y"} host arrays) and score `heldout_x`:
    real-valued class scores, (rows, classes), on the host."""
    block = config["block_size"]
    d = config["num_cosines"] * config["num_cosine_features"]
    k = config["num_classes"]
    if d % block:
        raise ValueError("the reference needs whole blocks")
    dtype = config["featurizer_input_dtype"]
    n = len(train["y"])
    w_host, b_host = weights(config, seed)
    with jax.default_matmul_precision("highest"):
        w, b = jnp.asarray(w_host), jnp.asarray(b_host)
        sum_a, sum_b = jnp.zeros((d,), jnp.float32), jnp.zeros((k,), jnp.float32)
        for x, y in _row_blocks(train, k):
            sum_a, sum_b = _add_sums(sum_a, sum_b, x, y, w, b, dtype)
        mu_a, mu_b = sum_a / n, sum_b / n
        gram, cross = jnp.zeros((d, d), jnp.float32), jnp.zeros((d, k), jnp.float32)
        for x, y in _row_blocks(train, k):
            gram, cross = _add_products(gram, cross, x, y, w, b, mu_a, mu_b, dtype)
        solved = _solve(
            gram, cross, block=block, epochs=config["num_epochs"], reg0=float(config["reg"])
        )
        out = _scores(_features(jnp.asarray(heldout_x), w, b, dtype), solved, mu_a, mu_b)
    return np.asarray(out)
