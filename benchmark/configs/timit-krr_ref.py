"""timit-krr: the plain reference, one column block of the kernel at a time.

Kernel ridge regression with the Gaussian kernel k(a, b) = exp(-g |a - b|^2)
on the raw frames, solved by block Gauss-Seidel on the dual (Tu, Roelofs,
Venkataraman, Recht, "Large Scale Kernel Learning using Block Coordinate
Descent", arXiv:1602.05310; upstream KernelRidgeRegression.scala). For
each block b of `block_size` training rows, in the order the seed gives:

    K_b   = k(X, X_b)                       the n x b column panel
    R     = K_b^T W                         the model's prediction on the block
    K_bb  = k(X_b, X_b)
    W_b  <- (K_bb + lambda I)^-1 (Y_b - R + K_bb W_b)

and the class scores of held-out rows are K(test, train) W, summed over
train blocks. The n x n kernel is never held: the largest array is one
n x b panel (2 GiB at 131,072 x 4,096), so the reference fits the chip
beside nothing (the driver drops the program's model first).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, on one device, importing
nothing from keystone_tpu. The one knob is the configuration's stated
`kernel_matmul_input_dtype`, written out as a cast: what the inputs of
the distance matmul a.b are rounded to before their products are summed
in float32. "float32" as shipped (the program runs it at HIGHEST); a test
and the tolerance's second reading state "bfloat16", which is what one
pass at the MXU default does to them, on any backend.

Departures from the paper's description, each for a stated reason:
- lambda enters as K_bb + lambda I, not lambda n I: the upstream
  implementation's form, which the program states as its parity.
- A last short block is solved at its own size (the paper's n is a whole
  number of blocks); the program pads it and masks the padding.
- The block order of an epoch is a permutation drawn from
  `numpy.random.default_rng(seed)`, one generator across the epochs: the
  program's `block_permuter` rule, written out again here. The paper
  draws a random permutation per epoch and does not fix the generator.
- Labels are -1/+1 indicators (the program's ClassLabelIndicators; the
  upstream pipeline's), not 0/1.
- The squared distance is clamped at zero before the exp: |a|^2 - 2 a.b +
  |b|^2 can round below zero for a row against itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def block_order(config: dict, seed: int, rows: int) -> list[int]:
    """The first row of every block step, all epochs in order."""
    block = config["block_size"]
    blocks = -(-rows // block)
    rng = np.random.default_rng(seed)
    starts = []
    for _ in range(config["num_epochs"]):
        order = np.arange(blocks)
        rng.shuffle(order)
        starts.extend(int(i) * block for i in order)
    return starts


def _panel(a, b, g, input_dtype: str):
    """k(a, b) for every pair of rows: (rows of a, rows of b)."""
    dots = jnp.matmul(
        a.astype(input_dtype), b.astype(input_dtype).T, preferred_element_type=jnp.float32
    )
    squared = jnp.sum(a * a, axis=1)[:, None] - 2.0 * dots + jnp.sum(b * b, axis=1)[None, :]
    return jnp.exp(-g * jnp.maximum(squared, 0.0))


@functools.partial(jax.jit, static_argnames=("size", "input_dtype"), donate_argnums=(0,))
def _block_step(w, x, y, start, g, lam, size: int, input_dtype: str):
    x_b = jax.lax.dynamic_slice(x, (start, 0), (size, x.shape[1]))
    y_b = jax.lax.dynamic_slice(y, (start, 0), (size, y.shape[1]))
    w_b = jax.lax.dynamic_slice(w, (start, 0), (size, w.shape[1]))
    residual = _panel(x, x_b, g, input_dtype).T @ w
    k_bb = _panel(x_b, x_b, g, input_dtype)
    factor = jax.scipy.linalg.cho_factor(k_bb + lam * jnp.eye(size, dtype=x.dtype), lower=True)
    w_new = jax.scipy.linalg.cho_solve(factor, y_b - residual + k_bb @ w_b)
    return jax.lax.dynamic_update_slice(w, w_new, (start, 0))


@functools.partial(jax.jit, static_argnames=("input_dtype",), donate_argnums=(0,))
def _add_scores(scores, heldout, x_b, w_b, g, input_dtype: str):
    return scores + _panel(heldout, x_b, g, input_dtype) @ w_b


def indicators(labels: np.ndarray, num_classes: int) -> np.ndarray:
    y = -np.ones((len(labels), num_classes), np.float32)
    y[np.arange(len(labels)), labels] = 1.0
    return y


def fit_duals(config: dict, seed: int, train: dict):
    """The dual weights W, (rows, classes), on the device."""
    n, block = len(train["y"]), config["block_size"]
    input_dtype = config["kernel_matmul_input_dtype"]
    g, lam = jnp.float32(config["kernel_gamma"]), jnp.float32(config["reg"])
    x = jnp.asarray(train["x"], jnp.float32)
    y = jnp.asarray(indicators(train["y"], config["num_classes"]))
    w = jnp.zeros_like(y)
    with jax.default_matmul_precision("highest"):
        for start in block_order(config, seed, n):
            w = _block_step(w, x, y, start, g, lam, size=min(block, n - start), input_dtype=input_dtype)
    return x, w


def reference_scores(
    config: dict, seed: int, train: dict, heldout_x: np.ndarray, given: dict
) -> np.ndarray:
    """Fit on `train` ({"x", "y"} host arrays) and score `heldout_x`:
    real-valued class scores, (rows, classes), on the host."""
    n, block = len(train["y"]), config["block_size"]
    input_dtype = config["kernel_matmul_input_dtype"]
    g = jnp.float32(config["kernel_gamma"])
    with jax.default_matmul_precision("highest"):
        x, w = fit_duals(config, seed, train)
        heldout = jnp.asarray(heldout_x, jnp.float32)
        scores = jnp.zeros((len(heldout_x), config["num_classes"]), jnp.float32)
        for start in range(0, n, block):
            stop = min(start + block, n)
            scores = _add_scores(scores, heldout, x[start:stop], w[start:stop], g, input_dtype=input_dtype)
    return np.asarray(scores)
