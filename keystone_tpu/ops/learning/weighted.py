"""Per-class mixture-weighted block least squares.

TPU-native re-design of
reference: nodes/learning/BlockWeightedLeastSquares.scala:36-372 and
nodes/learning/internal/ReWeightedLeastSquares.scala:18-142.

The solver fits, per class c, weights against a mixture of population and
class-conditional second-moment statistics controlled by ``mixture_weight``
(the reference's ImageNet configuration uses 0.25):

    jointXTX_c = (1−w)·popCov + w·classCov_c + w(1−w)·δ_c δ_cᵀ
    jointXTR_c = (1−w)·popXTR[:,c] + w·classXTR_c − jointMean_c·meanMix_c
    ΔW_c       = (jointXTX_c + λI)⁻¹ (jointXTR_c − λ·W_old[:,c])

with δ_c = classMean_c − popMean, per-block Gauss-Seidel over feature
blocks, and intercept b_c = jlm_c − Σ_d jointMean[c,d]·W[d,c] where
jlm_c = 2w + 2(1−w)·n_c/n − 1 (BlockWeightedLeastSquares.scala:149,318).

Execution re-design: the reference partitions the RDD so each partition
holds one class and computes class statistics partition-locally. Here
examples are sorted by class once; per-class covariances come from a
``lax.scan`` over classes reading static-size padded row windows of the
sorted batch, and cross-class quantities (classMean, classXTR, popXTR)
are single one-hot matmuls on the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...obs import spans as _spans
from ...parallel import linalg
from ...workflow.pipeline import LabelEstimator
from ..stats.core import _as_array_dataset
from .block import BlockLinearMapper, _round_up


def joint_label_means(counts, n, mixture_weight):
    """jlm_c = 2·mw + 2(1−mw)·n_c/n − 1, with the absent-class fallback:
    an all −1 target column's least-squares-consistent constant is −1
    (2·mw−1 would let a phantom class outrank trained negatives in top-k).
    Shared by both weighted estimators
    (reference: BlockWeightedLeastSquares.scala:149,318,
    PerClassWeightedLeastSquares.scala:190-196 computeJointLabelMean)."""
    counts = jnp.asarray(counts, jnp.float32)
    mw = mixture_weight
    jlm = 2.0 * mw + 2.0 * (1.0 - mw) * counts / jnp.float32(n) - 1.0
    return jnp.where(counts > 0, jlm, -1.0)


def weighted_intercept(jlm, joint_means, w):
    """b_c = jlm_c − Σ_d jointMean[c, d]·W[d, c]
    (reference: BlockWeightedLeastSquares.scala:318,
    PerClassWeightedLeastSquares.scala:122 finalB)."""
    return jnp.asarray(jlm, jnp.float32) - jnp.einsum(
        "cd,dc->c", joint_means, w, precision=linalg.precision()
    )


class BlockWeightedLeastSquaresEstimator(LabelEstimator):
    def __init__(self, block_size: int, num_iter: int, reg: float,
                 mixture_weight: float, solve_path: str = "auto"):
        self.block_size = block_size
        self.num_iter = num_iter
        self.reg = reg
        if not 0.0 <= mixture_weight <= 1.0:
            raise ValueError(f"mixture_weight must be in [0, 1], got {mixture_weight}")
        self.mixture_weight = mixture_weight
        # "auto" (flop-crossover Woodbury/dense choice) | "dense" |
        # "woodbury" — the explicit forms exist for A/B measurement.
        assert solve_path in ("auto", "dense", "woodbury"), solve_path
        # Woodbury's C diagonal divides by mw and mw·(1−mw): at either
        # endpoint the rank-update system is singular (inf/NaN weights)
        # where the dense path just loses its class/population term
        # gracefully — so the endpoints always take the dense path.
        if not 0.0 < mixture_weight < 1.0:
            if solve_path == "woodbury":
                raise ValueError(
                    "solve_path='woodbury' requires 0 < mixture_weight < 1 "
                    f"(got {mixture_weight}); use 'dense' or 'auto'"
                )
            solve_path = "dense"
        self.solve_path = solve_path

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def out_spec(self, in_specs):
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        x = np.asarray(jax.device_get(features.data), np.float32)[: features.num_examples]
        y = np.asarray(jax.device_get(targets.data), np.float32)[: targets.num_examples]
        n, d = x.shape
        num_classes = y.shape[1]

        class_idx = np.argmax(y, axis=1)
        counts = np.bincount(class_idx, minlength=num_classes).astype(np.int64)
        order = np.argsort(class_idx, kind="stable")
        offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
        m = int(counts.max())

        bs = min(self.block_size, d)
        d_pad = _round_up(d, bs)
        if d_pad != d:
            x = np.pad(x, ((0, 0), (0, d_pad - d)))
        num_blocks = d_pad // bs

        # Sorted copies with m zero rows appended so static windows may overrun.
        xs = np.concatenate([x[order], np.zeros((m, d_pad), np.float32)])
        onehot = np.zeros((n, num_classes), np.float32)
        onehot[np.arange(n), class_idx] = 1.0

        with _spans.span(
            "solver:weighted", classes=num_classes, blocks=num_blocks,
            rows=n, block=bs, largest_class=m,
        ):
            w, joint_means = _weighted_bcd(
                jnp.asarray(x),
                jnp.asarray(xs),
                jnp.asarray(y),
                jnp.asarray(onehot),
                jnp.asarray(offsets),
                jnp.asarray(counts.astype(np.float32)),
                jnp.float32(self.reg),
                jnp.float32(self.mixture_weight),
                num_blocks, bs, m, self.num_iter, self.solve_path,
            )

            jlm = joint_label_means(counts, n, self.mixture_weight)
            b = weighted_intercept(jlm, joint_means, w)
        return BlockLinearMapper(w, block_size=bs, intercept=b)


@functools.partial(linalg.mode_jit, static_argnums=(8, 9, 10, 11, 12))
def _weighted_bcd(x, xs, y, onehot, offsets, counts, reg, mw,
                  num_blocks, bs, m, num_iter, force_path="auto"):
    n, d_pad = x.shape
    num_classes = y.shape[1]
    nf = jnp.float32(n)
    jlm = joint_label_means(counts, n, mw)
    residual0 = y - jlm  # (n, C)
    eye = jnp.eye(bs, dtype=x.dtype)
    row_win = jnp.arange(m)
    # Per-class system structure: jointXTX_c = S + U_c C U_cᵀ with the
    # CLASS-INDEPENDENT part S = (1−mw)·popCov + λI and a rank-(m+2)
    # update (m window rows, −μ_cμ_cᵀ, +δ_cδ_cᵀ). When the update rank is
    # small against the block size, factoring S ONCE per block and
    # solving each class by Woodbury replaces C = num_classes Cholesky
    # factorizations (bs³/3 each — the whole cost of the flagship solve,
    # 1000 at bs=4096) with batched triangular solves of m+3 rhs. Flop
    # crossover: Woodbury ≈ 2(m+3)·bs² per class vs bs³/3 — use it when
    # the update work is under a third of a refactorization. One
    # structured residual-correction step keeps it solver-grade
    # (Woodbury's error grows with update conditioning; the correction
    # reuses the same factored apply).
    use_woodbury = (
        2 * (m + 3) < bs // 3 if force_path == "auto"
        else force_path == "woodbury"  # test seam: path parity checks
    )

    def block_slice(mat, block):
        return jax.lax.dynamic_slice(mat, (0, block * bs), (mat.shape[0], bs))

    def per_class(block_xs, residual, res_mean, pop_mean, pop_cov, pop_xtr,
                  w_old_b, factor_s):
        """scan over classes: returns (C, bs) ΔW and (C, bs) joint means."""

        def class_system(c):
            """Shared per-class quantities for both solve paths."""
            off = offsets[c]
            n_c = counts[c]
            # Classes absent from the data get no weight update (the
            # reference only ever iterates over observed class groups).
            present = (n_c > 0).astype(x.dtype)
            n_c_safe = jnp.maximum(n_c, 1.0)
            win = jax.lax.dynamic_slice(block_xs, (off, 0), (m, bs))
            valid = (row_win < n_c).astype(x.dtype)[:, None]
            win = win * valid
            r_win = jax.lax.dynamic_slice(residual, (off, 0), (m, num_classes))
            r_c = jax.lax.dynamic_index_in_dim(r_win, c, axis=1, keepdims=False)
            r_c = r_c * valid[:, 0]

            class_mean = jnp.sum(win, axis=0) / n_c_safe
            class_xtr = linalg.mm(win.T, r_c[:, None])[:, 0] / n_c_safe

            delta = class_mean - pop_mean
            joint_mean = mw * class_mean + (1 - mw) * pop_mean
            mean_mix = (1 - mw) * res_mean[c] + mw * jnp.sum(r_c) / n_c_safe
            pop_xtr_c = jax.lax.dynamic_index_in_dim(pop_xtr, c, axis=1, keepdims=False)
            joint_xtr = (1 - mw) * pop_xtr_c + mw * class_xtr - joint_mean * mean_mix

            w_old_c = jax.lax.dynamic_index_in_dim(w_old_b, c, axis=1, keepdims=False)
            rhs = joint_xtr - reg * w_old_c
            return present, n_c_safe, win, class_mean, delta, joint_mean, rhs

        def step_dense(carry, c):
            present, n_c_safe, win, class_mean, delta, joint_mean, rhs = (
                class_system(c)
            )
            class_cov = linalg.mm(win.T, win) / n_c_safe - jnp.outer(
                class_mean, class_mean
            )
            joint_xtx = (
                (1 - mw) * pop_cov + mw * class_cov
                + mw * (1 - mw) * jnp.outer(delta, delta)
            )
            factor = jax.scipy.linalg.cho_factor(joint_xtx + reg * eye, lower=True)
            dw = jax.scipy.linalg.cho_solve(factor, rhs)
            return carry, (dw * present, joint_mean)

        def step_woodbury(carry, c):
            present, n_c_safe, win, class_mean, delta, joint_mean, rhs = (
                class_system(c)
            )
            # jointXTX = S + U C Uᵀ, U = [√(mw/n_c)·winᵀ | μ_c | δ'],
            # C = diag(1,…,1, −mw, +mw(1−mw)); signs folded into c_diag.
            u = jnp.concatenate(
                [
                    win.T * jnp.sqrt(mw / n_c_safe),
                    class_mean[:, None],
                    delta[:, None],
                ],
                axis=1,
            )  # (bs, m+2)
            c_diag = jnp.concatenate([
                jnp.ones((m,), x.dtype),
                jnp.array([-mw], x.dtype),
                jnp.array([mw * (1 - mw)], x.dtype),
            ])

            z = jax.scipy.linalg.cho_solve(
                factor_s, jnp.concatenate([u, rhs[:, None]], axis=1)
            )  # S⁻¹[U | rhs], one batched triangular-solve pair
            zu, zr = z[:, :-1], z[:, -1]
            small = jnp.diag(1.0 / c_diag) + linalg.mm(u.T, zu)

            def wood_apply(sr, su_t_r):
                # (S + UCUᵀ)⁻¹ r given sr = S⁻¹r and Uᵀ·S⁻¹r.
                return sr - linalg.mm(zu, jnp.linalg.solve(small, su_t_r[:, None]))[:, 0]

            dw = wood_apply(zr, linalg.mm(u.T, zr[:, None])[:, 0])
            # One residual-correction step against the STRUCTURED
            # operator (never materializes jointXTX): r = rhs − (S·dw +
            # U·C·(Uᵀdw)), correct with the same factored apply.
            s_dw = (1 - mw) * linalg.mm(pop_cov, dw[:, None])[:, 0] + reg * dw
            ut_dw = linalg.mm(u.T, dw[:, None])[:, 0]
            resid = rhs - s_dw - linalg.mm(u, (c_diag * ut_dw)[:, None])[:, 0]
            s_res = jax.scipy.linalg.cho_solve(factor_s, resid[:, None])[:, 0]
            dw = dw + wood_apply(s_res, linalg.mm(u.T, s_res[:, None])[:, 0])
            return carry, (dw * present, joint_mean)

        _, (dws, joint_means) = jax.lax.scan(
            step_woodbury if use_woodbury else step_dense, 0,
            jnp.arange(num_classes),
        )
        return dws, joint_means  # (C, bs) each

    def one_block(state, block):
        w, residual, joint_means_all = state
        block_x = block_slice(x, block)          # original order (n, bs)
        block_xs = block_slice(xs, block)        # sorted + padded (n+m, bs)
        w_b = jax.lax.dynamic_slice(w, (block * bs, 0), (bs, num_classes))

        pop_mean = jnp.mean(block_x, axis=0)
        pop_cov = linalg.mm(block_x.T, block_x) / nf - jnp.outer(pop_mean, pop_mean)
        pop_xtr = linalg.mm(block_x.T, residual) / nf      # (bs, C)
        res_mean = jnp.mean(residual, axis=0)              # (C,)
        factor_s = (
            jax.scipy.linalg.cho_factor((1 - mw) * pop_cov + reg * eye, lower=True)
            if use_woodbury else None
        )

        dws, joint_means = per_class(
            block_xs, _sorted_residual(residual), res_mean,
            pop_mean, pop_cov, pop_xtr, w_b, factor_s,
        )
        w = jax.lax.dynamic_update_slice(w, w_b + dws.T, (block * bs, 0))
        residual = residual - linalg.mm(block_x, dws.T)
        joint_means_all = jax.lax.dynamic_update_slice(
            joint_means_all, joint_means, (0, block * bs)
        )
        return (w, residual, joint_means_all), None

    # residual must be readable in sorted order inside per_class; precompute
    # the sort permutation application as a gather captured in closure.
    sort_gather = None

    def _sorted_residual(residual):
        rs = residual[_order_idx]
        return jnp.concatenate([rs, jnp.zeros((m, num_classes), residual.dtype)])

    # offsets/counts refer to sorted order; reconstruct the permutation from
    # them via argsort of the (stable) class ordering used on host. We pass
    # it in as a constant derived from onehot.
    _order_idx = jnp.argsort(jnp.argmax(onehot, axis=1), stable=True)

    w0 = jnp.zeros((d_pad, num_classes), dtype=x.dtype)
    jm0 = jnp.zeros((num_classes, d_pad), dtype=x.dtype)
    blocks = jnp.tile(jnp.arange(num_blocks), num_iter)
    (w, _, joint_means), _ = jax.lax.scan(one_block, (w0, residual0, jm0), blocks)
    return w, joint_means


# --------------------------------------------- per-class re-weighted variant


class PerClassWeightedLeastSquaresEstimator(LabelEstimator):
    """Per-class example-weighted least squares.

    TPU-native re-design of
    reference: nodes/learning/PerClassWeightedLeastSquares.scala:31-223 +
    internal/ReWeightedLeastSquares.scala:18-142. Where
    :class:`BlockWeightedLeastSquaresEstimator` mixes per-class second
    moments, this variant solves one weighted problem per class c with
    scalar example weights

        b_i(c) = (1−mw)/n + 1[class_i = c]·mw/n_c

    features centered by the class's joint mean jfm_c = mw·classMean_c +
    (1−mw)·popMean, labels centered by jlm_c, via weighted BCD

        W_b = (X̃_bᵀ diag(b) X̃_b + λI) \\ X̃_bᵀ(b ∘ ỹ − r + b ∘ X̃_b W_b)

    The reference runs C sequential Spark solves with treeReduce per
    block; here the class loop, pass loop and block loop are one compiled
    ``lax.scan`` nest with the per-shard products on the MXU.
    """

    def __init__(self, block_size: int, num_iter: int, reg: float,
                 mixture_weight: float):
        self.block_size = block_size
        self.num_iter = num_iter
        self.reg = reg
        if not 0.0 <= mixture_weight <= 1.0:
            raise ValueError(f"mixture_weight must be in [0, 1], got {mixture_weight}")
        self.mixture_weight = mixture_weight

    def out_spec(self, in_specs):
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        x = np.asarray(jax.device_get(features.data), np.float32)[: features.num_examples]
        y = np.asarray(jax.device_get(targets.data), np.float32)[: targets.num_examples]
        n, d = x.shape
        num_classes = y.shape[1]

        class_idx = np.argmax(y, axis=1)
        counts = np.bincount(class_idx, minlength=num_classes).astype(np.float32)
        onehot = np.zeros((n, num_classes), np.float32)
        onehot[np.arange(n), class_idx] = 1.0

        bs = min(self.block_size, d)
        d_pad = _round_up(d, bs)
        if d_pad != d:
            x = np.pad(x, ((0, 0), (0, d_pad - d)))

        w, jfm, jlm = _pcwls_fit(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(onehot),
            jnp.asarray(counts), jnp.float32(self.reg),
            jnp.float32(self.mixture_weight),
            d_pad // bs, bs, self.num_iter,
        )
        b = weighted_intercept(jlm, jfm, w)
        return BlockLinearMapper(w, block_size=bs, intercept=b)


@functools.partial(linalg.mode_jit, static_argnums=(6, 7, 8))
def _pcwls_fit(x, y, onehot, counts, reg, mw, num_blocks, bs, num_iter):
    n, d_pad = x.shape
    num_classes = y.shape[1]
    nf = jnp.float32(n)
    counts_safe = jnp.maximum(counts, 1.0)
    present = (counts > 0).astype(x.dtype)

    pop_mean = jnp.mean(x, axis=0)                                   # (d,)
    class_mean = linalg.mm(onehot.T, x) / counts_safe[:, None]       # (C, d)
    jfm = mw * class_mean + (1.0 - mw) * pop_mean[None, :]           # (C, d)
    jlm = joint_label_means(counts, n, mw)                           # (C,)
    eye = jnp.eye(bs, dtype=x.dtype)

    def per_class(carry, c):
        xc = x - jax.lax.dynamic_index_in_dim(jfm, c, keepdims=True)   # (n, d)
        yc = jax.lax.dynamic_index_in_dim(y, c, axis=1, keepdims=False) \
            - jax.lax.dynamic_index_in_dim(jlm, c, keepdims=False)
        oc = jax.lax.dynamic_index_in_dim(onehot, c, axis=1, keepdims=False)
        n_c = jax.lax.dynamic_index_in_dim(counts_safe, c, keepdims=False)
        b_wt = (1.0 - mw) / nf + oc * (mw / n_c)                        # (n,)
        by = b_wt * yc

        def one_block(state, block):
            w_col, resid = state  # resid = b ∘ (X̃·w) accumulated
            start = block * bs
            xb = jax.lax.dynamic_slice(xc, (0, start), (n, bs))
            w_b = jax.lax.dynamic_slice(w_col, (start, 0), (bs, 1))
            g = linalg.mm(xb.T, b_wt[:, None] * xb)
            pred_old = b_wt * linalg.mm(xb, w_b)[:, 0]
            rhs = linalg.mm(xb.T, (by - (resid - pred_old))[:, None])
            factor = jax.scipy.linalg.cho_factor(g + reg * eye, lower=True)
            w_b_new = jax.scipy.linalg.cho_solve(factor, rhs)
            resid = resid + b_wt * linalg.mm(xb, w_b_new - w_b)[:, 0]
            w_col = jax.lax.dynamic_update_slice(w_col, w_b_new, (start, 0))
            return (w_col, resid), None

        blocks = jnp.tile(jnp.arange(num_blocks), num_iter)
        (w_col, _), _ = jax.lax.scan(
            one_block, (jnp.zeros((d_pad, 1), x.dtype), jnp.zeros((n,), x.dtype)),
            blocks,
        )
        w_col = w_col * jax.lax.dynamic_index_in_dim(present, c, keepdims=False)
        return carry, w_col[:, 0]

    _, w_cols = jax.lax.scan(per_class, 0, jnp.arange(num_classes))
    return w_cols.T, jfm, jlm  # (d_pad, C)
