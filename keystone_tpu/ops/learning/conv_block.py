"""Fused featurize-and-solve: BCD whose feature blocks are rematerialized
on device instead of stored.

The reference's CIFAR RandomPatch caches the 80,000-wide featurized RDD
and streams feature blocks out of the cache into BCD (reference:
RandomPatchCifar.scala:59-77, nodes/util/VectorSplitter.scala:10-37,
BlockLinearMapper.scala:234-240). On TPU the roles invert: HBM is the
scarce resource and the MXU makes convolution nearly free, so instead of
storing the (n, 80000) feature matrix anywhere (16 GB fp32 — beyond one
chip's HBM, and host streaming is PCIe/DCN-bound), each solver block's
features are *recomputed* from the raw images at the moment the block
update needs them. A solver block is chosen to coincide with a filter
block of the fused conv featurizer, so across one epoch every filter is
convolved exactly once — the same total conv work as featurizing once,
with device residency = raw images + one block panel + the (n, k)
predictions.

One jitted step serves every block: the kernel slice, filter sums and
whitener offsets are traced arguments of fixed shape. Mean/std
normalization (the pipeline's StandardScaler) happens inside the step
from masked psums, and the returned model folds 1/σ into the weights so
it applies to ordinary featurizer output.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ...data.dataset import ArrayDataset, Dataset
from ...parallel import linalg
from ...parallel.collectives import shard_map
from ...parallel.mesh import get_mesh, row_axes, row_shard_count
from ...parallel.partitioner import fit_mesh
from ...workflow.pipeline import BatchTransformer, LabelEstimator
from ..images.core import FusedConvFeaturizer, _norm_stats, _pack_filters, _pooled_block
from ..stats.core import _as_array_dataset
from .block import BlockLinearMapper


class ConvBlockModel(BatchTransformer):
    """Featurize (fused conv) then apply the solved linear model — the
    fitted form of :class:`ConvBlockLeastSquaresEstimator`.

    Application chunks the image batch so the full (n, 8·numFilters)
    feature matrix is never materialized at predict time either — only
    one chunk's features and the (n, k) scores are live."""

    def __init__(
        self,
        featurizer: FusedConvFeaturizer,
        linear: BlockLinearMapper,
        image_chunk: int = 2048,
    ):
        self.featurizer = featurizer
        self.linear = linear
        self.image_chunk = image_chunk

    @property
    def weights(self):
        return self.linear.weights

    def apply_arrays(self, images):
        n = images.shape[0]
        chunk = min(self.image_chunk, n)
        n_pad = _round_up(n, chunk)
        images = _pad_rows(images, n_pad)
        xr = images.reshape((n_pad // chunk, chunk) + images.shape[1:])

        def per_chunk(xc):
            return self.linear.apply_arrays(self.featurizer.apply_arrays(xc))

        out = lax.map(per_chunk, xr)
        return out.reshape(n_pad, -1)[:n]


class ConvBlockLeastSquaresEstimator(LabelEstimator):
    """Least squares over fused-conv features with on-device block
    rematerialization (featurize → standardize → BCD as one machine).

    Equivalent to the pipeline ``FusedConvFeaturizer → StandardScaler →
    BlockLeastSquaresEstimator(block_size, num_iter, reg)`` (both apply
    a scale-aware λ floor when reg=0 to keep the per-block solves PD;
    the block update order here is filter-major rather than
    column-contiguous, same fixed point) but the full feature matrix
    never exists; each epoch
    refeaturizes every filter block once. ``block_size`` must correspond
    to a whole number of filters (block_size divisible by the per-filter
    feature count — pool_x·pool_y·2 for the symmetric rectifier).
    """

    def __init__(
        self,
        featurizer: FusedConvFeaturizer,
        block_size: Optional[int] = 4096,
        num_iter: int = 1,
        reg: float = 0.0,
        standardize: bool = True,
        image_chunk: int = 2048,
    ):
        self.featurizer = featurizer
        # None = auto: the largest whole-filter block ≤ 4096 features.
        self.block_size = block_size
        self.num_iter = num_iter
        self.reg = reg
        self.standardize = standardize
        self.image_chunk = image_chunk

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    # ------------------------------------------------------------ geometry

    def _geometry(self, image_shape):
        """(features_per_filter, filters_per_block, num_blocks, px, py)."""
        conv = self.featurizer.conv
        rx = image_shape[0] - conv.conv_size + 1
        ry = image_shape[1] - conv.conv_size + 1
        pooled = jax.eval_shape(
            self.featurizer.pool.apply_arrays,
            jax.ShapeDtypeStruct((1, rx, ry, 1), jnp.float32),
        )
        px, py = int(pooled.shape[1]), int(pooled.shape[2])
        fpf = px * py * 2  # pos+neg channels per filter, per pool cell
        bs = self.block_size
        if bs is None:  # auto: largest whole-filter block ≤ 4096 features
            bs = max(fpf, (4096 // fpf) * fpf)
        if bs % fpf != 0:
            raise ValueError(
                f"block_size={bs} not divisible by the "
                f"per-filter feature count {fpf}"
            )
        fb = bs // fpf
        f = conv.num_filters
        nb = -(-f // fb)
        return fpf, fb, nb, px, py

    def _standard_permutation(self, px: int, py: int, fb: int, nb: int) -> np.ndarray:
        """Map block-major solved rows to the standard featurizer layout.

        Block-major: for block b, ``ImageVectorizer`` over the pooled
        (N, px, py, 2·fb) panel → index (y, x, c_local) with channels
        [pos_b | neg_b]. Standard: (y, x, c_global) over 2F channels
        [pos all | neg all]. Returns ``perm`` with
        ``standard_index = perm[block_major_index]``.
        """
        f_pad = nb * fb
        f = self.featurizer.conv.num_filters
        perm = np.empty(nb * px * py * 2 * fb, dtype=np.int64)
        i = 0
        for b in range(nb):
            for y in range(py):
                for x in range(px):
                    for c in range(2 * fb):
                        half, fi = divmod(c, fb)
                        g = half * f_pad + b * fb + fi  # padded-global channel
                        perm[i] = y * (px * 2 * f_pad) + x * (2 * f_pad) + g
                        i += 1
        return perm

    # ---------------------------------------------------------------- fit

    def fit(self, data: Dataset, labels: Dataset) -> ConvBlockModel:
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        mesh = fit_mesh(self)
        fz = self.featurizer
        conv = fz.conv

        images = jnp.asarray(features.data, jnp.float32)
        y = jnp.asarray(targets.data, jnp.float32)
        n = features.num_examples
        k = y.shape[1]
        fpf, fb, nb, px, py = self._geometry(images.shape[1:3])
        f_pad = nb * fb

        # Shared packing with the featurizer, at the solver's block width.
        kblocks, fsum_blocks, offset_blocks = _pack_filters(conv.kernel, conv.filter_sums, conv.offset, fb)

        # Row-shard images/labels; chunk size must divide the per-shard rows.
        ndev = row_shard_count(mesh)
        chunk = min(self.image_chunk, max(1, images.shape[0] // ndev))
        n_pad = _round_up(images.shape[0], chunk * ndev)
        images = _pad_rows(images, n_pad)
        y = _pad_rows(y, n_pad)
        x_dev = linalg.prepare_row_sharded(images, mesh)

        mu_b = jnp.sum(y[:n], axis=0) / n
        yc = y.at[:n].add(-mu_b).at[n:].set(0.0)
        y_dev = linalg.prepare_row_sharded(yc, mesh)
        mask = np.zeros((n_pad, 1), np.float32)
        mask[:n] = 1.0
        mask_dev = linalg.prepare_row_sharded(jnp.asarray(mask), mesh)
        p_dev = linalg.prepare_row_sharded(jnp.zeros((n_pad, k), jnp.float32), mesh)

        step = _conv_bcd_step_fn(
            mesh, fz, chunk, self.standardize, fpf, fb, px, py
        )
        if self.reg > 0:
            reg = jnp.float32(self.reg)
        elif self.standardize:
            # Standardized blocks have Gram diagonal ≈ n (unit variance):
            # floor λ relative to that scale so a rank-deficient block
            # stays fp32-Cholesky-finite (an absolute 1e-6 floor leaves
            # condition ~n/1e-6 and silent NaNs — see block.py's
            # _scale_aware_reg_floor for the full story).
            reg = jnp.float32(max(1e-6 * n, 1e-6))
        else:
            probe = self.featurizer.apply_arrays(images[: min(n, 256)])
            probe = probe - jnp.mean(probe, axis=0, keepdims=True)
            reg = jnp.float32(
                max(1e-6 * n * float(jnp.mean(jnp.square(probe))), 1e-6)
            )
        n_f = jnp.float32(n)
        bs = fpf * fb
        w_blocks = [jnp.zeros((bs, k), jnp.float32) for _ in range(nb)]
        mus = [None] * nb
        inv_sds = [None] * nb
        for _ in range(self.num_iter):
            for b in range(nb):
                w_blocks[b], p_dev, mus[b], inv_sds[b] = step(
                    x_dev, mask_dev, y_dev, p_dev, w_blocks[b],
                    kblocks[b], fsum_blocks[b], offset_blocks[b], reg, n_f,
                )

        # Assemble the standard-layout model: fold 1/σ into the weights so
        # the model applies directly to raw featurizer output.
        w_bm = jnp.concatenate(
            [w * isd[:, None] for w, isd in zip(w_blocks, inv_sds)], axis=0
        )
        mu_bm = jnp.concatenate(mus, axis=0)
        perm = self._standard_permutation(px, py, fb, nb)
        d_std = px * py * 2 * f_pad
        w_std = jnp.zeros((d_std, k), jnp.float32).at[perm].set(w_bm)
        mu_std = jnp.zeros((d_std,), jnp.float32).at[perm].set(mu_bm)
        # Drop padded-filter channels back to the true featurizer width
        # (standard layout interleaves (y, x) cells of 2·f_pad channels).
        f = conv.num_filters
        fi = np.arange(d_std) % (2 * f_pad) % f_pad
        keep_mask = fi < f
        w_std = w_std[keep_mask]
        mu_std = mu_std[keep_mask]

        linear = BlockLinearMapper(
            w_std, block_size=bs, intercept=mu_b,
            feature_mean=mu_std,
        )
        return ConvBlockModel(fz, linear, image_chunk=self.image_chunk)


# Bounded: each entry pins a featurizer's device arrays + a compiled
# executable, and the key includes a featurizer *instance* — unbounded
# growth would leak repeatedly-built pipelines.
@linalg.mode_cached(maxsize=8)
def _conv_bcd_step_fn(
    mesh: Mesh,
    featurizer: FusedConvFeaturizer,
    chunk: int,
    standardize: bool,
    fpf: int,
    fb: int,
    px: int,
    py: int,
):
    """One BCD update with on-device block featurization. Cached on
    (mesh, featurizer, static config); the kernel slice/filter sums/
    offsets are traced, so one executable serves every block."""
    axes = row_axes(mesh)
    bs = fpf * fb

    def featurize_block(x_local, kb, fs_b, off_b):
        nloc = x_local.shape[0]
        xr = x_local.reshape((nloc // chunk, chunk) + x_local.shape[1:])

        def per_chunk(xc):
            # Shared featurizer math (images.core._pooled_block), in the
            # featurizer's form: the solver computes exactly what the
            # featurizer computes.
            m, sd = _norm_stats(featurizer.spec, xc)
            return _pooled_block(featurizer.spec, xc, kb, fs_b, off_b, m, sd)

        return lax.map(per_chunk, xr).reshape(nloc, bs)

    def per_device(x_local, mask_local, y_local, p_local, w_b,
                   kb, fs_b, off_b, reg, n):
        a_raw = featurize_block(x_local, kb, fs_b, off_b)
        # Masked mean/std over the real rows (StandardScaler semantics,
        # reference: nodes/stats/StandardScaler.scala:16-77).
        s1 = lax.psum(jnp.sum(a_raw * mask_local, axis=0), axes)
        mu = s1 / n
        if standardize:
            s2 = lax.psum(jnp.sum((a_raw * mask_local) ** 2, axis=0), axes)
            var = (s2 - n * mu**2) / jnp.maximum(n - 1.0, 1.0)
            sd = jnp.sqrt(jnp.maximum(var, 0.0))
            inv_sd = jnp.where((sd < 1e-8) | ~jnp.isfinite(sd), 1.0, 1.0 / sd)
        else:
            inv_sd = jnp.ones_like(mu)
        a_b = (a_raw - mu) * inv_sd * mask_local
        w_b_new, p_local = linalg.block_update(a_b, y_local, p_local, w_b, reg, axes)
        return w_b_new, p_local, mu, inv_sd

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(axes, None, None, None), P(axes, None), P(axes, None),
            P(axes, None), P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), P(axes, None), P(), P()),
    )
    # arg 3 is the loop-owned residual carry, rebuilt every call from
    # this jit's own output.  # keystone: owns-donated
    return jax.jit(fn, donate_argnums=(3,))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_rows(a: jnp.ndarray, target: int) -> jnp.ndarray:
    if a.shape[0] == target:
        return a
    return jnp.pad(a, [(0, target - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
