"""Local Color Statistics (LCS) grid descriptors.

TPU-native re-design of reference: nodes/images/LCSExtractor.scala:1-130
(Clinchant et al., ImageEval 2007): around every keypoint on a regular
grid, a 4×4 neighborhood of sub-patches is described by the mean and
standard deviation of each color channel — 4·4·3·2 = 96 dims.

The reference loops pixels per image through ``ImageUtils.conv2D`` box
filters; here the box means/stds for the whole batch are two depthwise
convolutions (zero-padded, same-size, matching conv2D's padding at
ImageUtils.scala:226-266) and the keypoint/neighbor reads are one strided
gather.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...workflow.pipeline import BatchTransformer


def _box_filter_same(x: jnp.ndarray, size: int) -> jnp.ndarray:
    """Per-channel zero-padded mean filter over (N, X, Y, C), output same
    size, anchored like the reference's conv2D (pad floor((k-1)/2) low)."""
    n, xd, yd, c = x.shape
    k = jnp.full((size,), 1.0 / size, dtype=jnp.float32)
    lhs = jnp.transpose(x, (0, 3, 1, 2)).reshape(n * c, 1, xd, yd)
    pad_lo = (size - 1) // 2
    pad_hi = size - 1 - pad_lo
    kx = k[None, None, :, None]
    ky = k[None, None, None, :]
    # HIGHEST: at a TPU's default a float32 convolution multiplies in
    # bfloat16, and the standard deviation is a difference of two such
    # means (on the v5e, PR 36: a deviation off by up to 19.8 of 222 at
    # DEFAULT, 0.24 at HIGH, 0.003 at HIGHEST; 15.5, 20.9, 29.3 ms a 256 images)
    hi = lax.Precision.HIGHEST
    out = lax.conv_general_dilated(lhs, kx, (1, 1), [(pad_lo, pad_hi), (0, 0)], precision=hi)
    out = lax.conv_general_dilated(out, ky, (1, 1), [(0, 0), (pad_lo, pad_hi)], precision=hi)
    return jnp.transpose(out.reshape(n, c, xd, yd), (0, 2, 3, 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _lcs_descriptors(x, stride, stride_start, s, offsets):
    """(N, X, Y, C) -> (N, keypoints, 4*4*C*2), as ONE program: its
    operations carry the extractor's name in a device trace (the box
    filters and gathers dispatched one by one carry none)."""
    with jax.named_scope("feat/LCSExtractor"):
        return _lcs_body(x, stride, stride_start, s, np.asarray(offsets))


def _lcs_body(x, stride, stride_start, s, offs):
    x = x.astype(jnp.float32)
    n, xd, yd, c = x.shape

    means = _box_filter_same(x, s)
    sq = _box_filter_same(x * x, s)
    stds = jnp.sqrt(jnp.maximum(sq - means * means, 0.0))

    kx = np.arange(stride_start, xd - stride_start, stride)
    ky = np.arange(stride_start, yd - stride_start, stride)
    # absolute neighbor coordinates per keypoint: (nk, 4)
    ax = kx[:, None] + offs[None, :]
    ay = ky[:, None] + offs[None, :]
    if (ax < 0).any() or (ax >= xd).any() or (ay < 0).any() or (ay >= yd).any():
        raise ValueError(
            "LCS neighborhood exceeds image bounds; increase stride_start"
        )

    def grid_read(img):
        g = img[:, ax.reshape(-1), :, :][:, :, ay.reshape(-1), :]
        g = g.reshape(n, len(kx), len(offs), len(ky), len(offs), c)
        # → (N, kx, ky, C, nx, ny): per keypoint, per channel, 4×4 grid
        return jnp.transpose(g, (0, 1, 3, 5, 2, 4))

    m = grid_read(means)
    sd = grid_read(stds)
    # interleave mean/std last (reference emits mean,std pairs per
    # neighbor: LCSExtractor.scala:113-121)
    pairs = jnp.stack([m, sd], axis=-1)  # (N, kx, ky, C, 4, 4, 2)
    return pairs.reshape(n, len(kx) * len(ky), -1)


class LCSExtractor(BatchTransformer):
    """(N, X, Y, C) image batch → (N, num_keypoints, 4·4·C·2) descriptors.

    Keypoints at [stride_start, dim - stride_start) step ``stride``;
    neighbors at offsets -2s+s/2-1 … s+s/2-1 step s for sub-patch size s
    (reference: LCSExtractor.scala:56-70).
    """

    def __init__(self, stride: int = 4, stride_start: int = 16, sub_patch_size: int = 6):
        self.stride = stride
        self.stride_start = stride_start
        self.sub_patch_size = sub_patch_size

    def _neighbor_offsets(self) -> np.ndarray:
        s = self.sub_patch_size
        start = -2 * s + s // 2 - 1
        end = s + s // 2 - 1
        return np.arange(start, end + 1, s)

    def host_span(self, dataset):
        from .sift import descriptor_span

        xd, yd = jax.tree_util.tree_leaves(dataset.data)[0].shape[1:3]
        start, stride = self.stride_start, self.stride
        per_image = len(range(start, xd - start, stride)) * len(range(start, yd - start, stride))
        return descriptor_span(self, "lcs", dataset, per_image)

    def apply_arrays(self, x):
        return _lcs_descriptors(
            x, self.stride, self.stride_start, self.sub_patch_size,
            tuple(int(o) for o in self._neighbor_offsets()),
        )

    def apply_arrays_masked(self, x, dims):
        """Native-resolution LCS over a size-bucketed batch
        (see ``data.buckets``): ``x`` (N, Xb, Yb, C) padded, ``dims``
        (N, 2) true sizes. Returns ``(descriptors, valid)`` with the
        padded keypoint grid and a per-image validity mask.

        The box filters are zero-boundary, so the padded region is
        re-zeroed from ``dims`` first — valid keypoints then read exactly
        what a native-size ``apply_arrays`` run reads (the reference's
        per-image behavior, LCSExtractor.scala:56-70)."""
        x = x.astype(jnp.float32)
        n, xd, yd, c = x.shape
        s = self.sub_patch_size
        dims = jnp.asarray(dims, jnp.int32)
        xn = dims[:, 0][:, None, None, None]
        yn = dims[:, 1][:, None, None, None]
        rows = jnp.arange(xd)[None, :, None, None]
        cols = jnp.arange(yd)[None, None, :, None]
        x = jnp.where((rows < xn) & (cols < yn), x, 0.0)

        means = _box_filter_same(x, s)
        sq = _box_filter_same(x * x, s)
        stds = jnp.sqrt(jnp.maximum(sq - means * means, 0.0))

        kx = np.arange(self.stride_start, xd - self.stride_start, self.stride)
        ky = np.arange(self.stride_start, yd - self.stride_start, self.stride)
        if len(kx) == 0 or len(ky) == 0:
            raise ValueError("bucket too small for any LCS keypoint")
        offs = self._neighbor_offsets()
        ax = kx[:, None] + offs[None, :]
        ay = ky[:, None] + offs[None, :]
        if (ax < 0).any() or (ax >= xd).any() or (ay < 0).any() or (ay >= yd).any():
            raise ValueError(
                "LCS neighborhood exceeds image bounds; increase stride_start"
            )

        def grid_read(img):
            g = img[:, ax.reshape(-1), :, :][:, :, ay.reshape(-1), :]
            g = g.reshape(n, len(kx), len(offs), len(ky), len(offs), c)
            return jnp.transpose(g, (0, 1, 3, 5, 2, 4))

        pairs = jnp.stack([grid_read(means), grid_read(stds)], axis=-1)
        desc = pairs.reshape(n, len(kx) * len(ky), -1)

        # A keypoint exists at native size iff it lies in
        # [stride_start, native_dim - stride_start).
        valid = (
            (jnp.asarray(kx)[None, :, None] < (dims[:, 0] - self.stride_start)[:, None, None])
            & (jnp.asarray(ky)[None, None, :] < (dims[:, 1] - self.stride_start)[:, None, None])
        ).reshape(n, len(kx) * len(ky))
        return desc * valid[..., None], valid
