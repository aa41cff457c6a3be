"""Dense multi-scale SIFT, TPU-native.

Re-design of the reference's native VLFeat JNI kernel
(reference: src/main/cpp/VLFeat.cxx:37-292 ``getMultiScaleDSIFTs_f``,
nodes/images/external/SIFTExtractor.scala:16-40). The reference loops
per-image through vlfeat's ``vl_dsift`` C implementation; here the whole
batch is one XLA computation: a Gaussian pyramid (separable convs), 8
orientation-mass planes with linear orientation interpolation, triangular
spatial binning (the flat-window dense-SIFT formulation) as two products
with constant banded matrices a plane (``_band_product``: on the MXU,
where a one-channel convolution runs at a hundredth of the chip), and
strided gathers for the 4×4 descriptor grids — all static shapes, fused
by XLA, batched over images in HBM.

Algorithm parity notes (same knobs as the reference kernel):
- per scale ``s``: bin size ``b = bin_size + 2s``, Gaussian smoothing with
  sigma = b / 6 (magnif = 6, VLFeat.cxx:45,88), sampling step
  ``step + s*scale_step`` and bound offset ``(1 + 2*num_scales) - 3s``
  (VLFeat.cxx:78,95).
- descriptors are L2-normalized, clamped at 0.2, renormalized; descriptors
  whose pre-normalization mass is below the contrast threshold 0.005 are
  zeroed (VLFeat.cxx:63,146); values are quantized ``min(512·v, 255)``
  (VLFeat.cxx:258-260).
- output layout is (num_descriptors, 128) per image with orientation
  fastest, then x-bin, then y-bin. The reference emits 128-column-major
  with a transposed bin layout for MATLAB compatibility; numeric content
  is the same set of values.
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...data.dataset import Dataset
from ...obs import names as _names
from ...obs import spans as _spans
from ...workflow.pipeline import BatchTransformer

NUM_ORIENTATIONS = 8
NUM_SPATIAL_BINS = 4
DESCRIPTOR_SIZE = NUM_ORIENTATIONS * NUM_SPATIAL_BINS * NUM_SPATIAL_BINS  # 128
CONTRAST_THRESHOLD = 0.005
MAGNIF = 6.0


def _gaussian_kernel(sigma: float) -> np.ndarray:
    radius = max(1, int(math.ceil(4.0 * sigma)))
    xs = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (xs / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _triangular_kernel(bin_size: int) -> np.ndarray:
    """w(u) = 1 - |u|/b for |u| < b — bilinear spatial-bin interpolation as
    a convolution (the flat-window dense-SIFT trick)."""
    xs = np.arange(-(bin_size - 1), bin_size, dtype=np.float64)
    return np.maximum(0.0, 1.0 - np.abs(xs) / bin_size).astype(np.float32)


def _separable_conv(
    x: jnp.ndarray,
    kernel: np.ndarray,
    boundary: str = "zero",
    conv_dtype=None,
) -> jnp.ndarray:
    """Depthwise same-size separable 2-D convolution over (B, H, W): the
    extractors' smoother, and the form of the spatial binning that
    ``_spatial_binning`` keeps for an axis too long for its band products
    and is held to where it takes them (tests/ops/test_sift_binning.py).

    ``boundary='edge'`` replicates the border (vl_imsmooth's continuity
    padding — zero padding would fabricate gradients at the image edge);
    ``'zero'`` is correct for the spatial binning, where gradient mass
    outside the image really is zero.

    ``conv_dtype=jnp.bfloat16`` runs the conv inputs in bf16 with fp32
    accumulation (``preferred_element_type``). Measured: safe ONLY for
    the spatial-binning convs (100% of ×512-quantized entries within 1
    of the fp32 build); bf16 SMOOTHING fails the reference's
    99.5%-within-1 gate (97.5%) because the gradient stencil amplifies
    its rounding — callers must keep the boundary='edge' smoothing call
    in fp32 (see SIFTExtractor.binning_dtype).
    """
    k = jnp.asarray(kernel)
    pad = (len(kernel) - 1) // 2
    if boundary == "edge":
        x = jnp.pad(x, [(0, 0), (pad, pad), (pad, pad)], mode="edge")
        pads = [(0, 0), (0, 0)]
    else:
        pads = [(pad, pad), (pad, pad)]
    lhs = x[:, None, :, :]  # (B, 1, H, W)
    kx = k[None, None, :, None]
    ky = k[None, None, None, :]
    # float32 means float32 on the chip too: at a TPU's default precision
    # a float32 convolution multiplies in bfloat16, the very rounding of
    # the smoother that the docstring above rules out. Measured on the v5e
    # (PR 36; a 256-image SIFT prefix against a plain float32 reference):
    # DEFAULT 148 ms, 10.8% of the quantized entries off and 1% by more
    # than 1; HIGH 207 ms, 5e-4 off; HIGHEST 294 ms, 1e-5 off and none by
    # more than 1.
    precision = None
    if conv_dtype is not None:
        lhs = lhs.astype(conv_dtype)
        kx, ky = kx.astype(conv_dtype), ky.astype(conv_dtype)
    else:
        precision = lax.Precision.HIGHEST
    out = lax.conv_general_dilated(
        lhs, kx, (1, 1), [(pads[0][0], pads[0][1]), (0, 0)],
        preferred_element_type=jnp.float32, precision=precision,
    )
    if conv_dtype is not None:
        out = out.astype(conv_dtype)
    out = lax.conv_general_dilated(
        out, ky, (1, 1), [(0, 0), (pads[1][0], pads[1][1])],
        preferred_element_type=jnp.float32, precision=precision,
    )
    return out[:, 0].astype(jnp.float32)


# Longest axis, in columns a tap of the kernel, whose spatial binning runs as
# band products. A band product's work a pixel grows with the axis (2 x
# length multiply-adds, on the MXU), a one-channel convolution's with the
# taps (on the vector unit). Measured on a v5e, float32 at HIGHEST, 134 M
# pixels a pass (PR 37): the product 2.2-2.5 ms at 256 columns, the
# convolution 12.8, 17.8, 24.2, 25.4 ms at 7, 11, 15, 19 taps; at 512
# columns the whole SIFT program of 64 images 185 ms by products, 343 by
# convolutions. By those the product wins up to some 170 columns a tap; 128
# leaves room (896 columns at 7 taps, 2,432 at 19) and nothing past 512 has
# been timed. Output tiles of a fixed width, which make the product's work
# flat in the axis, were tried at 512 (tiles reading 128, 256, 384 columns:
# 237, 250, 258 ms) and lost to the one wide band: the overlapping copies
# cost more than the doubled product.
BAND_COLUMNS_PER_TAP = 128


def _binning_as_products(x_dim: int, y_dim: int, taps: int) -> bool:
    """The one rule that chooses the spatial binning's form, from static
    shapes alone: band products where neither axis outgrows the taps."""
    return max(x_dim, y_dim) <= BAND_COLUMNS_PER_TAP * taps


def _band_product(x: jnp.ndarray, kernel: np.ndarray, axis: int, dtype=None) -> jnp.ndarray:
    """Same-size correlation of ``x`` with ``kernel`` along ``axis``, zero
    outside, as one product with the constant banded matrix
    ``B[u, j] = kernel[u - j + pad]`` built here on the host: what
    ``lax.conv_general_dilated`` gives a one-channel stencil, on the unit
    built for products.

    No batch dimension (a batched float32 product at HIGHEST becomes a
    ``while`` over slices on the TPU); float32 operands at HIGHEST, or
    operands in ``dtype`` with float32 accumulation."""
    taps, length = len(kernel), x.shape[axis]
    if taps % 2 == 0:
        raise ValueError(f"a same-size band needs an odd kernel, not {taps} taps")
    tap = np.arange(length)[:, None] - np.arange(length)[None, :] + taps // 2
    inside = (tap >= 0) & (tap < taps)
    band = jnp.asarray(np.where(inside, kernel[np.clip(tap, 0, taps - 1)], 0).astype(np.float32))
    precision = lax.Precision.HIGHEST
    if dtype is not None:
        x, band, precision = x.astype(dtype), band.astype(dtype), None
    out = lax.dot_general(
        x, band, (((axis,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )
    return jnp.moveaxis(out, -1, axis)


def _spatial_binning(planes: jnp.ndarray, kernel: np.ndarray, dtype=None) -> jnp.ndarray:
    """``_separable_conv(planes, kernel, "zero", dtype)`` over (B, H, W):
    as two band products a plane, the last axis (a 2-D product as the
    planes stand) and then the middle one, the intermediate cast to
    ``dtype`` as the convolution casts it; as that convolution itself
    where ``_binning_as_products`` says an axis is too long for the taps."""
    if not _binning_as_products(*planes.shape[1:], len(kernel)):
        return _separable_conv(planes, kernel, "zero", dtype)
    out = _band_product(planes, kernel, 2, dtype)
    return _band_product(out, kernel, 1, dtype)


def descriptor_span(extractor, name: str, dataset, per_image: int, **attributes):
    """An extractor's ``image:<name>`` host span (``rows``,
    ``descriptors`` an image) and its count in
    ``keystone_image_descriptors_total{extractor}``."""
    rows = dataset.num_examples
    _names.metric(_names.IMAGE_DESCRIPTORS).inc(
        rows * per_image, extractor=type(extractor).__name__
    )
    return _spans.span("image:" + name, rows=rows, descriptors=per_image, **attributes)


class SIFTExtractor(BatchTransformer):
    """Dense SIFT at multiple scales
    (reference: nodes/images/external/SIFTExtractor.scala:16-40).

    Input: (N, X, Y) or (N, X, Y, 1) grayscale batch. Output:
    (N, num_descriptors, 128) quantized descriptors, scales concatenated
    along the descriptor axis exactly as the reference concatenates
    per-scale descriptor blocks.
    """

    def __init__(self, step_size: int = 3, bin_size: int = 4, scales: int = 4,
                 scale_step: int = 1, binning_dtype=None):
        self.step_size = step_size
        self.bin_size = bin_size
        self.scales = scales
        self.scale_step = scale_step
        # Dtype of the SPATIAL BINNING's operands only (8 orientation planes
        # a pixel a scale; the accumulation is float32 either way). Measured:
        # binning in bf16 stays 100% within-1 of the fp32 build at the
        # reference's x512 quantization, while bf16 SMOOTHING fails the
        # 99.5%-within-1 gate (97.5%) because the gradient stencil
        # amplifies its rounding — so the smoother is always fp32.
        # Default fp32 at HIGHEST. On a v5e (PR 37) the fp32 binning of 256
        # images of 256 x 256 at four scales, the planes' own arithmetic
        # with it, is 18 ms of a 126 ms SIFT program as band products (169
        # of 294 ms as one-channel convolutions): bf16 has at most those
        # 18 ms to give, and has not been timed.
        self.binning_dtype = binning_dtype

    @property
    def descriptor_size(self) -> int:
        return DESCRIPTOR_SIZE

    def grid_counts(self, x_dim: int, y_dim: int) -> List[int]:
        """Descriptors per scale for an x_dim × y_dim image."""
        counts = []
        for s in range(self.scales):
            b = self.bin_size + 2 * s
            step = self.step_size + s * self.scale_step
            off = max(0, (1 + 2 * self.scales) - 3 * s)
            span = (NUM_SPATIAL_BINS - 1) * b
            nx = (x_dim - 1 - off - span) // step + 1
            ny = (y_dim - 1 - off - span) // step + 1
            counts.append(max(0, nx) * max(0, ny))
        return counts

    def host_span(self, dataset):
        """``image:sift`` around the batch application that holds this
        extractor: its own, or the fused chain's it is a member of (whose
        input has the image's two dimensions where the gray plane's are)."""
        import jax

        x_dim, y_dim = jax.tree_util.tree_leaves(dataset.data)[0].shape[1:3]
        return descriptor_span(
            self, "sift", dataset, sum(self.grid_counts(x_dim, y_dim)),
            scales=self.scales, binning=self.binning_form(x_dim, y_dim),
        )

    def binning_form(self, x_dim: int, y_dim: int) -> str:
        """The form the spatial binning takes at this image size, as the
        ``image:sift`` span says it: ``product``, ``conv`` where the rule
        keeps the convolution, ``conv+product`` where the scales differ."""
        bins = (self.bin_size + 2 * s for s in range(self.scales))  # 2b - 1 taps each
        forms = {"product" if _binning_as_products(x_dim, y_dim, 2 * b - 1) else "conv" for b in bins}
        return "+".join(sorted(forms))

    def apply_arrays(self, x):
        if x.ndim == 4:
            x = x[..., 0]
        x = x.astype(jnp.float32)
        per_scale = []
        for s in range(self.scales):
            desc = self._one_scale(x, s)
            if desc is not None:
                per_scale.append(desc)
        if not per_scale:
            raise ValueError("image too small for any SIFT scale")
        return jnp.concatenate(per_scale, axis=1)

    def apply_arrays_masked(self, x, dims):
        """Native-resolution SIFT over a size-bucketed batch.

        ``x`` is (N, Xb, Yb[, 1]) *edge-replicate padded* (see
        ``data.buckets``), ``dims`` is (N, 2) true (x, y) sizes. Returns
        ``(descriptors, valid)`` where descriptors has the padded-grid
        shape and ``valid`` (N, n_desc) marks grid positions that exist at
        the image's native size.

        Exactness contract (the reference computes per-image at native
        size, VLFeat.cxx:170-186): valid descriptors equal a native-size
        ``apply_arrays`` run bit-for-float because (a) edge-replicate
        padding reproduces the smoother's edge boundary exactly, (b) the
        gradient stencil switches to the one-sided form at each image's
        true border, and (c) gradient planes are zeroed outside the native
        extent, reproducing the spatial binning's zero boundary.
        """
        if x.ndim == 4:
            x = x[..., 0]
        x = x.astype(jnp.float32)
        dims = jnp.asarray(dims, jnp.int32)
        per_scale, masks = [], []
        for s in range(self.scales):
            out = self._one_scale_masked(x, dims, s)
            if out is not None:
                per_scale.append(out[0])
                masks.append(out[1])
        if not per_scale:
            raise ValueError("bucket too small for any SIFT scale")
        return jnp.concatenate(per_scale, axis=1), jnp.concatenate(masks, axis=1)

    def _one_scale_masked(self, x: jnp.ndarray, dims: jnp.ndarray, s: int):
        n, xd, yd = x.shape
        b = self.bin_size + 2 * s
        step = self.step_size + s * self.scale_step
        off = max(0, (1 + 2 * self.scales) - 3 * s)
        span = (NUM_SPATIAL_BINS - 1) * b
        nx = (xd - 1 - off - span) // step + 1
        ny = (yd - 1 - off - span) // step + 1
        if nx <= 0 or ny <= 0:
            return None

        xn = dims[:, 0][:, None, None]  # (N, 1, 1) true x size
        yn = dims[:, 1][:, None, None]
        rows = jnp.arange(xd)[None, :, None]
        cols = jnp.arange(yd)[None, None, :]

        smoothed = _separable_conv(x, _gaussian_kernel(b / MAGNIF), boundary="edge")

        # Gradient stencil with the one-sided form at each image's TRUE
        # border (not the padded buffer's) — matches the native-size run.
        sxp = jnp.roll(smoothed, 1, axis=1)
        sxn = jnp.roll(smoothed, -1, axis=1)
        gx = 0.5 * (sxn - sxp)
        gx = jnp.where(rows == 0, sxn - smoothed, gx)
        gx = jnp.where(rows == xn - 1, smoothed - sxp, gx)
        syp = jnp.roll(smoothed, 1, axis=2)
        syn = jnp.roll(smoothed, -1, axis=2)
        gy = 0.5 * (syn - syp)
        gy = jnp.where(cols == 0, syn - smoothed, gy)
        gy = jnp.where(cols == yn - 1, smoothed - syp, gy)

        mag = jnp.sqrt(gx * gx + gy * gy)
        theta = jnp.mod(jnp.arctan2(gy, gx), 2.0 * jnp.pi)
        t = theta * (NUM_ORIENTATIONS / (2.0 * jnp.pi))

        orient = jnp.arange(NUM_ORIENTATIONS, dtype=jnp.float32)
        dist = jnp.abs(t[..., None] - orient)
        dist = jnp.minimum(dist, NUM_ORIENTATIONS - dist)
        w = jnp.maximum(0.0, 1.0 - dist)
        planes = mag[..., None] * w
        # Zero outside the native extent: the spatial binning then sees
        # exactly the zero boundary the native-size run sees.
        inside = ((rows < xn) & (cols < yn))[..., None]
        planes = jnp.where(inside, planes, 0.0)

        planes = jnp.transpose(planes, (0, 3, 1, 2)).reshape(n * NUM_ORIENTATIONS, xd, yd)
        binned = _spatial_binning(planes, _triangular_kernel(b), self.binning_dtype)
        binned = binned.reshape(n, NUM_ORIENTATIONS, xd, yd)

        ox = off + np.arange(nx) * step
        oy = off + np.arange(ny) * step
        bx = ox[:, None] + np.arange(NUM_SPATIAL_BINS) * b
        by = oy[:, None] + np.arange(NUM_SPATIAL_BINS) * b
        g = binned[:, :, bx.reshape(-1), :][:, :, :, by.reshape(-1)]
        g = g.reshape(n, NUM_ORIENTATIONS, nx, NUM_SPATIAL_BINS, ny, NUM_SPATIAL_BINS)
        g = jnp.transpose(g, (0, 2, 4, 5, 3, 1))
        raw = g.reshape(n, nx * ny, DESCRIPTOR_SIZE)

        eps = 1e-10
        norm1 = jnp.linalg.norm(raw, axis=-1, keepdims=True)
        d = raw / jnp.maximum(norm1, eps)
        d = jnp.minimum(d, 0.2)
        d = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True), eps)
        d = jnp.where(norm1 > CONTRAST_THRESHOLD, d, 0.0)
        desc = jnp.minimum(jnp.floor(512.0 * d), 255.0)

        # Grid positions that exist at the native size.
        nx_nat = jnp.maximum(0, (dims[:, 0] - 1 - off - span) // step + 1)
        ny_nat = jnp.maximum(0, (dims[:, 1] - 1 - off - span) // step + 1)
        valid = (
            (jnp.arange(nx)[None, :, None] < nx_nat[:, None, None])
            & (jnp.arange(ny)[None, None, :] < ny_nat[:, None, None])
        ).reshape(n, nx * ny)
        return desc * valid[..., None], valid

    def _one_scale(self, x: jnp.ndarray, s: int):
        n, xd, yd = x.shape
        b = self.bin_size + 2 * s
        step = self.step_size + s * self.scale_step
        off = max(0, (1 + 2 * self.scales) - 3 * s)
        span = (NUM_SPATIAL_BINS - 1) * b
        nx = (xd - 1 - off - span) // step + 1
        ny = (yd - 1 - off - span) // step + 1
        if nx <= 0 or ny <= 0:
            return None

        smoothed = _separable_conv(x, _gaussian_kernel(b / MAGNIF), boundary="edge")

        # Gradients: central differences inside, one-sided at the borders
        # (vl_dsift's gradient stencil).
        gx = (jnp.roll(smoothed, -1, axis=1) - jnp.roll(smoothed, 1, axis=1)) * 0.5
        gx = gx.at[:, 0, :].set(smoothed[:, 1, :] - smoothed[:, 0, :])
        gx = gx.at[:, -1, :].set(smoothed[:, -1, :] - smoothed[:, -2, :])
        gy = (jnp.roll(smoothed, -1, axis=2) - jnp.roll(smoothed, 1, axis=2)) * 0.5
        gy = gy.at[:, :, 0].set(smoothed[:, :, 1] - smoothed[:, :, 0])
        gy = gy.at[:, :, -1].set(smoothed[:, :, -1] - smoothed[:, :, -2])

        mag = jnp.sqrt(gx * gx + gy * gy)
        theta = jnp.mod(jnp.arctan2(gy, gx), 2.0 * jnp.pi)
        t = theta * (NUM_ORIENTATIONS / (2.0 * jnp.pi))  # [0, 8)

        # Linear interpolation into the two adjacent orientation bins,
        # expressed as a circular triangular weight so it vectorizes.
        orient = jnp.arange(NUM_ORIENTATIONS, dtype=jnp.float32)
        dist = jnp.abs(t[..., None] - orient)  # (N, X, Y, 8)
        dist = jnp.minimum(dist, NUM_ORIENTATIONS - dist)
        w = jnp.maximum(0.0, 1.0 - dist)
        planes = mag[..., None] * w  # (N, X, Y, 8)

        # Spatial bilinear binning = separable triangular convolution.
        planes = jnp.transpose(planes, (0, 3, 1, 2)).reshape(n * NUM_ORIENTATIONS, xd, yd)
        binned = _spatial_binning(planes, _triangular_kernel(b), self.binning_dtype)
        binned = binned.reshape(n, NUM_ORIENTATIONS, xd, yd)

        # Gather the 4×4 bin centers for every keypoint origin.
        ox = off + np.arange(nx) * step  # descriptor origins
        oy = off + np.arange(ny) * step
        bx = ox[:, None] + np.arange(NUM_SPATIAL_BINS) * b  # (nx, 4)
        by = oy[:, None] + np.arange(NUM_SPATIAL_BINS) * b  # (ny, 4)
        g = binned[:, :, bx.reshape(-1), :][:, :, :, by.reshape(-1)]
        g = g.reshape(n, NUM_ORIENTATIONS, nx, NUM_SPATIAL_BINS, ny, NUM_SPATIAL_BINS)
        # → (N, nx, ny, ybin, xbin, orientation): orientation fastest.
        g = jnp.transpose(g, (0, 2, 4, 5, 3, 1))
        raw = g.reshape(n, nx * ny, DESCRIPTOR_SIZE)

        # Normalize → clamp 0.2 → renormalize; zero low-contrast descriptors;
        # quantize min(512·v, 255) (VLFeat.cxx:146,258-260).
        eps = 1e-10
        norm1 = jnp.linalg.norm(raw, axis=-1, keepdims=True)
        d = raw / jnp.maximum(norm1, eps)
        d = jnp.minimum(d, 0.2)
        d = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True), eps)
        d = jnp.where(norm1 > CONTRAST_THRESHOLD, d, 0.0)
        return jnp.minimum(jnp.floor(512.0 * d), 255.0)
