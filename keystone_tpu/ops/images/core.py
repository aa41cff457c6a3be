"""Core image operators: convolution, pooling, rectification, patching.

TPU-native re-designs of the reference's image nodes. The reference runs
per-image Scala loops over an ``Image`` trait (im2col into a scratch
matrix, then a BLAS GEMM per image — reference:
nodes/images/Convolver.scala:20-221). Here every operator is a single
batched XLA computation over an (N, X, Y, C) array: convolutions lower to
``lax.conv_general_dilated`` (MXU), pooling to ``lax.reduce_window``, and
the per-patch normalization the reference does row-by-row in the im2col
matrix is re-derived as a closed form over box-filter statistics so the
whole Convolver stays one fused conv — no materialized patch matrix.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...data.dataset import ArrayDataset, Dataset, ObjectDataset
from ...obs import names as _names
from ...obs import spans as _spans
from ...utils import image as imutil
from ...workflow.pipeline import BatchTransformer, Transformer
from ..learning.zca import ZCAWhitener


class GrayScaler(BatchTransformer):
    """NTSC grayscale (reference: nodes/images/GrayScaler.scala)."""

    def apply_arrays(self, x):
        c = x.shape[-1]
        if c == 3:
            # Reference assumes BGR order (ImageUtils.scala:88-90).
            g = 0.2989 * x[..., 2] + 0.5870 * x[..., 1] + 0.1140 * x[..., 0]
        else:
            g = jnp.sqrt(jnp.mean(x**2, axis=-1))
        return g[..., None]


class PixelScaler(BatchTransformer):
    """[0,255] → [0,1] (reference: nodes/images/PixelScaler.scala)."""

    def apply_arrays(self, x):
        return x / 255.0


class ImageVectorizer(BatchTransformer):
    """Image → channel-major flat vector
    (reference: nodes/images/ImageVectorizer.scala)."""

    def apply_arrays(self, x):
        n = x.shape[0]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(n, -1)


class SymmetricRectifier(BatchTransformer):
    """Channel-doubling rectifier [max(v, x−α), max(v, −x−α)]
    (reference: nodes/images/SymmetricRectifier.scala)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def apply_arrays(self, x):
        pos = jnp.maximum(self.max_val, x - self.alpha)
        neg = jnp.maximum(self.max_val, -x - self.alpha)
        return jnp.concatenate([pos, neg], axis=-1)


def pack_filters(filter_images: np.ndarray) -> np.ndarray:
    """(F, s, s, C) filter images → (F, s·s·C) rows with layout
    index = c + x·C + y·C·s (reference: Convolver.scala packFilters:98-125)."""
    f = np.asarray(filter_images)
    n = f.shape[0]
    return np.ascontiguousarray(f.transpose(0, 2, 1, 3)).reshape(n, -1)


class Convolver(BatchTransformer):
    """Valid convolution of a filter bank over images, with optional
    per-patch normalization and ZCA whitening.

    Reference behavior (nodes/images/Convolver.scala:128-204): for each
    output location, extract the s×s×C patch, optionally normalize it
    (subtract patch mean, divide by sqrt(patch sample-variance + v)),
    optionally subtract the whitener means, then dot with each
    (pre-whitened) filter.

    TPU re-design: rather than materializing the (resW·resH, s²C) im2col
    matrix per image, the same math is computed as

        out = (raw − m·Σf) / sd − μ_w·f

    where ``raw`` is one batched NHWC valid conv of the images with the
    whitened filters (the only MXU-heavy term) and m/sd come from two
    cheap box-filter convs (patch sums / sums of squares). Identical
    numerics, no patch matrix, fully fused by XLA.

    ``filters`` is the packed (F, s·s·C) matrix, assumed already whitened
    when ``whitener`` is given — use :meth:`create` to go from raw filter
    images (mirrors the reference's companion apply:61-90).
    """

    def __init__(
        self,
        filters: np.ndarray,
        img_channels: int,
        whitener: Optional[ZCAWhitener] = None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
    ):
        filters = np.asarray(filters, dtype=np.float32)
        self.num_filters, patch_dim = filters.shape
        self.img_channels = img_channels
        self.conv_size = int(math.isqrt(patch_dim // img_channels))
        assert self.conv_size**2 * img_channels == patch_dim, "filters must be square"
        self.normalize_patches = normalize_patches
        self.var_constant = float(var_constant)
        # (F, y, x, c) -> spatial kernel (x, y, c, F) for NHWC/HWIO conv.
        s, c = self.conv_size, img_channels
        self.kernel = jnp.asarray(
            filters.reshape(self.num_filters, s, s, c).transpose(2, 1, 3, 0)
        )
        self.filter_sums = jnp.asarray(filters.sum(axis=1))  # (F,)
        if whitener is not None:
            means = np.asarray(whitener.means, dtype=np.float32)
            self.offset = jnp.asarray(means @ filters.T)  # μ_w · f per filter
        else:
            self.offset = None

    @staticmethod
    def create(
        filter_images: np.ndarray,
        whitener: Optional[ZCAWhitener] = None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
        flip_filters: bool = False,
    ) -> "Convolver":
        """From raw (F, s, s, C) filter images; whitens the packed filters
        with W·Wᵀ like the reference (Convolver.scala:74-80)."""
        filter_images = np.asarray(filter_images)
        if flip_filters:
            filter_images = imutil.flip_image(filter_images)
        packed = pack_filters(filter_images)
        if whitener is not None:
            w = np.asarray(whitener.whitener)
            mu = np.asarray(whitener.means)
            packed = (packed - mu) @ w @ w.T
        return Convolver(
            packed,
            img_channels=filter_images.shape[-1],
            whitener=whitener,
            normalize_patches=normalize_patches,
            var_constant=var_constant,
        )

    def apply_arrays(self, x):
        x = x.astype(jnp.float32)
        raw = lax.conv_general_dilated(
            x,
            self.kernel,
            window_strides=(1, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        out = raw
        if self.normalize_patches:
            m, sd = _patch_stats(x, self.conv_size, self.img_channels, self.var_constant)
            out = (raw - m * self.filter_sums) / sd
        if self.offset is not None:
            out = out - self.offset
        return out


#: The share of a device's memory that ONE convolution panel may take: the
#: (rows, rx, ry, filter_block) float32 responses of one filter block that
#: the featurizer's scan holds at a time. Its rows are the largest power of
#: two whose panel fits this share (512 rows of 27 x 27 x 512 at CIFAR's
#: widths on a 16 GB v5e: 0.76 GB, where 8,192 rows whole were 12.2 GB and
#: did not fit beside the fit's features, PERF.md section 6). In
#: XLA's form the compiler's temporaries are a few panels (the normalised
#: and rectified responses, the pooled outputs), so a sixteenth leaves the
#: rest of the chip to the features, the standardised copy and the solver.
#: In the kernel's form no panel exists: the row block bounds the patches
#: (113 MB at 512 rows) and the block's pooled cells (168 MB).
PANEL_SHARE = 1.0 / 16.0


class _ConvSpec(NamedTuple):
    """Everything of a :class:`FusedConvFeaturizer`'s program that is not
    an array: the key of its one compiled form. Two featurizers with other
    filters (every fit learns its own) and the same widths run the same
    program, with the filters as its arguments."""

    conv_size: int
    channels: int
    normalize: bool
    var_constant: float
    max_val: float
    alpha: float
    pool_stride: int
    pool_size: int
    pixel_function: Optional[Callable]
    pool_function: str
    num_filters: int
    filter_block: int


def _patch_stats(x, s: int, c: int, var_constant: float):
    """Patch mean / stddev maps for per-patch normalization of s x s x c
    patches: box sums of x and x * x, float32 at HIGHEST. At the
    TPU's default precision a float32 convolution rounds its inputs to
    bfloat16, and x * x (up to 65,025 for pixels) would lose 8 of its 16
    bits: the variance, a difference of two sums of about 4e6, would be off
    by about 12 next to the variance constant 10 on a low-contrast patch."""
    d = float(s * s * c)
    ones = jnp.ones((s, s, c, 1), dtype=jnp.float32)
    box = partial(
        lax.conv_general_dilated,
        rhs=ones,
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )
    m = box(x) / d  # (N, rx, ry, 1)
    var = jnp.maximum(box(x * x) - d * m * m, 0.0) / (d - 1.0)
    return m, jnp.sqrt(var + var_constant)


def _norm_stats(spec: _ConvSpec, x):
    """:func:`_patch_stats` where the featurizer normalizes patches, else
    (None, None)."""
    if not spec.normalize:
        return None, None
    return _patch_stats(x, spec.conv_size, spec.channels, spec.var_constant)


def _pooled_xla(spec: _ConvSpec, x, kb, fs_b, off_b, m, sd):
    """conv → normalize → rectify → pool for ONE filter block in XLA's
    form: (N, px, py, 2·fb). The main convolution at the MXU default, as
    shipped (bfloat16 inputs, float32 sums); its (N, rx, ry, fb) panel is
    written to HBM and read back by the pooling."""
    with jax.named_scope("conv/panel"):
        raw = lax.conv_general_dilated(
            x, kb, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    with jax.named_scope("conv/pool"):
        out = (raw - m * fs_b) / sd if m is not None else raw
        out = out - off_b
        pos = jnp.maximum(spec.max_val, out - spec.alpha)
        neg = jnp.maximum(spec.max_val, -out - spec.alpha)
        pool = Pooler(spec.pool_stride, spec.pool_size, spec.pixel_function, spec.pool_function)
        return jnp.concatenate([pool.apply_arrays(pos), pool.apply_arrays(neg)], axis=-1)


def _kernel_layout(spec: _ConvSpec, x_dim: int, y_dim: int):
    """(rx, ry, yp, kp) of the kernel's operands: the responses' extent,
    y padded to bfloat16's 16 sublanes, and the patch to 128 lanes."""
    s = spec.conv_size
    rx, ry = x_dim - s + 1, y_dim - s + 1
    return rx, ry, -(-ry // 16) * 16, -(-(s * s * spec.channels) // 128) * 128


def _conv_form(spec: _ConvSpec, x_dim: int, y_dim: int) -> str:
    """Which form computes the featurizer's panels for images of x_dim x
    y_dim: ``"kernel"`` (``ops/pallas/conv_pool.py``: the responses
    normalised, rectified and pooled in VMEM, only the pooled sums
    written) on a TPU backend, for every spec the kernel expresses (no
    pixel function; sum or max pooling, with or without normalisation)
    whose grid step fits the chip's VMEM; ``"xla"`` everywhere else.
    Decided at trace time, from the backend and the widths alone."""
    if jax.default_backend() != "tpu" or spec.pixel_function is not None:
        return "xla"
    from ..pallas import conv_pool  # Pallas takes a second to import: only where it runs

    rx, _, yp, kp = _kernel_layout(spec, x_dim, y_dim)
    return "kernel" if conv_pool.fits(rx, yp, kp, spec.num_filters, conv_pool.vmem_bytes()) else "xla"


def _pooled_kernel(spec: _ConvSpec, x, kernel, fsums, offset, m, sd, interpret: bool = False):
    """conv → normalize → rectify → pool for all of `kernel`'s F filters
    in the kernel's form, vectorised as the featurizer orders them: (N,
    py·px·2·F). The patches are made here from the images rounded to
    bfloat16, as the convolution at the MXU default rounds them, by a
    convolution with a one-hot filter bank (each output channel one
    element of the patch, exactly). `interpret` runs the kernel in the
    Pallas interpreter: the CPU's parity tests call this function with
    it."""
    from ..pallas import conv_pool

    n, x_dim, y_dim, c = x.shape
    s, f = spec.conv_size, kernel.shape[-1]
    rx, ry, yp, kp = _kernel_layout(spec, x_dim, y_dim)
    geometry = conv_pool.regions(rx, ry, spec.pool_stride, spec.pool_size)
    n_pad = -(-n // conv_pool.ROW_TILE) * conv_pool.ROW_TILE
    tile = conv_pool.filter_tile(f)
    f_pad = -(-f // tile) * tile
    with jax.named_scope("conv/panel"):
        # y padded so the valid convolution gives yp positions; the ones past ry are never pooled
        xb = jnp.pad(x.astype(jnp.bfloat16), ((0, n_pad - n), (0, 0), (0, yp - ry), (0, 0)))
        pick = jnp.eye(kp, dtype=jnp.bfloat16)[: s * s * c].reshape(s, s, c, kp)
        patches = lax.conv_general_dilated(xb, pick, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if m is None:  # (raw - 0 · Σf) · 1 is raw, to the bit
            m, inv = jnp.zeros((n, rx, ry, 1), jnp.float32), jnp.ones((n, rx, ry, 1), jnp.float32)
        else:
            inv = 1.0 / sd
        stats = jnp.swapaxes(jnp.stack([m[..., 0], inv[..., 0]], axis=1), 2, 3)  # (n, 2, ry, rx)
        stats = jnp.pad(stats, ((0, n_pad - n), (0, 0), (0, yp - ry), (0, -(-rx // 128) * 128 - rx)))
        weights = jnp.pad(kernel.reshape(-1, f), ((0, kp - s * s * c), (0, f_pad - f)))
        if offset is None:
            offset = jnp.zeros((f,), jnp.float32)
        pooled = conv_pool.conv_pool(
            patches.reshape(n_pad, rx * yp, kp), stats, weights.astype(jnp.bfloat16),
            jnp.pad(fsums, (0, f_pad - f)), jnp.pad(offset, (0, f_pad - f)),
            geometry=geometry, yp=yp, max_val=spec.max_val, alpha=spec.alpha,
            pool=spec.pool_function, interpret=interpret,
        )
    with jax.named_scope("conv/pool"):
        return jnp.concatenate([cell[:n, :f] for cell in pooled], axis=1)


def _pooled_block(spec: _ConvSpec, x, kb, fs_b, off_b, m, sd):
    """conv → normalize → rectify → pool for ONE filter block in the form
    :func:`_conv_form` picks, vectorised as the featurizer orders it: (N,
    py·px·2·fb), pooled positives of the block's filters then its
    negatives at each pooling cell."""
    if _conv_form(spec, *x.shape[1:3]) == "xla":
        pooled = _pooled_xla(spec, x, kb, fs_b, off_b, m, sd)
        return jnp.transpose(pooled, (0, 2, 1, 3)).reshape(x.shape[0], -1)
    return _pooled_kernel(spec, x, kb, fs_b, off_b, m, sd)


def _pack_filters(kernel, fsums, offset, fb: int):
    """Zero-padded (nb, s, s, c, fb) kernel blocks plus per-block filter
    sums and whitener offsets."""
    f = kernel.shape[-1]
    nb = -(-f // fb)
    f_pad = nb * fb
    if offset is None:
        offset = jnp.zeros((f,), jnp.float32)
    if f_pad != f:
        kernel = jnp.pad(kernel, ((0, 0), (0, 0), (0, 0), (0, f_pad - f)))
        fsums = jnp.pad(fsums, (0, f_pad - f))
        offset = jnp.pad(offset, (0, f_pad - f))
    s, _, c, _ = kernel.shape
    kblocks = jnp.moveaxis(kernel.reshape(s, s, c, nb, fb), 3, 0)
    return kblocks, fsums.reshape(nb, fb), offset.reshape(nb, fb)


def _scanned_blocks(spec: _ConvSpec, x, kernel, fsums, offset, m, sd):
    """XLA's form of one row block: a scan over blocks of filters, then
    (rows, py·px·2F) in global filter order."""
    f = spec.num_filters
    fb = min(spec.filter_block, f)
    kblocks, fsum_blocks, offset_blocks = _pack_filters(kernel, fsums, offset, fb)
    f_pad = kblocks.shape[0] * fb

    def block_step(_, inputs):
        kb, fs_b, off_b = inputs
        pooled = _pooled_xla(spec, x, kb, fs_b, off_b, m, sd)
        return _, (pooled[..., :fb], pooled[..., fb:])

    _, (pp, pn) = lax.scan(block_step, None, (kblocks, fsum_blocks, offset_blocks))
    # (nb, rows, px, py, fb) → (rows, px, py, nb·fb) in global filter order.
    r, px, py = pp.shape[1:4]
    pp = jnp.moveaxis(pp, 0, 3).reshape(r, px, py, f_pad)[..., :f]
    pn = jnp.moveaxis(pn, 0, 3).reshape(r, px, py, f_pad)[..., :f]
    pooled = jnp.concatenate([pp, pn], axis=-1)
    return jnp.transpose(pooled, (0, 2, 1, 3)).reshape(r, -1)


@partial(jax.jit, static_argnames=("spec", "row_block"))
def _featurize(x, kernel, fsums, offset, spec: _ConvSpec, row_block: int):
    """The featurizer's one program: over blocks of `row_block` rows (one
    step where the batch is no larger), in the form :func:`_conv_form`
    picks: the kernel over every filter, or XLA's scan over blocks of
    filters with at most one (row_block, rx, ry, filter_block) panel
    live."""
    with jax.named_scope("feat/FusedConvFeaturizer"):
        x = x.astype(jnp.float32)
        n = x.shape[0]
        form = _conv_form(spec, *x.shape[1:3])

        def rows(xr):
            with jax.named_scope("conv/stats"):
                m, sd = _norm_stats(spec, xr)
            if form == "xla":
                return _scanned_blocks(spec, xr, kernel, fsums, offset, m, sd)
            return _pooled_kernel(spec, xr, kernel, fsums, offset, m, sd)

        if row_block >= n:
            return rows(x)
        n_pad = -(-n // row_block) * row_block
        if n_pad != n:  # zero images normalise to zero: no NaN in the rows cut off
            x = jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1))
        out = lax.map(rows, x.reshape((n_pad // row_block, row_block) + x.shape[1:]))
        return out.reshape(n_pad, -1)[:n]


class FusedConvFeaturizer(BatchTransformer):
    """Memory-bounded conv → symmetric-rectify → pool → vectorize.

    Computes exactly ``ImageVectorizer(pool(rect(conv(x))))`` but scans
    over blocks of ``filter_block`` filters, and over blocks of rows
    (:meth:`row_block`), so the full (N, rx, ry, F) convolution output
    never materializes — per step only one (row_block, rx, ry,
    filter_block) panel plus the pooled outputs are live, whatever N and F.
    At the reference CIFAR config (numFilters=10000,
    examples/images/cifar_random_patch.sh:30-36) the unfused intermediate
    is ~37 GB for a 1k-image batch. Channel layout matches the unfused
    ops: pooled positives for all F filters, then pooled negatives for
    all F. One compiled program per widths and batch shape
    (:class:`_ConvSpec`): the filters are its arguments, so a new filter
    bank builds nothing.
    """

    def __init__(
        self,
        convolver: "Convolver",
        rectifier: "SymmetricRectifier",
        pooler: "Pooler",
        filter_block: int = 512,
    ):
        self.conv = convolver
        self.rect = rectifier
        self.pool = pooler
        self.filter_block = filter_block

    @property
    def spec(self) -> _ConvSpec:
        conv, pool = self.conv, self.pool
        return _ConvSpec(
            conv.conv_size, conv.img_channels, conv.normalize_patches, conv.var_constant,
            float(self.rect.max_val), float(self.rect.alpha), pool.stride, pool.pool_size,
            pool.pixel_function, pool.pool_function, conv.num_filters, self.filter_block,
        )

    def _panel_row_bytes(self, x_dim: int, y_dim: int) -> int:
        """Bytes of one image's responses to one filter block."""
        s = self.conv.conv_size
        return (x_dim - s + 1) * (y_dim - s + 1) * min(self.filter_block, self.conv.num_filters) * 4

    def row_block(self, rows: int, x_dim: int, y_dim: int) -> int:
        """Rows of one panel for a batch of ``rows`` images of x_dim x
        y_dim: the largest power of two whose panel takes at most
        ``PANEL_SHARE`` of the device's memory, or the whole batch where
        it is smaller, or the backend reports no memory (the CPU)."""
        from ...parallel.mesh import device_memory_limit_bytes

        limit = device_memory_limit_bytes()
        panel_row = self._panel_row_bytes(x_dim, y_dim)
        if limit is None or rows * panel_row <= limit * PANEL_SHARE:
            return rows
        block = 1
        while 2 * block * panel_row <= limit * PANEL_SHARE:
            block *= 2
        return block

    def panels(self, rows: int, x_dim: int, y_dim: int) -> dict:
        """What one application holds and computes: ``row_block``, the
        ``panels`` (row blocks x filter blocks) and one panel's bytes."""
        block = self.row_block(rows, x_dim, y_dim)
        fb = min(self.filter_block, self.conv.num_filters)
        return {
            "row_block": block,
            "panels": -(-rows // block) * -(-self.conv.num_filters // fb),
            "panel_bytes": block * self._panel_row_bytes(x_dim, y_dim),
        }

    def host_span(self, dataset):
        """``image:conv`` around the batch application that holds this
        featurizer (its own, or the fused chain's it heads), with the
        ``form`` that computes its panels (:func:`_conv_form`), and its
        panels in ``keystone_conv_panels_total`` /
        ``keystone_conv_panel_bytes``, and in
        ``keystone_conv_kernel_panels_total`` where the kernel computes
        them."""
        rows, x_dim, y_dim = jax.tree_util.tree_leaves(dataset.data)[0].shape[:3]
        held = self.panels(rows, x_dim, y_dim)
        form = _conv_form(self.spec, x_dim, y_dim)
        site = type(self).__name__
        _names.metric(_names.CONV_PANELS).inc(held["panels"], site=site)
        if form == "kernel":
            _names.metric(_names.CONV_KERNEL_PANELS).inc(held["panels"], site=site)
        _names.metric(_names.CONV_PANEL_BYTES).set(held["panel_bytes"], site=site)
        return _spans.span(
            "image:conv", rows=dataset.num_examples, filters=self.conv.num_filters,
            row_block=held["row_block"], filter_block=min(self.filter_block, self.conv.num_filters),
            panels=held["panels"], form=form,
        )

    def apply_arrays(self, x):
        conv = self.conv
        block = self.row_block(*x.shape[:3])
        return _featurize(x, conv.kernel, conv.filter_sums, conv.offset, spec=self.spec, row_block=block)


_POOL_FUNCTIONS = {
    "sum": (lax.add, 0.0),
    "max": (lax.max, -jnp.inf),
}


class Pooler(BatchTransformer):
    """Strided pooling over square regions with a per-pixel function
    (reference: nodes/images/Pooler.scala:22-69).

    Pool centers start at ``pool_size/2`` and advance by ``stride``; each
    pool covers ``[center − pool_size/2, center + pool_size/2)`` clipped to
    the image, with out-of-image cells contributing the identity (0 for
    sum — exactly the reference's zero-initialized pool buffer).
    """

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_function: Optional[Callable] = None,
        pool_function: str = "sum",
    ):
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_function = pixel_function
        if pool_function not in _POOL_FUNCTIONS:
            raise ValueError(f"pool_function must be one of {list(_POOL_FUNCTIONS)}")
        self.pool_function = pool_function

    def apply_arrays(self, x):
        x_dim, y_dim = x.shape[1], x.shape[2]
        stride_start = self.pool_size // 2
        half = self.pool_size // 2
        window = 2 * half  # [c−p/2, c+p/2) is 2·(p//2) wide
        num_x = max(0, -(-(x_dim - stride_start) // self.stride))
        num_y = max(0, -(-(y_dim - stride_start) // self.stride))
        if self.pixel_function is not None:
            x = self.pixel_function(x)
        op, init = _POOL_FUNCTIONS[self.pool_function]
        # Last window reaches (num−1)·stride + window; zero-pad to cover it.
        need_x = (num_x - 1) * self.stride + window
        need_y = (num_y - 1) * self.stride + window
        pad_x = max(0, need_x - x_dim)
        pad_y = max(0, need_y - y_dim)
        x = jnp.pad(x, ((0, 0), (0, pad_x), (0, pad_y), (0, 0)), constant_values=init)
        out = lax.reduce_window(
            x,
            jnp.array(init, dtype=x.dtype),
            op,
            window_dimensions=(1, window, window, 1),
            window_strides=(1, self.stride, self.stride, 1),
            padding="VALID",
        )
        return out[:, :num_x, :num_y, :]


class Cropper(BatchTransformer):
    """Fixed bounding-box crop (reference: nodes/images/Cropper.scala)."""

    def __init__(self, start_x: int, start_y: int, end_x: int, end_y: int):
        self.bounds = (start_x, start_y, end_x, end_y)

    def apply_arrays(self, x):
        sx, sy, ex, ey = self.bounds
        return x[:, sx:ex, sy:ey, :]


class RandomImageTransformer(Transformer):
    """Apply ``transform`` to each image with probability ``chance``
    (reference: nodes/images/RandomImageTransformer.scala)."""

    def __init__(self, chance: float, transform: Callable, seed: int = 12334):
        self.chance = chance
        self.transform = transform
        self._rng = np.random.default_rng(seed)

    def apply(self, img):
        if self._rng.random() < self.chance:
            return self.transform(img)
        return img

    def apply_batch(self, dataset: Dataset) -> Dataset:
        if isinstance(dataset, ArrayDataset):
            x = np.asarray(jax.device_get(dataset.data))[: dataset.num_examples]
            flip = self._rng.random(x.shape[0]) < self.chance
            out = np.where(
                flip.reshape((-1,) + (1,) * (x.ndim - 1)), np.asarray(self.transform(x)), x
            )
            return ArrayDataset(jnp.asarray(out))
        return dataset.map(self.apply)


def _flatmap_images(dataset: Dataset, per_image: Callable[[np.ndarray], np.ndarray]) -> ArrayDataset:
    """Host-side flatMap: each image yields a (k, px, py, C) stack; results
    concatenate along the example axis (analog of the reference's
    FunctionNode RDD flatMaps)."""
    if isinstance(dataset, ArrayDataset):
        imgs = np.asarray(jax.device_get(dataset.data))[: dataset.num_examples]
    else:
        imgs = np.stack(dataset.collect())
    pieces = [per_image(img) for img in imgs]
    return ArrayDataset(jnp.asarray(np.concatenate(pieces, axis=0)))


class Windower(Transformer):
    """All windows of size w on a stride grid, x-major
    (reference: nodes/images/Windower.scala:13-56). One image of (X, Y, C)
    yields ((X−w)/s+1)·((Y−w)/s+1) windows; a batch concatenates them. On
    the host, where the images are, and left there: the one consumer
    (RandomPatchCifar's filter learning) samples a share of them first."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def _windows(self, imgs: np.ndarray) -> np.ndarray:
        """(N, X, Y, C) → (N·nx·ny, w, w, C)."""
        w, s = self.window_size, self.stride
        view = np.lib.stride_tricks.sliding_window_view(imgs, (w, w), axis=(1, 2))[:, ::s, ::s]
        # (N, nx, ny, C, w, w) → (N, nx, ny, w, w, C)
        return np.ascontiguousarray(np.moveaxis(view, 3, -1)).reshape(-1, w, w, imgs.shape[-1])

    def apply(self, img):
        return self._windows(np.asarray(img)[None])

    def apply_batch(self, dataset: Dataset) -> Dataset:
        if isinstance(dataset, ArrayDataset):
            imgs = np.asarray(jax.device_get(dataset.data))[: dataset.num_examples]
        else:
            imgs = np.stack(dataset.collect())
        return ArrayDataset(self._windows(imgs))


class RandomPatcher(Transformer):
    """``num_patches`` uniformly random patches per image
    (reference: nodes/images/RandomPatcher.scala:16-47)."""

    def __init__(self, num_patches: int, patch_size_x: int, patch_size_y: int, seed: int = 12334):
        self.num_patches = num_patches
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self._rng = np.random.default_rng(seed)

    def _patches(self, img: np.ndarray) -> np.ndarray:
        px, py = self.patch_size_x, self.patch_size_y
        out = []
        for _ in range(self.num_patches):
            sx = self._rng.integers(0, img.shape[0] - px + 1)
            sy = self._rng.integers(0, img.shape[1] - py + 1)
            out.append(img[sx : sx + px, sy : sy + py, :])
        return np.stack(out)

    def apply(self, img):
        return self._patches(np.asarray(img))

    def apply_batch(self, dataset: Dataset) -> Dataset:
        return _flatmap_images(dataset, self._patches)


class CenterCornerPatcher(Transformer):
    """Four corner patches + center patch, optionally with horizontal flips
    (reference: nodes/images/CenterCornerPatcher.scala:18-48)."""

    def __init__(self, patch_size_x: int, patch_size_y: int, horizontal_flips: bool = False):
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self.horizontal_flips = horizontal_flips

    def _patches(self, img: np.ndarray) -> np.ndarray:
        px, py = self.patch_size_x, self.patch_size_y
        x_dim, y_dim = img.shape[0], img.shape[1]
        starts = [
            (0, 0),
            (x_dim - px, 0),
            (0, y_dim - py),
            (x_dim - px, y_dim - py),
            ((x_dim - px) // 2, (y_dim - py) // 2),
        ]
        out = []
        for sx, sy in starts:
            patch = img[sx : sx + px, sy : sy + py, :]
            out.append(patch)
            if self.horizontal_flips:
                out.append(imutil.flip_horizontal(patch))
        return np.stack(out)

    def apply(self, img):
        return self._patches(np.asarray(img))

    def apply_batch(self, dataset: Dataset) -> Dataset:
        return _flatmap_images(dataset, self._patches)


# ------------------------------------------------------- labeled-image glue


class LabelExtractor(Transformer):
    """{"image", "label"} dict → label
    (reference: nodes/images/LabeledImageExtractors.scala)."""

    def apply(self, datum):
        return datum["label"]

    def apply_batch(self, dataset: Dataset) -> Dataset:
        if isinstance(dataset, ArrayDataset):
            return ArrayDataset(dataset.data["label"], dataset.num_examples)
        return dataset.map(self.apply)


class ImageExtractor(Transformer):
    """{"image", "label"} dict → image."""

    def apply(self, datum):
        return datum["image"]

    def apply_batch(self, dataset: Dataset) -> Dataset:
        if isinstance(dataset, ArrayDataset):
            return ArrayDataset(dataset.data["image"], dataset.num_examples)
        return dataset.map(self.apply)


MultiLabelExtractor = LabelExtractor
MultiLabeledImageExtractor = ImageExtractor
