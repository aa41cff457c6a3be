"""One convolution panel normalised, rectified and pooled where it is made.

The fused convolution featurizer (``ops/images/core.py``) computes, for
every image and filter, the valid convolution's responses at each patch
position, normalises them by the patch's statistics, rectifies both
halves and sums (or maxes) them over a few pooling regions. In XLA's form
the (rows, rx, ry, filters) float32 responses are one fusion's output and
two reductions' input: each 0.76 GB panel of CIFAR's featurizer goes
through HBM three times, where what leaves is (rows, 2, 2, 2 x filters).
This kernel keeps the responses in VMEM: a grid step takes a tile of
images' patches and a tile of filters, and writes only the pooled sums.

Arithmetic, the same as XLA's form at the MXU default:

- the product of the patches and the filters, both bfloat16, with float32
  sums (``preferred_element_type``): what a float32 convolution at the
  TPU's default precision does;
- ``v = (raw - m * fsum) * inv_sd`` in float32, ``m`` and ``inv_sd = 1 /
  sd`` per patch position from the caller's statistics;
- the halves ``max(max_val, v - (offset + alpha))`` and ``max(max_val,
  (offset - alpha) - v)``, XLA's ``max(max_val, ±(v - offset) - alpha)``
  with the two per-filter constants summed once, pooled in float32 on the
  VPU: a region's sum (or max) over its x positions first, then over its
  y positions.

Layout (the caller's, ``core._pooled_kernel``): ``patches`` (N, rx * yp,
kp) bfloat16, position ``x * yp + y`` on the sublanes, the patch's
elements on the lanes (y padded to ``yp``, a multiple of 16, and the
patch to ``kp``, a multiple of 128); ``stats`` (N, 2, yp, xl) float32,
``m`` and ``inv_sd`` with y on the sublanes and x on the lanes;
``weights`` (kp, F) bfloat16; ``fsums`` and ``offsets`` (F,). The output
is one (N, F) array a pooling cell and half, cell ``(b * nx + a) * 2 + h``
for y-region b, x-region a and half h (the positive half first): the
order the featurizer vectorises.

Inside, a grid step takes ``ROW_TILE`` images and ``filter_tile(F)``
filters. Once a row tile, each position's statistics are spread into a
(yp, LANES) column (one lane picked out, exactly). Then a loop over
(image, group of ``LANES`` filters) pairs: one (rx * yp, kp) x (kp,
LANES) product on the MXU into one of two buffers while the pair before
it is pooled from the other. The x positions of the epilogue are unrolled
in the body and the pairs are a loop, because the body is traced in
Python once a process: unrolled over the pairs as well (some 9,500
operations) it took 22 s of a fit's set-up on the chip's host.

Measured on a TPU v5e at CIFAR's widths (8,192 images, 10,000 filters,
PERF.md section 6): the featurizer 1,285 ms in XLA's form, 1,545
with XLA's single-read pooling, 214 with this kernel.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Images a grid step: one patch tile of (ROW_TILE, rx * yp, kp) bfloat16
#: (1.8 MB at CIFAR's widths), double-buffered. Even: the products go in
#: pairs.
ROW_TILE = 8

#: Filters a grid step, at most (a multiple of ``LANES``): each image's
#: statistics are spread over the lanes once a row tile, whatever the
#: filters, so the larger the tile the fewer times.
FILTER_TILE = 2048

#: Filters of one product and its epilogue: both halves' accumulators,
#: (yp, LANES) each, are carried over a region's x positions.
LANES = 512

#: The scoped VMEM a grid step asks for. Measured on a v5e, whose
#: TensorCore has 128 MiB (``fits``).
VMEM_LIMIT = 64 * 2**20

_POOL = {"sum": (lax.add, 0.0), "max": (lax.max, -jnp.inf)}


class Regions(NamedTuple):
    """The pooling regions along each axis: half-open (lo, hi) position
    ranges, clipped to the responses."""

    x: tuple
    y: tuple


def regions(rx: int, ry: int, stride: int, pool_size: int) -> Regions:
    """``Pooler``'s regions: centres from ``pool_size // 2`` every
    ``stride``, each ``[c - pool_size // 2, c + pool_size // 2)``, clipped
    to the responses (what ``Pooler.apply_arrays`` pads with the
    identity)."""
    half = pool_size // 2

    def axis(n):
        count = max(0, -(-(n - half) // stride))
        return tuple((r * stride, min(r * stride + 2 * half, n)) for r in range(count))

    return Regions(axis(rx), axis(ry))


def filter_tile(filters: int) -> int:
    """The filters of one grid step: all of them on whole ``LANES``, up
    to ``FILTER_TILE``."""
    return min(FILTER_TILE, -(-filters // LANES) * LANES)


def fits(rx: int, yp: int, kp: int, filters: int, vmem: int) -> bool:
    """Whether a TensorCore with ``vmem`` bytes of VMEM runs a grid step:
    it holds twice ``VMEM_LIMIT``, the share the kernel was measured with
    (a chip with less falls back to XLA's form rather than fail in
    Mosaic's compile), and the step's largest buffers take at most three
    quarters of ``VMEM_LIMIT`` (the rest is the output tiles' and
    Mosaic's own): the patch tile and the filter tile, each
    double-buffered, the two products' buffers, and the row tile's
    statistics spread over ``LANES`` (37 MB at CIFAR's widths)."""
    positions = rx * yp
    step = (2 * ROW_TILE * positions * kp * 2 + 2 * kp * filter_tile(filters) * 2
            + 2 * positions * LANES * 4 + ROW_TILE * 2 * positions * LANES * 4)
    return vmem >= 2 * VMEM_LIMIT and step <= VMEM_LIMIT * 3 // 4


def vmem_bytes() -> int:
    """The default device's VMEM a TensorCore, by Pallas's table of TPU
    generations; 0 for a chip it does not know."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except (ValueError, NotImplementedError):
        return 0


def _kernel(p_ref, s_ref, w_ref, c_ref, *refs, geometry, yp, max_val, pool):
    *o_refs, front_ref, back_ref, spread_ref = refs
    op, init = _POOL[pool]
    rows, rx = p_ref.shape[0], spread_ref.shape[2]
    groups = w_ref.shape[0]
    nx = len(geometry.x)

    # Once a row tile (the filter tiles run in order, "arbitrary"): each
    # position's m and 1 / sd spread into a (yp, LANES) column, one lane
    # picked out, exactly.
    @pl.when(pl.program_id(1) == 0)
    def _():
        lane = lax.broadcasted_iota(jnp.int32, s_ref.shape[2:], 1)

        def spread(i, carry):
            t, x = i // rx, i % rx
            for k in range(2):
                col = jnp.sum(jnp.where(lane == x, s_ref[t, k], 0.0), axis=1, keepdims=True)
                spread_ref[t, k, x] = jnp.broadcast_to(col, (yp, LANES))
            return carry

        lax.fori_loop(0, rows * rx, spread, 0)

    # The body is traced in Python once a process, deep in a fit's stack:
    # lax throughout, not jnp (a jnp call is a nested jit), and what does
    # not change across products built once here.
    start = jnp.full((yp, LANES), init, jnp.float32)
    floor = jnp.full((yp, LANES), max_val, jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (yp, LANES), 0)
    inside = [lax.bitwise_and(lax.ge(row, y0), lax.lt(row, y1)) for y0, y1 in (
        (jnp.full((yp, LANES), lo, jnp.int32), jnp.full((yp, LANES), hi, jnp.int32)) for lo, hi in geometry.y)]
    image_row = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    products = rows * groups  # (image, filter group) pairs, image-major
    reduce = lax.reduce_sum if pool == "sum" else lax.reduce_max

    def product(i, into):
        i = lax.min(i, products - 1)  # the last pair's look-ahead: one product again, unread
        into[...] = lax.dot(p_ref[lax.div(i, groups)], w_ref[lax.rem(i, groups)], preferred_element_type=jnp.float32)

    def epilogue(i, raw_ref):
        t, g = lax.div(i, groups), lax.rem(i, groups)
        fs, hi, lo = (lax.broadcast_in_dim(c_ref[g, k:k + 1], (yp, LANES), (0, 1)) for k in range(3))
        image = pl.ds(t, 1)
        mine = lax.eq(image_row, lax.broadcast(t, (rows, LANES)))

        def stat(k, x):  # slices alone: an integer index costs an array in the trace
            return lax.reshape(spread_ref[image, k:k + 1, x:x + 1], (yp, LANES))

        for a, (x0, x1) in enumerate(geometry.x):
            pos = neg = start
            for x in range(x0, x1):
                centred = lax.sub(raw_ref[x * yp:(x + 1) * yp], lax.mul(stat(0, x), fs))
                v = lax.mul(centred, stat(1, x))
                pos = op(pos, lax.max(floor, lax.sub(v, hi)))
                neg = op(neg, lax.max(floor, lax.sub(lo, v)))
            for b in range(len(geometry.y)):
                for h, acc in enumerate((pos, neg)):
                    pooled = reduce(lax.select(inside[b], acc, start), (0,))
                    # image t's row of the cell's (rows, LANES) tile: a whole-tile store
                    cell = o_refs[(b * nx + a) * 2 + h]
                    cell[g] = lax.select(mine, lax.broadcast_in_dim(pooled, (rows, LANES), (1,)), cell[g])

    # Two buffers, so that one pair's product is made while the pair
    # before it is pooled: the MXU's work beside the VPU's.
    product(0, front_ref)

    def pair(k, carry):
        i = lax.mul(k, 2)
        product(lax.add(i, 1), back_ref)
        epilogue(i, front_ref)
        product(lax.add(i, 2), front_ref)
        epilogue(lax.add(i, 1), back_ref)
        return carry

    lax.fori_loop(0, products // 2, pair, 0)


@partial(jax.jit, static_argnames=("geometry", "yp", "max_val", "alpha", "pool", "interpret"))
def conv_pool(patches, stats, weights, fsums, offsets, *, geometry: Regions, yp: int, max_val: float,
              alpha: float, pool: str, interpret: bool = False):
    """The pooled rectified responses, one (N, F) array a pooling cell and
    half (module docstring). N is a multiple of ``ROW_TILE`` and F of
    ``filter_tile(F)``. Inside, the filters are laid out (F / LANES, ...,
    LANES): a product's filters are a leading index, which a loop may
    take."""
    n, positions, kp = patches.shape
    f = weights.shape[1]
    per_step, groups = filter_tile(f) // LANES, f // LANES
    cells = 2 * len(geometry.x) * len(geometry.y)
    rx = positions // yp
    # per filter: its sum, and the offset moved by alpha either way, so a
    # half is max(max_val, v - (offset + alpha)) or max(max_val, (offset - alpha) - v)
    columns = jnp.stack([fsums, offsets + alpha, offsets - alpha])
    kernel = partial(_kernel, geometry=geometry, yp=yp, max_val=max_val, pool=pool)
    pooled = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((groups, n, LANES), jnp.float32)] * cells,
        grid=(n // ROW_TILE, groups // per_step),
        in_specs=[
            pl.BlockSpec((ROW_TILE, positions, kp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((ROW_TILE,) + stats.shape[1:], lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((per_step, kp, LANES), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((per_step, 3, LANES), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((per_step, ROW_TILE, LANES), lambda i, j: (j, i, 0))] * cells,
        scratch_shapes=[
            pltpu.VMEM((positions, LANES), jnp.float32),
            pltpu.VMEM((positions, LANES), jnp.float32),
            pltpu.VMEM((ROW_TILE, 2, rx, yp, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        name="conv_pool",
    )(
        patches, stats,
        jnp.swapaxes(weights.reshape(kp, groups, LANES), 0, 1),
        jnp.swapaxes(columns.reshape(3, groups, LANES), 0, 1),
    )
    return [jnp.swapaxes(cell, 0, 1).reshape(n, f) for cell in pooled]
