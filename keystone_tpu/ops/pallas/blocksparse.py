"""Block-sparse (BSR) matmul and Gram accumulation kernels.

The repo's first real Pallas kernels — and unlike the round-3 Gaussian
panel candidates (see package docstring), these are NOT emitter-friendly:
the work to skip is *data-dependent* (which feature tiles of a
hashing-TF / sparse-featurized matrix are nonzero), exactly the case XLA's
dense matmul emitter cannot exploit. Dense dispatch on a 10%-block-dense
matrix wastes 90% of its MACs (BLaST, arXiv:2507.03117).

Layout: the host-side :class:`~keystone_tpu.utils.sparse.BlockSparseMatrix`
is flattened to a padded ELL view — fixed ``K`` block slots per block row,
unused slots holding a zero block at column 0 (inert under accumulation) —
so the device kernels run a static grid with no host-side raggedness.

Two interchangeable implementations of one interface:

- ``impl="pallas"`` — a TPU Pallas kernel: grid over (block row, output
  column tile, ELL slot), the ELL column indices scalar-prefetched into
  SMEM (``PrefetchScalarGridSpec``) where they drive the dense operand's
  ``index_map`` — each grid step DMAs the one (bn, tile) panel its slot
  names and accumulates on the MXU into a resident output tile. Selected
  on a TPU backend, where ``auto`` runs this kernel or raises — it never
  gives way to ``lax``. ``interpret=True`` runs the same kernel on CPU
  for parity tests ONLY (it is not a fast path).
- ``impl="lax"`` — a ``jax.lax`` block-gather path (take + einsum) with
  identical semantics, what ``auto`` means off-TPU. CI gates
  interpret-vs-lax parity at ≤1e-5 (scripts/tune_smoke.sh); the compiled
  kernel is checked against ``lax`` on the chip by ``chip_smoke.py``.

Gram accumulation (``bsr_gram_totals``) returns the SAME raw sufficient
statistics tuple as ``linalg.gram_stream_init``'s carry — (AᵀA, AᵀY, Σx,
Σy) — so the estimator fast path finishes through the exact
``linalg.gram_stream_finish`` + ``bcd_from_gram`` code the streaming
engine uses: identical math, parity for free. Both impls ride the
matmul via AᵀA = (Aᵀ)_bsr · A_dense — one-sided sparsity (MACs ∝ block
density) with a dense output, so no data-dependent scatter exists on
either backend. (A two-sided ELL·ELL scatter Gram was measured first
and lost: padded-slot work grows with the SQUARE of the max row
occupancy, and skewed occupancy plus scatter-add serialization made it
slower than dense at every swept density.)

Dispatch into the fast path is guarded by a TUNED density threshold
(:func:`density_threshold`): ``KEYSTONE_BLOCKSPARSE_THRESHOLD`` explicit
wins, else the best ``blocksparse:threshold`` profile-store entry the
autotuner persisted for this rows bucket, else a conservative default.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import numpy as np

from ...envknobs import env_set, env_str
from ...utils.sparse import BlockSparseMatrix

#: Dispatch below this stored-block fraction when no tuned/env threshold
#: exists. Deliberately conservative: the sparse Gram path's MACs scale
#: with density, but its ESTIMATOR competitor is direct block coordinate
#: descent, which never forms the full d×d Gram (per-epoch cost
#: n·d·block, not n·d²) — so at moderate density the Gram route loses
#: even though its kernels win the Gram-vs-Gram comparison. The
#: autotuner's ``blocksparse`` task measures the real fit-level
#: crossover per shape class and persists it over this default.
DEFAULT_DENSITY_THRESHOLD = 0.05

#: Feature-tile default: MXU-friendly lanes on TPU; tests and CPU fits
#: pass smaller tiles explicitly when d is small.
DEFAULT_BLOCK_SHAPE = (8, 128)


def default_block_shape(d: Optional[int] = None) -> Tuple[int, int]:
    """``KEYSTONE_BLOCKSPARSE_BLOCK`` ("8x128") or the default, shrunk to
    at most the feature width so tiny problems keep >1 block column."""
    raw = env_str("KEYSTONE_BLOCKSPARSE_BLOCK")
    if raw:
        parts = [int(p) for p in raw.lower().replace(",", "x").split("x") if p]
        bm, bn = (parts + parts)[:2]
    else:
        bm, bn = DEFAULT_BLOCK_SHAPE
    if d is not None and d > 0:
        bn = min(bn, max(8, 1 << (max(d // 4, 1).bit_length() - 1)))
    return bm, bn


def density_threshold(rows: Optional[str] = None) -> float:
    """The block-density ceiling below which fits take the block-sparse
    path. Resolution order (docs/AUTOTUNING.md): explicit
    ``KEYSTONE_BLOCKSPARSE_THRESHOLD`` → the highest-speedup
    ``blocksparse:threshold`` entry the autotuner persisted for this rows
    bucket → :data:`DEFAULT_DENSITY_THRESHOLD`."""
    from ...envknobs import env_float

    if env_set("KEYSTONE_BLOCKSPARSE_THRESHOLD"):
        return env_float("KEYSTONE_BLOCKSPARSE_THRESHOLD", DEFAULT_DENSITY_THRESHOLD)
    try:
        from ...obs import store as _store

        store = _store.get_store()
        if store is not None:
            best, best_speedup = None, None
            for _key, _shape, m in sorted(
                store.entries(key_prefix="blocksparse:threshold", rows=rows)
            ):
                if "threshold" not in m:
                    continue
                speedup = float(m.get("speedup", 0.0))
                if best_speedup is None or speedup > best_speedup:
                    best, best_speedup = float(m["threshold"]), speedup
            if best is not None:
                return best
    except Exception:  # a broken store must never block a fit
        pass
    return DEFAULT_DENSITY_THRESHOLD


def resolve_impl(impl: str = "auto") -> str:
    """``auto`` → the compiled Pallas kernel on a TPU, ``lax`` elsewhere.
    A backend that fails to initialize raises here; it is not read as
    "cpu"."""
    if impl == "auto":
        import jax

        return "pallas" if jax.default_backend() == "tpu" else "lax"
    return impl


# ------------------------------------------------------------- lax fallback


@functools.lru_cache(maxsize=None)
def _ell_matmul_lax_fn(bm: int, bn: int, precision):
    """Block-gather matmul: out block-row i = Σ_k blocks[i,k] @ B panel
    at block-column indices[i,k]. Padded slots gather panel 0 against a
    zero block — inert."""
    import jax
    import jax.numpy as jnp

    def run(indices, blocks, b):
        nbc = b.shape[0] // bn
        panels = b.reshape(nbc, bn, b.shape[1])
        gathered = jnp.take(panels, indices, axis=0)  # (nbr, K, bn, N)
        out = jnp.einsum(
            "rkab,rkbn->ran", blocks, gathered, precision=precision
        )
        return out.reshape(indices.shape[0] * bm, b.shape[1])

    return jax.jit(run)


# ------------------------------------------------------------ pallas kernel

#: Output-column tile of the Pallas kernel: one (bn, _N_TILE) panel of the
#: dense operand and one (bm, _N_TILE) accumulator are resident per grid
#: step (256 KiB + 16 KiB at the default 8×128 feature tile, double
#: buffered — far inside VMEM at any operand size).
_N_TILE = 512


def _ell_matmul_pallas(indices, blocks, b, *, bm, bn, precision, interpret):
    """The Pallas TPU kernel (docstring up top): grid (block row, output
    column tile, ELL slot). The ELL column indices are scalar-prefetched
    and drive the dense operand's ``index_map``, so Pallas itself
    double-buffers the (bn, tile) panel DMAs out of HBM; the output tile
    stays resident across the innermost slot axis and accumulates."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbr, k_slots = indices.shape
    n_out = b.shape[1]
    if not interpret and (bm % 8 or bn % 8):
        raise ValueError(
            f"compiled block-sparse kernel needs tile dims in multiples of "
            f"8 (float32 sublanes), got {bm}x{bn}"
        )
    # Lane alignment: output columns padded to whole tiles, cropped below.
    tn = min(_N_TILE, -(-n_out // 128) * 128)
    n_pad = -(-n_out // tn) * tn
    if n_pad != n_out:
        b = jnp.pad(b, ((0, 0), (0, n_pad - n_out)))

    def kernel(idx_ref, blocks_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.dot(
            blocks_ref[0, 0], b_ref[...],
            precision=precision, preferred_element_type=jnp.float32,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbr, n_pad // tn, k_slots),
        in_specs=[
            pl.BlockSpec(
                (1, 1, bm, bn), lambda i, j, k, idx_ref: (i, k, 0, 0)
            ),
            # Padded slots point at panel 0 against a zero block: inert.
            pl.BlockSpec(
                (bn, tn), lambda i, j, k, idx_ref: (idx_ref[i * k_slots + k], j)
            ),
        ],
        out_specs=pl.BlockSpec((bm, tn), lambda i, j, k, idx_ref: (i, j)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr * bm, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ell_matmul",
        # Indices flattened: a 2-D SMEM array pads its minor dim to 128.
    )(indices.reshape(-1), blocks, b)
    return out[:, :n_out]


@functools.lru_cache(maxsize=None)
def _ell_matmul_pallas_fn(bm: int, bn: int, precision, interpret: bool):
    import jax
    from jax import lax

    # Mosaic contracts at DEFAULT (one bf16 pass) or HIGHEST (fp32) only;
    # the 3-pass HIGH the lax path accepts rounds up.
    if precision == lax.Precision.HIGH:
        precision = lax.Precision.HIGHEST
    return jax.jit(
        functools.partial(
            _ell_matmul_pallas, bm=bm, bn=bn, precision=precision,
            interpret=interpret,
        )
    )


# -------------------------------------------------------------- public API


def _precision(precision):
    if precision is not None:
        return precision
    from jax import lax

    return lax.Precision.HIGHEST


def ell_matmul(
    indices: np.ndarray,
    blocks: np.ndarray,
    b,
    *,
    impl: str = "auto",
    interpret: bool = False,
    precision: Any = None,
):
    """Padded-ELL block-sparse × dense matmul → (nbr·bm, N) dense."""
    import jax.numpy as jnp

    impl = resolve_impl(impl)
    bm, bn = blocks.shape[2], blocks.shape[3]
    indices = jnp.asarray(indices, jnp.int32)
    blocks = jnp.asarray(blocks, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if b.shape[0] % bn:
        raise ValueError(
            f"dense operand rows {b.shape[0]} not a multiple of bn={bn}"
        )
    precision = _precision(precision)
    if impl == "pallas":
        return _ell_matmul_pallas_fn(bm, bn, precision, bool(interpret))(
            indices, blocks, b
        )
    return _ell_matmul_lax_fn(bm, bn, precision)(indices, blocks, b)


def bsr_matmul(
    bsr: BlockSparseMatrix,
    b,
    *,
    impl: str = "auto",
    interpret: bool = False,
    precision: Any = None,
):
    """``bsr @ b`` → logical (rows, N) dense. ``b`` is zero-row-padded to
    the BSR's padded column count; output padding is cropped."""
    import jax.numpy as jnp

    b = jnp.asarray(b, jnp.float32)
    dp = bsr.padded_shape[1]
    if b.shape[0] < dp:
        b = jnp.pad(b, ((0, dp - b.shape[0]), (0, 0)))
    idx, blocks = bsr.to_ell()
    out = ell_matmul(
        idx, blocks, b, impl=impl, interpret=interpret, precision=precision
    )
    return out[: bsr.shape[0]]


def bsr_gram_totals(
    bsr: BlockSparseMatrix,
    y,
    *,
    a_dense=None,
    impl: str = "auto",
    interpret: bool = False,
    precision: Any = None,
):
    """Raw sufficient statistics ``(AᵀA, AᵀY, Σx, Σy)`` of the logical
    (rows, d) matrix — the exact tuple ``linalg.gram_stream_init`` seeds,
    finished by ``linalg.gram_stream_finish``. ``y`` is the (rows, k)
    dense target matrix.

    One-sided sparsity via the matmul identity AᵀA = (Aᵀ)_bsr · A_dense,
    AᵀY = (Aᵀ)_bsr · Y: MACs scale with block density (zero tiles of Aᵀ
    never dispatch), the output is dense — no data-dependent scatter, so
    both the Pallas kernel and the lax gather fallback run it as regular
    batched matmuls. Pass ``a_dense`` when the caller already holds the
    dense matrix (the estimator fast path's dense-probe case); otherwise
    it is rebuilt from the blocks — never more resident memory than the
    dense Gram baseline this path replaces."""
    import jax.numpy as jnp

    impl = resolve_impl(impl)
    d = bsr.shape[1]
    mp, dp = bsr.padded_shape
    y = jnp.asarray(y, jnp.float32)
    if y.shape[0] < mp:  # pad rows are zero blocks: contribute nothing
        y = jnp.pad(y, ((0, mp - y.shape[0]), (0, 0)))
    at = bsr.transpose()
    a = jnp.asarray(
        bsr.to_dense() if a_dense is None else a_dense, jnp.float32
    )
    if a.shape[0] < mp:
        a = jnp.pad(a, ((0, mp - a.shape[0]), (0, 0)))
    if a.shape[1] < dp:
        a = jnp.pad(a, ((0, 0), (0, dp - a.shape[1])))
    idx_t, blocks_t = at.to_ell()
    g = ell_matmul(
        idx_t, blocks_t, a, impl=impl, interpret=interpret,
        precision=precision,
    )[:dp, :dp]
    c = ell_matmul(
        idx_t, blocks_t, y, impl=impl, interpret=interpret,
        precision=precision,
    )[:dp]
    sa = jnp.sum(a, axis=0)[:dp]
    sb = jnp.sum(y, axis=0)
    return g[:d, :d], c[:d], sa[:d], sb


__all__ = [
    "DEFAULT_DENSITY_THRESHOLD",
    "DEFAULT_BLOCK_SHAPE",
    "BlockSparseMatrix",
    "bsr_gram_totals",
    "bsr_matmul",
    "default_block_shape",
    "density_threshold",
    "ell_matmul",
    "resolve_impl",
]
