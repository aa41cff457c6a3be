"""Pallas TPU kernels — populated only where XLA's emitter can't win.

Round 3 measured the two candidate dense kernels on a real v5e chip with
dispatch-latency-free slope timing (K invocations inside one jitted
fori_loop over dynamically-offset slices, lo=8 / hi=72, medians of 3):

===========================  ==========  =============  =========
kernel (m=8192, n=4096,      XLA         Pallas         winner
d=1024, k=138, fp32)         TFLOP/s     TFLOP/s
===========================  ==========  =============  =========
Gaussian panel exp(-g*d2)    162.7       100.6          XLA 1.6x
fused panel @ W (ring hop)   164.3       127.2          XLA 1.3x
===========================  ==========  =============  =========

XLA's matmul emitter + fused elementwise epilogue already keeps the
squared-distance intermediate out of HBM well enough that hand tiling
loses; both dense kernels were therefore deleted rather than shipped dark
(round-2 verdict: "measure the Pallas kernels or delete them").

The package's first SHIPPED kernels (``blocksparse.py``) are exactly the
excepted case that verdict carved out: block-sparse (BSR) matmul and Gram
accumulation, where the work to skip is data-dependent (which feature
tiles of a hashing-TF matrix are nonzero) and no dense emitter can skip
it. A ``jax.lax`` block-gather fallback shares the interface off-TPU;
``interpret=True`` exists for parity tests only, and the on-chip slope
measurement discipline still applies before any new kernel becomes a
default.

``conv_pool.py`` is the case where XLA does NOT keep the
intermediate out of HBM: the fused convolution featurizer's normalised
responses are a convolution fusion's output and the pooling reductions'
input, since a reduction does not fuse into the convolution that feeds
it on this chip. Measured on a v5e against XLA's form and XLA's best
single-read pooling before it became the TPU's default (PERF.md section
6): CIFAR's featurizer 1,285 ms in XLA's form, 1,545 single-read,
214 in the kernel's form. Off the TPU the featurizer keeps XLA's form.
"""

from .blocksparse import (
    DEFAULT_BLOCK_SHAPE,
    DEFAULT_DENSITY_THRESHOLD,
    BlockSparseMatrix,
    bsr_gram_totals,
    bsr_matmul,
    default_block_shape,
    density_threshold,
    ell_matmul,
    resolve_impl,
)

__all__ = [
    "DEFAULT_BLOCK_SHAPE",
    "DEFAULT_DENSITY_THRESHOLD",
    "BlockSparseMatrix",
    "bsr_gram_totals",
    "bsr_matmul",
    "default_block_shape",
    "density_threshold",
    "ell_matmul",
    "resolve_impl",
]
