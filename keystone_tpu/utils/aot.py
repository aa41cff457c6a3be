"""Ahead-of-time compilation for known-shape flagship configs.

The flagship's cold numbers were dominated by XLA compiles, not compute
(round 3: GMM fit 29.4 s cold ≈ ~100 ms of EM + compile; not re-measured).
The persistent compilation cache (``utils.compilation_cache``) already
makes every SECOND process fast; this module closes the remaining gap —
the first-ever run — by tracing + compiling the streaming flagship's
computations for a declared shape set at a time of the caller's choosing
(install, deploy, cron), which also populates the persistent cache so
every later process starts warm.

reference analog: none — Spark/JVM had no compile step; this is a
TPU-specific cost and a TPU-specific fix.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np


def warm_buckets(
    batch_apply: Callable[[Any], Any],
    example: Any,
    bucket_sizes: Sequence[int],
    enable_persistent_cache: bool = True,
) -> Dict[str, float]:
    """Drive ``batch_apply`` (dataset → dataset, e.g. a serving model's
    apply path) through every batch-size bucket AHEAD of traffic, so no
    request size compiles at serve time.

    ``example`` is one request payload (array or pytree of arrays); each
    bucket runs a zero batch of that shape stacked ``bucket`` high with
    ``num_examples=1`` — logical rows < physical rows, which also warms
    the pad-row masking ops a partial serving batch executes (a
    full-occupancy batch skips them, so warming at full occupancy would
    leave the partial-batch path cold). Returns per-bucket seconds; with
    the persistent cache enabled the warmed executables outlive this
    process, so a restarted server's warmup is a disk load.

    Fused pipelines (workflow/fusion.py) warm through here unchanged:
    ``batch_apply`` executes the FUSED chain executable, so each bucket
    warms one whole-chain program — serving keeps its zero-recompile-
    after-warmup guarantee with fusion on, at one dispatch per batch."""
    import jax

    from ..data.dataset import ArrayDataset

    if enable_persistent_cache:
        from .compilation_cache import enable_persistent_cache as _enable

        _enable()

    out: Dict[str, float] = {}
    for bucket in sorted(set(int(b) for b in bucket_sizes)):
        if bucket < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {bucket}")
        zeros = jax.tree_util.tree_map(
            lambda a: np.zeros((bucket,) + np.asarray(a).shape, np.asarray(a).dtype),
            example,
        )
        t0 = time.perf_counter()
        result = batch_apply(ArrayDataset(zeros, num_examples=1))
        jax.block_until_ready(getattr(result, "data", result))
        out[f"bucket_{bucket}_s"] = round(time.perf_counter() - t0, 4)
    return out


def warm_flagship(
    config=None,
    bucket_shapes: Sequence[Tuple[int, int, int]] = ((64, 256, 256),),
    solver_shapes: Sequence[Tuple[int, int, int]] = (),
    enable_persistent_cache: bool = True,
) -> dict:
    """Compile (without running full-size) the streaming flagship's
    per-bucket encode for each ``(rows, x, y)`` bucket shape, plus the
    mixture-weighted solver for each ``(n, d, num_classes)`` shape.

    Uses throwaway codebooks (compilation depends only on shapes/dtypes);
    returns per-shape compile seconds. With the persistent cache enabled
    (default), the compiled executables outlive this process.
    """
    import jax
    import jax.numpy as jnp

    from ..pipelines.imagenet import ImageNetSiftLcsFVConfig
    from ..pipelines.imagenet_streaming import StreamingFlagship

    if enable_persistent_cache:
        from .compilation_cache import enable_persistent_cache as _enable

        _enable()

    cfg = config or ImageNetSiftLcsFVConfig()
    fs = StreamingFlagship(cfg)
    rng = np.random.default_rng(0)

    # Throwaway codebooks at the config's dimensions: PCA (128→descDim)
    # per branch + a unit GMM. Shapes are what matters to the compile.
    from ..ops.images.fisher import FisherVector
    from ..ops.learning.gmm import GaussianMixtureModel
    from ..pipelines.imagenet_streaming import FlagshipCodebooks

    def dummy_books():
        def gmm():
            return GaussianMixtureModel(
                means=rng.normal(size=(cfg.desc_dim, cfg.vocab_size)).astype(np.float32),
                variances=np.ones((cfg.desc_dim, cfg.vocab_size), np.float32),
                weights=np.full((cfg.vocab_size,), 1.0 / cfg.vocab_size, np.float32),
            )

        sift_raw = 128
        lcs_raw = int(
            fs._lcs._neighbor_offsets().size ** 2 * 3 * 2
        ) if hasattr(fs._lcs, "_neighbor_offsets") else 128
        return FlagshipCodebooks(
            sift_pca=jnp.asarray(
                rng.normal(size=(sift_raw, cfg.desc_dim)).astype(np.float32)
            ),
            sift_fv=FisherVector(gmm()),
            lcs_pca=jnp.asarray(
                rng.normal(size=(lcs_raw, cfg.desc_dim)).astype(np.float32)
            ),
            lcs_fv=FisherVector(gmm()),
        )

    fs.adopt_codebooks(dummy_books())

    out = {}
    for rows, x, y in bucket_shapes:
        t0 = time.perf_counter()
        lowered = jax.jit(fs._encode_bucket).lower(
            jax.ShapeDtypeStruct((rows, x, y, 3), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 2), jnp.int32),
            jax.ShapeDtypeStruct(np.asarray(fs.codebooks.sift_pca).shape, jnp.float32),
            jax.ShapeDtypeStruct(np.asarray(fs.codebooks.lcs_pca).shape, jnp.float32),
        )
        lowered.compile()
        out[f"encode_{rows}x{x}x{y}_s"] = round(time.perf_counter() - t0, 1)

    for n, d, num_classes in solver_shapes:
        # The weighted solver jit is keyed on static (num_blocks, bs, m,
        # num_iter) plus array shapes; trace via a minimal real fit on
        # zeros — fit() is host-orchestrated, so the compile IS the cost.
        from ..data.dataset import ArrayDataset
        from ..ops.learning.weighted import BlockWeightedLeastSquaresEstimator

        t0 = time.perf_counter()
        xs = np.zeros((n, d), np.float32)
        ys = -np.ones((n, num_classes), np.float32)
        ys[np.arange(n), rng.integers(0, num_classes, n)] = 1.0
        est = BlockWeightedLeastSquaresEstimator(
            cfg.solver_block_size, num_iter=1, reg=cfg.reg,
            mixture_weight=cfg.mixture_weight,
        )
        est.fit(ArrayDataset(xs), ArrayDataset(ys))
        out[f"solve_{n}x{d}x{num_classes}_s"] = round(time.perf_counter() - t0, 1)
    return out
