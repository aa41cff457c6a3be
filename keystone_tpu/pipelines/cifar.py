"""CIFAR-10 workloads: LinearPixels, RandomCifar, RandomPatchCifar and the
kernel variant.

TPU-native re-designs of
reference: pipelines/images/cifar/{LinearPixels,RandomCifar,
RandomPatchCifar,RandomPatchCifarKernel}.scala. The pipeline shapes and
hyperparameters match the reference; execution is whole-batch XLA: the
convolution featurizer runs as one fused NHWC conv over the image batch
(MXU) instead of per-image im2col GEMMs, and the solvers are the sharded
block/kernel solvers from ``ops.learning``.

The augmented variants (RandomPatchCifarAugmented*) reuse these builders
with RandomPatcher-expanded training data and CenterCornerPatcher +
AugmentedExamplesEvaluator at test time.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..data.dataset import ArrayDataset
from ..data.loaders.cifar import load_cifar
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..obs import spans
from ..ops.images import (
    Convolver,
    FusedConvFeaturizer,
    GrayScaler,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
    Windower,
)
from ..ops.learning.block import BlockLeastSquaresEstimator
from ..ops.learning.kernel import GaussianKernelGenerator, KernelRidgeRegression
from ..ops.learning.linear import LinearMapEstimator
from ..ops.learning.zca import ZCAWhitener, ZCAWhitenerEstimator
from ..ops.stats.core import Sampler, StandardScaler
from ..ops.util.labels import ClassLabelIndicators, MaxClassifier
from ..workflow.pipeline import Pipeline

logger = logging.getLogger(__name__)

NUM_CLASSES = 10
IMAGE_SIZE = 32
NUM_CHANNELS = 3


@dataclass
class RandomCifarConfig:
    """reference: RandomPatchCifar.scala:89-101 RandomCifarConfig."""

    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    whitening_epsilon: float = 0.1
    patch_size: int = 6
    patch_steps: int = 1
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    reg: Optional[float] = None
    sample_frac: Optional[float] = None
    # kernel variant (reference: RandomPatchCifarKernel.scala):
    gamma: float = 2e-4
    kernel_block_size: int = 2048
    num_epochs: int = 1
    # augmented variants (reference: RandomPatchCifarAugmented.scala):
    num_random_images_augment: int = 10
    augment_img_size: int = 24
    flip_chance: float = 0.5
    seed: int = 12334
    # memory bound for the featurizer: filters per fused conv block (the
    # (N, rx, ry, numFilters) conv output never materializes).
    filter_block: int = 512


def _load(config_location: str, sample_frac: Optional[float], seed: int) -> ArrayDataset:
    if not config_location:
        raise ValueError(
            "CIFAR workloads need --train-location pointing at a CIFAR-10 "
            "binary file (see examples/images/cifar_random_patch.sh)"
        )
    data = load_cifar(config_location)
    if sample_frac is not None:
        rng = np.random.default_rng(seed)
        keep = rng.random(len(data)) < sample_frac
        data = ArrayDataset(
            {
                "image": np.asarray(data.data["image"])[keep],
                "label": np.asarray(data.data["label"])[keep],
            }
        )
    return data


def normalize_rows(mat: np.ndarray, alpha: float = 1.0) -> np.ndarray:
    """Row mean/variance normalization (reference: utils/Stats.scala:112-124)."""
    means = np.nan_to_num(mat.mean(axis=1, keepdims=True))
    centred = mat - means
    var = np.square(centred).sum(axis=1, keepdims=True) / (mat.shape[1] - 1)
    sds = np.sqrt(var + alpha)
    sds[np.isnan(sds)] = np.sqrt(alpha)
    centred /= sds
    return centred


def sample_random_patches(
    train_images: ArrayDataset, config: RandomCifarConfig, whitener_size: int = 100000
) -> np.ndarray:
    """The (whitener_size, s*s*c) float64 row-normalised patches that the
    whitener is fitted on and the filters are drawn from
    (reference: RandomPatchCifar.scala:45-52): windows → sample →
    vectorize → row-normalize. The same rows for the same images and seed."""
    # Subsample images before windowing: at full CIFAR scale all windows of
    # all images is ~36M patches (~16 GB) of which the Sampler keeps 100k —
    # the reference streams this through an RDD, here we bound it up front.
    x_dim, y_dim = np.asarray(train_images.data).shape[1:3]
    per_image = (max(0, (x_dim - config.patch_size) // config.patch_steps) + 1) * (
        max(0, (y_dim - config.patch_size) // config.patch_steps) + 1
    )
    want_images = max(1, min(len(train_images), (2 * whitener_size) // per_image + 1))
    if want_images < len(train_images):
        idx = np.random.default_rng(config.seed).choice(
            len(train_images), size=want_images, replace=False
        )
        train_images = ArrayDataset(np.asarray(train_images.data)[idx])

    # sampled before they are vectorized (a row-by-row map: the same rows),
    # so that only the sample goes to the device
    patch_pipe = (
        Windower(config.patch_steps, config.patch_size)
        .to_pipeline()
        .then(Sampler(whitener_size, seed=config.seed))
        .then(ImageVectorizer())
    )
    base_filters = patch_pipe(train_images).get()
    return normalize_rows(np.asarray(base_filters.data, dtype=np.float64), 10.0)


def learn_random_patch_filters(
    train_images: ArrayDataset, config: RandomCifarConfig, whitener_size: int = 100000
) -> tuple[np.ndarray, ZCAWhitener]:
    """Sampled-patch filter bank + ZCA whitener
    (reference: RandomPatchCifar.scala:45-57): :func:`sample_random_patches`
    → fit ZCA → sample numFilters rows → whiten, L2-row-normalize,
    multiply by Wᵀ."""
    dim = config.patch_size ** 2 * np.asarray(train_images.data).shape[-1]
    with spans.span("build:filters", patches=whitener_size, filters=config.num_filters, dim=dim):
        base_mat = sample_random_patches(train_images, config, whitener_size)
        whitener = ZCAWhitenerEstimator(eps=config.whitening_epsilon).fit_single(base_mat)
        rng = np.random.default_rng(config.seed)
        idx = rng.choice(base_mat.shape[0], size=min(config.num_filters, base_mat.shape[0]), replace=False)
        sample_filters = base_mat[idx]
        w = np.asarray(whitener.whitener, dtype=np.float64)
        mu = np.asarray(whitener.means, dtype=np.float64)
        unnorm = (sample_filters - mu) @ w
        two_norms = np.sqrt((unnorm**2).sum(axis=1, keepdims=True))
        filters = (unnorm / (two_norms + 1e-10)) @ w.T
        return filters.astype(np.float32), whitener


def build_linear_pixels(train: ArrayDataset) -> Pipeline:
    """reference: LinearPixels.scala:20-56."""
    train_images = ArrayDataset(train.data["image"], train.num_examples)
    train_labels = ClassLabelIndicators(NUM_CLASSES)(
        ArrayDataset(train.data["label"], train.num_examples)
    )
    return (
        GrayScaler().to_pipeline()
        >> ImageVectorizer()
    ).then_label_estimator(LinearMapEstimator(), train_images, train_labels) >> MaxClassifier()


def build_random_patch(
    train: ArrayDataset,
    config: RandomCifarConfig,
    filters: Optional[np.ndarray] = None,
    whitener: Optional[ZCAWhitener] = None,
    solver: str = "block",
    with_classifier: bool = True,
) -> Pipeline:
    """The conv → rectify → pool → solve pipeline shared by RandomCifar
    (random filters), RandomPatchCifar (learned filters, block solver) and
    RandomPatchCifarKernel (learned filters, kernel solver)."""
    train_images = ArrayDataset(train.data["image"], train.num_examples)
    train_labels = ClassLabelIndicators(NUM_CLASSES)(
        ArrayDataset(train.data["label"], train.num_examples)
    )

    if filters is None:  # RandomCifar: gaussian random filter matrix
        rng = np.random.default_rng(config.seed)
        filters = rng.normal(
            size=(config.num_filters, config.patch_size**2 * NUM_CHANNELS)
        ).astype(np.float32)

    fused = FusedConvFeaturizer(
        Convolver(filters, NUM_CHANNELS, whitener=whitener, normalize_patches=True),
        SymmetricRectifier(alpha=config.alpha),
        Pooler(config.pool_stride, config.pool_size, None, "sum"),
        filter_block=config.filter_block,
    )
    if solver == "conv_block":
        # Rematerializing fast path: featurize→standardize→BCD as one
        # machine; the (n, 8·numFilters) feature matrix never exists
        # (ops/learning/conv_block.py). Equivalent problem to the
        # block path below, block partition in filter order.
        from ..ops.learning.conv_block import ConvBlockLeastSquaresEstimator
        from ..workflow.pipeline import Identity

        fitted = Identity().to_pipeline().then_label_estimator(
            ConvBlockLeastSquaresEstimator(
                fused, block_size=None, num_iter=1, reg=config.reg or 0.0
            ),
            train_images,
            train_labels,
        )
        return fitted >> MaxClassifier() if with_classifier else fitted

    featurizer = fused.to_pipeline()
    scaled = featurizer.then_estimator(StandardScaler(), train_images)
    if solver == "block":
        fitted = scaled.then_label_estimator(
            BlockLeastSquaresEstimator(4096, num_iter=1, reg=config.reg or 0.0),
            train_images,
            train_labels,
        )
    elif solver == "kernel":
        fitted = scaled.then_label_estimator(
            KernelRidgeRegression(
                GaussianKernelGenerator(config.gamma),
                config.reg or 0.0,
                config.kernel_block_size,
                config.num_epochs,
                block_permuter=config.seed,
            ),
            train_images,
            train_labels,
        )
    elif solver == "linear":
        fitted = scaled.then_label_estimator(LinearMapEstimator(config.reg), train_images, train_labels)
    else:
        raise ValueError(f"unknown solver {solver!r}")
    return fitted >> MaxClassifier() if with_classifier else fitted


def run_augmented(config: RandomCifarConfig, solver: str = "block") -> dict:
    """Augmented random-patch workload
    (reference: RandomPatchCifarAugmented.scala:33-105,
    RandomPatchCifarAugmentedKernel.scala): train on random
    ``augment_img_size`` crops with coin-flip horizontal flips and
    replicated labels; test on 10 deterministic views per image (center +
    four corners, each flipped) scored by the augmented-examples evaluator
    grouped per source image."""
    from ..evaluation.augmented import AugmentedExamplesEvaluator
    from ..ops.images import CenterCornerPatcher, RandomImageTransformer, RandomPatcher
    from ..utils.image import flip_horizontal

    start = time.time()
    train = _load(config.train_location, config.sample_frac, config.seed)
    train_images = ArrayDataset(train.data["image"], train.num_examples)
    filters, whitener = learn_random_patch_filters(train_images, config)

    size = config.augment_img_size
    mult = config.num_random_images_augment
    augmented_images = RandomImageTransformer(
        config.flip_chance, flip_horizontal, seed=config.seed
    ).apply_batch(
        RandomPatcher(mult, size, size, seed=config.seed).apply_batch(train_images)
    )
    augmented_train = ArrayDataset(
        {"image": augmented_images.data, "label": np.repeat(
            np.asarray(train.data["label"])[: train.num_examples], mult)},
        len(augmented_images),
    )
    pipeline = build_random_patch(
        augmented_train, config, filters, whitener, solver=solver,
        with_classifier=False,  # the augmented evaluator needs raw scores
    )

    results = {"pipeline": pipeline, "num_augmented_train": len(augmented_images)}
    if config.test_location:
        test = load_cifar(config.test_location)
        test_images = ArrayDataset(test.data["image"], test.num_examples)
        test_views = CenterCornerPatcher(size, size, horizontal_flips=True).apply_batch(
            test_images
        )
        num_views = 10  # center + 4 corners, each with a flip
        n_test = test.num_examples
        ids = np.repeat(np.arange(n_test), num_views)
        view_labels = np.repeat(np.asarray(test.data["label"])[:n_test], num_views)
        predictions = pipeline(test_views)
        # score on raw per-view scores: drop the trailing MaxClassifier
        scores = predictions.get() if hasattr(predictions, "get") else predictions
        evaluator = AugmentedExamplesEvaluator(ids, NUM_CLASSES)
        test_eval = evaluator.evaluate(scores, view_labels)
        logger.info("Test error is: %s", test_eval.total_error)
        results["test_error"] = test_eval.total_error
    results["seconds"] = time.time() - start
    return results


_PATCH_SOLVERS = {
    "random_patch": "block",
    "random_patch_fused": "conv_block",
    "random_patch_kernel": "kernel",
}


def run(config: RandomCifarConfig, variant: str = "random_patch") -> dict:
    """Run a CIFAR workload end to end; returns train/test error."""
    if variant in ("random_patch_augmented", "random_patch_kernel_augmented"):
        return run_augmented(config, solver="kernel" if "kernel" in variant else "block")

    start = time.time()
    train = _load(config.train_location, config.sample_frac, config.seed)
    train_images = ArrayDataset(train.data["image"], train.num_examples)

    if variant == "linear_pixels":
        pipeline = build_linear_pixels(train)
    elif variant == "random":
        pipeline = build_random_patch(train, config, solver="linear")
    elif variant in _PATCH_SOLVERS:
        # random_patch_fused = the rematerializing solver: featurize +
        # standardize + solve as one machine (ops/learning/conv_block.py).
        filters, whitener = learn_random_patch_filters(train_images, config)
        pipeline = build_random_patch(
            train, config, filters, whitener, solver=_PATCH_SOLVERS[variant]
        )
    else:
        raise ValueError(f"unknown variant {variant!r}")

    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline(train_images), train.data["label"])
    logger.info("Training error is: %s", train_eval.total_error)
    results = {"train_error": train_eval.total_error, "pipeline": pipeline}

    if config.test_location:
        test = load_cifar(config.test_location)
        test_images = ArrayDataset(test.data["image"], test.num_examples)
        test_eval = evaluator.evaluate(pipeline(test_images), test.data["label"])
        logger.info("Test error is: %s", test_eval.total_error)
        results["test_error"] = test_eval.total_error
    results["seconds"] = time.time() - start
    return results
