"""TIMIT phone-classification workload.

TPU-native re-design of reference: pipelines/speech/TimitPipeline.scala —
numCosines parallel CosineRandomFeatures branches (4096 features each,
Gaussian or Cauchy W), gathered and concatenated, then block least squares
over 4096-wide feature blocks and argmax classification against 147 phone
classes.

Each cosine branch is one whole-batch MXU GEMM + fused cos; the block
solver's per-block Gram/residual work is sharded over the mesh's data axis
with psum (the analog of the reference's treeReduce into mlmatrix BCD).

Two forms with one featurizer. Where the feature matrix fits the devices
(:func:`features_fit_in_core`), :func:`build_pipeline` builds the gather
of branches and the fit runs in core. Where it does not (TIMIT at its
published 2.2M frames is 144 GB of features), it builds the same W and b
(one draw, :func:`_draw_branches`: the branches side by side on host
threads, each by its own generator) stacked into ONE
``CosineRandomFeatures``: a single chain, which the
streaming plan rule absorbs, so the fit folds row chunks into a Gram
carry on each device of the data mesh and the features are never held
(docs/PARTITIONING.md "Fitting TIMIT beyond one chip's memory").

A third form, ``solver="kernel"`` (``keystone-tpu timit-kernel``): no
featurizer at all. The cosine branches with W = gamma N(0, 1) are the
random-feature estimate of k(x, y) = exp(-gamma^2 |x - y|^2 / 2); the
kernel form fits that kernel itself, on the raw frames, by kernel ridge
regression (dual block Gauss-Seidel, ``ops/learning/kernel.py``; Tu et
al., arXiv:1602.05310, whose TIMIT experiment compares the two). The
n x n kernel is never held: the live object is one n x block panel.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, replace
from typing import Optional

import numpy as np

from ..data.dataset import ArrayDataset
from ..data.loaders.csv import LabeledData
from ..data.loaders.timit import NUM_CLASSES, TIMIT_DIMENSION, load_timit
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..obs import spans
from ..ops.learning.block import BlockLeastSquaresEstimator
from ..ops.learning.kernel import GaussianKernelGenerator, KernelRidgeRegression
from ..ops.stats.core import CosineRandomFeatures
from ..ops.util.labels import ClassLabelIndicators, MaxClassifier
from ..ops.util.vectors import VectorCombiner
from ..parallel.mesh import device_memory_limit_bytes, get_mesh, row_shard_count
from ..workflow.pipeline import Pipeline

logger = logging.getLogger(__name__)

NUM_COSINE_FEATURES = 4096


@dataclass
class TimitConfig:
    train_data_location: str = ""
    train_labels_location: str = ""
    test_data_location: str = ""
    test_labels_location: str = ""
    num_cosines: int = 50
    gamma: float = 0.05555
    rf_type: str = "gaussian"  # or "cauchy"
    reg: float = 0.0
    num_epochs: int = 5
    num_cosine_features: int = NUM_COSINE_FEATURES
    seed: int = 123
    # "block": cosine random features into the primal block solver.
    # "kernel": the exact Gaussian kernel on the raw frames, by kernel
    # ridge regression (`reg` is its lambda, as K_bb + lambda I; `seed`
    # permutes its blocks in every epoch).
    solver: str = "block"
    kernel_gamma: Optional[float] = None  # exp(-g |x - y|^2); None: gamma ** 2 / 2, the kernel the features estimate
    kernel_block_size: int = 4096
    kernel_num_epochs: int = 1


def kernel_gamma(config: TimitConfig) -> float:
    """The Gaussian kernel generator's parameter for the kernel form: as
    given, or the kernel that the cosine features of ``config.gamma``
    estimate (W = gamma N(0, 1) gives exp(-gamma^2 |x - y|^2 / 2))."""
    if config.kernel_gamma is not None:
        return config.kernel_gamma
    return config.gamma ** 2 / 2.0


def _draw_branches(config: TimitConfig, input_dim: int) -> "list[tuple[np.ndarray, np.ndarray]]":
    """Branch i's float32 (W_i, b_i) (generator ``seed + i``), in branch
    order: the one draw both forms of the featurizer are made of."""
    return CosineRandomFeatures.draw_branches(
        input_dim,
        config.num_cosine_features,
        config.gamma,
        config.rf_type,
        [config.seed + i for i in range(config.num_cosines)],
    )


def build_featurizer(config: TimitConfig, input_dim: int = TIMIT_DIMENSION) -> Pipeline:
    branches = [CosineRandomFeatures(w, b) for w, b in _draw_branches(config, input_dim)]
    return Pipeline.gather(branches) >> VectorCombiner()


def build_stacked_featurizer(
    config: TimitConfig, input_dim: int = TIMIT_DIMENSION
) -> Pipeline:
    """:func:`build_featurizer`'s features from one transformer: branch
    i's W_i and b_i (the same draws, ``seed + i``) stacked in branch
    order into one ``CosineRandomFeatures`` of ``num_cosines x
    num_cosine_features`` outputs. Column j of the gather form is column
    j here, so a fit on either gives weights for the other; and a single
    chain is what the streaming plan rule takes."""
    draws = _draw_branches(config, input_dim)
    w = np.concatenate([w for w, _ in draws])
    b = np.concatenate([b for _, b in draws])
    return CosineRandomFeatures(w, b).to_pipeline()


def features_fit_in_core(rows: int, width: int) -> bool:
    """Whether the in-core fit can hold ``rows x width`` float32 features
    on this mesh. It keeps the features and their centred copy, row-sharded
    over the mesh, and its measured peak on the chip is three times the two
    together (12.2 GiB at 2 x 2 GiB: PERF.md section 5), so they may take a
    third of the smallest device's memory. A backend that reports no memory
    (the CPU) is taken to hold them."""
    limit = device_memory_limit_bytes()
    if limit is None:
        return True
    shards = row_shard_count(get_mesh())
    return 3 * (2 * 4 * rows * width) <= limit * shards


def build_pipeline(config: TimitConfig, train: LabeledData, input_dim: int = TIMIT_DIMENSION) -> Pipeline:
    # A phase of every fit that starts from a configuration, with the
    # device idle: the random features are drawn on the host, in numpy,
    # the branches side by side on host threads (`build:draw`), and are
    # concrete device arrays when this returns. The label indicators are
    # a lazy node of the graph, not work done here.
    with spans.span("build:pipeline"):
        labels = ClassLabelIndicators(NUM_CLASSES)(train.labels)
        if config.solver == "kernel":
            return KernelRidgeRegression(
                GaussianKernelGenerator(kernel_gamma(config)),
                config.reg,
                config.kernel_block_size,
                config.kernel_num_epochs,
                block_permuter=config.seed,
            ).with_data(train.data, labels) >> MaxClassifier()
        if config.solver != "block":
            raise ValueError(f"unknown solver {config.solver!r}")
        width = config.num_cosines * config.num_cosine_features
        if features_fit_in_core(len(train.data), width):
            featurizer = build_featurizer(config, input_dim)
        else:  # the single chain, which streams: the features are never held
            featurizer = build_stacked_featurizer(config, input_dim)
        return featurizer.then_label_estimator(
            BlockLeastSquaresEstimator(
                config.num_cosine_features, num_iter=config.num_epochs, reg=config.reg
            ),
            train.data,
            labels,
        ) >> MaxClassifier()


def run(config: TimitConfig, solver: Optional[str] = None) -> dict:
    """Fit and evaluate; ``solver`` (the CLI's ``timit-kernel`` variant)
    overrides the configuration's."""
    if solver is not None:
        config = replace(config, solver=solver)
    start = time.time()
    if config.train_data_location:
        data = load_timit(
            config.train_data_location,
            config.train_labels_location,
            config.test_data_location,
            config.test_labels_location,
        )
        train, test = data.train, data.test
        input_dim = TIMIT_DIMENSION
    else:
        train = synthetic_timit(4096, seed=config.seed)
        test = synthetic_timit(1024, seed=config.seed + 1)
        input_dim = TIMIT_DIMENSION

    pipeline = build_pipeline(config, train, input_dim)
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline(train.data), train.labels)
    logger.info("TRAIN error %.2f%%", 100 * train_eval.total_error)
    results = {"train_error": train_eval.total_error, "pipeline": pipeline}
    if test is not None:
        test_eval = evaluator.evaluate(pipeline(test.data), test.labels)
        logger.info("TEST error %.2f%%", 100 * test_eval.total_error)
        results["test_error"] = test_eval.total_error
    results["seconds"] = time.time() - start
    return results


def synthetic_timit(n: int, seed: int = 0) -> LabeledData:
    """Learnable synthetic stand-in: labels from a hidden linear rule over
    the 440-dim feature space."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, TIMIT_DIMENSION)).astype(np.float32)
    w = np.random.default_rng(54321).normal(size=(TIMIT_DIMENSION, NUM_CLASSES))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return LabeledData(ArrayDataset(y), ArrayDataset(x))
