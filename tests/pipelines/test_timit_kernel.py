"""The TIMIT workload's kernel variant (`keystone-tpu timit-kernel`):
the exact Gaussian kernel that the cosine branches estimate, fitted by
kernel ridge regression on the raw frames."""

import json

import numpy as np
import pytest

from keystone_tpu.cli import WORKLOADS, main
from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator
from keystone_tpu.ops.learning.kernel import KernelBlockLinearMapper, KernelRidgeRegression
from keystone_tpu.pipelines import timit as t


def kernel_config(**kw):
    defaults = dict(solver="kernel", kernel_block_size=128, reg=1.0)
    defaults.update(kw)
    return t.TimitConfig(**defaults)


def test_the_kernel_is_the_one_the_cosine_features_estimate():
    """W = gamma N(0, 1) gives E[2 cos(w.x + b) cos(w.y + b)] =
    exp(-gamma^2 |x - y|^2 / 2): the kernel generator's parameter is
    gamma^2 / 2 unless the configuration gives its own."""
    assert t.kernel_gamma(t.TimitConfig()) == pytest.approx(0.05555 ** 2 / 2)
    assert t.kernel_gamma(t.TimitConfig(gamma=0.1)) == pytest.approx(0.005)
    assert t.kernel_gamma(t.TimitConfig(kernel_gamma=0.25)) == 0.25
    # and the estimate converges to it: 16,384 features of 440 inputs
    from keystone_tpu.ops.stats.core import CosineRandomFeatures

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 440)).astype(np.float32)
    w, b = CosineRandomFeatures.draw(440, 16384, 0.05555, dist="gaussian", seed=1)
    feats = np.cos(x @ w.T + b)
    estimate = 2.0 * feats @ feats.T / 16384
    exact = np.exp(-t.kernel_gamma(t.TimitConfig()) * ((x[:, None] - x[None]) ** 2).sum(-1))
    assert np.abs(estimate - exact).max() < 0.03


def test_the_kernel_form_builds_fits_and_scores_on_the_normal_path():
    train = t.synthetic_timit(512, seed=0)
    pipeline = t.build_pipeline(kernel_config(seed=5), train)
    estimators = [op for op in pipeline.graph.operators.values() if isinstance(op, KernelRidgeRegression)]
    assert len(estimators) == 1
    est = estimators[0]
    assert (est.block_size, est.num_epochs, est.reg, est.block_permuter) == (128, 1, 1.0, 5)
    assert est.kernel_generator.gamma == pytest.approx(0.05555 ** 2 / 2)
    fitted = pipeline.fit()
    mappers = [op for op in fitted.graph.operators.values() if isinstance(op, KernelBlockLinearMapper)]
    assert len(mappers) == 1 and mappers[0].num_train == 512 and mappers[0].block_size == 128
    labels = np.asarray(fitted.apply_batch(train.data).data)[:512]
    assert labels.shape == (512,) and labels.dtype.kind == "i"
    evaluator = MulticlassClassifierEvaluator(t.NUM_CLASSES)
    metrics = evaluator.evaluate(ArrayDataset(labels), train.labels)
    # 147 classes: chance is 99.3%; the exact kernel fits its training rows far better
    assert metrics.total_error < 0.5, metrics.summary()


def test_an_unknown_solver_is_refused():
    with pytest.raises(ValueError, match="unknown solver"):
        t.build_pipeline(t.TimitConfig(solver="no-such"), t.synthetic_timit(64, seed=0))


def test_the_block_form_is_what_it_was():
    config = t.TimitConfig(num_cosines=2, num_cosine_features=64, reg=5.0, num_epochs=1)
    assert config.solver == "block"
    pipeline = t.build_pipeline(config, t.synthetic_timit(128, seed=1))
    assert not any(isinstance(op, KernelRidgeRegression) for op in pipeline.graph.operators.values())


def test_the_cli_lists_and_runs_the_kernel_variant(capsys):
    assert WORKLOADS["timit-kernel"][:4] == ("timit", "TimitConfig", "run", {"solver": "kernel"})
    assert main(["--list"]) == 0
    assert "timit-kernel" in capsys.readouterr().out
    # no train location: the workload makes its synthetic 4,096 frames
    rc = main(["timit-kernel", "--kernel-block-size", "1024", "--reg", "1.0"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["workload"] == "timit-kernel"
    assert 0.0 <= payload["train_error"] < 0.5 and 0.0 <= payload["test_error"] <= 1.0
