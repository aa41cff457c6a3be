"""TIMIT speech pipeline (reference: pipelines/speech/TimitPipeline.scala)."""

import numpy as np

from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator
from keystone_tpu.pipelines import timit as t


def small_config(**kw):
    defaults = dict(num_cosines=2, num_cosine_features=256, reg=5.0, num_epochs=1)
    defaults.update(kw)
    return t.TimitConfig(**defaults)


def test_end_to_end_synthetic():
    config = small_config()
    train = t.synthetic_timit(1024, seed=0)
    pipeline = t.build_pipeline(config, train)
    evaluator = MulticlassClassifierEvaluator(t.NUM_CLASSES)
    metrics = evaluator.evaluate(pipeline(train.data), train.labels)
    # 147 classes → chance error ≈ 99.3%; features must do much better.
    assert metrics.total_error < 0.8, metrics.summary()


def test_featurizer_output_width():
    config = small_config(num_cosines=3)
    train = t.synthetic_timit(64, seed=1)
    feats = t.build_featurizer(config)(train.data).get()
    assert np.asarray(feats.data).shape == (64, 3 * 256)


def test_cauchy_variant_runs():
    config = small_config(rf_type="cauchy")
    train = t.synthetic_timit(256, seed=2)
    pipeline = t.build_pipeline(config, train)
    preds = pipeline(train.data).get()
    assert len(np.asarray(preds.data)) >= 256


def test_fitted_pipeline_scores_in_one_dispatch_with_the_unfused_labels():
    """The fitted gather of cosine branches, their concatenation, the
    mapper and the arg-max are one program: one dispatch a request."""
    from keystone_tpu.obs import names
    from keystone_tpu.workflow import fusion_disabled
    from keystone_tpu.workflow.executor import PipelineEnv

    config = small_config(num_cosines=3)
    train = t.synthetic_timit(512, seed=4)
    x = t.synthetic_timit(200, seed=5).data
    PipelineEnv.reset()
    fitted = t.build_pipeline(config, train).fit()
    dispatches = names.metric(names.FUSION_BATCH_DISPATCHES)
    before = dispatches.value(fused="1"), dispatches.value(fused="0")
    labels = np.asarray(fitted.apply_batch(x).data)
    after = dispatches.value(fused="1"), dispatches.value(fused="0")
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)
    assert len(fitted.graph.nodes) == 1
    PipelineEnv.reset()
    with fusion_disabled():
        unfused = t.build_pipeline(config, train).fit()
        np.testing.assert_array_equal(labels, np.asarray(unfused.apply_batch(x).data))


def test_timit_loader(tmp_path):
    """Features CSV + 1-indexed sparse label files
    (reference: TimitFeaturesDataLoader.scala:326-390)."""
    rng = np.random.default_rng(0)
    for split in ("train", "test"):
        n = 6 if split == "train" else 4
        feats = rng.normal(size=(n, 5))
        np.savetxt(tmp_path / f"{split}.csv", feats, delimiter=",")
        lines = [f"{i + 1} {(i % 3) + 1}" for i in range(n)]
        (tmp_path / f"{split}.lab").write_text("\n".join(lines) + "\n")
    data = t.load_timit(
        str(tmp_path / "train.csv"),
        str(tmp_path / "train.lab"),
        str(tmp_path / "test.csv"),
        str(tmp_path / "test.lab"),
    )
    assert len(data.train.data) == 6 and len(data.test.data) == 4
    np.testing.assert_array_equal(
        np.asarray(data.train.labels.data), np.array([0, 1, 2, 0, 1, 2])
    )
