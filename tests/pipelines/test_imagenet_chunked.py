"""The flagship through the normal path where it does not fit: a
`build_pipeline(...).fit()` whose featurizer chains run over row chunks
gives the whole-batch fit's model to the bit, a request that fits runs
whole, and top-5's first column is the arg-max of the scores."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import names
from keystone_tpu.ops.learning.block import BlockLinearMapper
from keystone_tpu.ops.util.labels import ClassLabelIndicators
from keystone_tpu.pipelines.imagenet import ImageNetSiftLcsFVConfig, build_pipeline
from keystone_tpu.workflow import executor
from keystone_tpu.workflow.executor import PipelineEnv

CLASSES, ROWS = 8, 60


def _data(seed=0):
    rng = np.random.default_rng(seed)
    images = (rng.random((ROWS, 48, 48, 3)) * 255).astype(np.float32)
    return images, (np.arange(ROWS) % CLASSES).astype(np.int32)


def _config():
    return ImageNetSiftLcsFVConfig(
        desc_dim=16, vocab_size=4, num_classes=CLASSES, image_size=(48, 48),
        num_pca_samples=ROWS * 40, num_gmm_samples=ROWS * 40,
    )


def _mapper(fitted) -> BlockLinearMapper:
    (found,) = [
        m for op in fitted.graph.operators.values()
        for m in getattr(op, "members", (op,)) if isinstance(m, BlockLinearMapper)
    ]
    return found


@pytest.fixture(scope="module")
def fits():
    """limit -> (fitted pipeline, chunks counted during the fit)."""
    images, labels = _data()
    done = {}

    def get(limit):
        if limit not in done:
            PipelineEnv.reset()
            before = names.metric(names.EXEC_CHUNKS).value(reason="footprint")
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(executor, "device_memory_limit_bytes", lambda: limit)
                indicators = ClassLabelIndicators(CLASSES).apply_batch(ArrayDataset(labels))
                fitted = build_pipeline(_config(), ArrayDataset(images), indicators).fit()
            done[limit] = (fitted, names.metric(names.EXEC_CHUNKS).value(reason="footprint") - before)
        return done[limit]

    return get


@pytest.mark.parametrize("limit", [4_000_000, 1_500_000], ids=["chunks-of-16", "chunks-of-4-or-8"])
def test_a_fit_whose_chains_run_in_chunks_is_the_whole_fit_to_the_bit(fits, limit):
    whole, none = fits(None)
    chunked, chunks = fits(limit)
    assert none == 0 and chunks > 6  # three passes over either branch's prefix, in chunks
    for attribute in ("weights", "intercept"):
        a, b = np.asarray(getattr(_mapper(whole), attribute)), np.asarray(getattr(_mapper(chunked), attribute))
        assert a.shape == b.shape and np.array_equal(a, b), attribute
    assert whole.graph.nodes == chunked.graph.nodes  # the same fitted graph either way


def test_a_request_that_fits_runs_whole_and_one_that_does_not_runs_in_chunks_with_the_same_answer(fits, monkeypatch):
    fitted, _ = fits(None)
    images, _ = _data(seed=9)
    counter = names.metric(names.EXEC_CHUNKS)
    monkeypatch.setattr(executor, "device_memory_limit_bytes", lambda: 1 << 40)
    before = counter.value(reason="footprint")
    whole = np.asarray(fitted.apply_batch(ArrayDataset(images)).data)
    assert counter.value(reason="footprint") == before and whole.shape == (ROWS, 5)
    monkeypatch.setattr(executor, "device_memory_limit_bytes", lambda: 1_500_000)
    chunked = np.asarray(fitted.apply_batch(ArrayDataset(images)).data)
    assert counter.value(reason="footprint") > before and np.array_equal(chunked, whole)


def test_top_fives_first_column_is_the_argmax_of_the_scores(fits):
    from keystone_tpu.ops.util.vectors import VectorCombiner
    from keystone_tpu.workflow.pipeline import FittedPipeline

    fitted, _ = fits(None)
    images, _ = _data(seed=4)
    top5 = np.asarray(fitted.apply_batch(ArrayDataset(images)).data)
    graph = fitted.graph
    (combiner,) = [n for n, op in graph.operators.items() if isinstance(op, VectorCombiner)]
    graph, sink = graph.add_sink(combiner)
    features = FittedPipeline(graph, fitted.source, sink).apply_batch(ArrayDataset(images))
    scores = np.asarray(_mapper(fitted).apply_batch(features).data)
    assert scores.shape == (ROWS, CLASSES) and top5.shape == (ROWS, 5)
    assert np.array_equal(top5[:, 0], scores.argmax(1))
    assert all(len(set(row)) == 5 for row in top5)
    picked = np.take_along_axis(scores, top5, axis=1)
    assert np.all(np.diff(picked, axis=1) <= 0)  # best first


def test_a_fit_keeps_how_each_mixture_was_started_and_how_far_em_went(fits):
    from keystone_tpu.ops.images.fisher import FisherVector

    fitted, _ = fits(None)
    encoders = [op for op in fitted.graph.operators.values() if isinstance(op, FisherVector)]
    assert len(encoders) == 2
    for encoder in encoders:
        record = encoder.gmm.fit_record
        means0, vars0, weights0 = record["start"]
        assert means0.shape == vars0.shape == (4, 16) and weights0.shape == (4,)
        assert weights0.sum() == pytest.approx(1.0, abs=1e-5) and (vars0 > 0).all()
        assert 1 <= record["updates"] <= record["iterations"] <= 100
        assert record["iterations"] - record["updates"] in (0, 1)  # the last look may not update


def test_the_flagships_fitted_gather_is_not_fused(fits):
    """Its branches end in Fisher encodings of their own: no shared input
    of fusable branches, so its gather and concatenation stay nodes."""
    from keystone_tpu.ops.util.gather import GatherTransformer
    from keystone_tpu.workflow.fusion import FusedGather, fuse_graph

    fitted, _ = fits(4_000_000)
    ops = list(fitted.graph.operators.values())
    assert any(isinstance(op, GatherTransformer) for op in ops)
    assert not any(
        isinstance(m, FusedGather) for op in ops for m in getattr(op, "members", (op,))
    )
    before = names.metric(names.FUSION_GATHERS).total()
    assert fuse_graph(fitted.graph) == fitted.graph
    assert names.metric(names.FUSION_GATHERS).total() == before
