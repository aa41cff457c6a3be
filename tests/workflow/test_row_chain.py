"""The executor's row chains (workflow/executor.py, `_RowChain`): a chain
of row-by-row transformers that would not fit the device whole runs over
row chunks, to the bit what the whole batch gives; one that fits runs as
it always did."""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import names, spans
from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
from keystone_tpu.ops.images.fisher import FisherVector
from keystone_tpu.ops.images.lcs import LCSExtractor
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
from keystone_tpu.ops.learning.pca import BatchPCATransformer
from keystone_tpu.ops.stats.core import ColumnSampler, NormalizeRows, SignedHellingerMapper
from keystone_tpu.ops.util.vectors import MatrixVectorizer
from keystone_tpu.workflow import executor
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.pipeline import Transformer


def _images(rows, side=48, seed=5):
    return (np.random.default_rng(seed).random((rows, side, side, 3)) * 255).astype(np.float32)


def _sift_prefix():
    return PixelScaler().to_pipeline() >> GrayScaler() >> SIFTExtractor() >> SignedHellingerMapper()


def _encoder(width, dims=16, centres=4, seed=3):
    rng = np.random.default_rng(seed)
    components = np.linalg.qr(rng.normal(size=(width, dims)))[0].astype(np.float32)
    gmm = GaussianMixtureModel(
        rng.normal(size=(dims, centres)).astype(np.float32) * 4,
        rng.uniform(1.0, 9.0, size=(dims, centres)).astype(np.float32),
        np.full(centres, 1.0 / centres, np.float32),
    )
    return [BatchPCATransformer(components), FisherVector(gmm), MatrixVectorizer(), NormalizeRows()]


def _chains():
    sift_encoding = _sift_prefix()
    for op in _encoder(128):
        sift_encoding = sift_encoding >> op
    lcs_encoding = LCSExtractor().to_pipeline()
    for op in _encoder(96):
        lcs_encoding = lcs_encoding >> op
    return {
        "sift-sampler": _sift_prefix() >> ColumnSampler(20, seed=7),
        "lcs-sampler": LCSExtractor().to_pipeline() >> ColumnSampler(9, seed=7),
        "sift-encoding": sift_encoding,
        "lcs-encoding": lcs_encoding,
    }


@pytest.fixture
def chunk_spans(monkeypatch):
    """The attributes of every `exec:chunks` span the executor opens. Not
    through a span session: a session is a profiling mode that forces
    every node as it is reached, so under one no chain is ever left to
    run in chunks."""
    seen = []
    span = spans.span

    def recording(name, *args, **attributes):
        if name == "exec:chunks":
            seen.append(attributes)
        return span(name, *args, **attributes)

    monkeypatch.setattr(executor._spans, "span", recording)
    return seen


def _run(pipeline, images, limit, monkeypatch, seen):
    """(output, the exec:chunks spans it opened) of one application under a
    device of `limit` bytes (None: a backend that reports no memory, as
    the CPU)."""
    PipelineEnv.reset()
    monkeypatch.setattr(executor, "device_memory_limit_bytes", lambda: limit)
    del seen[:]
    out = pipeline(ArrayDataset(images)).get()
    return np.asarray(out.data), list(seen)


@pytest.mark.parametrize("rows", [64, 61], ids=["chunk-divides-the-rows", "chunk-does-not-divide-the-rows"])
@pytest.mark.parametrize("chain", sorted(_chains()))
def test_a_chain_in_chunks_gives_the_whole_batchs_answer_to_the_bit(chain, rows, monkeypatch, chunk_spans):
    """The sampler's columns and the 2K x D encoding, chunked against
    whole, where the chunk's rows divide the batch's and where the last
    chunk is short (padded up to the chunk's shape and trimmed again)."""
    pipeline, images = _chains()[chain], _images(rows)
    whole, none = _run(pipeline, images, None, monkeypatch, chunk_spans)
    assert none == []
    chunked, found = _run(pipeline, images, 2_500_000, monkeypatch, chunk_spans)
    assert len(found) == 1
    attributes = found[0]
    assert attributes["rows"] == rows and attributes["reason"] == "footprint"
    assert attributes["chunks"] == -(-rows // attributes["chunk_rows"]) >= 2
    assert attributes["chunk_rows"] > 1 and (rows % attributes["chunk_rows"] == 0) == (rows == 64)
    assert chunked.shape == whole.shape and chunked.dtype == whole.dtype
    assert np.array_equal(chunked, whole)


def test_chunks_are_counted_and_a_chain_that_fits_counts_nothing(monkeypatch, chunk_spans):
    pipeline, images = _chains()["sift-encoding"], _images(32)
    counter = names.metric(names.EXEC_CHUNKS)
    before = counter.value(reason="footprint")
    _, found = _run(pipeline, images, 1 << 40, monkeypatch, chunk_spans)  # fits: whole
    assert found == [] and counter.value(reason="footprint") == before
    _, found = _run(pipeline, images, 1_500_000, monkeypatch, chunk_spans)
    assert counter.value(reason="footprint") - before == found[0]["chunks"]
    assert found[0]["members"] == 4  # the fused prefix, PCA, Fisher, the fused tail


def test_the_smaller_the_device_the_smaller_the_chunk_and_never_under_one_row(monkeypatch, chunk_spans):
    pipeline, images = _chains()["sift-encoding"], _images(16)
    sizes = []
    for limit in (4_000_000, 1_000_000, 1):
        _, found = _run(pipeline, images, limit, monkeypatch, chunk_spans)
        sizes.append(found[0]["chunk_rows"] if found else None)
    assert sizes[0] is None or sizes[0] >= sizes[1]
    assert sizes[1] >= sizes[2] == 1
    assert all(s is None or s & (s - 1) == 0 for s in sizes)  # powers of two


def test_a_chain_starts_after_a_node_whose_output_is_already_there(monkeypatch, chunk_spans):
    """Forced once (by whoever read it first), a member's output is the
    head of the chains that follow: it is not computed again."""
    pipeline, images = _chains()["sift-sampler"], _images(32)
    whole, _ = _run(pipeline, images, None, monkeypatch, chunk_spans)
    PipelineEnv.reset()
    result = pipeline(ArrayDataset(images))
    graph = result._executor.graph
    (prefix,) = [n for n, op in graph.operators.items() if hasattr(op, "members")]
    result._executor.execute(prefix).get()  # forced, and memoized in this executor
    monkeypatch.setattr(executor, "device_memory_limit_bytes", lambda: 1_500_000)
    assert np.array_equal(np.asarray(result.get().data), whole)
    assert chunk_spans == []  # the sampler alone is no chain


def test_a_batch_that_is_on_the_device_already_runs_whole_whatever_the_limit(monkeypatch, chunk_spans):
    """Chunking keeps a host batch from ever being on the device whole; one
    that is there has shown that it fits, and the chains of the cells that
    ran before this rule (a gathered feature matrix into its mapper) are of
    that kind: they run the programs they ran."""
    pipeline, images = _chains()["lcs-encoding"], _images(32)
    whole, _ = _run(pipeline, images, None, monkeypatch, chunk_spans)
    same, found = _run(pipeline, jnp.asarray(images), 1, monkeypatch, chunk_spans)
    assert found == [] and np.array_equal(same, whole)


class _PerItem(Transformer):
    """A transformer nobody has said is row by row."""

    def apply(self, datum):
        return datum

    def apply_batch(self, dataset):
        return dataset


def test_a_transformer_that_does_not_say_it_is_row_by_row_ends_the_chain(monkeypatch, chunk_spans):
    assert _PerItem().chunk_applier() is None
    assert NormalizeRows().chunk_applier() is not None
    pipeline = _PerItem().to_pipeline() >> _sift_prefix() >> MatrixVectorizer() >> _PerItem()
    _, found = _run(pipeline, _images(32), 1_000_000, monkeypatch, chunk_spans)
    # the fused prefix and the vectorizer fuse into one node: no chain of two
    assert found == []


def test_a_member_whose_output_nobody_can_state_runs_whole(monkeypatch, chunk_spans):
    class Opaque(BatchPCATransformer):
        def out_spec(self, in_specs):
            raise RuntimeError("no idea")

    pipeline = _sift_prefix() >> Opaque(np.eye(128, 16, dtype=np.float32)) >> ColumnSampler(5)
    whole, none = _run(pipeline, _images(16), None, monkeypatch, chunk_spans)
    same, found = _run(pipeline, _images(16), 1, monkeypatch, chunk_spans)
    assert none == found == [] and np.array_equal(whole, same)


# ------------------------------------------------------------- the sampler


def test_the_samplers_columns_are_the_same_drawn_whole_and_chunk_by_chunk():
    sampler = ColumnSampler(7, seed=11)
    whole = sampler.sample_indices(np.random.default_rng(11), 10, 40)
    rng = np.random.default_rng(11)
    pieces = [sampler.sample_indices(rng, n, 40) for n in (4, 4, 2)]
    assert whole.shape == (10, 7) and np.array_equal(np.concatenate(pieces), whole)
    assert all(len(set(row)) == 7 for row in whole)  # without replacement


def test_descriptors_on_a_device_are_sampled_there_and_equal_the_hosts():
    x = np.random.default_rng(2).normal(size=(6, 30, 8)).astype(np.float32)
    sampler = ColumnSampler(5, seed=4)
    host = sampler.apply_batch(ArrayDataset(x))
    device = sampler.apply_batch(ArrayDataset(jnp.asarray(x)))
    assert isinstance(host.data, np.ndarray) and not isinstance(device.data, np.ndarray)
    assert host.data.shape == (30, 8) and np.array_equal(np.asarray(device.data), host.data)
    padded = ArrayDataset(jnp.asarray(np.concatenate([x, np.zeros_like(x[:2])])), num_examples=6)
    assert np.array_equal(np.asarray(sampler.apply_batch(padded).data), host.data)


def test_a_session_that_would_keep_a_chunked_chain_whole_is_refused_with_its_reason(monkeypatch, chunk_spans):
    """A span session forces every node whole as it is reached, so under
    one no chain is left to run in chunks: where the chain would have
    run in chunks, the executor says so instead of launching what cannot
    fit; where it fits whole (or the backend reports no memory), a
    session changes nothing."""
    pipeline, images = _chains()["sift-encoding"], _images(32)
    whole, _ = _run(pipeline, images, None, monkeypatch, chunk_spans)
    with spans.tracing_session("a-profile"):
        traced, found = _run(pipeline, images, None, monkeypatch, chunk_spans)
        assert found == [] and np.array_equal(traced, whole)
        with pytest.raises(RuntimeError, match="would run in chunks of .* rows, but a span session"):
            _run(pipeline, images, 2_500_000, monkeypatch, chunk_spans)
        roomy, found = _run(pipeline, images, 10**12, monkeypatch, chunk_spans)
        assert found == [] and np.array_equal(roomy, whole)
    chunked, found = _run(pipeline, images, 2_500_000, monkeypatch, chunk_spans)
    assert len(found) == 1 and np.array_equal(chunked, whole)
