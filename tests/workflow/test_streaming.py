"""Streaming chunked execution: plan rewrite, boundaries, parity,
bounded memory, compile/overlap invariants, and failure shutdown
(workflow/streaming.py, docs/STREAMING.md)."""

import threading

import numpy as np
import pytest

from keystone_tpu.data.dataset import (
    ArrayDataset,
    ObjectDataset,
    default_ingest_workers,
    transfer_dtype,
)
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.ops.util.misc import CacherOperator
from keystone_tpu.workflow import (
    BatchTransformer,
    LabelEstimator,
    Pipeline,
    streaming_disabled,
)
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.streaming import (
    ChunkStream,
    StreamingFitOperator,
    last_stream_report,
)

CHUNK = 64


@pytest.fixture(autouse=True)
def _small_chunks(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))


class Scale(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    def apply_arrays(self, x):
        return x * self.c


class Shift(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    def apply_arrays(self, x):
        return x + self.c


def _problem(n=8 * CHUNK, d=32, k=4, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(dtype)
    w = rng.normal(size=(d, k)).astype(np.float32)
    y = (x.astype(np.float32) @ w + 0.01 * rng.normal(size=(n, k))).astype(
        np.float32
    )
    return x, y


def _chain_pipeline(x, y, est=None):
    feat = Scale(2.0).to_pipeline().then(Shift(0.5))
    est = est or BlockLeastSquaresEstimator(16, num_iter=2, reg=1e-3)
    return feat.then_label_estimator(est, ArrayDataset(x), ArrayDataset(y))


def _fit_predict(pipe, x):
    handle = pipe.apply(ArrayDataset(x))
    return handle, np.asarray(handle.get().data)[: x.shape[0]]


def _stream_ops(graph):
    return [
        op
        for op in graph.operators.values()
        if isinstance(op, StreamingFitOperator)
    ]


# ---------------------------------------------------------------- plan rewrite


def test_plan_rewrites_eligible_chain():
    x, y = _problem()
    handle = _chain_pipeline(x, y).apply(ArrayDataset(x))
    graph = handle._executor.graph
    ops = _stream_ops(graph)
    assert len(ops) == 1
    # The fit-side featurize chain was absorbed (flattened out of the
    # fused node) and its nodes removed from the graph.
    assert [type(m).__name__ for m in ops[0].members] == ["Scale", "Shift"]
    # The apply side keeps its own (fused) chain: output is still the
    # model applied to featurized input.
    _, preds = handle._executor, np.asarray(handle.get().data)
    assert preds.shape[1] == y.shape[1]


def test_no_rewrite_without_fit_stream_support():
    class ToyEstimator(LabelEstimator):
        def fit(self, data, labels):
            return Shift(0.0)

    x, y = _problem(n=4 * CHUNK)
    handle = _chain_pipeline(x, y, est=ToyEstimator()).apply(ArrayDataset(x))
    assert not _stream_ops(handle._executor.graph)


def test_no_rewrite_below_row_floor():
    x, y = _problem(n=CHUNK)  # one chunk: materialized path wins
    handle = _chain_pipeline(x, y).apply(ArrayDataset(x))
    assert not _stream_ops(handle._executor.graph)


def test_no_rewrite_when_disabled():
    x, y = _problem()
    with streaming_disabled():
        handle = _chain_pipeline(x, y).apply(ArrayDataset(x))
        assert not _stream_ops(handle._executor.graph)


# -------------------------------------------------------------------- parity


def test_parity_synthetic_chain():
    x, y = _problem()
    _, streamed = _fit_predict(_chain_pipeline(x, y), x)
    assert last_stream_report() is not None
    assert last_stream_report().chunks == 8
    PipelineEnv.reset()
    with streaming_disabled():
        _, materialized = _fit_predict(_chain_pipeline(x, y), x)
    rel = np.linalg.norm(streamed - materialized) / np.linalg.norm(materialized)
    assert rel <= 1e-5


def test_parity_mnist_fft_features():
    """Streaming-vs-materialized on MNIST-FFT featurized data — the
    reg-floor (reg=0) block solve, the realistic parity risk. A 64-pixel
    variant keeps the system overdetermined (n > d): parity at the
    reg FLOOR is only meaningful when the solution is data-determined,
    not floor-determined."""
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )

    n, pixels = 8 * CHUNK, 64
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, pixels)).astype(np.float32)
    feats_handle = build_featurizer(
        MnistRandomFFTConfig(num_ffts=2), image_size=pixels
    ).apply(ArrayDataset(x))
    feats = np.asarray(feats_handle.get().data)[:n].astype(np.float32)
    assert feats.shape[1] < n  # overdetermined by construction
    y = -np.ones((n, 10), np.float32)
    y[np.arange(n), rng.integers(0, 10, n)] = 1.0

    def build():
        est = BlockLeastSquaresEstimator(64, num_iter=1, reg=0.0)
        return est.with_data(ArrayDataset(feats), ArrayDataset(y))

    handle, streamed = _fit_predict(build(), feats)
    assert _stream_ops(handle._executor.graph), "direct dataset→fit did not stream"
    PipelineEnv.reset()
    with streaming_disabled():
        _, materialized = _fit_predict(build(), feats)
    rel = np.linalg.norm(streamed - materialized) / np.linalg.norm(materialized)
    assert rel <= 1e-5


def test_parity_cacher_boundary():
    """A Cacher between featurize stages cuts the streamed chain: the
    stream starts from the cached materialization, and results match the
    materialized path exactly."""
    x, y = _problem()

    def build():
        graph_pipe = Scale(3.0).to_pipeline()
        # splice a CacherOperator after Scale by direct surgery
        graph = graph_pipe.graph
        graph, cache_node = graph.add_node(
            CacherOperator("t"), [graph.get_sink_dependency(graph_pipe.sink)]
        )
        graph = graph.set_sink_dependency(graph_pipe.sink, cache_node)
        cached = Pipeline(graph, graph_pipe.source, graph_pipe.sink)
        feat = cached.then(Shift(-0.25))
        return feat.then_label_estimator(
            BlockLeastSquaresEstimator(16, num_iter=1, reg=1e-3),
            ArrayDataset(x),
            ArrayDataset(y),
        )

    handle, streamed = _fit_predict(build(), x)
    ops = _stream_ops(handle._executor.graph)
    assert len(ops) == 1
    # Chain stops AT the cacher: only Shift is streamed.
    assert [type(m).__name__ for m in ops[0].members] == ["Shift"]
    assert any(
        isinstance(op, CacherOperator)
        for op in handle._executor.graph.operators.values()
    )
    PipelineEnv.reset()
    with streaming_disabled():
        _, materialized = _fit_predict(build(), x)
    rel = np.linalg.norm(streamed - materialized) / np.linalg.norm(materialized)
    assert rel <= 1e-5


def test_fit_stream_linear_map_exact_parity():
    x, y = _problem(d=24, k=3)
    est = LinearMapEstimator(reg=1e-2)
    stream = ChunkStream(ArrayDataset(x), ArrayDataset(y), (), chunk_rows=CHUNK)
    streamed = est.fit_stream(stream)
    materialized = est.fit(ArrayDataset(x), ArrayDataset(y))
    a = np.asarray(streamed.apply_arrays(x))
    b = np.asarray(materialized.apply_arrays(x))
    assert np.linalg.norm(a - b) / np.linalg.norm(b) <= 1e-5


# ---------------------------------------------------- memory/compile/overlap


def test_bounded_host_memory():
    """Dataset 10× chunk; peak concurrently-live host chunk buffers stay
    under 2× one chunk's bytes (queue depth 1 + one in hand)."""
    x, y = _problem(n=10 * CHUNK, d=64, k=4)
    _fit_predict(_chain_pipeline(x, y), x)
    rep = last_stream_report()
    assert rep is not None and rep.chunks == 10
    chunk_bytes = CHUNK * 64 * 4 + CHUNK * 4 * 4 + CHUNK * 4  # x + y + mask
    assert rep.host_buffer_peak_bytes <= 2 * chunk_bytes
    assert rep.host_buffer_peak_bytes < x.nbytes / 2  # O(chunk), not O(n)


def test_one_compile_per_chunk_shape_and_overlap():
    from keystone_tpu.workflow import streaming as streaming_mod

    # the step cache is keyed on chain STRUCTURE: an earlier test's chain
    # of the same shape would already have paid this trace
    if streaming_mod._STEP_JIT_CACHE:
        streaming_mod._STEP_JIT_CACHE.clear()
    x, y = _problem()
    pipe = _chain_pipeline(x, y)
    _fit_predict(pipe, x)
    rep = last_stream_report()
    assert rep.compiles_first_chunk == 1  # one fused step trace
    assert rep.compiles_steady_state == 0  # tail chunk padded to same shape
    assert rep.overlap_ok()
    # Re-fit of the same pipeline (fresh planning): the shared step jit
    # is reused — this fold traces nothing, at its first chunk or later.
    PipelineEnv.reset()
    _fit_predict(pipe, x)
    rep2 = last_stream_report()
    assert rep2.compiles_first_chunk == 0
    assert rep2.compiles_steady_state == 0


def test_uint8_chunks_cross_narrow_and_cast_on_device():
    rng = np.random.default_rng(5)
    n, h = 8 * CHUNK, 16
    imgs = rng.integers(0, 256, size=(n, h), dtype=np.uint8)
    w = rng.normal(size=(h, 3)).astype(np.float32)
    y = (imgs.astype(np.float32) @ w).astype(np.float32)
    pipe = _chain_pipeline(imgs, y)  # Scale casts on device (uint8 input)
    handle, _ = _fit_predict(pipe, imgs.astype(np.float32))
    rep = last_stream_report()
    per_chunk = CHUNK * h * 1 + CHUNK * 3 * 4 + CHUNK * 4  # uint8 x + y + mask
    assert rep.bytes_transferred == 8 * per_chunk


def test_object_dataset_streams_via_worker_stacking():
    """Host ObjectDataset (the ingest staging ground) streams too: the
    prefetch workers stack item windows into chunks."""
    x, y = _problem(n=6 * CHUNK, d=16, k=2)
    rows = ObjectDataset([x[i] for i in range(len(x))])
    est = BlockLeastSquaresEstimator(8, num_iter=1, reg=1e-3)
    pipe = Scale(1.5).to_pipeline().then_label_estimator(
        est, rows, ArrayDataset(y)
    )
    handle, streamed = _fit_predict(pipe, x)
    assert _stream_ops(handle._executor.graph)
    assert last_stream_report().chunks == 6
    PipelineEnv.reset()
    with streaming_disabled():
        pipe2 = Scale(1.5).to_pipeline().then_label_estimator(
            est, ObjectDataset([x[i] for i in range(len(x))]), ArrayDataset(y)
        )
        _, materialized = _fit_predict(pipe2, x)
    rel = np.linalg.norm(streamed - materialized) / np.linalg.norm(materialized)
    assert rel <= 1e-5


def test_runtime_fallback_on_unchunkable_dataset():
    """A planned stream whose data turns out unchunkable at run time
    (here a BucketedDataset) must take the materialized path, not crash."""
    from keystone_tpu.data.dataset import BucketedDataset
    from keystone_tpu.workflow.streaming import StreamingFitOperator

    x, y = _problem(n=4 * CHUNK, d=16, k=2)
    buckets = BucketedDataset(
        [ArrayDataset(x[i : i + CHUNK]) for i in range(0, len(x), CHUNK)]
    )
    op = StreamingFitOperator(
        BlockLeastSquaresEstimator(8, num_iter=1, reg=1e-3), (Scale(2.0),)
    )
    model = op.fit_datasets([buckets, ArrayDataset(y)])
    ref = BlockLeastSquaresEstimator(8, num_iter=1, reg=1e-3).fit(
        Scale(2.0).apply_batch(ArrayDataset(x)), ArrayDataset(y)
    )
    a = np.asarray(model.apply_arrays(x))
    b = np.asarray(ref.apply_arrays(x))
    assert np.linalg.norm(a - b) / np.linalg.norm(b) <= 1e-6


# ------------------------------------------------------------------ failure


def test_prefetch_shutdown_on_midstream_failure():
    from keystone_tpu.reliability.faultinject import FaultSpec, injected

    x, y = _problem()
    pipe = _chain_pipeline(x, y)
    with injected(FaultSpec(match="streaming.chunk", kind="transient", calls=(3,))):
        with pytest.raises(ConnectionError):
            pipe.apply(ArrayDataset(x)).get()
    for _ in range(50):
        if not [
            t
            for t in threading.enumerate()
            if "prefetch" in t.name and t.is_alive()
        ]:
            break
        import time

        time.sleep(0.05)
    leaked = [t.name for t in threading.enumerate() if "prefetch" in t.name]
    assert not leaked, f"leaked prefetch workers: {leaked}"


# ------------------------------------------------------------- data plumbing


def test_iter_chunks_array_and_object():
    x = np.arange(10 * 3, dtype=np.float32).reshape(10, 3)
    chunks = list(ArrayDataset(x).iter_chunks(4))
    assert [n for _, n in chunks] == [4, 4, 2]
    assert np.allclose(np.concatenate([c for c, _ in chunks]), x)
    obj = ObjectDataset([x[i] for i in range(10)])
    chunks_o = list(obj.iter_chunks(4))
    assert [n for _, n in chunks_o] == [4, 4, 2]
    assert np.allclose(np.concatenate([c for c, _ in chunks_o]), x)


def test_dtype_preserved_through_pad_and_shard():
    import jax

    from keystone_tpu.parallel.mesh import get_mesh

    ds = ArrayDataset(np.zeros((10, 4, 4, 3), np.uint8))
    padded = ds.padded_to(8)
    assert all(
        l.dtype == np.uint8 for l in jax.tree_util.tree_leaves(padded.data)
    )
    sharded = ds.shard(get_mesh())
    assert all(
        l.dtype == np.uint8 for l in jax.tree_util.tree_leaves(sharded.data)
    )
    # 64-bit host data narrows to 32-bit for the transfer
    wide = ArrayDataset(np.zeros((10, 4), np.float64)).shard(get_mesh())
    assert all(
        l.dtype == np.float32 for l in jax.tree_util.tree_leaves(wide.data)
    )
    assert transfer_dtype(np.float64) == np.float32
    assert transfer_dtype(np.uint8) == np.uint8


def test_ingest_workers_env(monkeypatch):
    monkeypatch.setenv("KEYSTONE_INGEST_WORKERS", "3")
    assert default_ingest_workers() == 3
    monkeypatch.delenv("KEYSTONE_INGEST_WORKERS")
    assert default_ingest_workers() >= 2


def test_prefetch_queue_order_errors_and_close():
    from keystone_tpu.data.ingest import PrefetchQueue

    q = PrefetchQueue(iter(range(20)), lambda i: i * i, depth=3, workers=3)
    assert list(q) == [i * i for i in range(20)]
    q.close()

    def boom(i):
        if i == 5:
            raise ValueError("bad item")
        return i

    q2 = PrefetchQueue(iter(range(10)), boom, depth=2, workers=2)
    got = []
    with pytest.raises(ValueError, match="bad item"):
        for v in q2:
            got.append(v)
    assert got == [0, 1, 2, 3, 4]  # order preserved up to the failure
    q2.close()
    assert not [t for t in threading.enumerate() if "prefetch" in t.name]
