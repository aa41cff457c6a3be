"""The fused chunk step is compiled once per chain STRUCTURE: a fresh
Pipeline of the same member types, static parameters and array shapes
reuses it (no trace, no compile, no cache entry, no retired chain kept
alive), and a streamed fit shows its phases as spans."""

import gc
import weakref

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import spans
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.ops.stats.core import CosineRandomFeatures
from keystone_tpu.parallel import linalg
from keystone_tpu.utils.compilation_cache import compile_count, install_compile_counter
from keystone_tpu.workflow import streaming
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.streaming import last_stream_report

ROWS, D_IN, D, K, CHUNK = 512, 12, 64, 3, 64


def _data(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, D_IN)).astype(np.float32)
    y = rng.normal(size=(ROWS, K)).astype(np.float32)
    return x, y


def _pipeline(seed, estimator=None, d=D):
    """A NEW chain (fresh members, fresh weights) over new data."""
    x, y = _data(seed)
    featurizer = CosineRandomFeatures.create(D_IN, d, 0.3, seed=seed)
    estimator = estimator or BlockLeastSquaresEstimator(32, num_iter=2, reg=1e-3)
    pipeline = featurizer.to_pipeline().then_label_estimator(
        estimator, ArrayDataset(x), ArrayDataset(y)
    )
    return pipeline, featurizer, x


@pytest.fixture
def chunked(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))
    install_compile_counter()
    if streaming._STEP_JIT_CACHE:
        streaming._STEP_JIT_CACHE.clear()


def _weights(fitted):
    (mapper,) = [
        m for op in fitted.graph.operators.values()
        for m in getattr(op, "members", (op,)) if hasattr(m, "weights")
    ]
    return np.asarray(mapper.weights)


def test_a_second_pipeline_of_the_same_structure_traces_compiles_and_caches_nothing(chunked):
    first, _, _ = _pipeline(1)
    fitted = first.fit()
    report = last_stream_report()
    assert report.chunks == ROWS // CHUNK and report.compiles_first_chunk == 1
    entries = len(streaming._STEP_JIT_CACHE)
    (traces,) = [entry[1] for entry in streaming._STEP_JIT_CACHE.values()]
    assert len(traces) == 1
    del fitted, first

    compiles = compile_count()
    second, _, _ = _pipeline(2)  # other weights, other data, the same shapes
    second.fit()
    report = last_stream_report()
    assert report.chunks == ROWS // CHUNK
    assert report.compiles_first_chunk == 0 and report.compiles_steady_state == 0
    assert len(traces) == 1  # the step was not traced again
    assert len(streaming._STEP_JIT_CACHE) == entries
    assert compile_count() == compiles  # nothing built, nothing loaded


def test_the_shared_step_uses_each_chains_own_weights(chunked):
    """The weights are arguments of the shared step, not constants of the
    first chain that compiled it."""
    for seed in (1, 2):
        pipeline, featurizer, x = _pipeline(seed, LinearMapEstimator(reg=1e-3))
        streamed = _weights(pipeline.fit())
        feats = np.asarray(featurizer.apply_arrays(x), np.float64)
        y = _data(seed)[1].astype(np.float64)
        fc, yc = feats - feats.mean(0), y - y.mean(0)
        want = np.linalg.solve(fc.T @ fc + 1e-3 * np.eye(D), fc.T @ yc)
        np.testing.assert_allclose(streamed, want, rtol=2e-3, atol=2e-4)


def test_no_retired_chain_stays_pinned_by_the_cache(chunked):
    pipeline, featurizer, _ = _pipeline(1)
    pipeline.fit()
    gone = weakref.ref(featurizer)
    del pipeline, featurizer
    PipelineEnv.reset()  # the prefix table keeps fitted prefixes, by design
    gc.collect()
    assert gone() is None
    for jitted, _traces in streaming._STEP_JIT_CACHE.values():
        assert not isinstance(jitted, tuple)  # no (members, ...) tuple rides along


@pytest.mark.parametrize("change", ["width", "step", "static"])
def test_what_the_trace_depends_on_is_in_the_key(chunked, change):
    _pipeline(1)[0].fit()
    entries = len(streaming._STEP_JIT_CACHE)
    if change == "width":  # other array shapes
        _pipeline(2, d=32)[0].fit()
    elif change == "step":  # the same chain under another step function
        members = (CosineRandomFeatures.create(D_IN, D, 0.3, seed=3),)
        streaming._shared_step_jit(members, lambda c, x, y: c)
    else:  # a static (non-array) attribute that differs
        member = CosineRandomFeatures.create(D_IN, D, 0.3, seed=3)
        member.flavour = "other"
        streaming._shared_step_jit((member,), linalg.gram_stream_step)
    assert len(streaming._STEP_JIT_CACHE) == entries + 1


def test_a_member_that_cannot_be_split_is_keyed_on_itself():
    member = CosineRandomFeatures.create(D_IN, D, 0.3, seed=3)
    member.table = [1, 2, 3]  # unhashable: the member stays whole
    key, template, arrays = streaming._lift_member(member)
    assert key == ("whole", id(member)) and template is member and arrays == {}
    key, template, arrays = streaming._lift_member(CosineRandomFeatures.create(D_IN, D, 0.3, seed=3))
    assert sorted(arrays) == ["b", "w"] and template.w is None and key[0] is CosineRandomFeatures


def test_a_streamed_fit_shows_its_phases_as_spans(chunked):
    pipeline, _, _ = _pipeline(1)
    with spans.tracing_session("t", sync_timings=False) as session:
        pipeline.fit()
    names = [s.name for s in session.spans()]
    chunks = session.find("stream:chunk")
    assert [s.attributes["index"] for s in chunks] == list(range(ROWS // CHUNK))
    assert all(s.attributes["rows"] == CHUNK for s in chunks)
    # one wait for the queue per chunk, and the one that finds it empty
    assert names.count("stream:stall") == ROWS // CHUNK + 1
    assert names.count("stream:finish") == 1
    fold = session.find("stream:fold")[0]
    inside = {s.name for s in session.spans() if s.parent_id == fold.span_id}
    assert {"stream:chunk", "stream:stall"} <= inside
    if last_stream_report().shards > 1:
        (reduce,) = session.find("stream:reduce")
        assert reduce.attributes["shards"] == last_stream_report().shards
        assert reduce.parent_id == fold.span_id


def test_the_device_scopes_are_in_the_lowered_step():
    import jax

    member = CosineRandomFeatures.create(D_IN, D, 0.3, seed=3)
    step, _ = streaming._shared_step_jit((member,), linalg.gram_stream_step)
    carry = linalg.gram_stream_init(D, K)
    # narrow rows, as images are uploaded: float32 rows need no cast, and
    # `stream/cast` then names no operation
    x = np.zeros((CHUNK, D_IN), np.uint8)
    y = np.zeros((CHUNK, K), np.float32)
    mask = np.ones((CHUNK, 1), np.float32)
    text = step.jitted.lower(carry, x, y, mask, step.arrays).as_text(debug_info=True)
    for scope in ("stream/cast", "feat/CosineRandomFeatures", "gram/step"):
        assert scope in text, scope
    reduce = streaming._reduce_fn(2, 1, None)
    stacked = jax.tree_util.tree_map(lambda a: np.zeros((2,) + a.shape, np.float32), carry)
    assert "gram/reduce" in reduce.lower(stacked).as_text(debug_info=True)
    finish = linalg._gram_finish_fn().lower(*carry, np.float32(CHUNK))
    assert "gram/finish" in finish.as_text(debug_info=True)


# --------------------------------------- what else stood in a big fit's way


def test_the_fold_state_crosses_to_the_host_when_it_is_asked_for():
    """`_capture_state` keeps the carry where the fold left it and
    returns; the O(d²) fetch is `export_stream_state`'s, made once, and
    the estimator holds no device buffer afterwards."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.refit.state import _HostFetch

    estimator = LinearMapEstimator(reg=1e-3)
    carry = tuple(a + i for i, a in enumerate(linalg.gram_stream_init(8, 2), start=1))
    estimator._capture_state(carry, 5, reg=1e-3)
    fetch = estimator._stream_fetch
    assert fetch._host is None and len(fetch._arrays) == len(carry)  # nothing copied for a fit nobody exports
    state = estimator.export_stream_state()
    assert state.num_examples == 5 and state.meta == {"reg": 1e-3}
    assert all(isinstance(a, np.ndarray) for a in state.carry)
    for host, device in zip(state.carry, carry):
        np.testing.assert_array_equal(host, np.asarray(device))
    assert fetch._arrays == () and not hasattr(estimator, "_stream_fetch")
    assert estimator.export_stream_state() is state  # fetched once
    assert not any(isinstance(v, jax.Array) for v in jax.tree_util.tree_leaves(vars(estimator)))
    # an estimator copied or pickled before its export takes the arrays along, not the thread
    import copy
    import pickle

    estimator._capture_state(carry, 5, reg=1e-3)
    for twin in (copy.deepcopy(estimator), pickle.loads(pickle.dumps(copy.deepcopy(estimator)))):
        np.testing.assert_array_equal(twin.export_stream_state().carry[0], np.asarray(carry[0]))
    # a fetch that failed says so where its result is asked for
    gone = jnp.ones(3)
    gone.delete()
    with pytest.raises(RuntimeError):
        _HostFetch([gone]).result()


def test_labels_whose_rows_are_not_contiguous_fold_to_the_same_weights(chunked):
    """A label matrix fetched from the device comes back with padded rows;
    the fold copies a chunk's rows at a time, never the whole matrix."""
    x, y = _data(4)
    weights = []
    for labels in (y, np.asfortranarray(y)):
        featurizer = CosineRandomFeatures.create(D_IN, D, 0.3, seed=4)
        fitted = featurizer.to_pipeline().then_label_estimator(
            LinearMapEstimator(reg=1e-3), ArrayDataset(x), ArrayDataset(labels)
        ).fit()
        weights.append(_weights(fitted))
    assert not np.asfortranarray(y).flags.c_contiguous
    np.testing.assert_array_equal(weights[0], weights[1])
    kept = streaming._labels_host(ArrayDataset(np.asfortranarray(y)))
    assert kept.shape == y.shape and not kept.flags.c_contiguous
