"""A host batch that feeds k batch transformers is uploaded once an
execution (workflow/executor.py::_SharedUpload): how many bytes cross the
bus, under which `site`, who is handed whose arrays, and that the copy
dies with the call. Counted with the program's own `keystone_h2d_*`
counters and `h2d` spans; the values are compared with the same branches
applied one by one to `jnp.asarray(x)`.
"""

import gc
import weakref

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset, BucketedDataset, ObjectDataset
from keystone_tpu.obs import names, spans
from keystone_tpu.ops.stats.core import CosineRandomFeatures, LinearRectifier
from keystone_tpu.ops.util.vectors import VectorCombiner
from keystone_tpu.workflow import Estimator, Identity, Pipeline, Transformer
from keystone_tpu.workflow.pipeline import BatchTransformer

ROWS, DIM, WIDTH = 64, 12, 8
SOURCE, COSINE = "DatasetOperator", "CosineRandomFeatures"


def _x(seed=0):
    return np.random.default_rng(seed).standard_normal((ROWS, DIM)).astype(np.float32)


def _branches(k):
    return [CosineRandomFeatures.create(DIM, WIDTH, 0.3, seed=s) for s in range(k)]


def _one_by_one(branches, x):
    device = jnp.asarray(x)
    return np.concatenate([np.asarray(b.apply_arrays(device)) for b in branches], axis=1)


_COUNTERS = (names.H2D_BYTES, names.H2D_TRANSFERS, names.H2D_REUSES)


def _h2d():
    """`{site: [bytes, transfers, reuses]}` as the three counters stand."""
    out = {}
    for i, name in enumerate(_COUNTERS):
        for labels, value in names.metric(name).series().items():
            out.setdefault(dict(labels)["site"], [0, 0, 0])[i] = int(value)
    return out


class _Counts:
    """Calling it gives what the `keystone_h2d_*` counters gained since
    construction, `{site: (bytes, transfers, reuses)}`, sites that gained
    nothing left out."""

    def __init__(self):
        self._base = _h2d()

    def __call__(self):
        gained = {
            site: tuple(n - b for n, b in zip(now, self._base.get(site, (0, 0, 0))))
            for site, now in _h2d().items()
        }
        return {site: g for site, g in gained.items() if any(g)}


class _Recording(Estimator):
    """Keeps the features it was fitted on (in a list, which a planner's
    shallow copy of the operator shares)."""

    def __init__(self):
        self.seen = []

    def fit(self, data):
        self.seen.append(data)
        return Identity()


# Fitted, a gather into a VectorCombiner is one program that uploads its
# input itself (workflow/fusion.py); a gather that is not concatenated
# keeps its branches as nodes, which share one upload.


def _joined(out):
    return np.concatenate([np.asarray(p) for p in out.data], axis=1)


def _through_apply_batch(branches, x):
    fitted = Pipeline.gather(branches).fit()
    return _joined(fitted.apply_batch(ArrayDataset(x)))


def _through_compiled_apply(branches, x):
    fitted = Pipeline.gather(branches).fit()
    return _joined(fitted.compiled_apply()(ArrayDataset(x)))


def _through_fit(branches, x):
    recording = _Recording()
    featurizer = Pipeline.gather(branches) >> VectorCombiner()
    featurizer.then_estimator(recording, ArrayDataset(x)).fit()
    (seen,) = recording.seen
    return np.asarray(seen.data)


ROUTES = {
    "apply_batch": _through_apply_batch,
    "compiled_apply": _through_compiled_apply,
    "fit": _through_fit,
}


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_gather_of_k_branches_uploads_its_host_input_once(route, k):
    branches, x = _branches(k), _x()
    expected = _one_by_one(branches, x)
    counts = _Counts()
    with spans.tracing_session("t", sync_timings=False) as session:
        got = ROUTES[route](branches, x)
    assert counts() == {SOURCE: (x.nbytes, 1, k - 1)}  # and no consumer uploaded for itself
    (upload,) = session.find("h2d")
    assert upload.attributes == {"site": SOURCE, "bytes": x.nbytes, "consumers": k}
    assert got.dtype == expected.dtype and np.array_equal(got, expected)


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("route", ["apply_batch", "compiled_apply"])
def test_a_fitted_gather_into_a_combiner_goes_up_once_in_its_one_program(route, k):
    branches, x = _branches(k), _x()
    fitted = (Pipeline.gather(branches) >> VectorCombiner()).fit()
    apply = fitted.apply_batch if route == "apply_batch" else fitted.compiled_apply()
    counts = _Counts()
    with spans.tracing_session("t", sync_timings=False) as session:
        got = np.asarray(apply(ArrayDataset(x)).data)
    assert counts() == {"FusedTransformerOperator": (x.nbytes, 1, 0)}
    (upload,) = session.find("h2d")
    assert upload.attributes == {"site": "FusedTransformerOperator", "bytes": x.nbytes, "consumers": 1}
    assert np.array_equal(got, _one_by_one(branches, x))


def test_a_single_chain_uploads_in_its_consumer_as_before():
    (cosine,), x = _branches(1), _x()
    fitted = cosine.to_pipeline().fit()
    counts = _Counts()
    with spans.tracing_session("t", sync_timings=False) as session:
        got = np.asarray(fitted.apply_batch(ArrayDataset(x)).data)
    assert counts() == {COSINE: (x.nbytes, 1, 0)}
    (upload,) = session.find("h2d")
    assert upload.attributes == {"site": COSINE, "bytes": x.nbytes, "consumers": 1}
    assert np.array_equal(got, _one_by_one([cosine], x))


@pytest.mark.parametrize("route", ["apply_batch", "compiled_apply"])
def test_a_device_resident_input_moves_nothing(route):
    branches, x = _branches(2), _x()
    fitted = (Pipeline.gather(branches) >> VectorCombiner()).fit()
    apply = fitted.apply_batch if route == "apply_batch" else fitted.compiled_apply()
    device = ArrayDataset(jnp.asarray(x))
    counts = _Counts()
    got = np.asarray(apply(device).data)
    assert counts() == {}
    assert np.array_equal(got, _one_by_one(branches, x))


def test_masked_descriptors_go_up_in_each_consumer_as_before():
    desc = np.random.default_rng(1).standard_normal((6, 5, DIM)).astype(np.float32)
    valid = np.ones((6, 5), bool)
    branches = [LinearRectifier(0.0, a) for a in (0.1, 0.2)]
    fitted = Pipeline.gather(branches).fit()
    counts = _Counts()
    first, second = fitted.apply_batch(ArrayDataset({"desc": desc, "valid": valid})).collect()[0]
    assert counts() == {"LinearRectifier": (2 * desc.nbytes, 2, 0)}
    assert np.array_equal(first["desc"], np.maximum(0.0, desc[0] - np.float32(0.1)))
    assert np.array_equal(second["valid"], valid[0])  # validity flows through, on the host


def test_a_bucketed_dataset_goes_up_bucket_by_bucket_as_before():
    small, large = _x(1)[:8], _x(2)[:24]
    branches = _branches(2)
    fitted = Pipeline.gather(branches).fit()
    counts = _Counts()
    out = fitted.apply_batch(BucketedDataset([ArrayDataset(small), ArrayDataset(large)]))
    assert counts() == {COSINE: (2 * (small.nbytes + large.nbytes), 4, 0)}
    assert len(out) == 32


def test_an_object_dataset_is_stacked_and_uploaded_by_each_consumer_as_before():
    x = _x()
    fitted = Pipeline.gather(_branches(2)).fit()
    counts = _Counts()
    fitted.apply_batch(ObjectDataset(list(x)))
    assert counts() == {COSINE: (2 * x.nbytes, 2, 0)}


class _HostSum(Transformer):
    """A host-side transformer: wants the host's copy, and says what it got."""

    def apply_batch(self, dataset):
        self.got = dataset.data
        return ArrayDataset(np.asarray(dataset.data).sum(axis=1, keepdims=True))


class _OwnBatchPath(BatchTransformer):
    """A batch transformer with an `apply_batch` of its own (the native
    extractors, the patchers): not one of the sharers."""

    def apply_batch(self, dataset):
        self.got = dataset.data
        return ArrayDataset(jnp.asarray(dataset.data)[:, :1])


def test_other_consumers_of_the_node_still_read_the_host_copy():
    cosines, x = _branches(2), _x()
    host, own = _HostSum(), _OwnBatchPath()
    fitted = Pipeline.gather(cosines + [host, own]).fit()
    counts = _Counts()
    fitted.apply_batch(ArrayDataset(x))
    assert counts() == {SOURCE: (x.nbytes, 1, 1)}  # the two cosines, and nobody else
    assert host.got is x and own.got is x


def test_consumers_are_counted_on_the_graph_that_runs():
    """The optimizer merges two equal branches into one node, and fuses a
    branch's chain into one: what is counted is what is left to run."""
    (cosine,), x = _branches(1), _x()
    counts = _Counts()
    twice = (Pipeline.gather([cosine, cosine]) >> VectorCombiner())(ArrayDataset(x)).get()
    assert counts() == {COSINE: (x.nbytes, 1, 0)}  # one consumer after the merge: its own upload
    assert np.array_equal(np.asarray(twice.data), _one_by_one([cosine, cosine], x))

    chains = [b >> LinearRectifier(0.0, 0.25) for b in _branches(2)]
    fitted = Pipeline.gather(chains).fit()
    labels = sorted(fitted.graph.get_operator(n).label for n in fitted.graph.nodes)
    assert labels.count("Fused[CosineRandomFeatures+LinearRectifier]") == 2
    counts = _Counts()
    fitted.apply_batch(ArrayDataset(x))
    assert counts() == {SOURCE: (x.nbytes, 1, 1)}


class _Witness(BatchTransformer):
    """Keeps the arrays it is handed."""

    def __init__(self):
        self.handed = []

    def apply_arrays(self, x):
        self.handed.append(x)
        return x.sum(axis=1, keepdims=True)


@pytest.mark.parametrize("route", ["apply_batch", "compiled_apply", "lazy apply"])
def test_the_copy_is_shared_in_a_call_gone_after_it_and_made_again_by_the_next(route):
    witnesses, x = [_Witness(), _Witness()], _x()
    pipeline = Pipeline.gather(witnesses)
    held = []  # the lazy result handles, which keep their executors alive
    if route == "lazy apply":

        def apply(dataset):
            held.append(pipeline(dataset))
            return held[-1].get()
    else:
        fitted = pipeline.fit()
        apply = fitted.apply_batch if route == "apply_batch" else fitted.compiled_apply()
    same = ArrayDataset(x)
    counts = _Counts()
    copies = []
    for call in (1, 2):
        out = apply(same)
        np.asarray(out.data)  # the request's work is done
        (first,), (second,) = (w.handed for w in witnesses)
        assert first is second and first is not x  # one device copy, handed to both
        assert counts() == {SOURCE: (call * x.nbytes, call, call)}  # the same numpy array, uploaded again
        copies.append(weakref.ref(first))
        del first, second
        for w in witnesses:
            w.handed.clear()
        gc.collect()
        assert copies[-1]() is None  # nothing the program holds references the copy
    assert len(held) == (2 if route == "lazy apply" else 0)
