"""TIMIT beyond one device's memory: the shipped entry point yields the
gather form's featurizer as ONE chain, which `Pipeline.fit` streams over
the data mesh; its weights are the in-core fit's."""

import threading

import jax
import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.learning.block import BlockLinearMapper
from keystone_tpu.ops.stats import core as stats_core
from keystone_tpu.ops.stats.core import CosineRandomFeatures
from keystone_tpu.parallel.mesh import make_mesh, use_mesh
from keystone_tpu.pipelines import timit as t
from keystone_tpu.workflow.streaming import last_stream_report

ROWS, CHUNK = 2048, 256
# float32 on both sides, another summation order (chunks and shards, the
# Gram centred algebraically against a centred copy) through 10 Cholesky
# block solves at n = 4d: measured 2.3e-5 and 3.7e-5 of the largest weight
# on four devices and on one, 2.1e-7 on the feature means
WEIGHTS_BOUND = 2e-4


def _config(**kw):
    return t.TimitConfig(**{**dict(num_cosines=2, num_cosine_features=256, num_epochs=5, seed=11), **kw})


def _mapper(fitted) -> BlockLinearMapper:
    (mapper,) = [
        m for op in fitted.graph.operators.values()
        for m in getattr(op, "members", (op,)) if isinstance(m, BlockLinearMapper)
    ]
    return mapper


def _members(pipeline):
    """The distinct cosine transformers of a pipeline's graph (one sits
    on the training path and on the apply path both)."""
    found = {
        id(op): op for op in pipeline.graph.operators.values() if isinstance(op, CosineRandomFeatures)
    }
    return list(found.values())


@pytest.fixture
def does_not_fit(monkeypatch):
    """The CPU reports no device memory, so the entry point takes every
    size to fit: tell it of a device that holds a megabyte."""
    monkeypatch.setattr(t, "device_memory_limit_bytes", lambda: 1 << 20)
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))


@pytest.mark.parametrize("workers", [1, 4], ids=["inline", "pool"])
def test_the_stacked_featurizer_is_the_gather_forms_to_the_bit(monkeypatch, workers):
    # both forms are made of one draw (`_draw_branches`), inline or on the pool
    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: workers)
    config = _config(num_cosines=3)
    branches = [
        CosineRandomFeatures.create(t.TIMIT_DIMENSION, 256, config.gamma, seed=config.seed + i)
        for i in range(3)
    ]  # build_featurizer's draws, in branch order
    (stacked,) = _members(t.build_stacked_featurizer(config))
    assert np.array_equal(np.concatenate([np.asarray(m.w) for m in branches]), np.asarray(stacked.w))
    assert np.array_equal(np.concatenate([np.asarray(m.b) for m in branches]), np.asarray(stacked.b))
    x = ArrayDataset(t.synthetic_timit(64, seed=3).data.data)
    gathered = np.asarray(t.build_featurizer(config)(x).get().data)
    assert np.array_equal(gathered, np.asarray(t.build_stacked_featurizer(config)(x).get().data))


def test_create_still_draws_what_it_drew():
    w, b = CosineRandomFeatures.draw(5, 7, 0.5, seed=9)
    made = CosineRandomFeatures.create(5, 7, 0.5, seed=9)
    rng = np.random.default_rng(9)
    assert np.array_equal(w, rng.normal(size=(7, 5)) * 0.5)
    assert np.array_equal(b, rng.uniform(0.0, 2.0 * np.pi, size=7))
    assert np.array_equal(np.asarray(made.w), w.astype(np.float32))


@pytest.mark.parametrize("limit,streams", [(None, False), (1 << 40, False), (1 << 20, True)])
def test_the_entry_point_streams_only_what_does_not_fit(monkeypatch, limit, streams):
    monkeypatch.setattr(t, "device_memory_limit_bytes", lambda: limit)
    pipeline = t.build_pipeline(_config(), t.synthetic_timit(ROWS, seed=0))
    assert len(_members(pipeline)) == (1 if streams else 2)


@pytest.mark.parametrize("rf_type", ["gaussian", "cauchy"])
@pytest.mark.parametrize("limit", [None, 1 << 20], ids=["gather", "stacked"])
def test_the_built_pipeline_holds_todays_weights_and_nothing_of_the_draw(monkeypatch, limit, rf_type):
    """What `build_pipeline` returns is what it returned when the branches
    were drawn one after another: concrete float32 device arrays equal to
    one `create` a branch; no thread of the pool is alive."""
    monkeypatch.setattr(t, "device_memory_limit_bytes", lambda: limit)
    monkeypatch.setattr(stats_core, "default_ingest_workers", lambda: 4)
    config = _config(num_cosines=4, num_cosine_features=64, rf_type=rf_type)
    pipeline = t.build_pipeline(config, t.synthetic_timit(ROWS, seed=0))
    assert not [th for th in threading.enumerate() if th.name.startswith("keystone-draw")]
    members = _members(pipeline)
    assert all(isinstance(m.w, jax.Array) and m.w.dtype == np.float32 for m in members)
    made = [
        CosineRandomFeatures.create(t.TIMIT_DIMENSION, 64, config.gamma, dist=rf_type, seed=config.seed + i)
        for i in range(4)
    ]
    if limit is None:  # a graph's operators are in no order: find each branch by its bits
        assert len(members) == 4
        for m in made:
            assert sum(
                np.array_equal(np.asarray(m.w), np.asarray(g.w)) and np.array_equal(np.asarray(m.b), np.asarray(g.b))
                for g in members
            ) == 1
    else:
        (stacked,) = members
        assert np.array_equal(np.concatenate([np.asarray(m.w) for m in made]), np.asarray(stacked.w))
        assert np.array_equal(np.concatenate([np.asarray(m.b) for m in made]), np.asarray(stacked.b))


def test_fit_in_core_counts_every_shard_of_the_mesh(monkeypatch):
    # 3 x (features + centred copy) of 1024 x 512 float32 is 12 MiB
    monkeypatch.setattr(t, "device_memory_limit_bytes", lambda: 4 << 20)
    with use_mesh(make_mesh(devices=jax.devices()[:1])):
        assert not t.features_fit_in_core(1024, 512)
    with use_mesh(make_mesh(devices=jax.devices()[:4])):
        assert t.features_fit_in_core(1024, 512)


def test_streamed_weights_on_four_devices_are_the_in_core_fits_and_the_one_device_streams(does_not_fit, monkeypatch):
    config = _config()
    train = t.synthetic_timit(ROWS, seed=5)
    streamed = {}
    for devices in (4, 1):
        with use_mesh(make_mesh(devices=jax.devices()[:devices])):
            fitted = t.build_pipeline(config, train).fit()
        report = last_stream_report()
        assert (report.shards, report.chunks, report.num_examples) == (devices, ROWS // CHUNK, ROWS)
        streamed[devices] = _mapper(fitted)
    monkeypatch.setattr(t, "device_memory_limit_bytes", lambda: None)
    with use_mesh(make_mesh(devices=jax.devices()[:4])):
        in_core = _mapper(t.build_pipeline(config, train).fit())
    for name in ("weights", "intercept", "feature_mean"):
        want = np.asarray(getattr(in_core, name))
        scale = np.abs(want).max()
        for devices, mapper in streamed.items():
            error = np.abs(np.asarray(getattr(mapper, name)) - want).max() / scale
            assert error < WEIGHTS_BOUND, (name, devices, error)
        across = np.abs(np.asarray(getattr(streamed[4], name)) - np.asarray(getattr(streamed[1], name))).max()
        assert across / scale < WEIGHTS_BOUND, (name, across / scale)
