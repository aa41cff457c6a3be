"""The symmetric Gram product (`linalg.gram_sym`) through the streaming
engine: a streamed fit whose step computes the upper block triangle and
mirrors it leaves the carry and the weights of the full product, the
carry symmetric to the bit after every chunk (what a durable resume and
the refit export read), on one device and over a 1-D mesh; and the fold
says how many panels it ran with."""

import contextlib

import numpy as np
import pytest

import jax

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import metrics, names, spans
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.parallel import linalg
from keystone_tpu.parallel.mesh import make_mesh, use_mesh
from keystone_tpu.parallel.partitioner import partition_disabled
from keystone_tpu.workflow import BatchTransformer, streaming
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.streaming import last_stream_report

CHUNK, D, K = 64, 24, 3
PANEL = 6  # the width rule cut down to the tests' widths: 24 columns, 4 panels
ROWS = 3 * CHUNK + 17  # three whole chunks and a tail padded with zero rows


class Scale(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    def apply_arrays(self, a):
        return a * self.c


@pytest.fixture(autouse=True)
def _chunked(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))


@pytest.fixture
def data():
    rng = np.random.default_rng(31)
    x = rng.normal(size=(ROWS, D)).astype(np.float32)
    w = rng.normal(size=(D, K)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(size=(ROWS, K))).astype(np.float32)
    return x, y


@contextlib.contextmanager
def _panel_width(monkeypatch, width):
    """The width rule at ``width`` columns a panel, with no fused step
    traced under another rule to be found in the engine's cache."""
    with monkeypatch.context() as patch:
        patch.setattr(linalg, "_GRAM_SYM_PANEL", width)
        patch.setattr(streaming, "_STEP_JIT_CACHE", None)
        yield


def _devices(count):
    if count == 1:
        return partition_disabled()
    return use_mesh(make_mesh(devices=jax.devices()[:count]))


def _streamed_fit(x, y):
    """(Gram carry on the host, predictions on the first rows) of one
    streamed fit of ``Scale(2) -> BlockLeastSquares``."""
    PipelineEnv.reset()
    est = BlockLeastSquaresEstimator(8, num_iter=2, reg=1e-3)
    fitted = Scale(2.0).to_pipeline().then_label_estimator(
        est, ArrayDataset(x), ArrayDataset(y)
    ).fit()
    gram = np.asarray(est.export_stream_state().carry[0])
    return gram, np.asarray(fitted.apply_batch(ArrayDataset(x[:32])).data)


@pytest.mark.parametrize("devices", [1, 4])
def test_streamed_fit_with_panels_equals_the_full_product(data, monkeypatch, devices):
    x, y = data
    # one fit per prefix of chunks (a single chunk is not streamed): the
    # carry as the fold leaves it after its second, third and last chunk
    for rows in (2 * CHUNK, 3 * CHUNK, ROWS):
        with _devices(devices):
            with _panel_width(monkeypatch, PANEL):
                assert linalg.gram_panels(D) == 4
                gram, preds = _streamed_fit(x[:rows], y[:rows])
                assert last_stream_report().shards == devices
                assert last_stream_report().chunks == -(-rows // CHUNK)
            with _panel_width(monkeypatch, D + 1):  # the single full product
                assert linalg.gram_panels(D) == 1
                gram_full, preds_full = _streamed_fit(x[:rows], y[:rows])
        assert np.array_equal(gram, gram.T)
        feats = 2.0 * x[:rows].astype(np.float64)
        scale = np.abs(feats.T @ feats).max()
        assert np.abs(gram - gram_full).max() <= 4e-7 * scale
        assert np.abs(gram - feats.T @ feats).max() <= 2e-6 * scale
        np.testing.assert_allclose(preds, preds_full, rtol=0, atol=2e-5 * np.abs(preds_full).max())


def test_the_carry_is_symmetric_to_the_bit_after_every_chunk(data, monkeypatch):
    """The engine's fused step, chunk by chunk, the tail's pad rows zero."""
    x, y = data
    with _panel_width(monkeypatch, PANEL):
        step, _ = streaming._shared_step_jit((Scale(2.0),), linalg.gram_stream_step)
        carry = linalg.gram_stream_init(D, K)
        for start in range(0, ROWS, CHUNK):
            rows = min(CHUNK, ROWS - start)
            pad = ((0, CHUNK - rows), (0, 0))
            mask = np.pad(np.ones((rows, 1), np.float32), pad)
            carry, _ = step(
                carry, np.pad(x[start:start + rows], pad),
                np.pad(y[start:start + rows], pad), mask,
            )
            gram = np.asarray(carry[0])
            assert np.array_equal(gram, gram.T)
            feats = 2.0 * x[:start + rows].astype(np.float64)
            assert np.abs(gram - feats.T @ feats).max() <= 2e-6 * np.abs(gram).max()


@pytest.mark.parametrize("panel,panels", [(PANEL, "4"), (D + 1, "1")])
def test_the_fold_says_how_many_panels_its_gram_has(data, monkeypatch, panel, panels):
    """`stream:fold` carries `gram_panels`, and the counter takes one
    count a fold under the same label ("1" where the helper fell back)."""
    x, y = data
    registry = metrics.get_registry()

    def counted():
        metric = registry.get(names.GRAM_SYMMETRIC)
        return metric.value(panels=panels) if metric else 0.0

    before = counted()
    with _panel_width(monkeypatch, panel):
        with spans.tracing_session("t", sync_timings=False) as session:
            _streamed_fit(x, y)
    (fold,) = session.find("stream:fold")
    assert fold.attributes["gram_panels"] == panels
    assert counted() - before == 1
