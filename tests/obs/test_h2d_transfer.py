"""`h2d:transfer`: the span from an upload's enqueue to its arrival, held
by the process's one watcher thread while somebody is recording
(`obs/device.py::watch_transfer`), and `spans.recording()`, the one
question that decides whether the watcher exists at all."""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from keystone_tpu.obs import device, spans

WATCHED = (1024, 1024)  # float32: 4 MiB, over `_WATCH_MIN_BYTES`
THREAD = "keystone-h2d-watcher"


def _rows(seed=0, shape=WATCHED):
    return np.random.default_rng(seed).random(shape, dtype=np.float32)


def _transfers(session, count, timeout=10.0):
    """The session's `h2d:transfer` spans once `count` of them are
    finished: the watcher closes a span on its own thread, after the
    caller has gone on."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        found = session.find("h2d:transfer")
        if len(found) >= count:
            return found
        time.sleep(0.005)
    raise AssertionError(f"{len(session.find('h2d:transfer'))} of {count} transfer spans after {timeout} s")


def _watcher_threads():
    return [t for t in threading.enumerate() if t.name == THREAD]


# ----------------------------------------------------------- recording()


def test_recording_is_false_with_neither_a_session_nor_a_trace():
    assert spans.active_session() is None
    assert spans.recording() is False


def test_recording_is_true_under_a_session_and_false_after_it():
    with spans.tracing_session():
        assert spans.recording() is True
    assert spans.recording() is False


def test_recording_is_true_inside_a_profiler_trace_on_the_cpu(tmp_path):
    assert spans.recording() is False
    with jax.profiler.trace(str(tmp_path)):
        assert spans.active_session() is None and spans.recording() is True
    assert spans.recording() is False


def test_recording_asks_the_class_the_bridge_resolved_and_survives_a_jax_without_a_profiler(monkeypatch):
    asked = []

    class Annotation:
        @staticmethod
        def is_enabled():
            asked.append(1)
            return True

    monkeypatch.setattr(spans, "_annotation_cls", Annotation)
    assert spans.recording() is True and asked == [1]
    monkeypatch.setattr(spans, "_annotation_cls", False)  # jax is there, its profiler is not
    assert spans.recording() is False


# ------------------------------------------------- with nobody recording


def test_with_nobody_recording_an_upload_queues_nothing_and_asks_one_question(monkeypatch):
    asked, handed = [], []
    monkeypatch.setattr(spans, "recording", lambda: asked.append(1) or False)
    monkeypatch.setattr(device._WATCHER, "watch", lambda *a: handed.append(a))
    out = device.to_device({"x": _rows(), "y": _rows(1)}, site="test")
    assert isinstance(out["x"], jax.Array) and asked == [1] and handed == []
    assert device._WATCHER._queue.empty()


def test_a_fresh_process_that_never_records_starts_no_watcher_thread():
    code = """
import threading
import numpy as np
from keystone_tpu.obs import device, spans
x = np.ones((1024, 1024), np.float32)
for _ in range(100):
    device.to_device(x, site="test")
assert spans.recording() is False
assert device._WATCHER._thread is None and device._WATCHER._queue.empty()
assert [t.name for t in threading.enumerate()].count("keystone-h2d-watcher") == 0
print("no watcher")
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("no watcher")


def test_the_watcher_is_a_daemon_and_does_not_keep_its_process_alive():
    code = """
import threading, time
import numpy as np
from keystone_tpu.obs import device, spans
with spans.tracing_session() as session:
    device.to_device(np.ones((1024, 1024), np.float32), site="test")
    while not session.find("h2d:transfer"):
        time.sleep(0.005)
(watcher,) = [t for t in threading.enumerate() if t.name == "keystone-h2d-watcher"]
assert watcher.daemon and watcher.is_alive()
print("leaving")
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("leaving")


# ------------------------------------------------------- under a session


def test_one_transfer_span_a_watched_upload_with_its_site_bytes_and_parent():
    x = _rows()
    with spans.tracing_session() as session:
        with spans.span("request") as request:
            out = device.to_device(x, site="Consumer", consumers=2)
        (transfer,) = _transfers(session, 1)
        ready_by = time.perf_counter()
        jax.block_until_ready(out)
        assert time.perf_counter() - ready_by < 0.5  # it had arrived: the span ended no earlier
    (h2d,) = [s for s in session.spans() if s.name == "h2d"]
    assert h2d.attributes == {"site": "Consumer", "bytes": x.nbytes, "consumers": 2}
    assert transfer.attributes == {"site": "Consumer", "bytes": x.nbytes}
    assert transfer.parent_id == h2d.parent_id == request.span_id
    assert transfer.trace_id == h2d.trace_id
    assert transfer.start_s >= h2d.end_s and transfer.end_s >= transfer.start_s
    assert transfer.thread_name == THREAD and transfer.thread_id != h2d.thread_id
    assert transfer.status == "ok" and np.array_equal(np.asarray(out), x)


def test_an_upload_outside_every_span_parents_its_transfer_at_the_sessions_root_as_its_h2d_span():
    with spans.tracing_session() as session:
        device.to_device(_rows(), site="test")
        (transfer,) = _transfers(session, 1)
    (h2d,) = [s for s in session.spans() if s.name == "h2d"]
    assert transfer.parent_id is None and h2d.parent_id is None
    assert transfer.trace_id == h2d.trace_id == session.trace_id


def test_two_uploads_give_two_spans_in_enqueue_order_that_do_not_overlap():
    with spans.tracing_session() as session:
        device.to_device(_rows(0), site="first")
        device.to_device([_rows(1), _rows(2)], site="second")  # two leaves, one upload
        first, second = _transfers(session, 2)
    assert [first.attributes["site"], second.attributes["site"]] == ["first", "second"]
    assert second.attributes["bytes"] == 2 * first.attributes["bytes"]
    assert first.end_s <= second.start_s
    assert len(_watcher_threads()) == 1


def test_a_deleted_array_closes_its_span_as_deleted_and_the_next_upload_is_still_watched(monkeypatch):
    real = device._TransferWatcher._await
    gate = threading.Event()

    def held_back(arrays, site, nbytes, parent):
        gate.wait(10)  # the watcher reaches the entry only after the caller deleted it
        return real(arrays, site, nbytes, parent)

    monkeypatch.setattr(device._TransferWatcher, "_await", staticmethod(held_back))
    with spans.tracing_session() as session:
        gone = device.to_device(_rows(0), site="gone")
        jax.block_until_ready(gone)
        gone.delete()
        kept = device.to_device(_rows(1), site="kept")
        gate.set()
        first, second = _transfers(session, 2)
    assert first.attributes == {"site": "gone", "bytes": _rows().nbytes, "ended": "deleted"}
    assert first.status == "ok"
    assert second.attributes == {"site": "kept", "bytes": _rows().nbytes}
    assert np.array_equal(np.asarray(kept), _rows(1))
    assert _watcher_threads()[0].is_alive()


def test_an_exception_in_the_watcher_reaches_nobody_and_the_thread_lives_on(monkeypatch, caplog):
    real = device._TransferWatcher._await

    def fails_once(arrays, site, nbytes, parent):
        if site == "fails":
            raise ValueError("anything at all")
        return real(arrays, site, nbytes, parent)

    monkeypatch.setattr(device._TransferWatcher, "_await", staticmethod(fails_once))
    with spans.tracing_session() as session, caplog.at_level("WARNING", logger=device.__name__):
        out = device.to_device(_rows(0), site="fails")  # returns as ever
        device.to_device(_rows(1), site="after")
        (after,) = _transfers(session, 1)
    assert isinstance(out, jax.Array) and after.attributes["site"] == "after"
    assert any("h2d:transfer watcher" in r.getMessage() and r.exc_info for r in caplog.records)
    assert _watcher_threads()[0].is_alive()


def test_the_watcher_lets_go_of_an_upload_at_its_arrival():
    import weakref

    with spans.tracing_session() as session:
        out = device.to_device(_rows(), site="test")
        _transfers(session, 1)
        alive = weakref.ref(out)
        del out
        deadline = time.monotonic() + 5
        while alive() is not None and time.monotonic() < deadline:
            time.sleep(0.005)  # the watcher's frame unwinds just after the span closes
        assert alive() is None
    assert device._WATCHER._queue.empty()


def test_an_upload_under_the_size_constant_is_not_watched():
    assert device._WATCH_MIN_BYTES == 3 << 20
    small = np.zeros(((3 << 20) // 4 - 1,), np.float32)  # four bytes short
    with spans.tracing_session() as session:
        device.to_device(small, site="small")
        device.to_device(np.zeros(((3 << 20) // 4,), np.float32), site="just-enough")
        (transfer,) = _transfers(session, 1)
        time.sleep(0.05)
        assert [s.attributes["site"] for s in session.find("h2d:transfer")] == ["just-enough"]
    assert [s.attributes["site"] for s in session.spans() if s.name == "h2d"] == ["small", "just-enough"]
    assert transfer.attributes["bytes"] == 3 << 20


def test_a_narrow_batch_is_watched_as_the_flat_array_that_went_up(monkeypatch):
    handed = []
    monkeypatch.setattr(device._WATCHER, "watch", lambda arrays, *rest: handed.append((arrays, rest)))
    images = _rows(shape=(16, 128, 128, 3))
    with spans.tracing_session():
        out = device.to_device(images, site="images")
    ((arrays, (site, nbytes, _parent)),) = handed
    assert out.shape == images.shape and [a.shape for a in arrays] == [(16, 128 * 128 * 3)]
    assert (site, nbytes) == ("images", images.nbytes)


def test_a_profiler_trace_alone_shows_the_transfer_beside_its_h2d_span(tmp_path):
    """No session: the watcher runs on `recording()`, and the span is the
    bridge's `ks:h2d:transfer` annotation on the watcher's own line."""
    import glob
    import os

    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    assert spans.active_session() is None
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = device.to_device(_rows(), site="traced")
        jax.block_until_ready(out)
        deadline = time.monotonic() + 5
        while not device._WATCHER._queue.empty() and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)  # the span closes just after the queue empties
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ks:h2d"):
                        events[e.name] = (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
    assert sorted(events) == ["ks:h2d", "ks:h2d:transfer"]
    assert events["ks:h2d:transfer"][0] >= events["ks:h2d"][1]
    assert events["ks:h2d:transfer"][2] == {"site": "traced", "bytes": _rows().nbytes}


# ------------------------------------------------------ the streamed fold


def test_the_streamed_fold_hands_each_chunk_over_as_chunkstream(monkeypatch):
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.workflow import BatchTransformer
    from keystone_tpu.workflow.streaming import last_stream_report

    class Scale(BatchTransformer):
        def apply_arrays(self, x):
            return x * 2.0

    chunk, chunks = 64, 4
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(chunk))
    monkeypatch.setattr(device, "_WATCH_MIN_BYTES", 0)  # a tiny chunk is a few KiB
    rng = np.random.default_rng(0)
    x = rng.normal(size=(chunks * chunk, 32)).astype(np.float32)
    y = rng.normal(size=(chunks * chunk, 4)).astype(np.float32)
    pipeline = Scale().to_pipeline().then_label_estimator(
        BlockLeastSquaresEstimator(16, num_iter=2, reg=1e-3), ArrayDataset(x), ArrayDataset(y)
    )
    with spans.tracing_session() as session:
        pipeline.apply(ArrayDataset(x)).get()
        report = last_stream_report()
        assert report is not None and report.chunks == chunks
        transfers = [s for s in _transfers(session, chunks) if s.attributes["site"] == "ChunkStream"]
    assert len(transfers) == chunks
    assert sum(s.attributes["bytes"] for s in transfers) == report.bytes_transferred
    folds = {s.span_id for s in session.spans() if s.name.startswith("stream:")}
    assert all(s.parent_id in folds for s in transfers)  # caused by the fold, not a root
    for earlier, later in zip(transfers, transfers[1:]):
        assert earlier.end_s <= later.start_s


# ------------------------------------------------- the static lock model


def test_the_lock_model_knows_the_watchers_lock_its_thread_and_no_order_edge():
    """The watcher's one lock guards its thread's start and is held over
    nothing that takes another: the model (lint/lockmodel.py) sees the
    lock and the daemon spawn, and no acquired-while-holding edge."""
    import os

    import keystone_tpu
    from keystone_tpu.lint.concurrency import analyze_model
    from keystone_tpu.lint.lockmodel import build_model

    model = build_model([os.path.dirname(keystone_tpu.__file__)])
    lock = "obs.device._TransferWatcher._lock"
    assert lock in model.locks and model.locks[lock].kind == "lock"
    assert not [edge for edge in model.edge_pairs() if lock in edge]
    assert [t.func for t in model.threads if t.path.endswith(os.path.join("obs", "device.py"))] == [
        "obs.device._TransferWatcher.watch"
    ]
    assert not [f for f in analyze_model(model) if f.path.endswith(os.path.join("obs", "device.py"))]
