"""Durable elastic fits (docs/RELIABILITY.md "Durable fits"): mid-stream
checkpoints, crash-resume parity, KV306 stale-entry refusal, shard-loss
elasticity, and the no-leaked-threads contract of an abandoned fold.

The cross-PROCESS face (a real SIGKILL + fresh-process resume) is
scripts/elastic_smoke.sh; these tests pin the same machinery in-process:
a fault aborts the fold, ``PipelineEnv.reset()`` stands in for the fresh
process, and the re-planned pipeline must find, validate, and seed from
the persisted cursor.
"""

import threading

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.parallel.partitioner import partition_disabled
from keystone_tpu.reliability import enable_checkpointing, faultinject
from keystone_tpu.reliability.durable import (
    load_resume_entry,
    resume_key,
    stream_ckpt_chunks,
)
from keystone_tpu.reliability.faultinject import FaultSpec
from keystone_tpu.reliability.recovery import get_recovery_log
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.pipeline import BatchTransformer
from keystone_tpu.workflow.streaming import last_stream_report
from keystone_tpu.workflow.verify import VerificationError, verify_stream_resume

N, D, K, CHUNK = 512, 8, 2, 64  # 8 chunks; divisible by the 8-device mesh
rng = np.random.default_rng(7)
X = rng.normal(size=(N, D)).astype(np.float32)
W = rng.normal(size=(D, K)).astype(np.float32)
Y = (X @ W + 0.01 * rng.normal(size=(N, K))).astype(np.float32)
PROBE = rng.normal(size=(32, D)).astype(np.float32)


class Scale(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    def apply_arrays(self, a):
        return a * self.c


def build(x=X, y=Y):
    return Scale(2.0).to_pipeline().then_label_estimator(
        LinearMapEstimator(reg=1e-3), ArrayDataset(x), ArrayDataset(y)
    )


def preds(fitted):
    return np.asarray(fitted.apply_batch(ArrayDataset(PROBE)).data)


def rel_err(a, b):
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture()
def chunked(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "2")


@pytest.fixture()
def reference(chunked):
    """Uninterrupted single-device predictions (no store attached)."""
    PipelineEnv.reset()
    with partition_disabled():
        out = preds(build().fit())
    PipelineEnv.reset()
    return out


def _crash_at(store_dir, call, spec_kind="transient"):
    """Run a durable fit that dies at streaming.chunk call ``call``."""
    PipelineEnv.reset()
    enable_checkpointing(str(store_dir))
    with pytest.raises(ConnectionError):
        with faultinject.injected(
            FaultSpec(match="streaming.chunk", kind=spec_kind, calls=(call,))
        ):
            build().fit()


# ---------------------------------------------------- the chunk boundary


class _FakeStore:
    def __init__(self, events):
        self.events = events

    def save(self, node, entry, digest):
        self.events.append(("commit", entry.cursor.chunk_index))
        return True


class _FakeLease:
    """Yields at the boundaries named; every consultation is recorded."""

    def __init__(self, events, yield_at):
        self.events, self.yield_at, self.at = events, yield_at, None

    def should_yield(self):
        self.events.append(("asked", self.at))
        return self.at in self.yield_at

    def mark_preempted(self, chunk_index):
        self.events.append(("marked", chunk_index))


@pytest.mark.parametrize(
    "every,boundaries,suspend_before,yield_at,want",
    [
        # every 2 chunks, never before the first chunk
        (2, [0, 1, 2, 3, 4, 5, 6], None, None,
         [("commit", 12), ("commit", 14), ("commit", 16)]),
        # a boundary asked twice commits once
        (1, [0, 1, 1, 2, 2], None, None, [("commit", 11), ("commit", 12)]),
        # none after a shard loss suspended them, the restart at 0 included
        (1, [0, 1, 2, 0, 1, 2], 3, None, [("commit", 11), ("commit", 12)]),
        # checkpoints off: nothing, and no snapshot is built
        (0, [0, 1, 2, 3], None, None, []),
        # a yield commits whatever the cadence, BEFORE the lease is marked;
        # the lease is not asked before the first chunk
        (0, [0, 1, 2, 3], None, {0, 2},
         [("asked", 1), ("asked", 2), ("commit", 12), ("marked", 12), ("raised", 12)]),
        # a yield on a cadence boundary commits once
        (2, [0, 1, 2], None, {2},
         [("asked", 1), ("asked", 2), ("commit", 12), ("marked", 12), ("raised", 12)]),
        # a suspended plan still yields, without a cursor
        (1, [0, 1], 0, {1}, [("asked", 1), ("marked", 11), ("raised", 11)]),
        # a lease and no plan: the chunk index is the attempt's own
        (None, [0, 1], None, {1}, [("asked", 1), ("marked", 1), ("raised", 1)]),
    ],
)
def test_chunk_boundary_decision_table(every, boundaries, suspend_before, yield_at, want):
    """The one chunk-boundary call, on a fake store and a fake lease: who
    commits when (`DurableFold.at_boundary`), and the order commit, mark,
    raise (`streaming._chunk_boundary`)."""
    from keystone_tpu.reliability.durable import DurableFold
    from keystone_tpu.workflow.streaming import (
        FoldPreempted,
        StreamReport,
        _chunk_boundary,
    )

    events, snapshots = [], []
    durable = None
    if every is not None:
        durable = DurableFold(
            store=_FakeStore(events), key="k" * 40, kind="gram", estimator="e",
            ckpt_every=every, start_chunk=10,
            fingerprints=dict(
                dataset_digest="x", labels_digest="y", chain_digest="c",
                feature_width=D, feature_dtype="float32",
            ),
        )
    lease = _FakeLease(events, yield_at) if yield_at is not None else None
    report = StreamReport()

    def snapshot():
        snapshots.append(1)
        return dict(
            host_carry=(np.zeros(1, np.float32),), rows_consumed=0, chunk_rows=CHUNK
        )

    for step, dispatched in enumerate(boundaries):
        if step == suspend_before:
            durable.suspend()
        if lease is not None:
            lease.at = dispatched
        try:
            _chunk_boundary(durable, lease, dispatched, snapshot, report)
        except FoldPreempted as preempted:
            events.append(("raised", preempted.chunk_index))
            break
    assert events == want
    commits = [e for e in events if e[0] == "commit"]
    assert report.checkpoints == len(snapshots) == len(commits)
    raised = [at for kind, at in events if kind == "raised"]
    assert report.preempted_at_chunk == (raised[0] if raised else None)


# ----------------------------------------------------------- checkpoints


def test_mid_fit_checkpoints_commit_and_retire(tmp_path, chunked):
    PipelineEnv.reset()
    store = enable_checkpointing(str(tmp_path))
    fitted = build().fit()
    report = last_stream_report()
    # 8 chunks, K=2 → commits before chunks 3, 5, 7 (dispatched = 2/4/6).
    assert report.checkpoints == 3
    assert report.resumed_from_chunk is None
    kinds = [e.kind for e in get_recovery_log().events()]
    assert kinds.count("stream_checkpoint") == 3
    # A COMPLETED fit retires its resume entry — nothing to mis-resume.
    est = LinearMapEstimator(reg=1e-3)
    key = resume_key(est, (Scale(2.0),), N)
    assert load_resume_entry(store, key) is None
    assert preds(fitted).shape == (32, K)


def test_checkpoint_off_path_untouched(tmp_path, chunked, monkeypatch):
    # Explicit 0 disables even with a store attached: no durable plan,
    # no commits, no resume machinery — today's fold.
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "0")
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    build().fit()
    report = last_stream_report()
    assert report.checkpoints == 0 and report.resumed_from_chunk is None
    assert not get_recovery_log().events("stream_checkpoint")


def test_auto_arm_above_row_threshold(monkeypatch):
    monkeypatch.delenv("KEYSTONE_STREAM_CKPT_CHUNKS", raising=False)
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_AUTO_ROWS", "1000")
    assert stream_ckpt_chunks(999) == 0
    assert stream_ckpt_chunks(1000) == 32
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "5")
    assert stream_ckpt_chunks(10) == 5
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "0")
    assert stream_ckpt_chunks(10**9) == 0


# ---------------------------------------------------------- crash-resume


def test_crash_resume_parity_sharded(tmp_path, reference):
    _crash_at(tmp_path, call=5)
    assert last_stream_report().chunks == 4
    PipelineEnv.reset()  # the "fresh process"
    enable_checkpointing(str(tmp_path))
    fitted = build().fit()
    report = last_stream_report()
    assert report.resumed_from_chunk == 4
    assert report.reingested_chunks == 8 - 4 == report.chunks
    assert report.shards == 8
    assert rel_err(preds(fitted), reference) <= 1e-6
    kinds = {e.kind for e in get_recovery_log().events()}
    assert "stream_resume" in kinds


def test_crash_resume_parity_one_device_from_sharded_checkpoint(
    tmp_path, reference
):
    # The cursor snapshot is mesh-independent: a fit killed on the
    # 8-device mesh resumes on ONE device with exact parity.
    _crash_at(tmp_path, call=3)
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    with partition_disabled():
        fitted = build().fit()
    report = last_stream_report()
    assert report.resumed_from_chunk == 2 and report.shards == 1
    assert rel_err(preds(fitted), reference) <= 1e-6


def test_stale_resume_refused_kv306_warn_mode(tmp_path, reference):
    _crash_at(tmp_path, call=5)
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    # Same shapes, same key — different dataset CONTENT.
    drifted_x = X + np.float32(0.25)
    fitted = build(x=drifted_x).fit()
    report = last_stream_report()
    assert report.resumed_from_chunk is None  # refused → from scratch
    assert report.chunks == 8
    kinds = {e.kind for e in get_recovery_log().events()}
    assert "resume_refused" in kinds
    # The refused fit is the DRIFTED data's correct fit, not a blend.
    PipelineEnv.reset()
    with partition_disabled():
        clean = preds(build(x=drifted_x).fit())
    assert rel_err(preds(fitted), clean) <= 1e-6


def test_stale_resume_raises_in_strict_mode_and_preserves_entry(
    tmp_path, reference, monkeypatch
):
    _crash_at(tmp_path, call=5)
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    monkeypatch.setenv("KEYSTONE_VERIFY", "strict")
    with pytest.raises(VerificationError, match="KV306"):
        build(x=X + np.float32(0.25)).fit()
    # Strict refuses the FIT, not the entry: the mismatch may have been
    # this run's mistake, and the legitimate job's checkpoint work must
    # survive it — the original fit still resumes.
    monkeypatch.setenv("KEYSTONE_VERIFY", "warn")
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    fitted = build().fit()
    assert last_stream_report().resumed_from_chunk == 4
    assert rel_err(preds(fitted), reference) <= 1e-6


def test_geometry_drift_discards_entry(tmp_path, chunked, monkeypatch):
    _crash_at(tmp_path, call=5)
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK * 2))
    fitted = build().fit()
    report = last_stream_report()
    assert report.resumed_from_chunk is None
    kinds = {e.kind for e in get_recovery_log().events()}
    assert "resume_discard" in kinds
    assert preds(fitted).shape == (32, K)


def test_verify_stream_resume_flags_each_field():
    from keystone_tpu.reliability.durable import StreamCursor

    cursor = StreamCursor(
        chunk_index=4,
        rows_consumed=256,
        chunk_rows=64,
        dataset_digest="aaa",
        labels_digest="bbb",
        chain_digest="ccc",
        feature_width=8,
        feature_dtype="float32",
    )
    same = {
        "dataset_digest": "aaa",
        "labels_digest": "bbb",
        "chain_digest": "ccc",
        "feature_width": 8,
        "feature_dtype": "float32",
    }
    assert verify_stream_resume(cursor, same).ok
    for field, bad in (
        ("dataset_digest", "zzz"),
        ("labels_digest", "zzz"),
        ("chain_digest", "zzz"),
        ("feature_width", 16),
        ("feature_dtype", "float64"),
    ):
        report = verify_stream_resume(cursor, {**same, field: bad})
        assert not report.ok
        (diag,) = report.errors()
        assert diag.code == "KV306" and diag.details["field"] == field


# ------------------------------------------------------------ shard loss


def test_shard_loss_mid_stream_completes_on_survivors(reference):
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(3,))
    ):
        fitted = build().fit()
    report = last_stream_report()
    assert report.shard_losses == 1
    assert report.shards == 7  # continued on the shrunken mesh
    assert report.reingested_chunks == 2  # the lost slices of chunks 1-2
    assert rel_err(preds(fitted), reference) <= 1e-5
    kinds = {e.kind for e in get_recovery_log().events()}
    assert {"shard_loss", "shard_resume"} <= kinds


def test_seed_bearing_shard_zero_loss_recovers_exactly(
    reference, monkeypatch
):
    # Shard 0 carries the fold's seed block: its loss must re-add the
    # host-side seed, not silently drop it.
    monkeypatch.setenv("KEYSTONE_SHARD_LOSS_INDEX", "0")
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(4,))
    ):
        fitted = build().fit()
    assert last_stream_report().shard_losses == 1
    assert rel_err(preds(fitted), reference) <= 1e-5


def test_loss_before_first_chunk_keeps_compile_accounting_exact(reference):
    # A loss at the very first dispatch re-plans before anything folded:
    # the shrunken-mesh attempt's first chunk is the fold's first chunk,
    # and its compiles must not double-count as steady-state.
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(1,))
    ):
        fitted = build().fit()
    report = last_stream_report()
    assert report.shard_losses == 1 and report.reingested_chunks == 0
    assert report.compiles_steady_state == 0
    assert rel_err(preds(fitted), reference) <= 1e-5


def test_dataset_fingerprint_bounded_and_sensitive(monkeypatch):
    from keystone_tpu.reliability import durable

    big = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    ds = ArrayDataset(big.copy())
    base = durable.dataset_fingerprint(ds)
    assert base == durable.dataset_fingerprint(ArrayDataset(big.copy()))
    # Force the sampled path: every row lands in the sample at this size.
    monkeypatch.setattr(durable, "FULL_HASH_MAX_BYTES", 16)
    sampled = durable.dataset_fingerprint(ArrayDataset(big.copy()))
    assert sampled != base  # different scheme, still deterministic
    assert sampled == durable.dataset_fingerprint(ArrayDataset(big.copy()))
    drifted = big.copy()
    drifted[0, 0] += 1.0  # first row is always sampled
    assert durable.dataset_fingerprint(ArrayDataset(drifted)) != sampled
    # The sample is bounded: a huge leaf hashes ≤ FINGERPRINT_SAMPLE_ROWS
    # rows, not the matrix (shape/length changes still always differ).
    assert (
        durable.dataset_fingerprint(ArrayDataset(big[:32].copy())) != sampled
    )


def test_two_sequential_losses_still_converge(reference):
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(2, 6))
    ):
        fitted = build().fit()
    report = last_stream_report()
    assert report.shard_losses == 2 and report.shards == 6
    assert rel_err(preds(fitted), reference) <= 1e-5


# --------------------------------------------------------- thread hygiene


def _prefetch_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and "prefetch" in t.name
    ]


def test_faulted_fold_joins_prefetch_workers(tmp_path, chunked):
    # An abandoned fold (fault mid-stream, resume-abort, shard loss —
    # any exit) must join its PrefetchQueue workers before re-raising:
    # leaked decode threads outlive the fit and pin chunk buffers.
    assert not _prefetch_threads()
    _crash_at(tmp_path, call=3)
    assert not _prefetch_threads()
    # The shard-loss recovery path swaps queues mid-fold: every
    # abandoned attempt's workers must be joined too.
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(2,))
    ):
        build().fit()
    assert not _prefetch_threads()
