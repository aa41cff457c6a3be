"""Checkpoint/restore of fitted pipeline state: digest stability, torn-file
tolerance, in-process resume, and the killed-then-resumed subprocess run
(the lineage-recovery replacement, ISSUE acceptance criterion)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.reliability import (
    CheckpointStore,
    enable_checkpointing,
    get_recovery_log,
    prefix_digest,
)
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.pipeline import Estimator, Transformer
from keystone_tpu.workflow.prefix import Prefix

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _Scale(Transformer):
    def __init__(self, s):
        self.s = s

    def apply(self, datum):
        return datum * self.s

    def apply_batch(self, ds):
        return ArrayDataset(np.asarray(ds.data) * self.s, ds.num_examples)


class _CountingEstimator(Estimator):
    fits = []

    def __init__(self, tag):
        self.tag = tag

    def fit(self, data):
        _CountingEstimator.fits.append(self.tag)
        return _Scale(float(np.mean(np.asarray(data.data))))


@pytest.fixture(autouse=True)
def _clear_counts():
    _CountingEstimator.fits = []
    yield


# ------------------------------------------------------------------ digests


def _prefix_for(est, arr):
    data_op = DatasetOperator(ArrayDataset(arr))
    return Prefix(((est, ((data_op, ()),))))


def test_prefix_digest_stable_across_fresh_objects():
    # Identity-hashed operators, content-equal state: equal digests —
    # the property that makes resume work in a NEW process.
    a = _prefix_for(_CountingEstimator("A"), np.arange(12.0))
    b = _prefix_for(_CountingEstimator("A"), np.arange(12.0))
    assert a.tree[0] is not b.tree[0]
    assert prefix_digest(a) == prefix_digest(b)


def test_prefix_digest_stable_for_set_attributes():
    # Set iteration order follows PYTHONHASHSEED; the digest must not.
    class SetEst(_CountingEstimator):
        def __init__(self, names):
            self.names = names

    arr = np.arange(4.0)
    a = _prefix_for(SetEst({"zebra", "apple", "mango"}), arr)
    b = _prefix_for(SetEst({"mango", "zebra", "apple"}), arr)
    c = _prefix_for(SetEst({"zebra", "apple"}), arr)
    assert prefix_digest(a) == prefix_digest(b)
    assert prefix_digest(a) != prefix_digest(c)


def test_prefix_digest_sensitive_to_config_and_data():
    base = _prefix_for(_CountingEstimator("A"), np.arange(12.0))
    other_cfg = _prefix_for(_CountingEstimator("B"), np.arange(12.0))
    other_data = _prefix_for(_CountingEstimator("A"), np.arange(12.0) + 1)
    assert prefix_digest(base) != prefix_digest(other_cfg)
    assert prefix_digest(base) != prefix_digest(other_data)


# -------------------------------------------------------------------- store


def test_store_round_trip_and_torn_file(tmp_path):
    store = CheckpointStore(str(tmp_path))
    prefix = _prefix_for(_CountingEstimator("A"), np.arange(4.0))
    model = _Scale(3.5)
    assert store.save(prefix, model)
    restored = store.lookup(prefix)
    assert isinstance(restored, _Scale) and restored.s == 3.5
    # torn entry (killed mid-write after rename... simulated corruption):
    # must read as a miss, not crash the resume
    entry = os.path.join(str(tmp_path), prefix_digest(prefix) + ".pkl")
    with open(entry, "wb") as f:
        f.write(b"\x80truncated garbage")
    from keystone_tpu.reliability.checkpoint import _MISS

    assert store.lookup(prefix) is _MISS
    assert store.stats()["writes"] == 1


def test_unpicklable_fit_is_skipped_not_fatal(tmp_path):
    store = CheckpointStore(str(tmp_path))
    prefix = _prefix_for(_CountingEstimator("A"), np.arange(4.0))
    assert store.save(prefix, lambda x: x) is False  # lambdas don't pickle
    assert os.listdir(str(tmp_path)) == []


# ------------------------------------------------------------------- resume


def test_in_process_resume_skips_refit(tmp_path):
    ck = str(tmp_path / "ck")
    enable_checkpointing(ck)
    data = ArrayDataset(np.arange(8.0).reshape(8, 1))
    out1 = _CountingEstimator("A").with_data(data).apply(data).get()
    assert _CountingEstimator.fits == ["A"]

    # "new process": fresh env, fresh operator objects, same data content
    PipelineEnv.reset()
    enable_checkpointing(ck)
    data2 = ArrayDataset(np.arange(8.0).reshape(8, 1))
    out2 = _CountingEstimator("A").with_data(data2).apply(data2).get()
    assert _CountingEstimator.fits == ["A"]  # NOT refit
    assert get_recovery_log().summary()["checkpoint_hits"] == 1
    np.testing.assert_allclose(np.asarray(out1.data), np.asarray(out2.data))


def test_changed_estimator_config_refits(tmp_path):
    ck = str(tmp_path / "ck")
    enable_checkpointing(ck)
    data = ArrayDataset(np.arange(8.0).reshape(8, 1))
    _CountingEstimator("A").with_data(data).apply(data).get()
    PipelineEnv.reset()
    enable_checkpointing(ck)
    _CountingEstimator("B").with_data(data).apply(data).get()
    assert _CountingEstimator.fits == ["A", "B"]  # different digest → refit


_RESUME_SCRIPT = """
import os, sys
import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.workflow.pipeline import Estimator, Transformer
from keystone_tpu import reliability as R

ckdir, countfile, mode = sys.argv[1], sys.argv[2], sys.argv[3]


class Scale(Transformer):
    def __init__(self, s):
        self.s = s

    def apply(self, d):
        return d * self.s

    def apply_batch(self, ds):
        return ArrayDataset(np.asarray(ds.data) * self.s, ds.num_examples)


class CountingEst(Estimator):
    def __init__(self, tag):
        self.tag = tag

    def fit(self, data):
        with open(countfile, "a") as f:
            f.write(self.tag + "\\n")
        return Scale(float(np.mean(np.asarray(data.data))) + 1.0)


R.enable_checkpointing(ckdir)
data = ArrayDataset(np.arange(8.0).reshape(8, 1))

# stage 1: fit estimator A (write-through to the checkpoint)
out_a = CountingEst("A").with_data(data).apply(data).get()
if mode == "kill":
    os._exit(137)  # simulated preemption AFTER A's fit, before the run ends

# stage 2 (resumed run only): A again — must restore, not refit — plus B
out_a2 = CountingEst("A").with_data(data).apply(data).get()
out_b = CountingEst("B").with_data(data).apply(data).get()
hits = R.get_recovery_log().summary()["checkpoint_hits"]
print("RESUME_OK hits=%d" % hits)
assert hits >= 1, hits
"""


def test_killed_then_resumed_run_reuses_fitted_prefixes(tmp_path):
    """ISSUE acceptance: kill a run after an estimator fit; the resumed
    run (fresh process) must reuse the checkpointed fit without refitting."""
    ck = str(tmp_path / "ck")
    countfile = str(tmp_path / "fits.txt")
    script = str(tmp_path / "resume_script.py")
    with open(script, "w") as f:
        f.write(_RESUME_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))

    run1 = subprocess.run(
        [sys.executable, script, ck, countfile, "kill"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert run1.returncode == 137, run1.stderr[-2000:]
    assert open(countfile).read().splitlines() == ["A"]

    run2 = subprocess.run(
        [sys.executable, script, ck, countfile, "resume"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert run2.returncode == 0, (run2.stdout + run2.stderr)[-2000:]
    assert "RESUME_OK" in run2.stdout
    # A fit exactly once ACROSS BOTH PROCESSES; B fit once in run 2.
    assert sorted(open(countfile).read().splitlines()) == ["A", "B"]


def test_token_memo_hashes_shared_values_once(monkeypatch):
    """Digesting N prefixes of one plan re-tokenizes the same training
    array N times; inside token_memo() the content hash is paid once and
    the digests are unchanged."""
    import numpy as np

    from keystone_tpu.reliability import checkpoint as cp

    arr = np.arange(64, dtype=np.float32)
    cold = cp._value_token(arr)

    calls = {"n": 0}
    real_sha1 = cp.hashlib.sha1

    def counting_sha1(*a, **kw):
        calls["n"] += 1
        return real_sha1(*a, **kw)

    monkeypatch.setattr(cp.hashlib, "sha1", counting_sha1)
    with cp.token_memo():
        tokens = [cp._value_token(arr) for _ in range(5)]
    assert calls["n"] == 1
    assert all(t == cold for t in tokens)
    # the memo dies with the scope: a later call re-hashes
    assert cp._value_token(arr) == cold
    assert calls["n"] == 2
