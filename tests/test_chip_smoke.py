"""chip_smoke.py on the CPU: the `--tiny` rehearsal drives the same code
path the chip run does (streamed fit, shipped fit, the real `serve` CLI,
the block-sparse kernel in interpret mode) on the virtual 8-device mesh;
without `--tiny` a CPU backend is refused before any work; a phase that
raises fails the run. The pass itself is only ever recorded on the chip
(CHANGES.md)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env():
    # conftest already put JAX_PLATFORMS=cpu and the 8 virtual devices
    # into os.environ; the smoke asserts where the cache resolves, so a
    # test-isolation knob must not leak in.
    env = dict(os.environ)
    env.pop("KEYSTONE_COMPILATION_CACHE", None)
    return env


def _run(args, timeout=240):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=timeout,
    )


def test_tiny_rehearsal_runs_every_phase_and_is_never_a_pass():
    proc = _run([SMOKE, "--tiny"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is False and verdict["rehearsal"] is True
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    summary = json.loads(
        next(ln for ln in lines if ln.startswith("CHIP_SMOKE:"))[len("CHIP_SMOKE:"):]
    )
    assert set(summary["smoke_wall_s"]) == {"stream", "shipped", "serve", "kernel"}
    assert summary["stream"]["chunks"] == 16 and summary["stream"]["shards"] == 8
    assert summary["stream"]["compiles_steady_state"] == 0
    assert summary["serve"]["served"] == 64
    assert summary["serve"]["xla_compiles_since_warmup"] == 0
    assert summary["kernel"]["compiled"] is False  # interpret mode on CPU
    assert summary["cache_entries"] > 0


def test_cpu_backend_without_tiny_is_refused_before_any_work():
    proc = _run([SMOKE], timeout=120)
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and "CHIP_SMOKE:" not in proc.stdout


def test_a_phase_that_raises_fails_the_run():
    driver = (
        "import sys, chip_smoke\n"
        "from keystone_tpu import reliability\n"
        "spec = reliability.FaultSpec(\n"
        "    match='BlockLeastSquaresEstimator.solve', kind='oom', first_n=1)\n"
        "with reliability.injected(spec):\n"
        "    sys.exit(chip_smoke.main(['--tiny']))\n"
    )
    proc = _run(["-c", driver])
    assert proc.returncode not in (0, None)
    assert "injected OOM" in proc.stderr
    assert '"ok"' not in proc.stdout and "CHIP_SMOKE:" not in proc.stdout
