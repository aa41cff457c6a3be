"""Each cell's loop, end to end on the CPU at a tiny size: the same
`run_cell` the command calls, reached through its arguments. Every tiny
cell runs once untraced and once traced; the tests read those results."""

import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench_paths import ROOT

from benchmark.harness import compare
from benchmark.harness.runner import NO_DEVICE_EXIT, Run, Sample, run_cell

TINY_CELLS = ["timit-tiny.fit-incore", "timit-tiny.score-tiny", "cifar-tiny.fit-incore"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2**31 + 12345  # the driver's seeds are large: more than 32 signed bits hold


@pytest.fixture(scope="module")
def cache_in_a_temporary_directory(tmp_path_factory):
    """`run_cell` turns the program's persistent compile cache on: keep it
    out of the checkout, and put jax's settings back afterwards."""
    import jax

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    from jax.experimental.compilation_cache import compilation_cache

    before = {k: getattr(jax.config, k) for k in keys}
    old = os.environ.get("KEYSTONE_COMPILATION_CACHE")
    os.environ["KEYSTONE_COMPILATION_CACHE"] = str(tmp_path_factory.mktemp("xla-cache"))
    compilation_cache.reset_cache()  # a cache opened elsewhere would not move
    yield
    if old is None:
        del os.environ["KEYSTONE_COMPILATION_CACHE"]
    else:
        os.environ["KEYSTONE_COMPILATION_CACHE"] = old
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def results(tiny_bench, tmp_path_factory, cache_in_a_temporary_directory):
    """(cell, traced) -> (exit code, printed lines, parsed last line), run on demand."""
    from keystone_tpu import reliability

    done = {}

    def get(cell, traced):
        if (cell, traced) not in done:
            reliability.reset_recovery_log()  # other tests of this process may have left events
            out = io.StringIO()
            rc = run_cell(
                tiny_bench, cell, SEED, 0.3, traced, time.time(),
                require_platform="cpu",
                state_dir=str(tmp_path_factory.mktemp("state")), out=out,
            )
            lines = out.getvalue().splitlines()
            done[(cell, traced)] = (rc, lines, json.loads(lines[-1]))
        return done[(cell, traced)]

    return get


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("cell", TINY_CELLS)
def test_last_line_has_exactly_the_contract_keys(results, cell, traced):
    rc, lines, result = results(cell, traced)
    assert rc == 0 and len(lines) == 1
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert ("breakdown" in result) == traced
    assert result["attempted"] >= 1 and result["failed"] == 0
    device = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert (device["platform"], device["kind"]) == ("cpu", "cpu")
    assert ({"busy_s", "window_s"} <= set(device)) == traced
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float)


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_untraced_run_reports_the_cells_end_to_end_metrics(results, tiny_bench, cell):
    _, _, result = results(cell, False)
    want = {m["name"]: m["unit"] for m in tiny_bench.metrics_of("end_to_end", cell)}
    assert {k: v["unit"] for k, v in result["metrics"].items()} == want
    assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_traced_run_reports_only_per_layer_metrics_it_could_read(results, tiny_bench, cell):
    """No device plane on the CPU: the trace's readers find nothing and
    are left out; the compile counter's reader is always there."""
    _, _, result = results(cell, True)
    names = {m["name"] for m in tiny_bench.metrics_of("per_layer", cell)}
    assert set(result["metrics"]) <= names
    counter = next(n for n in names if n.startswith("window_compiles."))
    assert result["metrics"][counter]["unit"] == "count"
    assert result["device"]["busy_s"] == 0.0 and result["device"]["window_s"] > 0
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_plain_reference_agrees_with_the_program_at_the_tiny_size(results, cell):
    assert results(cell, False)[2]["correct"] is True
    assert results(cell, True)[2]["correct"] is True


def test_scoring_window_compiles_nothing(results):
    _, _, result = results("timit-tiny.score-tiny", True)
    assert result["metrics"]["window_compiles.apply"]["value"] == 0.0


def test_timit_fit_window_compiles_nothing(results):
    """A count, so it carries over to the chip: a second fresh Pipeline of
    the TIMIT form retraces nothing. (CIFAR's does: PERF.md section 6.)"""
    _, _, result = results("timit-tiny.fit-incore", True)
    assert result["metrics"]["window_compiles.fit"]["value"] == 0.0


# ------------------------------------------------- the comparison itself


def _fake_run(tiny_bench, tmp_path, config="timit-tiny"):
    cfg = tiny_bench.config(config)
    run = Run(
        bench=tiny_bench, cell_name="t", workload={}, cell={}, config=cfg,
        traffic={}, seed=7, seconds=0, traced=False, state_dir=str(tmp_path),
    )
    run.sut = tiny_bench.load_module("configs", cfg["files"]["sut"])
    run.reference = tiny_bench.load_module("configs", cfg["files"]["reference"])
    return run


def test_scores_beyond_the_tolerance_are_not_correct(tiny_bench, tmp_path):
    run = _fake_run(tiny_bench, tmp_path)
    reference = np.random.default_rng(0).normal(size=(64, 147)).astype(np.float32)
    assert compare.compare_scores(run, reference.copy(), reference) == []
    tolerance = run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    off = reference.copy()
    off[3, 5] += 3 * tolerance * np.abs(reference).max()
    assert "over the tolerance" in compare.compare_scores(run, off, reference)[0]
    off[3, 5] = np.nan
    assert "not finite" in compare.compare_scores(run, off, reference)[0]
    assert compare.score_error(2 * reference, reference) == pytest.approx(1.0)


def test_a_featurizer_at_another_precision_than_stated_fails_the_tolerance(tiny_bench, tmp_path):
    """The reference rounds the featurizer's inputs as the configuration
    states. On the CPU the program computes in float32, so a reference
    told `bfloat16` must disagree by more than the tolerance: the
    tolerance is tight enough to see a precision that is not the stated one."""
    run = _fake_run(tiny_bench, tmp_path)
    train = run.sut.make_data(run.config, 7, run.config["rows"], 0)
    held = run.sut.make_data(run.config, 7, run.config["heldout_rows"], 1)["x"]
    fitted = run.sut.fit(run.config, train, 7)
    program = run.sut.scores(run.config, fitted, held, 7)
    stated = run.reference.reference_scores(run.config, 7, train, held, {})
    other = run.reference.reference_scores(
        dict(run.config, featurizer_input_dtype="bfloat16"), 7, train, held, {}
    )
    tolerance = run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    assert compare.score_error(program, stated) < tolerance / 10
    assert compare.score_error(program, other) > tolerance


def test_reference_answers_are_kept_and_keyed(tiny_bench, tmp_path):
    run = _fake_run(tiny_bench, tmp_path)
    train = run.sut.make_data(run.config, 7, 512, 0)
    held = run.sut.make_data(run.config, 7, 64, 1)["x"]
    first = compare.reference_scores(run, train, held, {})
    kept = os.listdir(os.path.join(str(tmp_path), "reference"))
    assert len(kept) == 1
    real = run.reference
    run.reference = type("Kept", (), {"__file__": real.__file__})  # a second ask must not compute
    assert np.array_equal(compare.reference_scores(run, train, held, {}), first)
    run.reference = real
    run.seed = 8  # another seed is another question
    compare.reference_scores(run, train, held, {})
    assert len(os.listdir(os.path.join(str(tmp_path), "reference"))) == 2
    # and so are other rows of the same seed and sizes: the fit cell and
    # the scoring cell of one configuration share this directory (on the
    # chip in PR 24 they shared a key, and 3 of 12 scoring runs read the
    # fit cell's answers)
    other = run.sut.make_data(run.config, 8, 64, 2)["x"]
    assert not np.array_equal(compare.reference_scores(run, train, other, {}), first)
    assert len(os.listdir(os.path.join(str(tmp_path), "reference"))) == 3


# ------------------------------------------------------------ the traffic


@pytest.mark.parametrize("config", ["timit-tiny", "cifar-tiny"])
def test_data_comes_from_the_seed_alone(tiny_bench, config):
    cfg = tiny_bench.config(config)
    sut = tiny_bench.load_module("configs", cfg["files"]["sut"])
    a = sut.make_data(cfg, SEED, 64, 0)
    b = sut.make_data(cfg, SEED, 64, 0)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert a["x"].dtype == np.float32 and a["y"].dtype == np.int32
    assert not np.array_equal(a["x"], sut.make_data(cfg, SEED, 64, 1)["x"])
    assert not np.array_equal(a["x"], sut.make_data(cfg, SEED + 1, 64, 0)["x"])
    assert 0 <= a["y"].min() and a["y"].max() < cfg["num_classes"]
    assert len(np.unique(sut.make_data(cfg, SEED, 512, 0)["y"])) > 1


def test_rates_are_over_all_the_work_and_all_the_time(tiny_bench):
    run = Run(
        bench=tiny_bench, cell_name="t", workload={}, cell={}, config={}, traffic={},
        seed=0, seconds=0, traced=False, state_dir="",
    )
    run.samples = [Sample(10.0, 11.0, 100), Sample(11.5, 12.0, 100, ok=False), Sample(12.0, 14.0, 100)]
    run.setup_s = 12.5
    value = {
        name: tiny_bench.load_module("end_to_end", name + ".py").value(run)
        for name in ("fit_rows_per_s", "apply_rows_per_s", "apply_p95_ms", "setup_s")
    }
    assert value["fit_rows_per_s"] == value["apply_rows_per_s"] == 200 / 4.0
    assert value["apply_p95_ms"] == pytest.approx(1e3 * (1.0 + 0.95 * 1.0))
    assert value["setup_s"] == 12.5


# ---------------------------------------------------------- the command


def _command(args, cwd=ROOT, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_the_command_refuses_to_measure_without_a_tpu():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = _command(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode == NO_DEVICE_EXIT
    assert "no result" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_the_command_fails_on_a_cell_that_is_not_in_the_manifest():
    proc = _command(["--workload", "no-such.cell", "--seed", "1", "--seconds", "1"])
    assert proc.returncode not in (0, None)
    assert "no-such.cell" in proc.stderr and '"correct"' not in proc.stdout


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds BENCHMARK.json and the files under `paths`
    and nothing else: no program, so no result."""
    import shutil

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(
            os.path.join(ROOT, path), tmp_path / path,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    cell = manifest["workloads"][0]["name"]
    proc = _command(["--workload", cell, "--seed", "1", "--seconds", "1"], cwd=str(tmp_path))
    assert proc.returncode not in (0, None)
    assert "keystone_tpu" in proc.stderr and '"correct"' not in proc.stdout
