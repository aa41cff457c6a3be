"""The exact-kernel cell (`timit-krr.fit-incore`): its configuration, its
plain reference against the program through `Pipeline.fit`, its adapter's
`health`, its cost functions, and its loop end to end on the suite's
virtual CPU devices at a tiny size.

`TINY_CELLS` of `test_bench_cells.py` and the tiny manifest are files the
benchmark already had, so this cell's tiny form is laid over a copy of
the tiny manifest here, as `test_bench_stream_cell.py` does for its cell.
That file holds the manifest to "the streamed cell's entries are the
last"; this PR's entries came after them, which is the only place they
may go, so four of its cases are expected failures (marked in
tests/conftest.py) and what they held is held here, where it now stands."""

import io
import json
import os
import time

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark.harness import compare
from benchmark.harness.manifest import Bench
from benchmark.harness.peaks import PEAKS, least_seconds
from benchmark.harness.runner import Run, run_cell

# `run_cell` turns the program's persistent compile cache on: the streamed
# cell's fixture keeps it out of the checkout and puts jax's settings back
from test_bench_stream_cell import cache_in_a_temporary_directory  # noqa: E402,F401

CELL, TINY_CELL = "timit-krr.fit-incore", "timit-krr-tiny.fit-incore"
STREAM_CELL = "timit-rf16k-stream.fit-stream"
NEW_METRIC = "host_idle_ms.kernel.fit"
STREAM_METRICS = ["host_idle_ms.stream.fit", "collective_ms.fit", "chip_imbalance_pct.fit"]
SEED = 2**31 + 98765  # the driver's seeds are large


# ------------------------------------------------------------ the manifest


def test_the_manifest_gained_one_configuration_one_cell_and_one_metric_at_the_end(bench):
    manifest = bench.manifest
    config = manifest["configs"][-1]
    assert (config["name"], config["reduced"]) == ("timit-krr", ["rows"])
    assert "KernelRidgeRegression.scala" in config["source"] and "arXiv:1602.05310" in config["source"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "timit-krr", "fit-incore", 1)
    metric = manifest["per_layer"][-1]
    assert (metric["name"], metric["workloads"], metric["moves"]) == (NEW_METRIC, [CELL], "fit_rows_per_s")
    assert (metric["source"], metric["unit"], metric["better"]) == ("program_span", "ms", "lower")
    assert metric["layer"] == next(
        m["layer"] for m in manifest["per_layer"] if m["name"] == "kernel_roofline_pct.fit"
    )
    assert {m["name"] for m in bench.metrics_of("end_to_end", CELL)} == {"fit_rows_per_s", "setup_s"}
    reported = {m["name"] for m in bench.metrics_of("per_layer", CELL)}
    fit_metrics = {m["name"] for m in manifest["per_layer"] if m["name"].endswith(".fit")}
    # every fit metric but those that exist only across chips or in a streamed fit
    assert reported == fit_metrics - set(STREAM_METRICS)


def test_what_the_streamed_cells_tests_held_still_holds_one_place_earlier(bench):
    """`test_bench_stream_cell.py` looks for PR 30's entries at the end of
    their lists; they now stand one before the end, unchanged."""
    manifest = bench.manifest
    config = manifest["configs"][-2]
    assert (config["name"], config["reduced"]) == ("timit-rf16k-stream", ["rows"])
    cell = manifest["workloads"][-2]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        STREAM_CELL, "timit-rf16k-stream", "fit-stream", 4,
    )
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == [STREAM_CELL]
    assert [m["name"] for m in manifest["per_layer"][-4:-1]] == STREAM_METRICS
    for metric in manifest["per_layer"][-4:-1]:
        assert metric["workloads"] == [STREAM_CELL]


def test_the_eleven_host_idle_entries_stand_and_every_list_only_grew(bench):
    from test_bench_host_idle import APPLY_PHASES, BUILD_PHASE, FIT_PHASES

    manifest = bench.manifest
    names = [m["name"] for m in manifest["per_layer"]]
    eleven = [n for n in {**FIT_PHASES, **APPLY_PHASES} if n not in BUILD_PHASE] + list(BUILD_PHASE)
    at = names.index(eleven[0])
    assert names[at:at + 11] == eleven and names[at + 11:] == STREAM_METRICS + [NEW_METRIC]
    assert [n for n in names if n.startswith("host_idle_ms.")] == eleven + STREAM_METRICS[:1] + [NEW_METRIC]
    accepted = ["timit-rf16k.fit-incore", STREAM_CELL]
    for entry in manifest["per_layer"]:
        if not entry["name"].endswith(".fit") or entry["name"] == NEW_METRIC:
            assert CELL not in entry["workloads"] or entry["name"] == NEW_METRIC
        elif entry["name"] in STREAM_METRICS:
            assert entry["workloads"] == [STREAM_CELL]
        else:  # an accepted cell was taken off no list: this PR's cell came last
            assert entry["workloads"] == accepted + [CELL], entry["name"]
    fit_rate = next(m for m in manifest["end_to_end"] if m["name"] == "fit_rows_per_s")
    assert fit_rate["workloads"] == accepted + [CELL] and fit_rate["bound"] == 0.015


def test_the_new_metric_resolves_to_the_reader_the_benchmark_has(bench):
    spec = bench.layer_metric(NEW_METRIC)
    assert spec["reader"] == "host_idle_ms" and spec["params"] == {"span": "fit", "phase": ["ks:kernel:"]}
    assert callable(bench.load_module("readers", "host_idle_ms.py").read)
    entry = bench.manifest["per_layer"][-1]
    assert (spec["unit"], spec["layer"], spec["moves"]) == (entry["unit"], entry["layer"], entry["moves"])


def test_the_configuration_cuts_rows_alone_and_states_what_it_assumes(bench):
    config = bench.config("timit-krr")
    incore = bench.config("timit-rf16k")
    assert (config["input_dim"], config["num_classes"]) == (440, 147) == (incore["input_dim"], incore["num_classes"])
    assert config["kernel"] == "gaussian" and config["reduced"] == ["rows"] and config["reduced_why"]
    assert config["rows"] in (131072, 65536) and config["published_rows"] == 2251569
    assert config["rows"] % config["block_size"] == 0 and config["block_size"] == incore["block_size"]
    # the kernel whose random-feature estimate timit-rf16k runs
    assert config["kernel_gamma"] == pytest.approx(incore["gamma"] ** 2 / 2, rel=1e-4)
    assert set(config["assumed"]) == {"kernel_gamma", "block_size", "num_epochs", "block_permuter", "reg", "data"}
    assert all(isinstance(v, str) and len(v) > 20 for v in config["assumed"].values())
    assert config["kernel_matmul_input_dtype"] == "float32" and config["heldout_rows"] == 1024
    lam = config["reg"]
    assert lam > 0 and 10 ** round(np.log10(lam)) == pytest.approx(lam)  # a power of ten
    assert "KEYSTONE_" not in json.dumps(config)  # the cell runs the shipped defaults
    # the live panel and the model, as the deployment states them
    assert 4 * config["rows"] * config["block_size"] == 2 * 2**30 * config["rows"] // 131072
    tolerance = config["tolerance"]
    assert 0 < tolerance["scores_max_abs_over_ref_max_abs"] < 1e-3 and len(tolerance["why"]) > 100


def test_the_reference_imports_nothing_of_the_program(bench):
    with open(bench.find("configs", "timit-krr_ref.py")) as f:
        source = f.read()
    assert "keystone_tpu" not in source.replace("nothing from keystone_tpu", "")
    assert 'default_matmul_precision("highest")' in source and "Departures from the paper" in source


# ------------------------------------------------------------ the cost


def test_fit_cost_is_the_algorithms_work_and_its_share_stays_under_a_sixth(bench):
    config = bench.config("timit-krr")
    cost = bench.load_module("configs", config["files"]["cost"])
    n, d, k, b = config["rows"], 440, 147, 4096
    steps = config["num_epochs"] * n // b
    fit = cost.fit_cost(config, n)
    assert fit["flops"] == pytest.approx(
        steps * (2 * n * b * d + 2 * n * b * k + 2 * b * b * d + 4 * b * b * k + b ** 3 / 3)
    )
    assert fit["bytes"] == pytest.approx(4 * (steps * (n * d + n * k + b * k) + 2 * n * k))
    least, bound = least_seconds(fit["flops"], fit["bytes"], PEAKS["TPU v5 lite"])
    assert bound == "compute"
    # float32 at HIGHEST is six bfloat16 passes: at the 30.4 TFLOP/s it
    # reaches on a v5e the share of the 197 TFLOP/s peak stays under 17%
    assert 100 * least / (fit["flops"] / 30.4e12) < 17
    assert cost.fit_cost(config, 2 * n)["flops"] > 3.8 * fit["flops"]  # quadratic in rows, but for the factorizations
    apply = cost.apply_cost(config, 1024)
    assert apply["flops"] == pytest.approx(2 * 1024 * n * (d + k))
    assert apply["bytes"] == pytest.approx(4 * (1024 * d + n * d + n * k + 1024 * k))


# --------------------------------------- program against reference, on the CPU


@pytest.fixture(scope="module")
def tiny_krr_bench(bench, tmp_path_factory):
    """The tiny manifest with the tiny kernel configuration and cell
    appended as the real manifest has the real ones: the same per-layer
    metrics, in the same order."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    real = bench.manifest
    manifest["configs"].append({
        **real["configs"][-1], "name": "timit-krr-tiny", "reduced": ["rows", "block_size"],
        "file": "tests/benchmark/tiny/configs/timit-krr-tiny.json",
    })
    manifest["workloads"].append({**real["workloads"][-1], "name": TINY_CELL, "config": "timit-krr-tiny"})
    next(m for m in manifest["end_to_end"] if m["name"] == "fit_rows_per_s")["workloads"].append(TINY_CELL)
    have = {m["name"]: m for m in manifest["per_layer"]}
    for metric in bench.metrics_of("per_layer", CELL):
        if metric["name"] in have:
            have[metric["name"]]["workloads"].append(TINY_CELL)
        else:
            manifest["per_layer"].append({**metric, "workloads": [TINY_CELL]})
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return Bench(ROOT, manifest_path=str(path), search=[TINY, os.path.join(ROOT, "benchmark")])


def _fake_run(tiny_krr_bench, tmp_path, **changes):
    config = dict(tiny_krr_bench.config("timit-krr-tiny"), **changes)
    run = Run(
        bench=tiny_krr_bench, cell_name="t", workload={}, cell={}, config=config,
        traffic={}, seed=SEED, seconds=0, traced=False, state_dir=str(tmp_path),
    )
    run.sut = tiny_krr_bench.load_module("configs", config["files"]["sut"])
    run.reference = tiny_krr_bench.load_module("configs", config["files"]["reference"])
    return run


@pytest.mark.parametrize(
    "rows,block,epochs",
    [(512, 128, 2), (500, 128, 2), (300, 64, 1), (96, 128, 1)],
    ids=["whole-blocks-two-epochs", "short-last-block", "short-last-block-one-epoch", "one-short-block"],
)
def test_the_program_through_pipeline_fit_agrees_with_the_plain_reference(
    tiny_krr_bench, tmp_path, rows, block, epochs
):
    """Seeded data, permuted blocks: the program pads and masks a short
    last block and shards the rows over the suite's eight devices; the
    reference solves the short block at its own size on one device."""
    run = _fake_run(tiny_krr_bench, tmp_path, rows=rows, block_size=block, num_epochs=epochs)
    train = run.sut.make_data(run.config, SEED, rows, 0)
    held = run.sut.make_data(run.config, SEED, 64, 2)["x"]
    fitted = run.sut.fit(run.config, train, SEED)
    assert run.sut.health(fitted) == [] and run.sut.given(fitted) == {}
    mapper = run.sut.kernel_mapper(fitted)
    assert (mapper.num_train, mapper.block_size) == (rows, min(block, rows))
    program = run.sut.scores(run.config, fitted, held, SEED)
    reference = run.reference.reference_scores(run.config, SEED, train, held, {})
    assert program.shape == reference.shape == (64, 147)
    assert compare.score_error(program, reference) < run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    # and the fitted pipeline's own answer is the arg-max of those scores
    from keystone_tpu.data.dataset import ArrayDataset

    labels = np.asarray(fitted.apply_batch(ArrayDataset(held)).data)[:64]
    assert np.array_equal(labels, program.argmax(1))


def test_the_block_order_is_the_seeds_and_the_program_follows_it(tiny_krr_bench, tmp_path):
    run = _fake_run(tiny_krr_bench, tmp_path)
    order = run.reference.block_order(run.config, SEED, 512)
    assert sorted(order[:4]) == sorted(order[4:]) == [0, 128, 256, 384] and len(order) == 8
    assert order == run.reference.block_order(run.config, SEED, 512)
    assert order != run.reference.block_order(run.config, SEED + 1, 512) or order[:4] != [0, 128, 256, 384]
    # a fit under another seed's order differs: the order is part of the answer after one epoch
    train = run.sut.make_data(run.config, SEED, 512, 0)
    held = run.sut.make_data(run.config, SEED, 64, 2)["x"]
    a = run.sut.scores(run.config, run.sut.fit(run.config, train, SEED), held, SEED)
    b = run.sut.scores(run.config, run.sut.fit(run.config, train, SEED + 1), held, SEED + 1)
    reference = run.reference.reference_scores(run.config, SEED, train, held, {})
    assert compare.score_error(a, reference) < 1e-5 < compare.score_error(b, reference)


def test_a_distance_matmul_at_the_precision_below_fails_the_tolerance(tiny_krr_bench, tmp_path):
    """The reference told that its distance matmul's inputs are bfloat16
    (one pass at the MXU default) must disagree with the program by more
    than the tolerance, by a wide margin: the tolerance can see it."""
    run = _fake_run(tiny_krr_bench, tmp_path)
    train = run.sut.make_data(run.config, SEED, 512, 0)
    held = run.sut.make_data(run.config, SEED, 64, 2)["x"]
    program = run.sut.scores(run.config, run.sut.fit(run.config, train, SEED), held, SEED)
    stated = run.reference.reference_scores(run.config, SEED, train, held, {})
    below = run.reference.reference_scores(
        dict(run.config, kernel_matmul_input_dtype="bfloat16"), SEED, train, held, {}
    )
    tolerance = run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    assert compare.score_error(program, stated) < tolerance / 5
    assert compare.score_error(program, below) > 10 * tolerance
    assert compare.compare_scores(run, program, stated) == []
    assert "over the tolerance" in compare.compare_scores(run, program, below)[0]


def test_a_fit_whose_ladder_stepped_down_is_reported_by_health(tiny_krr_bench, tmp_path):
    """An out-of-memory error does not fail a kernel fit: the ladder
    halves the block and the fit goes on. The cell must see it."""
    from keystone_tpu import reliability
    from keystone_tpu.reliability import FaultSpec, injected

    run = _fake_run(tiny_krr_bench, tmp_path)
    train = run.sut.make_data(run.config, SEED, 512, 0)
    reliability.reset_recovery_log()
    with injected(FaultSpec(match="KernelRidgeRegression.solve", kind="oom", calls=(1,))):
        fitted = run.sut.fit(run.config, train, SEED)
    mapper = run.sut.kernel_mapper(fitted)
    assert mapper.block_size == 64 and mapper.degradation is not None
    problems = run.sut.health(fitted)
    assert any("degradation" in p for p in problems) and any("recovery log" in p for p in problems)
    reliability.reset_recovery_log()
    assert run.sut.health(run.sut.fit(run.config, train, SEED)) == []


def test_the_adapter_fails_at_import_where_the_entry_point_has_no_kernel_form(tiny_krr_bench, monkeypatch):
    """Laid over a commit before PR 34, the cell must fail at once: the
    adapter names the kernel form as it is imported."""
    from keystone_tpu.pipelines import timit

    class Before:  # TimitConfig as the parent has it: no `solver`
        def __init__(self, num_cosines=50, gamma=0.05555, reg=0.0, num_epochs=5, seed=123):
            pass

    monkeypatch.setattr(timit, "TimitConfig", Before)
    path = tiny_krr_bench.find("configs", "timit-krr_sut.py")
    import importlib.util

    spec = importlib.util.spec_from_file_location("krr_sut_on_the_parent", path)
    with pytest.raises(TypeError, match="solver"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


def test_data_comes_from_the_seed_alone_by_timit_rf16ks_rule(tiny_krr_bench, bench):
    config = tiny_krr_bench.config("timit-krr-tiny")
    sut = tiny_krr_bench.load_module("configs", config["files"]["sut"])
    other = bench.load_module("configs", "timit-rf16k_sut.py")
    a, b = sut.make_data(config, SEED, 64, 1), other.make_data(bench.config("timit-rf16k"), SEED, 64, 1)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert a["x"].dtype == np.float32 and a["y"].dtype == np.int32
    assert not np.array_equal(a["x"], sut.make_data(config, SEED + 1, 64, 1)["x"])


# ------------------------------------------------------- the tiny cell's run


@pytest.fixture(scope="module")
def results(tiny_krr_bench, tmp_path_factory, cache_in_a_temporary_directory):
    """traced -> (exit code, printed lines, parsed last line), run on demand."""
    from keystone_tpu import reliability

    done = {}

    def get(traced):
        if traced not in done:
            reliability.reset_recovery_log()
            out = io.StringIO()
            rc = run_cell(
                tiny_krr_bench, TINY_CELL, SEED, 0.5, traced, time.time(),
                require_platform="cpu",
                state_dir=str(tmp_path_factory.mktemp("state")), out=out,
            )
            lines = out.getvalue().splitlines()
            done[traced] = (rc, lines, json.loads(lines[-1]))
        return done[traced]

    return get


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_last_line_has_exactly_the_contract_keys(results, traced):
    rc, lines, result = results(traced)
    assert rc == 0 and len(lines) == 1
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed", "metrics", "device"}
    assert ("breakdown" in result) == traced
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float)


def test_untraced_run_reports_the_cells_end_to_end_metrics(results):
    _, _, result = results(False)
    assert {k: v["unit"] for k, v in result["metrics"].items()} == {"fit_rows_per_s": "rows/s", "setup_s": "s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_the_kernel_fit_agrees_with_the_reference_in_the_harness(results, traced):
    assert results(traced)[2]["correct"] is True


def test_fresh_pipelines_compile_nothing_in_the_window_and_the_trace_readers_stay_silent_on_the_cpu(results):
    """Every fit is a new Pipeline over the other data set. No device
    plane on the CPU: the trace's readers, the new metric's among them,
    find nothing and the line leaves their metrics out."""
    _, _, result = results(True)
    assert result["metrics"] == {"window_compiles.fit": {"value": 0.0, "unit": "count"}}
