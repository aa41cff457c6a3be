"""CIFAR RandomPatch's cell (`cifar-rp10k-8k.fit-incore`, PR 40): its
entries in the manifest, its configuration at the published widths, its
seeded images with their low-contrast square, its staged reference with
three comparisons and the precisions each has to see, the new readers,
and its loop end to end on the suite's CPU devices at a tiny size.

Every entry is found BY NAME, never by its place in a list, so the next
PR's appended entries do not break this file. The tiny twin
(`tests/benchmark/tiny/configs/cifar-rp10k-8k-tiny.json`) is appended to
a copy of the tiny manifest here, as the real cell is to the real one."""

import importlib.util
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

from test_bench_stream_cell import cache_in_a_temporary_directory  # noqa: E402,F401

CONFIG, CELL = "cifar-rp10k-8k", "cifar-rp10k-8k.fit-incore"
TINY_CONFIG, TINY_CELL = "cifar-rp10k-8k-tiny", "cifar-rp10k-8k-tiny.fit-incore"
FIT_METRICS = [
    "host_gap_ms.fit", "kernel_roofline_pct.fit", "device_idle_pct.fit", "window_compiles.fit",
    "host_idle_ms.plan.fit", "host_idle_ms.h2d.fit", "host_idle_ms.nodes.fit", "host_idle_ms.solver.fit",
    "host_idle_ms.finish.fit", "host_idle_ms.unlabelled.fit", "host_idle_ms.build.fit",
    "h2d_transfer_ms.fit", "h2d_exposed_ms.fit",
]
NEW_METRICS = {
    "scope_ms.conv.fit": ("scope_ms", {"span": "fit", "scope": "conv"}, "ms", "lower"),
    "conv_roofline_pct.fit": ("conv_roofline_pct", {"span": "fit", "scope": "conv", "cost": "conv_cost"}, "%", "higher"),
}
PUBLISHED = {
    "image_size": 32, "num_channels": 3, "num_filters": 10000, "patch_size": 6, "patch_steps": 1,
    "whitening_epsilon": 1e-5, "patch_var_constant": 10.0, "alpha": 0.25, "pool_size": 14, "pool_stride": 13,
    "feature_dim": 80000, "num_classes": 10, "block_size": 4096, "num_epochs": 1, "reg": 3000.0,
}
SEED = 2**31 + 40404  # the driver's seeds are large


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


# ------------------------------------------------------------ the manifest


def test_the_manifest_has_the_configuration_and_its_one_chip_cell_by_name(bench):
    manifest = bench.manifest
    config = _named(manifest["configs"], CONFIG)
    assert config["reduced"] == ["rows"] and config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"].endswith("images/cifar/RandomPatchCifar.scala")
    cell = bench.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fit-incore", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [w["name"] for w in manifest["workloads"] if w["config"] == CONFIG] == [CELL]
    assert "cifar-rp10k" not in {c["name"] for c in manifest["configs"]}  # the old files have no cell
    assert {m["name"] for m in bench.metrics_of("end_to_end", CELL)} == {"fit_rows_per_s", "setup_s"}
    assert {m["name"] for m in bench.metrics_of("per_layer", CELL)} == set(FIT_METRICS) | set(NEW_METRICS)
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name", FIT_METRICS + ["fit_rows_per_s"])
def test_every_fit_metric_gained_the_cell_after_the_cells_it_had(bench, name):
    section = "end_to_end" if name == "fit_rows_per_s" else "per_layer"
    workloads = _named(bench.manifest[section], name)["workloads"]
    assert workloads.index("timit-krr.fit-incore") < workloads.index(CELL)
    assert len(set(workloads)) == len(workloads)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_is_this_cells_alone_and_resolves_to_its_reader(bench, name):
    reader, params, unit, better = NEW_METRICS[name]
    entry = _named(bench.manifest["per_layer"], name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "fit_rows_per_s"
    assert (entry["unit"], entry["better"], entry["source"]) == (unit, better, "device_trace")
    assert entry["layer"] == _named(bench.manifest["per_layer"], "kernel_roofline_pct.fit")["layer"]
    spec = bench.layer_metric(name)
    assert (spec["reader"], spec["params"]) == (reader, params)
    assert callable(bench.load_module("readers", reader + ".py").read)


def test_the_cell_file_says_who_sends_it_and_what_it_bypasses(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fit-incore", 1)
    assert all(len(cell[k]) > 40 for k in ("who", "exercises", "bypasses"))
    assert "random_patch" in cell["who"] and "StandardScaler" in cell["exercises"]


# ------------------------------------------------------- the configuration


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_widths_hold(bench, key):
    config = bench.config(CONFIG)
    assert config[key] == PUBLISHED[key] and key not in config["reduced"]


def test_the_configuration_states_its_cut_its_precisions_and_its_limits(bench):
    config = bench.config(CONFIG)
    assert (config["rows"], config["published_rows"], config["heldout_rows"]) == (8192, 50000, 1024)
    assert config["reduced"] == ["rows"] and len(config["reduced_why"]) > 200
    cost = bench.load_module("configs", config["files"]["cost"])
    assert cost._shapes(config)["features"] == config["feature_dim"] == 80000
    assert tuple(config[k] for k in ("conv_input_dtype", "patch_stats_dtype", "solver_input_dtype", "whitener_dtype")) == (
        "bfloat16", "float32", "float32", "float32",
    )
    assert {"featurizer", "patch_statistics", "standardisation", "solver", "whitening"} <= set(config["precision"])
    assert config["architecture"] is None and "KEYSTONE_" not in json.dumps(config)
    tolerance = config["tolerance"]
    reference = bench.load_module("configs", config["files"]["reference"])
    assert set(reference.LIMITED) <= set(tolerance)
    assert max(tolerance[k] for k in reference.LIMITED) < tolerance["scores_max_abs_over_ref_max_abs"]


def test_the_reference_imports_nothing_of_the_program(bench):
    with open(bench.find("configs", bench.config(CONFIG)["files"]["reference"])) as f:
        source = f.read()
    assert "keystone_tpu" not in source.replace("nothing from\nkeystone_tpu", "").replace("importing nothing from", "")
    assert 'default_matmul_precision("highest")' in source and "Departures from the p" in source


# ----------------------------------------------------------------- the cost


def test_the_featurizers_least_time_is_its_flops_and_the_fit_holds_it(bench):
    config = bench.config(CONFIG)
    cost = bench.load_module("configs", config["files"]["cost"])
    conv = cost.conv_cost(config, 8192)
    assert conv["flops"] == pytest.approx(2 * 8192 * 729 * 108 * 10000)  # 12.9 TFLOP
    assert conv["bytes"] == pytest.approx(4 * (8192 * 3072 + 10000 * 108 + 8192 * 80000))
    least, bound = least_seconds(conv["flops"], conv["bytes"], PEAKS["TPU v5 lite"])
    assert bound == "compute" and 0.06 < least < 0.07
    fit = cost.fit_cost(config, 8192)
    assert fit["flops"] > conv["flops"] + 20 * 2 * 8192 * 4096**2 and fit["bytes"] > conv["bytes"]


def test_the_roofline_reader_is_silent_where_the_scope_reader_has_nothing(tiny_cifar_bench):
    reader = tiny_cifar_bench.load_module("readers", "conv_roofline_pct.py")
    run = _run(tiny_cifar_bench, "state")
    assert reader.read(run, NEW_METRICS["conv_roofline_pct.fit"][1]) is None  # no peaks: not a TPU
    run.peaks, run.reduction = PEAKS["TPU v5 lite"], None
    assert reader.read(run, NEW_METRICS["conv_roofline_pct.fit"][1]) is None  # untraced


# ----------------------------------------------------------------- the data


def test_images_come_from_the_seed_alone_and_each_has_its_low_contrast_square(bench):
    config = bench.config(CONFIG)
    sut = bench.load_module("configs", config["files"]["sut"])
    a, b = sut.make_data(config, SEED, 256, 0), sut.make_data(config, SEED, 256, 0)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert a["x"].shape == (256, 32, 32, 3) and a["x"].dtype == np.float32 and a["y"].dtype == np.int32
    assert not np.array_equal(a["x"], sut.make_data(config, SEED, 256, 1)["x"])
    assert np.array_equal(a["x"], np.round(a["x"])) and 0 <= a["x"].min() and a["x"].max() <= 255
    assert len(np.unique(a["y"])) >= 5
    side = sut.FLAT_SIDE
    for image in a["x"][:16]:
        windows = np.lib.stride_tricks.sliding_window_view(image, (side, side, 3)).reshape(-1, side * side * 3)
        spread = windows.max(axis=1) - windows.min(axis=1)
        assert spread.min() <= 2 * sut.FLAT_JITTER  # the square is somewhere
        assert windows[spread.argmin()].mean() >= sut.FLAT_LEVELS[0] - sut.FLAT_JITTER


# --------------------------------------- program against reference, on the CPU


@pytest.fixture(scope="module")
def tiny_cifar_bench(bench, tmp_path_factory):
    """The tiny manifest with the tiny twin appended as the real manifest
    has the real configuration and cell: with the metrics the real cell
    reports."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        **_named(bench.manifest["configs"], CONFIG), "name": TINY_CONFIG,
        "file": f"tests/benchmark/tiny/configs/{TINY_CONFIG}.json",
    })
    manifest["workloads"].append({**bench.workload(CELL), "name": TINY_CELL, "config": TINY_CONFIG})
    for section in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in manifest[section]}
        for metric in bench.metrics_of(section, CELL):
            if metric["name"] not in have:
                manifest[section].append({**metric, "workloads": [TINY_CELL]})
            elif "workloads" in have[metric["name"]]:
                have[metric["name"]]["workloads"].append(TINY_CELL)
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return Bench(ROOT, manifest_path=str(path), search=[TINY, os.path.join(ROOT, "benchmark")])


def _run(tiny_cifar_bench, state):
    config = tiny_cifar_bench.config(TINY_CONFIG)
    run = Run(
        bench=tiny_cifar_bench, cell_name="t", workload={}, cell={}, config=config,
        traffic={}, seed=SEED, seconds=0, traced=False, state_dir=state,
    )
    run.sut = tiny_cifar_bench.load_module("configs", config["files"]["sut"])
    run.reference = tiny_cifar_bench.load_module("configs", config["files"]["reference"])
    run.cost = tiny_cifar_bench.load_module("configs", config["files"]["cost"])
    return run


@pytest.fixture(scope="module")
def fitted_once(tiny_cifar_bench, tmp_path_factory):
    """(run, train, held-out images, fitted, the program's scores, given)."""
    run = _run(tiny_cifar_bench, str(tmp_path_factory.mktemp("state")))
    config = run.config
    train = run.sut.make_data(config, SEED, config["rows"], 0)
    held = run.sut.make_data(config, SEED, config["heldout_rows"], 2)["x"]
    fitted = run.sut.fit(config, train, SEED)
    program = run.sut.scores(config, fitted, held, SEED)
    return run, train, held, fitted, program, run.sut.given(fitted)


def test_what_crosses_in_given_and_what_each_is_for(fitted_once):
    run, train, held, fitted, program, given = fitted_once
    config, width = run.config, run.config["feature_dim"]
    assert set(given) == {
        "filters", "whitener", "whitener_means", "patches", "heldout_features", "heldout_scores", "train_features",
    }
    assert all(isinstance(v, np.ndarray) for v in given.values())
    assert given["filters"].shape == (config["num_filters"], 108) and given["whitener_means"].shape == (108,)
    assert given["whitener"].shape == (108, 108) and given["patches"].shape == (100000, 108)
    assert given["heldout_features"].shape == (len(held), width) and given["train_features"].shape == (len(train["x"]), width)
    assert np.array_equal(given["heldout_scores"], program)
    assert run.sut.health(fitted) == []


def test_the_program_agrees_with_the_staged_reference_by_all_three_comparisons(fitted_once):
    run, train, held, fitted, program, given = fitted_once
    readings, own = run.reference.compared(run.config, train, held, given)
    limits = run.config["tolerance"]
    for key in run.reference.LIMITED:
        assert readings[key] < limits[key] / 10, key
    assert compare.compare_scores(run, program, run.reference.reference_scores(run.config, SEED, train, held, given)) == []
    assert compare.score_error(program, own) < limits["scores_max_abs_over_ref_max_abs"] / 10


MOVES = {  # what each precision knob moves, and nothing else
    "patch_stats_dtype": {"features_row_l2_apart"},
    "solver_input_dtype": {"solve_scores_apart"},
    "whitener_dtype": {"whitening_apart", "filters_apart"},
}


@pytest.mark.parametrize(
    "knob,reading",
    [
        ("patch_stats_dtype", "features_row_l2_apart"),
        ("solver_input_dtype", "solve_scores_apart"),
        ("whitener_dtype", "whitening_apart"),
    ],
    ids=["patch-statistics-at-bfloat16", "a-solver-at-the-mxu-default", "a-bfloat16-svd"],
)
def test_the_precision_below_fails_its_own_comparison_and_only_that(fitted_once, knob, reading):
    """(a) sees the patch statistics' precision, (b) the solver's and (d)
    the ZCA fit's: the reference told the nearest precision below (squared
    pixels, the solve's products' inputs, or the centred patches of its own
    whitener's SVD, rounded to bfloat16) reads more than three times its
    limit where the stated one reads under a tenth; the other comparisons
    do not move."""
    run, train, held, fitted, program, given = fitted_once
    limits = run.config["tolerance"]
    first, _ = run.reference.compared(run.config, train, held, given)
    second, _ = run.reference.compared(dict(run.config, **{knob: "bfloat16"}), train, held, given)
    print(f"{knob}: first reading {first[reading]:.3e}, second {second[reading]:.3e}, limit {limits[reading]:.1e}")
    assert first[reading] < limits[reading] / 10 and second[reading] > 3 * limits[reading]
    for other in set(run.reference.LIMITED) - MOVES[knob]:
        assert second[other] == first[other], other
    with pytest.raises(ValueError, match=f"not the reference: {reading}"):
        run.reference.reference_scores(dict(run.config, **{knob: "bfloat16"}), SEED, train, held, given)


def test_the_reference_computes_with_the_given_filters_and_compares_the_rest(fitted_once):
    run, train, held, fitted, program, given = fitted_once
    _, own = run.reference.compared(run.config, train, held, given)
    other = dict(given, heldout_features=given["heldout_features"] * 1.01, heldout_scores=given["heldout_scores"] * 0.9)
    readings, same = run.reference.compared(run.config, train, held, other)
    assert np.array_equal(same, own)
    assert readings["features_row_l2_apart"] == pytest.approx(0.01, rel=1e-2)
    assert len(run.reference.over_their_limits(run.config, readings)) == 2
    with pytest.raises(RuntimeError, match="no held-out features"):
        run.sut.given(run.sut.Fitted(fitted.pipeline, train["x"], {}))


@pytest.mark.parametrize(
    "change,reading",
    [
        (lambda g: dict(g, whitener_means=g["whitener_means"] + 1e-2), "whitening_apart"),
        (lambda g: dict(g, whitener=g["whitener"] * 1.01), "whitening_apart"),
        (lambda g: dict(g, filters=g["filters"] * 1.001), "filters_apart"),
    ],
    ids=["means-off", "scale-off", "filters-unnormalised"],
)
def test_the_filter_learning_is_held_to_the_patches_it_was_fitted_on(fitted_once, change, reading):
    """(d): a whitener centred elsewhere or scaled, or filters not of unit
    length in the whitened metric, read over their limit; the program's
    own read under a tenth of it."""
    run, train, held, fitted, program, given = fitted_once
    limits = run.config["tolerance"]
    assert run.reference.whitening(run.config, given)[reading] < limits[reading] / 10
    assert run.reference.whitening(run.config, change(given))[reading] > 3 * limits[reading]


def test_the_adapter_fails_at_import_on_a_program_without_the_row_bound(tiny_cifar_bench, monkeypatch):
    """Laid over the parent commit the cell fails at once, at the
    adapter's import, and not after its data were made."""
    from keystone_tpu.ops.images.core import FusedConvFeaturizer

    monkeypatch.delattr(FusedConvFeaturizer, "row_block")  # as a commit before PR 40 has it
    path = tiny_cifar_bench.find("configs", tiny_cifar_bench.config(TINY_CONFIG)["files"]["sut"])
    spec = importlib.util.spec_from_file_location("sut_on_the_parent", path)
    with pytest.raises(AttributeError, match="row_block"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_the_tiny_twin_runs_through_run_cell_correct_and_builds_nothing_in_its_window(
    tiny_cifar_bench, tmp_path, cache_in_a_temporary_directory, traced  # noqa: F811
):
    from keystone_tpu import reliability

    reliability.reset_recovery_log()
    out = io.StringIO()
    rc = run_cell(
        tiny_cifar_bench, TINY_CELL, SEED, 1.0, traced, time.time(),
        require_platform="cpu", state_dir=str(tmp_path), out=out,
    )
    lines = out.getvalue().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    if traced:  # the CPU has no device plane: the trace readers stay silent, the counter does not
        assert result["metrics"] == {"window_compiles.fit": {"value": 0.0, "unit": "count"}}
    else:
        assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}
