"""The flagship's cell (`imagenet-siftlcs-fv.score-bulk`): its entries in
the manifest, its configuration, traffic and cost functions, its seeded
images, its plain reference against the program through `Pipeline.fit`,
the reader of device time by scope, and its loop end to end on the
suite's CPU devices at a tiny size.

Every entry is found BY NAME (`bench.config(...)`, `next(m for m in ... if
m["name"] == ...)`), never by its place in a list, so the next PR's
appended entries do not break this file. The files that do pin the
manifest's tail (`test_bench_host_idle.py`, `test_bench_stream_cell.py`,
`test_bench_krr_cell.py`) fail on any appended entry, and their module
fixtures copy "the last cell", which is now this one with its
`apply_loop` traffic; those cases are expected failures in
tests/conftest.py. What the kernel cell's and the streamed cell's marked
cases guarded (program against reference at the tiny size, what crosses
in `given`, the precision below failing the tolerance, `health`, the
harness's run of the tiny cell) is guarded again here for
`timit-krr-tiny` and `timit-tiny-stream` by name, as cases of the same
parametrised tests that guard `imagenet-tiny`."""

import importlib.util
import io
import json
import os
import time

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark.harness import compare
from benchmark.harness import trace as tracing
from benchmark.harness.manifest import NAME_RE, Bench
from benchmark.harness.peaks import PEAKS, least_seconds
from benchmark.harness.runner import Run, run_cell

from test_bench_stream_cell import (  # noqa: E402,F401
    cache_in_a_temporary_directory,
    features_that_do_not_fit,
)

CONFIG, CELL, TRAFFIC = "imagenet-siftlcs-fv", "imagenet-siftlcs-fv.score-bulk", "score-bulk-images"
NEW_METRICS = {
    "scope_ms.sift.apply": ("scope_ms", {"span": "apply", "scope": "feat/SIFTExtractor"}, "device_trace"),
    "scope_ms.lcs.apply": ("scope_ms", {"span": "apply", "scope": "feat/LCSExtractor"}, "device_trace"),
    "scope_ms.fisher.apply": ("scope_ms", {"span": "apply", "scope": "feat/FisherVector"}, "device_trace"),
    "host_idle_ms.image.apply": ("host_idle_ms", {"span": "apply", "phase": ["ks:image:"]}, "program_span"),
}
APPLY_METRICS = [
    "host_gap_ms.apply", "kernel_roofline_pct.apply", "device_idle_pct.apply", "window_compiles.apply",
    "host_idle_ms.bind.apply", "host_idle_ms.h2d.apply", "host_idle_ms.nodes.apply",
    "host_idle_ms.unlabelled.apply",
]
SEED = 2**31 + 24680  # the driver's seeds are large

# tiny configuration -> (its cell, the real configuration, the real cell, its traffic)
TINY_CELLS = {
    "imagenet-tiny": ("imagenet-tiny.score-bulk", CONFIG, CELL, "score-tiny-images"),
    "timit-krr-tiny": ("timit-krr-tiny.fit-incore", "timit-krr", "timit-krr.fit-incore", "fit-incore"),
    "timit-tiny-stream": (
        "timit-tiny-stream.fit-stream", "timit-rf16k-stream", "timit-rf16k-stream.fit-stream", "fit-stream",
    ),
}
COMPARED = ["imagenet-tiny", "timit-krr-tiny"]  # program against reference, case by case


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


# ------------------------------------------------------------ the manifest


def test_the_manifest_has_the_configuration_and_its_one_chip_cell_by_name(bench):
    manifest = bench.manifest
    config = _named(manifest["configs"], CONFIG)
    assert config["reduced"] == ["rows", "num_pca_samples", "num_gmm_samples"]
    assert config["source"].endswith("imagenet/ImageNetSiftLcsFV.scala#L132-L167")
    assert config["file"] == f"benchmark/configs/{CONFIG}.json" and len(config["why"]) <= 200
    cell = bench.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and f"{bench.traffic(TRAFFIC)['request_rows']}-image" in cell["why"]
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == ["timit-rf16k-stream.fit-stream"]
    assert [w["name"] for w in manifest["workloads"] if w["config"] == CONFIG] == [CELL]
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME_RE.match(entry["name"])
    assert {m["name"] for m in bench.metrics_of("end_to_end", CELL)} == {
        "apply_rows_per_s", "apply_p95_ms", "setup_s",
    }
    assert len(json.dumps(manifest)) < 64 * 1024


@pytest.mark.parametrize("name", APPLY_METRICS + ["apply_rows_per_s", "apply_p95_ms"])
def test_every_apply_metric_gained_the_cell_after_the_cells_it_had(bench, name):
    section = "end_to_end" if name in ("apply_rows_per_s", "apply_p95_ms") else "per_layer"
    workloads = _named(bench.manifest[section], name)["workloads"]
    assert workloads.index("timit-rf16k.score-bulk") < workloads.index(CELL)
    assert len(set(workloads)) == len(workloads)


def test_no_fit_metric_lists_the_scoring_cell(bench):
    for entry in bench.manifest["per_layer"] + bench.manifest["end_to_end"]:
        if entry["name"].endswith(".fit") or entry["name"] == "fit_rows_per_s":
            assert CELL not in entry["workloads"], entry["name"]
    reported = {m["name"] for m in bench.metrics_of("per_layer", CELL)}
    assert reported == set(APPLY_METRICS) | set(NEW_METRICS)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_is_this_cells_alone_and_resolves_to_its_reader(bench, name):
    reader, params, source = NEW_METRICS[name]
    entry = _named(bench.manifest["per_layer"], name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "apply_rows_per_s"
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", source)
    assert entry["layer"] == _named(bench.manifest["per_layer"], "kernel_roofline_pct.apply")["layer"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    spec = bench.layer_metric(name)
    assert (spec["reader"], spec["params"]) == (reader, params)
    assert (spec["unit"], spec["layer"], spec["moves"]) == (entry["unit"], entry["layer"], entry["moves"])
    assert callable(bench.load_module("readers", reader + ".py").read)


def test_the_traffic_is_a_closed_loop_of_requests_that_hold_the_held_out_rows(bench):
    traffic, config = bench.traffic(TRAFFIC), bench.config(CONFIG)
    assert (traffic["kind"], traffic["inputs"]) == ("apply_loop", 2)
    assert traffic["request_rows"] in (128, 256, 512, 1024) and len(traffic["request_rows_note"]) > 100
    assert config["heldout_rows"] == 128 <= traffic["request_rows"]
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert all(len(cell[k]) > 40 for k in ("who", "exercises", "bypasses"))
    assert "imagenet_streaming" not in json.dumps(cell) or "Nothing" in json.dumps(cell)


# ------------------------------------------------------- the configuration


def test_the_configuration_cuts_no_width_and_states_what_it_cut_and_assumed(bench):
    config = bench.config(CONFIG)
    published = {
        "sift_step_size": 3, "sift_bin_size": 4, "sift_scales": 4, "sift_scale_step": 1,
        "lcs_stride": 4, "lcs_border": 16, "lcs_patch": 6, "desc_dim": 64, "vocab_size": 16,
        "feature_dim": 4096, "num_classes": 1000, "reg": 6e-5, "mixture_weight": 0.25,
        "block_size": 4096, "num_iter": 1, "top_k": 5,
    }
    assert {k: config[k] for k in published} == published
    assert config["feature_dim"] == 2 * 2 * config["desc_dim"] * config["vocab_size"]
    assert config["reduced"] == ["rows", "num_pca_samples", "num_gmm_samples"] and len(config["reduced_why"]) > 200
    assert (config["rows"], config["published_rows"]) == (2048, 1281167)
    assert config["rows"] >= 2 * config["num_classes"]  # every class twice
    assert config["num_pca_samples"] == config["num_gmm_samples"] == 10**6
    assert config["published_num_pca_samples"] == config["published_num_gmm_samples"] == 10**7
    assert config["architecture"] is None and config["image_size"] == [256, 256]
    assert {"images", "data", "channel_order"} <= set(config["assumed"])
    assert all(isinstance(v, str) and len(v) > 20 for v in config["assumed"].values())
    assert {"smoothing", "binning", "products", "smoothing_input_dtype"} <= set(config["precision"])
    assert config["smoothing_input_dtype"] == "float32" and len(config["deployment"]) > 200
    assert "KEYSTONE_" not in json.dumps(config)  # the cell runs the shipped defaults
    tolerance = config["tolerance"]
    assert 0 < tolerance["scores_max_abs_over_ref_max_abs"] < 0.1
    assert "First reading" in tolerance["why"] and "Second reading" in tolerance["why"]
    reference = bench.load_module("configs", config["files"]["reference"])
    assert set(reference.LIMITED) | {"encodings_row_l2_apart"} <= set(tolerance)
    # the limit that tells the precisions apart is on the encodings; the scores' is the wide one
    assert tolerance["encodings_row_l2_apart"] < tolerance["scores_max_abs_over_ref_max_abs"]


def test_the_programs_extractors_count_the_descriptors_the_configuration_states(bench):
    from keystone_tpu.ops.images.sift import SIFTExtractor

    config = bench.config(CONFIG)
    assert SIFTExtractor(scale_step=1).grid_counts(256, 256) == [6241, 3364, 2116, 1444]
    assert config["sift_descriptors_per_image"] == 13165 and config["lcs_descriptors_per_image"] == 56 * 56
    cost = bench.load_module("configs", config["files"]["cost"])
    assert sum(n for _, _, n in cost._sift_scales(config)) == 13165 and cost._lcs_keypoints(config) == 3136


@pytest.mark.parametrize("name", [CONFIG, "timit-krr"])
def test_the_reference_imports_nothing_of_the_program(bench, name):
    with open(bench.find("configs", bench.config(name)["files"]["reference"])) as f:
        source = f.read()
    assert "keystone_tpu" not in source.replace("nothing from keystone_tpu", "")
    assert 'default_matmul_precision("highest")' in source and "Departures from the p" in source


# ----------------------------------------------------------------- the cost


def test_a_requests_least_time_is_its_bytes_and_reads_under_the_roofline(bench):
    config = bench.config(CONFIG)
    cost = bench.load_module("configs", config["files"]["cost"])
    one = cost.image_cost(config)
    # an image: between 40 and 100 MB of compulsory traffic, under 1 GFLOP
    assert 40e6 < one["bytes"] < 100e6 and 0.2e9 < one["flops"] < 1e9
    rows = bench.traffic(TRAFFIC)["request_rows"]
    request = cost.apply_cost(config, rows)
    assert request["bytes"] == pytest.approx(rows * one["bytes"] + 4 * 4096 * 1000)
    assert request["flops"] == pytest.approx(rows * one["flops"])
    least, bound = least_seconds(request["flops"], request["bytes"], PEAKS["TPU v5 lite"])
    assert bound == "memory" and 0.002 < least < 0.1
    assert cost.apply_cost(config, 2 * rows)["bytes"] < 2 * request["bytes"]  # the weights once
    fit = cost.fit_cost(config, config["rows"])
    assert fit["flops"] > 3 * config["rows"] * one["flops"]


# ----------------------------------------------------------------- the data


def test_images_come_from_the_seed_alone_with_every_class_twice(bench):
    config = bench.config(CONFIG)
    sut = bench.load_module("configs", config["files"]["sut"])
    small = dict(config, image_size=[64, 64])
    a, b = sut.make_data(small, SEED, 2048, 0), sut.make_data(small, SEED, 2048, 0)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert a["x"].shape == (2048, 64, 64, 3) and a["x"].dtype == np.float32 and a["y"].dtype == np.int32
    assert 0.0 <= a["x"].min() < 40 and 215 < a["x"].max() <= 255.0
    assert np.bincount(a["y"], minlength=1000).min() >= 2
    other = sut.make_data(small, SEED + 1, 64, 0)
    assert not np.array_equal(a["x"][:64], other["x"])
    assert not np.array_equal(a["x"][:64], sut.make_data(small, SEED, 64, 1)["x"])


def test_images_have_structure_between_one_and_fifty_percent_of_descriptors_are_flat(bench):
    """The data rule: not white noise, not flat. At the real image size,
    by the reference's own SIFT: a share of the descriptors, and not
    most, lies under the contrast threshold and is zero."""
    import jax

    config = bench.config(CONFIG)
    sut = bench.load_module("configs", config["files"]["sut"])
    reference = bench.load_module("configs", config["files"]["reference"])
    images = sut.make_data(config, SEED, 4, 1)["x"]
    with jax.default_matmul_precision("highest"):
        descriptors = np.asarray(reference.sift(config, images))
    assert descriptors.shape == (4, 13165, 128)
    flat = float(np.mean(descriptors.sum(-1) == 0))
    assert 0.01 < flat < 0.5
    assert descriptors.max() == 255.0 or descriptors.max() > 100  # and the rest has contrast


# --------------------------------------- program against reference, on the CPU


@pytest.fixture(scope="module")
def tiny_cells_bench(bench, tmp_path_factory):
    """The tiny manifest with each tiny configuration and cell of
    `TINY_CELLS` appended as the real manifest has the real ones: found
    there by name, with the metrics the real cell reports."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    real = bench.manifest
    for tiny, (tiny_cell, config, cell, traffic) in TINY_CELLS.items():
        manifest["configs"].append({
            **_named(real["configs"], config), "name": tiny,
            "file": f"tests/benchmark/tiny/configs/{tiny}.json",
        })
        manifest["workloads"].append({
            **bench.workload(cell), "name": tiny_cell, "config": tiny, "traffic": traffic,
        })
        for section in ("end_to_end", "per_layer"):
            have = {m["name"]: m for m in manifest[section]}
            for metric in bench.metrics_of(section, cell):
                if metric["name"] not in have:
                    manifest[section].append({**metric, "workloads": [tiny_cell]})
                elif "workloads" in have[metric["name"]]:
                    have[metric["name"]]["workloads"].append(tiny_cell)
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return Bench(ROOT, manifest_path=str(path), search=[TINY, os.path.join(ROOT, "benchmark")])


@pytest.fixture(scope="module")
def fitted_once(tiny_cells_bench, tmp_path_factory):
    """name -> (run, train, held-out rows, fitted, the program's scores,
    the stated reference's): a tiny fit is seconds, so one a configuration."""
    done = {}

    def get(name):
        if name not in done:
            config = tiny_cells_bench.config(name)
            run = Run(
                bench=tiny_cells_bench, cell_name="t", workload={}, cell={}, config=config,
                traffic={}, seed=SEED, seconds=0, traced=False,
                state_dir=str(tmp_path_factory.mktemp("state")),
            )
            run.sut = tiny_cells_bench.load_module("configs", config["files"]["sut"])
            run.reference = tiny_cells_bench.load_module("configs", config["files"]["reference"])
            train = run.sut.make_data(config, SEED, config["rows"], 0)
            held = run.sut.make_data(config, SEED, 64, 2)["x"][: config["heldout_rows"]]
            fitted = run.sut.fit(config, train, SEED)
            program = run.sut.scores(config, fitted, held, SEED)
            stated = run.reference.reference_scores(config, SEED, train, held, run.sut.given(fitted))
            done[name] = (run, train, held, fitted, program, stated)
        return done[name]

    return get


@pytest.mark.parametrize("name", COMPARED)
def test_the_program_through_pipeline_fit_agrees_with_the_plain_reference(fitted_once, name):
    run, train, held, fitted, program, stated = fitted_once(name)
    assert run.sut.health(fitted) == []
    assert program.shape == stated.shape == (len(held), run.config["num_classes"])
    assert compare.score_error(program, stated) < run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    assert compare.compare_scores(run, program, stated) == []
    # and the fitted pipeline's own answer is the arg-max of those scores
    from keystone_tpu.data.dataset import ArrayDataset

    answer = np.asarray(fitted.apply_batch(ArrayDataset(held)).data)
    best = answer[:, 0] if answer.ndim == 2 else answer
    assert np.array_equal(best[: len(held)], program.argmax(1))
    if answer.ndim == 2:  # top-5: best first, as the scores order them
        assert answer.shape[1] == 5 and np.array_equal(answer, np.argsort(-program, axis=1, kind="stable")[:, :5])


@pytest.mark.parametrize("name", COMPARED)
def test_the_reference_at_the_precision_below_fails_the_tolerance(fitted_once, name):
    """The reference told the nearest precision below the stated one (the
    smoothing's inputs, or the distance matmul's, rounded to bfloat16)
    must disagree with the program by more than a tolerance: the kernel
    cell's by its scores, the flagship's by its held-out encodings (and
    by the basis, which no longer diagonalises the samples' covariance),
    through the very call the harness makes."""
    run, train, held, fitted, program, stated = fitted_once(name)
    knob = {"imagenet-tiny": "smoothing_input_dtype", "timit-krr-tiny": "kernel_matmul_input_dtype"}[name]
    assert run.config[knob] == "float32"
    below_config, given = dict(run.config, **{knob: "bfloat16"}), run.sut.given(fitted)
    limits = run.config["tolerance"]
    if name == "imagenet-tiny":
        with pytest.raises(ValueError, match="sift encodings_row_l2_apart .* over the tolerance"):
            run.reference.reference_scores(below_config, SEED, train, held, given)
        first, _ = run.reference.compared(run.config, train, held, given)
        second, _ = run.reference.compared(below_config, train, held, given)
        for key in ("encodings_row_l2_apart", "pca_not_diagonal"):
            print(f"{name}: {key}: first reading {first['sift'][key]:.3e}, second {second['sift'][key]:.3e}, limit {limits[key]:.1e}")
            assert first["sift"][key] < limits[key] / 3 and second["sift"][key] > 3 * limits[key]
        assert run.reference.over_their_limits(run.config, first) == []
        # the knob is the smoothing's: LCS, which smooths nothing, reads the same under both
        assert first["lcs"] == second["lcs"]
        return
    below = run.reference.reference_scores(below_config, SEED, train, held, given)
    tolerance = limits["scores_max_abs_over_ref_max_abs"]
    first, second = compare.score_error(program, stated), compare.score_error(program, below)
    print(f"{name}: first reading {first:.3e}, second {second:.3e}, tolerance {tolerance:.1e}")
    assert first < tolerance / 1.5 and second > 1.5 * tolerance
    assert "over the tolerance" in compare.compare_scores(run, program, below)[0]


@pytest.mark.parametrize("name", COMPARED)
def test_what_is_random_in_the_program_reaches_the_reference_and_matters(fitted_once, name):
    run, train, held, fitted, program, stated = fitted_once(name)
    given = run.sut.given(fitted)
    assert all(isinstance(v, np.ndarray) for v in given.values())
    tolerance = run.config["tolerance"]["scores_max_abs_over_ref_max_abs"]
    if name == "timit-krr-tiny":  # the block order is the seed's, made again by the reference
        assert given == {}
        order = run.reference.block_order(run.config, SEED, run.config["rows"])
        assert sorted(set(order)) == list(range(0, run.config["rows"], run.config["block_size"]))
        assert order == run.reference.block_order(run.config, SEED, run.config["rows"])
        other = run.reference.reference_scores(run.config, SEED + 1, train, held, given)
    else:  # the sampler's columns and the mixtures' start cross in `given`
        per_image = run.config["num_pca_samples"] // run.config["rows"]
        assert given["sift_columns"].shape == (run.config["rows"], per_image)
        assert given["lcs_columns"].shape == (run.config["rows"], 16)  # all 16 keypoints of a 48 x 48 image
        assert given["sift_gmm_means0"].shape == (run.config["vocab_size"], run.config["desc_dim"])
        assert 1 <= int(given["sift_gmm_updates"]) <= 100 and 1 <= int(given["lcs_gmm_updates"]) <= 100
        again = run.sut.sampled_columns(run.config, SEED, run.config["rows"])
        assert np.array_equal(again["sift_columns"], given["sift_columns"])
        # what the program fitted and computed crosses too, to be compared
        assert given["sift_components"].shape == (128, run.config["desc_dim"])
        assert given["lcs_components"].shape == (96, run.config["desc_dim"])
        assert given["lcs_gmm_means"].shape == given["lcs_gmm_variances"].shape == given["lcs_gmm_means0"].shape
        assert given["heldout_encodings"].shape == (len(held), run.config["feature_dim"])
        # told that EM never ran, the reference's own mixture is the start itself, and part (a) says so
        never = dict(given, sift_gmm_updates=np.asarray(0, np.int32))
        with pytest.raises(ValueError, match="not the reference: sift gmm_log_likelihood_apart"):
            run.reference.reference_scores(run.config, SEED, train, held, never)
        return
    assert compare.score_error(program, stated) < tolerance < compare.score_error(program, other)


def _turned(components, radians):
    """The first two components turned into each other: as orthonormal,
    the same subspace, no longer the principal axes."""
    out = np.array(components)
    c, s = np.cos(radians), np.sin(radians)
    out[:, 0], out[:, 1] = c * components[:, 0] + s * components[:, 1], c * components[:, 1] - s * components[:, 0]
    return out


@pytest.mark.parametrize(
    "spoil,named",
    [
        (lambda g: dict(g, lcs_components=g["lcs_components"] * 1.01), "lcs pca_not_orthonormal"),
        (lambda g: dict(g, sift_components=np.roll(g["sift_components"], 1, axis=0)), "sift pca_variance_missed"),
        (lambda g: dict(g, sift_components=_turned(g["sift_components"], 0.01)), "sift pca_not_diagonal"),
        (lambda g: dict(g, lcs_gmm_variances=g["lcs_gmm_variances"] * 3.0), "lcs gmm_log_likelihood_apart"),
        (lambda g: dict(g, heldout_encodings=g["heldout_encodings"] * np.linspace(1.0, 1.02, 256, dtype=np.float32)),
         "sift encodings_row_l2_apart"),
    ],
    ids=[
        "components-not-orthonormal", "components-of-another-subspace", "components-turned-in-their-subspace",
        "a-mixture-that-fits-worse", "encodings-a-hundredth-off",
    ],
)
def test_what_the_program_fitted_or_encoded_off_the_references_own_is_not_correct(fitted_once, spoil, named):
    """Parts (a) and (b) of `correct`: the program's basis has to be
    principal for the reference's own samples (orthonormal, capturing the
    variance, diagonalising the covariance: the last is first order, a
    turn of 0.01 inside the subspace shows), its mixture has to explain
    the samples as well as the reference's own fit from the same start,
    and its held-out encodings have to be the reference's."""
    run, train, held, fitted, _, _ = fitted_once("imagenet-tiny")
    given = run.sut.given(fitted)
    readings, _ = run.reference.compared(run.config, train, held, given)
    limits = run.config["tolerance"]
    for branch in ("sift", "lcs"):
        assert set(run.reference.LIMITED) | {"encodings_row_l2_apart"} <= set(readings[branch])
        for key in (*run.reference.LIMITED, "encodings_row_l2_apart"):
            assert readings[branch][key] < limits[key] / 3, (branch, key)
    with pytest.raises(ValueError, match="the program is not the reference: .*" + named):
        run.reference.reference_scores(run.config, SEED, train, held, spoil(given))


def test_the_reference_computes_with_nothing_the_program_fitted_but_the_checked_basis(fitted_once):
    """The program's mixtures and encodings are compared and never
    computed with: given other ones, the reference's own scores and its
    readings of the basis are the same to the bit. The basis, once it has
    passed for principal, is the reference's coordinates."""
    run, train, held, fitted, _, stated = fitted_once("imagenet-tiny")
    given = run.sut.given(fitted)
    other = dict(
        given,
        sift_gmm_means=given["sift_gmm_means"][::-1] * 2.0, lcs_gmm_variances=given["lcs_gmm_variances"] * 7.0,
        sift_gmm_weights=given["sift_gmm_weights"][::-1], heldout_encodings=np.zeros_like(given["heldout_encodings"]),
    )
    readings, scores = run.reference.compared(run.config, train, held, other)
    assert np.array_equal(scores, stated)
    mine, _ = run.reference.compared(run.config, train, held, given)
    for branch in ("sift", "lcs"):
        for key in ("pca_not_orthonormal", "pca_variance_missed", "pca_not_diagonal", "pca_least_cosine"):
            assert readings[branch][key] == mine[branch][key]
    assert readings["sift"]["encodings_row_l2_apart"] == pytest.approx(1.0)  # against encodings of zero
    assert len(run.reference.over_their_limits(run.config, readings)) >= 3


def test_an_adapter_that_has_scored_nothing_has_no_encodings_to_give(fitted_once):
    run, _, _, fitted, _, _ = fitted_once("imagenet-tiny")
    kept = fitted.bench_heldout_encodings
    try:
        del fitted.bench_heldout_encodings
        with pytest.raises(RuntimeError, match="no held-out encodings"):
            run.sut.given(fitted)
    finally:
        fitted.bench_heldout_encodings = kept


@pytest.mark.parametrize("name", COMPARED)
def test_a_fit_that_recovered_from_something_is_reported_by_health(fitted_once, name):
    from keystone_tpu import reliability

    run, train, _, fitted, _, _ = fitted_once(name)
    assert run.sut.health(fitted) == []
    if name == "timit-krr-tiny":  # an out-of-memory error halves the block and the fit goes on
        from keystone_tpu.reliability import FaultSpec, injected

        reliability.reset_recovery_log()
        with injected(FaultSpec(match="KernelRidgeRegression.solve", kind="oom", calls=(1,))):
            degraded = run.sut.fit(run.config, train, SEED)
        problems = run.sut.health(degraded)
        assert any("degradation" in p for p in problems) and any("recovery log" in p for p in problems)
    else:
        reliability.get_recovery_log().record("retry", "node:SIFTExtractor", attempt=2)
        assert any("recovery log" in p for p in run.sut.health(fitted))
    reliability.reset_recovery_log()
    assert run.sut.health(fitted) == []


@pytest.mark.parametrize("name", COMPARED)
def test_the_adapter_fails_at_import_on_a_program_without_what_the_cell_needs(tiny_cells_bench, monkeypatch, name):
    """Laid over the parent commit, the cell must fail at once, at the
    adapter's import, and not after its data were made."""
    sut = tiny_cells_bench.config(name)["files"]["sut"]
    if name == "timit-krr-tiny":
        from keystone_tpu.pipelines import timit

        class Before:  # TimitConfig as a commit before PR 34 has it: no `solver`
            def __init__(self, num_cosines=50, gamma=0.05555, reg=0.0, num_epochs=5, seed=123):
                pass

        monkeypatch.setattr(timit, "TimitConfig", Before)
        expected = pytest.raises(TypeError, match="solver")
    else:
        from keystone_tpu.ops.stats.core import ColumnSampler

        monkeypatch.delattr(ColumnSampler, "sample_indices")  # as a commit before PR 36 has it
        expected = pytest.raises(AttributeError, match="sample_indices")
    spec = importlib.util.spec_from_file_location("sut_on_the_parent", tiny_cells_bench.find("configs", sut))
    with expected:
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


def test_the_two_sampler_draws_of_a_branch_are_one_set_of_columns(tiny_cells_bench):
    """`build_pipeline` gives the PCA's sampler and the mixture's the same
    seed and count, so the reference reads one set a branch; an adapter
    that found them different must say so and not guess."""
    config = tiny_cells_bench.config("imagenet-tiny")
    sut = tiny_cells_bench.load_module("configs", config["files"]["sut"])
    columns = sut.sampled_columns(config, SEED, 96)
    assert set(columns) == {"sift_columns", "lcs_columns"}
    assert columns["sift_columns"].dtype == np.int32 and columns["sift_columns"].max() < 151
    assert all(len(set(row)) == len(row) for row in columns["sift_columns"][:8])
    with pytest.raises(RuntimeError, match="samplers differ"):
        sut.sampled_columns(dict(config, num_gmm_samples=2 * config["num_pca_samples"]), SEED, 96)


# ------------------------------------------------------ device time by scope


def _profile(tmp_path, scopes):
    """An `.xplane.pb` of one device plane: three `bench:apply` spans on
    the host, and under each one event per (scope, microseconds) of
    `scopes`, the second nested in the first."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    device = space.planes.add(name="/device:TPU:0")
    device.stat_metadata[1].name = "tf_op"
    line = device.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (scope, _) in enumerate(scopes, start=1):
        meta = device.event_metadata[i]
        meta.id, meta.name = i, f"%fusion.{i} = f32[8]{{0}} fusion(f32[8] %p)"
        stat = meta.stats.add(metadata_id=1)
        stat.str_value = scope
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench:apply"
    thread = host.lines.add(name="main", timestamp_ns=0)
    for k in range(3):
        start = 1_000_000 * (k + 1)
        thread.events.add(metadata_id=1, offset_ps=start * 1000, duration_ps=900_000 * 1000)
        at = start + 10_000
        for i, (_, micros) in enumerate(scopes, start=1):
            line.events.add(metadata_id=i, offset_ps=at * 1000, duration_ps=micros * 1_000_000)
            if i == 1:
                at += 1_000  # the second starts inside the first
            elif i == 2:
                at = start + 10_000 + scopes[0][1] * 1000 + 1_000  # the third after the first's end
            else:
                at += micros * 1000 + 1_000
    path = tmp_path / "plugins" / "profile" / "1" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_scope_ms_reads_self_time_by_the_metadatas_scope(bench, tmp_path):
    reader = bench.load_module("readers", "scope_ms.py")
    path = _profile(tmp_path, [
        ("jit(fused_chain)/jit(main)/feat/SIFTExtractor/conv", 400),
        ("jit(fused_chain)/jit(main)/feat/SIFTExtractor/sqrt", 100),  # nested in the conv's event
        ("jit(_lcs_descriptors)/jit(main)/feat/LCSExtractor/gather", 50),
        ("", 25),
    ])
    spans = tracing.spans(tracing.read_xplane(path), "apply")
    assert len(spans) == 3
    planes = reader._scoped(path, (spans[0].start, spans[-1].end))
    (events,) = planes.values()
    assert len(events) == 12 and {scope.split("/")[-2] for _, scope in events if scope} == {
        "SIFTExtractor", "LCSExtractor",
    }
    by_scope = reader.self_ms_by_scope(planes, len(spans))
    assert by_scope["jit(fused_chain)/jit(main)/feat/SIFTExtractor/conv"] == pytest.approx(0.3)
    assert by_scope["jit(fused_chain)/jit(main)/feat/SIFTExtractor/sqrt"] == pytest.approx(0.1)
    assert reader.under(by_scope, "feat/SIFTExtractor") == pytest.approx(0.4)
    assert reader.under(by_scope, "feat/LCSExtractor") == pytest.approx(0.05)
    assert reader.under(by_scope, "feat/FisherVector") == 0.0
    assert reader.under(by_scope, "feat/SIFT") == 0.0  # whole names only


def test_scope_ms_has_nothing_to_read_without_a_device_plane_a_probe_or_a_trace(bench, tmp_path):
    reader = bench.load_module("readers", "scope_ms.py")
    params = {"span": "apply", "scope": "feat/SIFTExtractor"}

    class Sut:
        pass

    run = Run(
        bench=bench, cell_name="t", workload={}, cell={}, config={}, traffic={},
        seed=1, seconds=0, traced=True, state_dir=str(tmp_path), sut=Sut(),
    )
    assert reader.read(run, params) is None  # untraced: no reduction
    run.reduction = tracing.reduce(tracing.Trace({}, []))
    assert reader.read(run, params) is None  # the CPU: no device plane
    run.__dict__.pop("_scope_ms", None)
    run.reduction = tracing.reduce(tracing.Trace({"/device:TPU:0": [tracing.Event("%a", 0, 5)]}, []))
    assert reader.read(run, params) is None  # a program whose adapter has no probe


# ------------------------------------------------------ the tiny cells' runs


@pytest.fixture(scope="module")
def results(tiny_cells_bench, tmp_path_factory, cache_in_a_temporary_directory, features_that_do_not_fit):
    """(tiny configuration, traced) -> (exit code, printed lines, parsed
    last line), run on demand."""
    from keystone_tpu import reliability

    done = {}

    def get(name, traced):
        if (name, traced) not in done:
            reliability.reset_recovery_log()
            out = io.StringIO()
            rc = run_cell(
                tiny_cells_bench, TINY_CELLS[name][0], SEED, 0.5, traced, time.time(),
                require_platform="cpu",
                state_dir=str(tmp_path_factory.mktemp("state")), out=out,
            )
            lines = out.getvalue().splitlines()
            done[name, traced] = (rc, lines, json.loads(lines[-1]))
        return done[name, traced]

    return get


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_last_line_has_exactly_the_contract_keys(results, name, traced):
    rc, lines, result = results(name, traced)
    assert rc == 0 and len(lines) == 1
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed", "metrics", "device"}
    assert ("breakdown" in result) == traced
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float)


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_untraced_run_reports_the_cells_end_to_end_metrics(results, name):
    _, _, result = results(name, False)
    expected = (
        {"apply_rows_per_s": "rows/s", "apply_p95_ms": "ms", "setup_s": "s"}
        if name == "imagenet-tiny" else {"fit_rows_per_s": "rows/s", "setup_s": "s"}
    )
    assert {k: v["unit"] for k, v in result["metrics"].items()} == expected
    assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_the_tiny_cell_agrees_with_its_reference_in_the_harness(results, name, traced):
    assert results(name, traced)[2]["correct"] is True


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_nothing_compiles_in_the_window_and_the_trace_readers_stay_silent_on_the_cpu(results, name):
    """No device plane on the CPU: the trace's readers, `scope_ms` among
    them, find nothing and the line leaves their metrics out."""
    _, _, result = results(name, True)
    counter = "window_compiles.apply" if name == "imagenet-tiny" else "window_compiles.fit"
    assert result["metrics"] == {counter: {"value": 0.0, "unit": "count"}}
