"""BENCHMARK.json against the contract's form, and every name in it
against the files it has to resolve to. Parametrised by entry, so that a
later PR's new cell, configuration or metric is one more case."""

import json
import os
import re

import pytest

from bench_paths import ROOT, TINY

from benchmark.harness.manifest import MANIFEST_KEYS, NAME_RE, UNIT_RE, Bench

MANIFESTS = {
    "real": os.path.join(ROOT, "BENCHMARK.json"),
    "tiny": os.path.join(TINY, "BENCHMARK.json"),
}
SEARCH = {
    "real": [os.path.join(ROOT, "benchmark")],
    "tiny": [TINY, os.path.join(ROOT, "benchmark")],
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(which):
    with open(MANIFESTS[which]) as f:
        return json.load(f)


def _bench(which):
    return Bench(ROOT, manifest_path=MANIFESTS[which], search=SEARCH[which])


def _entries(section):
    return [
        pytest.param(which, entry, id=f"{which}:{entry['name']}")
        for which in MANIFESTS
        for entry in _load(which)[section]
    ]


@pytest.mark.parametrize("which", MANIFESTS)
def test_manifest_has_exactly_the_contract_keys(which):
    manifest = _load(which)
    assert set(manifest) == MANIFEST_KEYS
    assert os.path.getsize(MANIFESTS[which]) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert 1 <= len(manifest["paths"]) <= 16
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_command_and_paths_stay_inside_the_benchmark():
    manifest = _load("real")
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path)), path
        assert not path.startswith("/") and ".." not in path.split("/")
    named = [w for w in manifest["command"] if "/" in w or w.endswith(".py")]
    for word in named:
        assert any(word.startswith(p + "/") for p in manifest["paths"]), word
        assert os.path.isfile(os.path.join(ROOT, word)), word


def test_full_check_fits_the_budget_with_24_cells():
    rs = _load("real")["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("which,entry", _entries("configs"))
def test_configuration_entry(which, entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME_RE.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert len(entry["reduced"]) <= 16 and all(NAME_RE.match(k) for k in entry["reduced"])
    paths = _load("real")["paths"]
    assert any(entry["file"].startswith(p + "/") for p in paths), entry["file"]
    config = _bench(which).config(entry["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert key in config, f"reduced names {key}, which the file does not have"
        # a width is never cut: not a size of a feature, head, state or projection
        assert not key.endswith(("_dim", "_rank")) and "hidden" not in key
    used = {w["config"] for w in _load(which)["workloads"]}
    assert entry["name"] in used, "a configuration no cell uses"


@pytest.mark.parametrize("which,entry", _entries("configs"))
def test_configuration_files_resolve(which, entry):
    bench = _bench(which)
    config = bench.config(entry["name"])
    assert set(config["files"]) == {"sut", "reference", "cost"}
    for filename in config["files"].values():
        assert os.path.isfile(bench.find("configs", filename))
    with open(bench.find("configs", config["files"]["reference"])) as f:
        text = f.read()
    assert not re.search(r"^\s*(import|from)\s+keystone_tpu", text, re.M)
    assert config["tolerance"]["scores_max_abs_over_ref_max_abs"] > 0
    assert config["tolerance"]["why"]


@pytest.mark.parametrize("which,entry", _entries("workloads"))
def test_cell_entry_and_its_files(which, entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME_RE.match(entry[key]), entry[key]
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"] and "\t" not in entry["why"]
    bench = _bench(which)
    assert bench.config(entry["config"])  # the configuration is in the manifest
    traffic = bench.traffic(entry["traffic"])
    assert os.path.isfile(bench.find("drivers", traffic["kind"] + ".py"))
    driver = bench.load_module("drivers", traffic["kind"] + ".py")
    assert all(callable(getattr(driver, f)) for f in ("setup", "window", "check"))
    cell = bench.cell(entry["name"])
    for key in ("config", "traffic", "chips"):
        assert cell[key] == entry[key], f"cells/{entry['name']}.json disagrees on {key}"
    assert cell["who"]


@pytest.mark.parametrize("which", MANIFESTS)
def test_cells_are_distinct_and_few_take_four_chips(which):
    workloads = _load(which)["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in workloads]
    assert len(set(pairs)) == len(pairs)
    names = [w["name"] for w in workloads]
    assert len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in workloads)
    assert four <= max(1, len(workloads) // 4)


@pytest.mark.parametrize("which,entry", _entries("end_to_end"))
def test_end_to_end_metric(which, entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME_RE.match(entry["name"]) and UNIT_RE.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.1
    bench = _bench(which)
    module = bench.load_module("end_to_end", entry["name"] + ".py")
    assert callable(module.value)
    cells = {w["name"] for w in bench.manifest["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells


@pytest.mark.parametrize("which,entry", _entries("per_layer"))
def test_per_layer_metric(which, entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME_RE.match(entry["name"]) and UNIT_RE.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    if "roofline" in entry["name"] or "mfu" in entry["name"]:
        assert entry["unit"] == "%"
    bench = _bench(which)
    spec = bench.layer_metric(entry["name"])
    for key in ("layer", "unit", "moves"):
        assert spec[key] == entry[key], f"layer_metrics/{entry['name']}.json disagrees on {key}"
    reader = bench.load_module("readers", spec["reader"] + ".py")
    assert callable(reader.read)
    # `moves` is an end-to-end metric reported in every cell where this one is
    manifest = bench.manifest
    cells = {w["name"] for w in manifest["workloads"]}
    moved = next(m for m in manifest["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry.get("workloads", cells)) <= set(moved.get("workloads", cells))


@pytest.mark.parametrize("which", MANIFESTS)
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(which):
    bench = _bench(which)
    names = [m["name"] for sec in ("end_to_end", "per_layer") for m in bench.manifest[sec]]
    assert len(set(names)) == len(names)
    for w in bench.manifest["workloads"]:
        e2e = [m["name"] for m in bench.metrics_of("end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert bench.metrics_of("per_layer", w["name"]), w["name"]


def test_files_under_paths_are_named_from_allowed_characters():
    for path in _load("real")["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert NAME_RE.match(name), os.path.join(folder, name)


PUBLISHED = {
    "timit-rf16k": {
        "input_dim": 440, "num_cosines": 4, "num_cosine_features": 4096,
        "gamma": 0.05555, "num_classes": 147, "block_size": 4096,
        "num_epochs": 5, "reg": 0.0, "rf_type": "gaussian",
    },
    "cifar-rp10k": {
        "num_filters": 10000, "patch_size": 6, "reg": 3000.0,
        "whitening_epsilon": 1e-5, "pool_size": 14, "pool_stride": 13,
        "alpha": 0.25, "block_size": 4096, "num_epochs": 1,
        "image_size": 32, "num_channels": 3, "num_classes": 10,
    },
}


@pytest.mark.parametrize(
    "name,key", [(n, k) for n, keys in PUBLISHED.items() for k in keys]
)
def test_published_sizes_are_not_cut(name, key):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".json")
    with open(path) as f:
        config = json.load(f)
    assert config[key] == PUBLISHED[name][key]
    assert key not in config["reduced"]
    assert "KEYSTONE_" not in json.dumps(config)  # cells run the shipped defaults


def test_a_cell_added_as_files_is_picked_up_with_no_harness_edit(tmp_path):
    """A later PR adds a manifest entry, a cell file and a traffic file;
    nothing that exists is edited."""
    manifest = _load("tiny")
    manifest["workloads"].append({
        "name": "timit-tiny.fit-three", "config": "timit-tiny",
        "traffic": "fit-three", "chips": 1, "why": "a test's own mix",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "timit-tiny.fit-incore" in metric.get("workloads", []):
            metric["workloads"].append("timit-tiny.fit-three")
    (tmp_path / "cells").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "traffic" / "fit-three.json").write_text(
        json.dumps({"name": "fit-three", "kind": "fit_loop", "datasets": 3, "rows": 512})
    )
    (tmp_path / "cells" / "timit-tiny.fit-three.json").write_text(json.dumps({
        "name": "timit-tiny.fit-three", "config": "timit-tiny",
        "traffic": "fit-three", "chips": 1, "who": "this test",
    }))
    bench = Bench(
        ROOT, manifest_path=str(tmp_path / "BENCHMARK.json"),
        search=[str(tmp_path)] + SEARCH["tiny"],
    )
    assert bench.traffic(bench.workload("timit-tiny.fit-three")["traffic"])["datasets"] == 3
    assert bench.cell("timit-tiny.fit-three")["who"] == "this test"
    assert [m["name"] for m in bench.metrics_of("end_to_end", "timit-tiny.fit-three")] == [
        "fit_rows_per_s", "setup_s",
    ]
    assert len(bench.metrics_of("per_layer", "timit-tiny.fit-three")) == 4
