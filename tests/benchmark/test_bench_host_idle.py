"""`host_idle_ms`: the device's idle time inside an operation, put down
to the innermost program span (`ks:...`) that covers it. Checked on
hand-built traces, with no chip; and the eleven metrics that read
through it, in the manifest and, laid over a copy of the tiny one, in the
tiny cells."""

import io
import json
import os
import time

import pytest

from bench_paths import ROOT, TINY

from benchmark.harness import trace as tracing
from benchmark.harness.manifest import Bench

E = tracing.Event
MS = 1e6  # nanoseconds
TPU = "/device:TPU:0"

FIT_PHASES = {
    "host_idle_ms.plan.fit": ["ks:fit:plan", "ks:fit:verify", "ks:optimize"],
    "host_idle_ms.h2d.fit": ["ks:h2d"],
    "host_idle_ms.nodes.fit": ["ks:node:"],
    "host_idle_ms.solver.fit": ["ks:solver:"],
    "host_idle_ms.finish.fit": ["ks:fit:splice", "ks:fit:fuse"],
    "host_idle_ms.unlabelled.fit": None,
}
APPLY_PHASES = {
    "host_idle_ms.bind.apply": ["ks:apply:bind"],
    "host_idle_ms.h2d.apply": ["ks:h2d"],
    "host_idle_ms.nodes.apply": ["ks:node:"],
    "host_idle_ms.unlabelled.apply": None,
}
# added when the first traced run showed 122 ms of a fit outside every span
BUILD_PHASE = {"host_idle_ms.build.fit": ["ks:build:"]}
FIT_PHASES.update(BUILD_PHASE)


@pytest.fixture(scope="module")
def reader():
    return Bench(ROOT).load_module("readers", "host_idle_ms.py")


class _Run:
    def __init__(self, trace):
        self.reduction = tracing.reduce(trace) if trace is not None else None
        self.said = []

    def say(self, message):
        self.said.append(message)


def _fit_trace():
    """Two fits of 20 ms. Times in ms, device busy in [ ]:

    fit 0 [0, 20):   (build:pipeline would come first; this trace has none)
                     plan (0, 4) with optimize:rules (1, 3) inside it;
                     node:Cos (4, 9) with h2d (5, 7) inside it, busy [6, 9);
                     node:Solver (9, 18) with solver:fit (9.5, 18) and in that
                     solver:reg_floor (10, 11) and solver:bcd (11, 18),
                     busy [11.5, 17.5); fit:fuse (18, 19); nothing (19, 20).
    fit 1 [20, 40):  the same, 20 ms later, but busy only [31.5, 37.5).
    """
    host, device = [], []
    for i, base in enumerate((0.0, 20.0)):
        def at(name, lo, hi, base=base):
            return E(name, (base + lo) * MS, (base + hi) * MS)

        host += [
            E("bench:fit", base * MS, (base + 20) * MS, {"i": i}),
            at("ks:fit:plan", 0, 4), at("ks:optimize:rules", 1, 3),
            at("ks:node:Cos", 4, 9), at("ks:h2d", 5, 7),
            at("ks:node:Solver", 9, 18), at("ks:solver:fit", 9.5, 18),
            at("ks:solver:reg_floor", 10, 11), at("ks:solver:bcd", 11, 18),
            at("ks:fit:fuse", 18, 19),
            at("PjitFunction(_squeeze)", 10, 11),  # JAX's own events name nothing here
        ]
        if i == 0:
            device.append(at("cos", 6, 9))
        device.append(at("while", 11.5, 17.5))
    return tracing.Trace(device={TPU: device}, host=host)


def _by_span(reader, trace, operation_index=0):
    reduction = tracing.reduce(trace)
    operation = tracing.spans(reduction.trace)[operation_index]
    program = [e for e in trace.host if e.name.startswith("ks:")]
    return reader.idle_by_span(reduction.busy_by_chip[TPU], program, operation), reduction, operation


def test_the_innermost_span_wins_and_a_gap_across_phases_is_cut(reader):
    by_span, _, _ = _by_span(reader, _fit_trace())
    # the first idle gap of fit 0 runs from 0 to 6 ms, across plan,
    # optimize:rules inside it, node:Cos and the h2d inside that
    assert by_span == {
        "ks:fit:plan": pytest.approx(2 * MS),        # (0, 1) and (3, 4)
        "ks:optimize:rules": pytest.approx(2 * MS),  # (1, 3), not its parent's
        "ks:node:Cos": pytest.approx(1 * MS),        # (4, 5); (7, 9) is busy
        "ks:h2d": pytest.approx(1 * MS),             # (5, 6) idle, (6, 7) busy
        "ks:node:Solver": pytest.approx(0.5 * MS),   # (9, 9.5), before its solver:fit opens
        "ks:solver:fit": pytest.approx(0.5 * MS),    # (9.5, 10)
        "ks:solver:reg_floor": pytest.approx(1 * MS),
        "ks:solver:bcd": pytest.approx(1 * MS),      # (11, 11.5) and (17.5, 18)
        "ks:fit:fuse": pytest.approx(1 * MS),
        None: pytest.approx(1 * MS),                 # (19, 20)
    }


@pytest.mark.parametrize("operation_index", [0, 1])
def test_the_pieces_of_an_operation_add_up_to_its_span_less_its_busy_time(reader, operation_index):
    by_span, reduction, operation = _by_span(reader, _fit_trace(), operation_index)
    span_ns = operation.end - operation.start
    busy_ns = 1e9 * reduction.busy_inside(operation.start, operation.end)
    assert sum(by_span.values()) == pytest.approx(span_ns - busy_ns)
    assert span_ns - busy_ns == pytest.approx((11 if operation_index == 0 else 14) * MS)


@pytest.mark.parametrize("name,want", [
    # medians over the two fits; fit 1 has no busy time in (26, 29)
    ("host_idle_ms.plan.fit", 4.0),        # plan 2 + optimize:rules 2, both fits
    ("host_idle_ms.h2d.fit", 1.5),         # 1 and 2
    ("host_idle_ms.nodes.fit", 2.5),       # node:Cos 1 and 3, node:Solver's own 0.5
    ("host_idle_ms.solver.fit", 2.5),      # solver:fit 0.5, reg_floor 1, bcd 1
    ("host_idle_ms.finish.fit", 1.0),
    ("host_idle_ms.unlabelled.fit", 1.0),
    ("host_idle_ms.build.fit", 0.0),       # spans there, none of this phase: 0, not None
])
def test_each_fit_metric_on_the_hand_built_trace(reader, name, want):
    spec = Bench(ROOT).layer_metric(name)
    assert spec["reader"] == "host_idle_ms" and spec["params"]["span"] == "fit"
    assert spec["params"]["phase"] == FIT_PHASES[name]
    run = _Run(_fit_trace())
    assert reader.read(run, spec["params"]) == pytest.approx(want)
    assert len(run.said) == 1 and "2 operations" in run.said[0]


def test_the_fit_metrics_add_up_to_host_gap_where_the_fits_are_alike(reader):
    """Medians add up only where the operations are alike; per operation
    the phases always do (the test above)."""
    trace = _fit_trace()
    trace.device[TPU].append(E("cos", 26 * MS, 29 * MS))  # now fit 1 is fit 0 again
    bench = Bench(ROOT)
    total = sum(
        reader.read(_Run(trace), bench.layer_metric(name)["params"]) for name in FIT_PHASES
    )
    gap = bench.load_module("readers", "host_gap_ms.py").read(_Run(trace), {"span": "fit"})
    assert total == pytest.approx(gap) == pytest.approx(11.0)


@pytest.mark.parametrize("name,want", [
    ("host_idle_ms.bind.apply", 1.0),
    ("host_idle_ms.h2d.apply", 2.0),
    ("host_idle_ms.nodes.apply", 3.0),       # (3, 4) of the first node, (6, 8) of the second
    ("host_idle_ms.unlabelled.apply", 2.0),  # (8, 10): the label fetch, outside the program
])
def test_each_apply_metric_on_a_hand_built_request(reader, name, want):
    host = [
        E("bench:apply", 0, 10 * MS, {"i": 0}),
        E("ks:apply:bind", 0, 1 * MS),
        E("ks:node:CosineRandomFeatures", 1 * MS, 5 * MS), E("ks:h2d", 1 * MS, 3 * MS),
        E("ks:node:Fused[BlockLinearMapper+MaxClassifier]", 6 * MS, 8 * MS),
        E("np.asarray(jax.Array)", 8 * MS, 10 * MS),
    ]
    trace = tracing.Trace(device={TPU: [E("cos", 4 * MS, 6 * MS)]}, host=host)
    spec = Bench(ROOT).layer_metric(name)
    assert spec["params"] == {"span": "apply", "phase": APPLY_PHASES[name]}
    assert reader.read(_Run(trace), spec["params"]) == pytest.approx(want)


def test_a_program_span_under_half_a_millisecond_is_not_seen_and_its_time_falls_to_its_parent(reader):
    """`read_profile` keeps host events of 0.5 ms or more (and the
    benchmark's own): a known limit, written down in PERF.md."""
    from jax.profiler import ProfileData

    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 4000000000 duration_ps: 6000000000 } }
  event_metadata { key: 1 value { id: 1 name: "while" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 10 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 3000000000 duration_ps: 300000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:fit" } }
  event_metadata { key: 2 value { id: 2 name: "ks:fit:plan" } }
  event_metadata { key: 3 value { id: 3 name: "ks:optimize:batch:cse" } }
  event_metadata { key: 4 value { id: 4 name: "ks:fit:verify" } }
  stat_metadata { key: 1 value { id: 1 name: "i" } } }
"""
    trace = tracing.read_profile(ProfileData.from_text_proto(text))
    assert sorted(e.name for e in trace.host) == ["bench:fit", "ks:fit:plan"]
    by_span, _, _ = _by_span(reader, trace)
    assert by_span == {
        "ks:fit:plan": pytest.approx(3 * MS),  # the 0.4 ms of optimize:batch:cse among them
        None: pytest.approx(1 * MS),           # fit:verify's 0.3 ms among them
    }


@pytest.mark.parametrize("name", sorted(FIT_PHASES) + sorted(APPLY_PHASES))
def test_nothing_to_read_is_none_and_never_raises(reader, name):
    params = Bench(ROOT).layer_metric(name)["params"]
    span = "bench:" + params["span"]
    busy = {TPU: [E("while", 1 * MS, 2 * MS)]}
    program = [E("ks:fit:plan", 0, 1 * MS), E("ks:apply:bind", 0, 1 * MS)]
    # no traced run; no device plane (a CPU run); no such operation;
    # a program from before the bridge, which writes no `ks:` span
    assert reader.read(_Run(None), params) is None
    assert reader.read(_Run(tracing.Trace({}, [E(span, 0, 5 * MS)] + program)), params) is None
    assert reader.read(_Run(tracing.Trace(busy, program)), params) is None
    before = tracing.Trace(busy, [E(span, 0, 5 * MS), E("PjitFunction(f)", 0, 1 * MS)])
    assert reader.read(_Run(before), params) is None
    # and with all three there, a phase that no span of this run names reads 0
    value = reader.read(_Run(tracing.Trace(busy, [E(span, 0, 5 * MS)] + program)), params)
    assert isinstance(value, float) and 0.0 <= value <= 4.0


# ------------------------------------------------- the manifests' entries


def _entries(manifest):
    return {m["name"]: m for m in manifest["per_layer"] if m["name"].startswith("host_idle_ms.")}


def _cells_reporting(manifest, end_to_end):
    return next(m for m in manifest["end_to_end"] if m["name"] == end_to_end)["workloads"]


def test_the_manifest_holds_the_eleven_metrics_as_the_files_define_them(bench):
    manifest = bench.manifest
    entries = _entries(manifest)
    assert sorted(entries) == sorted({**FIT_PHASES, **APPLY_PHASES})
    for name, entry in entries.items():
        fit = name.endswith(".fit")
        assert entry["source"] == "program_span" and entry["unit"] == "ms" and entry["better"] == "lower"
        assert entry["moves"] == ("fit_rows_per_s" if fit else "apply_p95_ms")
        assert entry["layer"] == next(
            m["layer"] for m in manifest["per_layer"]
            if m["name"] == ("host_gap_ms.fit" if fit else "host_gap_ms.apply")
        )
        assert entry["workloads"] and set(entry["workloads"]) <= set(_cells_reporting(manifest, entry["moves"]))
    # new entries went to the end of the list, after everything that was there
    names = [m["name"] for m in manifest["per_layer"]]
    ten = [n for n in {**FIT_PHASES, **APPLY_PHASES} if n not in BUILD_PHASE]
    assert names[-11:] == ten + list(BUILD_PHASE)


@pytest.fixture
def tiny_with_host_idle(bench, tmp_path):
    """The tiny manifest is a file the benchmark already had, so it stays
    as it is; the real manifest's eleven entries are laid over a copy of
    it here, each in the tiny cells that report the metric it moves."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for entry in _entries(bench.manifest).values():
        manifest["per_layer"].append(
            {**entry, "workloads": list(_cells_reporting(manifest, entry["moves"]))}
        )
    path = tmp_path / "manifest" / "BENCHMARK.json"
    path.parent.mkdir()
    path.write_text(json.dumps(manifest))
    return Bench(ROOT, manifest_path=str(path), search=[TINY, os.path.join(ROOT, "benchmark")])


@pytest.mark.parametrize("cell,phases", [
    ("timit-tiny.fit-incore", FIT_PHASES),
    ("cifar-tiny.fit-incore", FIT_PHASES),
    ("timit-tiny.score-tiny", APPLY_PHASES),
])
def test_the_entries_resolve_to_their_files_in_the_tiny_cells_too(tiny_with_host_idle, cell, phases):
    listed = {m["name"] for m in tiny_with_host_idle.metrics_of("per_layer", cell)}
    assert {n for n in listed if n.startswith("host_idle_ms.")} == set(phases)
    for name in phases:
        spec = tiny_with_host_idle.layer_metric(name)
        assert spec["reader"] == "host_idle_ms" and spec["params"]["phase"] == phases[name]


@pytest.fixture
def cache_in_a_temporary_directory(tmp_path, monkeypatch):
    """`run_cell` turns the program's persistent compile cache on: keep it
    out of the checkout, and put jax's settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("KEYSTONE_COMPILATION_CACHE", str(tmp_path / "xla-cache"))
    compilation_cache.reset_cache()
    yield
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def test_a_tiny_traced_run_on_the_cpu_leaves_the_new_metrics_out_and_still_passes(
    tiny_with_host_idle, tmp_path, cache_in_a_temporary_directory
):
    """No device plane on the CPU: the reader finds nothing, as
    `host_gap_ms` does, and the line leaves the metrics out."""
    from benchmark.harness.runner import run_cell
    from keystone_tpu import reliability

    cell = "timit-tiny.score-tiny"
    tiny_bench = tiny_with_host_idle
    listed = {m["name"] for m in tiny_bench.metrics_of("per_layer", cell)}
    assert set(APPLY_PHASES) <= listed
    reliability.reset_recovery_log()
    out = io.StringIO()
    rc = run_cell(
        tiny_bench, cell, 2**31 + 77, 0.3, True, time.time(),
        require_platform="cpu", state_dir=str(tmp_path), out=out,
    )
    result = json.loads(out.getvalue().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert not any(name.startswith(("host_idle_ms.", "host_gap_ms.")) for name in result["metrics"])
    assert result["metrics"]["window_compiles.apply"]["value"] == 0.0
