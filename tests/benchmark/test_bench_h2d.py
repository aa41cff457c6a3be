"""`h2d_transfer_ms` and `h2d_exposed_ms`: how long a host batch was on
its way inside an operation (the union of the program's `ks:h2d` and
`ks:h2d:transfer` spans), and how much of that the device stood still
for. Checked on hand-built traces, with no chip; the four entries found
in the manifest by NAME; and what the new span does to the `host_idle_ms`
reader that was there."""

import pytest

from bench_paths import ROOT

from benchmark.harness import trace as tracing
from benchmark.harness.manifest import Bench

E = tracing.Event
MS = 1e6  # nanoseconds
TPU = "/device:TPU:0"

NEW = {
    "h2d_transfer_ms.apply": ("apply", "transfer"),
    "h2d_transfer_ms.fit": ("fit", "transfer"),
    "h2d_exposed_ms.apply": ("apply", "exposed"),
    "h2d_exposed_ms.fit": ("fit", "exposed"),
}
SCORING = ["timit-rf16k.score-bulk", "imagenet-siftlcs-fv.score-bulk"]
FITTING = ["timit-rf16k.fit-incore", "timit-rf16k-stream.fit-stream", "timit-krr.fit-incore"]


@pytest.fixture(scope="module")
def reader():
    return Bench(ROOT).load_module("readers", "h2d_ms.py")


class _Run:
    def __init__(self, trace):
        self.reduction = tracing.reduce(trace) if trace is not None else None
        self.said = []

    def say(self, message):
        self.said.append(message)


def _read(reader, trace, span, what):
    return reader.read(_Run(trace), {"span": span, "what": what})


def _request(base=0.0, busy=((12, 18),), transfer=(1, 12), index=0):
    """One request of 20 ms, times in ms from `base`: the enqueue
    `ks:h2d` (0, 1) inside `node:Cos` (0, 3), the transfer on the
    watcher's thread, `node:Combiner` (3, 19) where the host waits."""
    def at(name, lo, hi):
        return E(name, (base + lo) * MS, (base + hi) * MS)

    host = [
        E("bench:apply", base * MS, (base + 20) * MS, {"i": index}),
        at("ks:node:Cos", 0, 3), at("ks:h2d", 0, 1), at("ks:node:Combiner", 3, 19),
        at("PjitFunction(cos)", 1, 3),
    ]
    if transfer is not None:
        host.append(at("ks:h2d:transfer", *transfer))
    device = [at("fusion", lo, hi) for lo, hi in busy]
    return host, device


def _trace(*requests):
    host, device = [], []
    for h, d in requests:
        host += h
        device += d
    return tracing.Trace(device={TPU: device}, host=host)


# ------------------------------------------------------------ the reader


def test_transfer_is_the_union_of_enqueue_and_transfer_and_exposed_the_idle_time_inside_it(reader):
    trace = _trace(_request())  # in flight (0, 12), the device busy (12, 18) only
    assert _read(reader, trace, "apply", "transfer") == pytest.approx(12.0)
    assert _read(reader, trace, "apply", "exposed") == pytest.approx(12.0)
    run = _Run(trace)
    reader.read(run, {"span": "apply", "what": "exposed"})
    assert len(run.said) == 1 and "1 operations" in run.said[0]


def test_device_work_during_the_transfer_is_not_exposed(reader):
    trace = _trace(_request(busy=((4, 9), (12, 18))))  # the last request's tail hides 5 ms
    assert _read(reader, trace, "apply", "transfer") == pytest.approx(12.0)
    assert _read(reader, trace, "apply", "exposed") == pytest.approx(7.0)


def test_overlapping_spans_count_once_and_a_gap_between_uploads_is_not_in_flight(reader):
    host, device = _request(transfer=(0.5, 6))  # starts inside the enqueue: the union is (0, 6)
    host += [E("ks:h2d", 8 * MS, 9 * MS), E("ks:h2d:transfer", 9 * MS, 11 * MS)]  # a second upload
    trace = _trace((host, device))
    assert _read(reader, trace, "apply", "transfer") == pytest.approx(6.0 + 3.0)
    assert _read(reader, trace, "apply", "exposed") == pytest.approx(9.0)


def test_a_transfer_that_runs_past_its_operations_end_is_clipped_to_it(reader):
    first = _request(transfer=(1, 26))  # arrives 6 ms into the next request
    second = _request(base=20.0, transfer=None, index=1)
    trace = _trace(first, second)
    reduction = tracing.reduce(trace)
    one, two = tracing.spans(reduction.trace, "apply")
    every = reader.in_flight(trace.host)
    assert every == [(0.0, 26 * MS)]  # the second's own enqueue (20, 21) is inside the first's tail
    assert tracing.clip(every, one.start, one.end) == [(0.0, 20 * MS)]
    assert tracing.clip(every, two.start, two.end) == [(20 * MS, 26 * MS)]
    assert _read(reader, trace, "apply", "transfer") == pytest.approx((20 + 6) / 2)


def test_two_operations_give_the_median_of_each_and_other_operations_are_not_read(reader):
    short = _request(transfer=(1, 5), busy=((5, 18),))
    long = _request(base=20.0, transfer=(1, 15), busy=((8, 10), (15, 18)), index=1)
    fit = ([E("bench:fit", 40 * MS, 60 * MS, {"i": 0}), E("ks:h2d:transfer", 41 * MS, 59 * MS)], [])
    trace = _trace(short, long, fit)
    assert _read(reader, trace, "apply", "transfer") == pytest.approx((5 + 15) / 2)
    assert _read(reader, trace, "apply", "exposed") == pytest.approx((5 + 13) / 2)
    assert _read(reader, trace, "fit", "transfer") == pytest.approx(18.0)


def test_exposed_is_never_more_than_transfer_nor_than_the_operations_host_gap(reader):
    gap_reader = Bench(ROOT).load_module("readers", "host_gap_ms.py")
    for busy, transfer in [
        (((12, 18),), (1, 12)), (((2, 6), (12, 18)), (1, 12)), (((0, 20),), (1, 12)),
        ((), (1, 19)), (((12, 18),), (1, 30)),
    ]:
        trace = _trace(_request(busy=busy, transfer=transfer))
        if not busy:  # a device plane that ran nothing in the window: still a plane
            trace.device[TPU].append(E("fusion", 30 * MS, 31 * MS))
        exposed = _read(reader, trace, "apply", "exposed")
        assert 0.0 <= exposed <= _read(reader, trace, "apply", "transfer")
        assert exposed <= gap_reader.read(_Run(trace), {"span": "apply"}) + 1e-9


def test_on_several_chips_the_idle_time_is_the_busiest_chips(reader):
    host, device = _request()
    trace = tracing.Trace(
        device={TPU: device, "/device:TPU:1": [E("fusion", 5 * MS, 18 * MS)]},  # busier, and busy from 5
        host=host,
    )
    assert _read(reader, trace, "apply", "exposed") == pytest.approx(5.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none_and_never_raises(reader, name):
    span, what = NEW[name]
    operation = E("bench:" + span, 0, 20 * MS)
    busy = {TPU: [E("fusion", 12 * MS, 18 * MS)]}
    both = [E("ks:h2d", 0, 1 * MS), E("ks:h2d:transfer", 1 * MS, 12 * MS)]
    params = {"span": span, "what": what}
    assert reader.read(_Run(None), params) is None  # not a traced run
    assert reader.read(_Run(tracing.Trace({}, [operation] + both)), params) is None  # no device plane: the CPU
    assert reader.read(_Run(tracing.Trace(busy, both)), params) is None  # no such operation
    # the parent commit: it writes `ks:h2d` and no transfer span, and the enqueue says nothing of the copy
    assert reader.read(_Run(tracing.Trace(busy, [operation, both[0]])), params) is None
    assert reader.read(_Run(tracing.Trace(busy, [operation])), params) is None
    value = reader.read(_Run(tracing.Trace(busy, [operation] + both)), params)
    assert value == pytest.approx(12.0)


# ------------------------------------------------- the manifest's entries


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_resolves_to_its_file_and_reader_by_name(bench, name):
    span, what = NEW[name]
    entry = _named(bench.manifest["per_layer"], name)
    like = _named(bench.manifest["per_layer"], f"host_idle_ms.h2d.{span}")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", "program_span")
    assert (entry["layer"], entry["moves"]) == (like["layer"], like["moves"])
    assert entry["workloads"] == (SCORING if span == "apply" else FITTING)
    reporting = _named(bench.manifest["end_to_end"], entry["moves"])["workloads"]
    assert set(entry["workloads"]) <= set(reporting)
    spec = bench.layer_metric(name)
    assert (spec["reader"], spec["params"]) == ("h2d_ms", {"span": span, "what": what})
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        name, entry["unit"], entry["layer"], entry["moves"]
    )
    assert callable(bench.load_module("readers", "h2d_ms.py").read)


@pytest.mark.parametrize("cell", SCORING + FITTING)
def test_each_cell_reports_the_two_metrics_of_its_phase_and_not_the_others(bench, cell):
    reported = {m["name"] for m in bench.metrics_of("per_layer", cell)} & set(NEW)
    phase = "apply" if cell in SCORING else "fit"
    assert reported == {n for n in NEW if n.endswith("." + phase)}


def test_no_fit_metric_lists_a_scoring_cell(bench):
    """What tests/benchmark/test_bench_imagenet_cell.py's case of this
    name held before the manifest outgrew its other half."""
    for entry in bench.manifest["per_layer"] + bench.manifest["end_to_end"]:
        if entry["name"].endswith(".fit") or entry["name"] == "fit_rows_per_s":
            assert not set(SCORING) & set(entry["workloads"]), entry["name"]


# ----------------------- what the new span does to the reader that was there


def _idle_phases(bench, trace, names):
    idle = bench.load_module("readers", "host_idle_ms.py")
    return {
        name: idle.read(_Run(trace), bench.layer_metric(name)["params"]) for name in names
    }


APPLY_PHASES = [
    "host_idle_ms.bind.apply", "host_idle_ms.h2d.apply", "host_idle_ms.nodes.apply",
    "host_idle_ms.unlabelled.apply",
]


def test_the_transfer_takes_the_idle_time_no_later_span_covers_and_a_later_node_still_wins(bench):
    """A request of 20 ms: `ks:h2d` (0, 1); `node:Extract` (1, 3) dispatches;
    the host then sits in the benchmark's fetch, under no program span,
    while the upload arrives at 12; `node:Late` (8, 10) opens in between;
    the device runs (12, 18). The transfer (1, 12) is on another thread,
    which the reader never asks about."""
    host = [
        E("bench:apply", 0, 20 * MS, {"i": 0}),
        E("ks:h2d", 0, 1 * MS), E("ks:node:Extract", 1 * MS, 3 * MS),
        E("ks:node:Late", 8 * MS, 10 * MS), E("np.asarray(jax.Array)", 3 * MS, 20 * MS),
    ]
    device = {TPU: [E("fusion", 12 * MS, 18 * MS)]}
    before = _idle_phases(bench, tracing.Trace(device, list(host)), APPLY_PHASES)
    assert before == {
        "host_idle_ms.bind.apply": 0.0,
        "host_idle_ms.h2d.apply": pytest.approx(1.0),         # the enqueue alone
        "host_idle_ms.nodes.apply": pytest.approx(4.0),       # (1, 3) and (8, 10)
        "host_idle_ms.unlabelled.apply": pytest.approx(9.0),  # (3, 8), (10, 12), (18, 20)
    }
    watched = tracing.Trace(device, host + [E("ks:h2d:transfer", 1 * MS, 12 * MS)])
    after = _idle_phases(bench, watched, APPLY_PHASES)
    assert after == {
        "host_idle_ms.bind.apply": 0.0,
        "host_idle_ms.h2d.apply": pytest.approx(1.0 + 7.0),   # and (3, 8), (10, 12): uncovered before
        "host_idle_ms.nodes.apply": pytest.approx(4.0),       # both started after the transfer: unmoved
        "host_idle_ms.unlabelled.apply": pytest.approx(2.0),  # (18, 20): after the arrival
    }
    # no piece fell out of every phase: they add up to the host gap as before
    gap = bench.load_module("readers", "host_gap_ms.py").read(_Run(watched), {"span": "apply"})
    assert sum(after.values()) == pytest.approx(sum(before.values())) == pytest.approx(gap) == pytest.approx(14.0)
