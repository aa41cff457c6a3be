"""The yardstick's arithmetic, checked without a chip: the reduction of
a profiler trace (on a hand-built one in the v5e's layout), the cost
functions against hand-computed values, the peaks table, the statistics."""

import json
import os

import pytest

from bench_paths import DATA, ROOT

from benchmark.harness import stats
from benchmark.harness import trace as tracing
from benchmark.harness.manifest import Bench
from benchmark.harness.peaks import PEAKS, least_seconds, peaks_for


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "trace_two_fits.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    return tracing.reduce(tracing.read_profile(profile))


def test_trace_is_read_into_device_ops_and_host_spans(reduction):
    trace = reduction.trace
    assert list(trace.device) == ["/device:TPU:0"]
    assert len(trace.device["/device:TPU:0"]) == 5  # the "XLA Modules" line is not ops
    fits = tracing.spans(trace, "fit")
    assert [s.stats["i"] for s in fits] == [0, 1]
    assert [(s.start, s.end) for s in fits] == [(0.0, 10e6), (10e6, 20e6)]
    assert tracing.spans(trace, "apply") == []


def test_busy_union_does_not_count_nested_operations_twice(reduction):
    # while [1, 9) holds fusion.1 and fusion.2; then copy [12, 13), fusion.1 [15, 19)
    assert reduction.busy_s == pytest.approx(13e-3)
    assert reduction.window_s == pytest.approx(20e-3)
    assert reduction.busy_by_chip["/device:TPU:0"] == [(1e6, 9e6), (12e6, 13e6), (15e6, 19e6)]


def test_busy_time_inside_each_span(reduction):
    first, second = tracing.spans(reduction.trace, "fit")
    assert reduction.busy_inside(first.start, first.end) == pytest.approx(8e-3)
    assert reduction.busy_inside(second.start, second.end) == pytest.approx(5e-3)
    assert reduction.busy_inside(2e6, 12.5e6) == pytest.approx(7.5e-3)
    assert reduction.busy_inside(9e6, 12e6) == 0.0


def test_top_operations_are_by_self_time(reduction):
    assert reduction.device_ops == [
        ["fusion.1", pytest.approx(6e-3)],
        ["fusion.2", pytest.approx(5e-3)],
        ["while", pytest.approx(1e-3)],  # 8 ms long, 7 of them its body's
        ["copy.3", pytest.approx(1e-3)],
    ]


def test_idle_gaps_are_labelled_by_span_and_host_event(reduction):
    # [0, 1) [13, 15) [19, 20) fall in a fit with nothing else on the host;
    # [9, 12) is mostly under TransferToDevice [10, 11.9)
    assert reduction.idle_gaps == [
        ["fit", pytest.approx(4e-3)],
        ["fit: TransferToDevice", pytest.approx(3e-3)],
    ]
    assert sum(s for _, s in reduction.idle_gaps) == pytest.approx(
        reduction.window_s - reduction.busy_s
    )


def test_short_gaps_are_lumped_and_gaps_outside_spans_are_named():
    e = tracing.Event
    trace = tracing.Trace(
        device={"/device:TPU:0": [e("a", 0, 100), e("b", 200, 300), e("c", 5e6, 6e6)]},
        host=[e("bench:apply", 0, 1000)],
    )
    r = tracing.reduce(trace)
    assert r.window == (0, 1000)
    assert r.idle_gaps == [
        ["apply: gaps under 1 ms", pytest.approx(800e-9)],
    ]
    no_spans = tracing.reduce(tracing.Trace(device=trace.device, host=[]))
    assert no_spans.window == (0, 6e6)
    assert no_spans.idle_gaps[0] == ["between operations", pytest.approx((5e6 - 300) / 1e9)]


def test_a_trace_with_no_device_plane_reduces_to_nothing_busy():
    r = tracing.reduce(tracing.Trace(device={}, host=[tracing.Event("bench:fit", 0, 10)]))
    assert r.busy_s == 0.0 and r.device_ops == [] and r.idle_gaps == []
    assert r.busy_inside(0, 10) == 0.0


@pytest.mark.parametrize(
    "hlo,want",
    [
        (
            "%fusion.1885 = f32[4096,4096]{1,0:T(8,128)S(1)} fusion(f32[32768,16384]{1,0:T(8,128)} "
            "%get-tuple-element.2192, s32[]{:T(128)S(6)} %select_n.15), kind=kOutput",
            "%fusion.1885 fusion f32[4096,4096]",
        ),
        (
            "%iota_reduce_fusion = (bf16[65536]{0:T(1024)(128)(2,1)}, s32[65536]{0:T(1024)}) "
            "fusion(f32[16384,147]{0,1:T(8,128)} %constant.8), kind=kOutput",
            "%iota_reduce_fusion fusion (bf16[65536], s32[65536])",
        ),
        ("%cos.1 = f32[32768,4096]{1,0:T(8,128)} cosine(f32[32768,4096]{1,0:T(8,128)} %x.1)",
         "%cos.1 cosine f32[32768,4096]"),
        ("copy.3", "copy.3"),
        ("x" * 300, "x" * tracing.NAME_CHARS),
    ],
)
def test_operation_names_as_the_v5e_trace_gives_them_are_shortened(hlo, want):
    assert tracing.short_name(hlo) == want


@pytest.mark.parametrize(
    "intervals,want",
    [
        ([(0, 1), (1, 2)], [(0, 2)]),
        ([(5, 6), (0, 3), (2, 4)], [(0, 4), (5, 6)]),
        ([(0, 10), (2, 3)], [(0, 10)]),
        ([], []),
    ],
)
def test_union(intervals, want):
    assert tracing.union(intervals) == want


def test_gaps_and_clip():
    busy = [(2, 4), (6, 8)]
    assert tracing.gaps(busy, 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tracing.gaps(busy, 3, 7) == [(4, 6)]
    assert tracing.clip(busy, 3, 7) == [(3, 4), (6, 7)]
    assert tracing.total(busy) == 4


class _Run:
    """What a reader sees, with a reduction and nothing else real."""

    def __init__(self, reduction, bench, config_name, rows, compiles=0):
        self.reduction = reduction
        self.peaks = peaks_for("TPU v5 lite")
        self.config = bench.config(config_name)
        self.cost = bench.load_module("configs", self.config["files"]["cost"])
        self.window_compiles = compiles
        sample = type("S", (), {"rows": rows, "ok": True})
        self.samples = [sample, sample]
        self.completed = self.samples

    def say(self, message):
        pass


READERS = {
    # median of (10 - 8, 10 - 5) ms
    "host_gap_ms.fit": 3.5,
    # 1 - 13 / 20
    "device_idle_pct.fit": 35.0,
    "window_compiles.fit": 1.5,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_the_hand_built_trace(name, reduction, bench):
    spec = bench.layer_metric(name)
    reader = bench.load_module("readers", spec["reader"] + ".py")
    run = _Run(reduction, bench, "timit-rf16k", 32768, compiles=3)
    assert reader.read(run, spec.get("params", {})) == pytest.approx(READERS[name])


def test_roofline_reader_divides_least_time_by_busy_time_per_operation(reduction, bench):
    spec = bench.layer_metric("kernel_roofline_pct.fit")
    reader = bench.load_module("readers", spec["reader"] + ".py")
    run = _Run(reduction, bench, "timit-rf16k", 32768)
    cost = run.cost.fit_cost(run.config, 32768)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # 13 ms busy over two fits
    assert reader.read(run, spec["params"]) == pytest.approx(100 * least / 6.5e-3)


@pytest.mark.parametrize("name", ["host_gap_ms.apply", "kernel_roofline_pct.apply"])
def test_a_reader_that_finds_nothing_returns_nothing(name, reduction, bench):
    """The hand-built trace has no `apply` span: the metric is left out."""
    spec = bench.layer_metric(name)
    reader = bench.load_module("readers", spec["reader"] + ".py")
    assert reader.read(_Run(reduction, bench, "timit-rf16k", 65536), spec["params"]) is None
    assert reader.read(_Run(None, bench, "timit-rf16k", 65536), spec["params"]) is None


# ------------------------------------------------------------------ costs

SMALL_TIMIT = {
    "input_dim": 10, "num_cosines": 2, "num_cosine_features": 8,
    "num_classes": 3, "block_size": 4, "num_epochs": 2,
}
SMALL_CIFAR = {
    "image_size": 8, "num_channels": 3, "num_filters": 5, "patch_size": 3,
    "pool_size": 4, "pool_stride": 3, "num_classes": 2, "block_size": 16,
    "num_epochs": 1,
}


def _cost(name):
    return Bench(ROOT).load_module("configs", name + "_cost.py")


def test_timit_fit_cost_by_hand():
    # n = 100 rows, d = 16 features in 4 blocks of 4, 2 epochs: 8 block steps
    featurize = 2 * 100 * 10 * 16
    per_step = 2 * 100 * 4 * 4 + 3 * (2 * 100 * 4 * 3) + 4 ** 3 / 3 + 2 * 4 * 4 * 3
    cost = _cost("timit-rf16k").fit_cost(SMALL_TIMIT, 100)
    assert cost["flops"] == pytest.approx(featurize + 8 * per_step)
    assert cost["bytes"] == 4 * (100 * 10 + 100 * 3 + 100 * 16 + 8 * 100 * 4 + 16 * 3)


def test_timit_apply_cost_by_hand():
    cost = _cost("timit-rf16k").apply_cost(SMALL_TIMIT, 100)
    assert cost["flops"] == 2 * 100 * 10 * 16 + 2 * 100 * 16 * 3
    assert cost["bytes"] == 4 * (100 * 10 + 10 * 16 + 16 + 16 * 3 + 3 + 100)


def test_cifar_fit_cost_by_hand():
    # 8x8 images, 3x3 patches: 6x6 = 36 positions of 27 values; pools of 4
    # stride 3 centred at 2 and 5: 2x2 pools x 2 signs x 5 filters = 40
    # features, padded to 3 blocks of 16
    featurize = 2 * 50 * 36 * 27 * 5
    per_step = 2 * 50 * 16 * 16 + 3 * (2 * 50 * 16 * 2) + 16 ** 3 / 3 + 2 * 16 * 16 * 2
    cost = _cost("cifar-rp10k").fit_cost(SMALL_CIFAR, 50)
    assert cost["flops"] == pytest.approx(featurize + 3 * per_step)
    assert cost["bytes"] == 4 * (50 * 192 + 50 * 2 + 2 * 50 * 40 + 3 * 50 * 16 + 48 * 2)


@pytest.mark.parametrize(
    "name,function,rows,flops",
    [
        # 4.7e11 of featurizing, 2.2e13 of Gram (as ISSUE 24 reckons them), 3e12 of the rest
        ("timit-rf16k", "fit_cost", 32768, 2.54e13),
        ("timit-rf16k", "apply_cost", 65536, 1.26e12),
        # 6.4e12 of convolution and 2.7e12 of Gram at 4096 rows
        ("cifar-rp10k", "fit_cost", 4096, 9.7e12),
    ],
)
def test_cost_of_the_real_cells_is_of_the_reckoned_size(name, function, rows, flops):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    cost = getattr(_cost(name), function)(config, rows)
    assert cost["flops"] == pytest.approx(flops, rel=0.05)
    least, bound = least_seconds(cost["flops"], cost["bytes"], peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(cost["flops"] / 197e12)


# ------------------------------------------------------- peaks, statistics


def test_peaks_are_the_published_v5e_numbers_with_their_source():
    v5e = peaks_for("TPU v5 lite")
    assert (v5e["flops_per_s"], v5e["bytes_per_s"], v5e["memory_bytes"]) == (197e12, 819e9, 16e9)
    assert all(p["source"] for p in PEAKS.values())


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(LookupError, match="TPU v9"):
        peaks_for("TPU v9")


def test_least_seconds_says_which_bound_applies():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert least_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert least_seconds(10.0, 1000.0, peaks) == (100.0, "memory")


@pytest.mark.parametrize(
    "values,q,want",
    [([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 95, 3.85), ([7], 95, 7), ([4, 1, 3, 2], 0, 1)],
)
def test_percentile_interpolates_between_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
