"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the last line.

The order matters and is fixed here: the device is proved first (no TPU,
no run); set-up ends where the first measured operation starts; peak
memory is read after the window and before the reference touches the
chip; the result is the last line of standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Optional

from . import trace as tracing
from .manifest import Bench, BenchmarkError
from .peaks import peaks_for

NO_DEVICE_EXIT = 3
GIVE_UP_AFTER = 3  # failed operations in a row end a window


@dataclass
class Sample:
    """One measured operation (a fit, a request) on the host's clock."""

    start: float  # time.perf_counter()
    end: float
    rows: int
    ok: bool = True


@dataclass
class Run:
    """What the drivers, the end-to-end metrics and the readers see."""

    bench: Bench
    cell_name: str
    workload: dict  # the manifest's entry
    cell: dict  # cells/<cell>.json
    config: dict  # the configuration as it is run
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    state_dir: str  # trace and reference answers, inside the checkout
    sut: Any = None  # configs/<config>_sut.py: the calls into the program
    reference: Any = None  # configs/<config>_ref.py
    cost: Any = None  # configs/<config>_cost.py
    device: dict = field(default_factory=dict)
    peaks: Optional[dict] = None
    samples: list = field(default_factory=list)
    setup_s: float = 0.0
    window_compiles: int = 0  # built or loaded from disk inside the window
    window_cache_loads: int = 0  # the part of them loaded from disk
    reduction: Optional[tracing.Reduction] = None

    def say(self, message: str) -> None:
        print(f"bench[{self.cell_name}]: {message}", flush=True)

    def span(self, name: str, index: int):
        """A span of the benchmark's own around one measured operation:
        in the profiler's trace when this run is traced, else nothing."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name, i=index)

    @property
    def completed(self) -> list:
        return [s for s in self.samples if s.ok]

    def rows_per_s(self) -> float:
        """Rows of all completed operations over the time from the first
        operation's start to the last completed one's end: all the work
        over all the time of the window."""
        done = self.completed
        return sum(s.rows for s in done) / (max(s.end for s in done) - self.samples[0].start)

    def closed_loop(self, name: str, rows: int, operation) -> tuple[list, Any]:
        """One client, for `seconds`: `operation(i)` again as soon as the
        last one returned, each under a span `name`. Returns the samples
        and the last completed operation's (result, index). An operation
        that raises counts as failed; `GIVE_UP_AFTER` in a row end the window."""
        samples, last, failures, i = [], None, 0, 0
        begin = time.perf_counter()
        while time.perf_counter() - begin < self.seconds and failures < GIVE_UP_AFTER:
            with self.span(name, i):
                start = time.perf_counter()
                try:
                    result, ok = operation(i), True
                except Exception:
                    traceback.print_exc()
                    result, ok = None, False
                end = time.perf_counter()
            samples.append(Sample(start, end, rows, ok))
            failures = 0 if ok else failures + 1
            if ok:
                last = (result, i)
            result = None
            i += 1
        return samples, last


def parse_args(argv) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="run one cell of BENCHMARK.json")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def main(argv, t0: float, root: str) -> int:
    args = parse_args(argv)
    if importlib.util.find_spec("keystone_tpu") is None:
        print("benchmark: the program (keystone_tpu) is not in this checkout: no result", file=sys.stderr)
        return 2
    try:
        return run_cell(
            Bench(root), args.workload, args.seed, args.seconds, bool(args.trace), t0
        )
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


def _device_facts(require_platform: str, chips: int) -> Optional[dict]:
    import jax

    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if facts["platform"] != require_platform or facts["count"] < chips:
        print(
            f"benchmark: needs {chips} {require_platform} device(s), JAX found "
            f"{facts['count']} of platform {facts['platform']!r}: no result",
            file=sys.stderr,
        )
        return None
    return facts


def _memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the
    backend reports no memory statistics, as the CPU does)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    return max((int(s.get("peak_bytes_in_use", 0)) for s in stats if s), default=0)


def _start_trace(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer's events are most of a trace and none are read
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def run_cell(
    bench: Bench,
    cell_name: str,
    seed: int,
    seconds: float,
    traced: bool,
    t0: float,
    require_platform: str = "tpu",
    state_dir: Optional[str] = None,
    out=None,
) -> int:
    """Run one cell and print its result line. Tests call this with
    `require_platform="cpu"` and a tiny tree; the command never does."""
    out = out or sys.stdout
    workload = bench.workload(cell_name)
    config = bench.config(workload["config"])
    traffic = bench.traffic(workload["traffic"])
    run = Run(
        bench=bench, cell_name=cell_name, workload=workload,
        cell=bench.cell(cell_name), config=config, traffic=traffic,
        seed=seed, seconds=seconds, traced=traced,
        state_dir=state_dir or os.path.join(bench.root, ".keystone_cache", "benchmark"),
    )
    driver = bench.load_module("drivers", traffic["kind"] + ".py")
    end_to_end = bench.metrics_of("end_to_end", cell_name)
    per_layer = bench.metrics_of("per_layer", cell_name)

    device = _device_facts(require_platform, workload["chips"])
    if device is None:
        return NO_DEVICE_EXIT
    run.device = device
    run.peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else None

    # The program's own cache set-up: JAX_COMPILATION_CACHE_DIR where it
    # is set, else the fixed .keystone_cache/xla-cache of the checkout.
    from keystone_tpu.utils.compilation_cache import (
        cache_hit_count,
        compile_count,
        enable_persistent_cache,
        install_compile_counter,
    )

    cache_dir = enable_persistent_cache()
    install_compile_counter()
    run.say(f"device {device}, compile cache {cache_dir}, seed {seed}")

    run.sut = bench.load_module("configs", config["files"]["sut"])
    run.reference = bench.load_module("configs", config["files"]["reference"])
    run.cost = bench.load_module("configs", config["files"]["cost"])

    state = driver.setup(run)
    built = compile_count() - cache_hit_count()
    run.say(f"set-up compiled {built} programs and loaded {cache_hit_count()} from the cache")

    trace_dir = os.path.join(run.state_dir, "trace", cell_name)
    if traced:
        _start_trace(trace_dir)
    compiles0, loads0 = compile_count(), cache_hit_count()
    run.setup_s = time.time() - t0
    try:
        run.samples = driver.window(run, state)
    finally:
        if traced:
            import jax

            jax.profiler.stop_trace()
    run.window_compiles = compile_count() - compiles0
    run.window_cache_loads = cache_hit_count() - loads0
    memory_peak = _memory_peak_bytes()  # before the reference runs
    done = run.completed
    walls = sorted(s.end - s.start for s in done)
    run.say(
        f"window: {len(run.samples)} operations, {len(done)} completed, "
        f"{run.window_compiles} programs compiled or loaded "
        f"({run.window_cache_loads} loaded), peak {memory_peak} bytes"
    )
    if walls:
        run.say(
            f"operation wall: n={len(walls)} min={walls[0]:.4f}s "
            f"median={walls[len(walls) // 2]:.4f}s max={walls[-1]:.4f}s"
        )

    if traced:
        run.reduction = tracing.reduce(tracing.read_xplane(tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = [run.reduction.busy_inside(s.start, s.end) for s in tracing.spans(run.reduction.trace)]
        if busy:  # flat across the window, or something grows
            run.say(
                f"device busy per operation: first={busy[0]:.4f}s last={busy[-1]:.4f}s "
                f"min={min(busy):.4f}s max={max(busy):.4f}s over {len(busy)}"
            )

    try:
        problems = driver.check(run, state) if done else ["no operation completed"]
    except Exception:
        traceback.print_exc()
        problems = ["the check against the reference raised (traceback above)"]
    for problem in problems:
        run.say(f"NOT CORRECT: {problem}")

    metrics: dict[str, dict] = {}
    if traced:
        for metric in per_layer:
            spec = bench.layer_metric(metric["name"])
            reader = bench.load_module("readers", spec["reader"] + ".py")
            value = reader.read(run, spec.get("params", {}))
            if value is not None:  # nothing to read: the metric is left out
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    elif done:
        for metric in end_to_end:
            module = bench.load_module("end_to_end", metric["name"] + ".py")
            metrics[metric["name"]] = {"value": module.value(run), "unit": metric["unit"]}

    device = dict(run.device, memory_peak_bytes=memory_peak)
    result = {
        "correct": not problems,
        "attempted": len(run.samples),
        "failed": len(run.samples) - len(done),
        "metrics": metrics,
        "device": device,
    }
    if run.reduction is not None:
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s
        result["breakdown"] = {
            "device_ops": run.reduction.device_ops,
            "idle_gaps": run.reduction.idle_gaps,
        }
    print(json.dumps(result), file=out, flush=True)
    return 0
