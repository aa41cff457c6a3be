"""Device time one operation spends under one of the program's
`jax.named_scope`s, in milliseconds: the self time of the device's
`XLA Ops` events whose operation was traced under `params.scope`
(`feat/SIFTExtractor`, ...), per operation, mean over chips.

An operation's scope is no part of its event: it is the stat `tf_op` of
the event's METADATA (the HLO instruction's `op_name`,
`jit(fused_chain)/jit(main)/feat/SIFTExtractor/...`), which
`jax.profiler.ProfileData` does not show and the harness's `Trace` does
not keep, and the window's trace file is gone by the time a reader
runs. So this reader takes a short trace of its own after the window: it
asks the adapter for the cell's operation again (`probe(run)`: a function
of no arguments that runs one operation as the window ran them, the same
fitted model on the same inputs, so the programs the window ran), runs
it `PROBES` times under `jax.profiler`, and reads the raw `.xplane.pb`
once for every scope of this run. `params.span` names the kind of
operation, as for the other readers, and is what the probe's own spans
are called.

Nothing to read, and `None`: an untraced run, a backend with no device
plane (the CPU), an adapter with no `probe`, a profile without the
`tf_op` stat, or a device that ran nothing. A scope that no operation of
the probe carries reads 0.0.
"""

from __future__ import annotations

import os
import shutil

from benchmark.harness import trace as tracing

PROBES = 3
STAT = "tf_op"


def _scoped(path: str, window) -> dict:
    """{plane name: [(Event, tf_op), ...]} for the `XLA Ops` events of
    every device plane that start inside `window` (ns)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(tracing.DEVICE_PLANE_PREFIX):
            continue
        stat_ids = {i for i, m in plane.stat_metadata.items() if m.name == STAT}
        scope_of = {}
        for i, meta in plane.event_metadata.items():
            for stat in meta.stats:
                if stat.metadata_id in stat_ids:
                    scope_of[i] = stat.str_value or plane.stat_metadata[stat.ref_value].name
        for line in plane.lines:
            if line.name != tracing.DEVICE_OPS_LINE:
                continue
            events = out.setdefault(plane.name, [])
            for e in line.events:
                start = line.timestamp_ns + e.offset_ps / 1e3
                if window[0] <= start < window[1]:
                    event = tracing.Event(str(len(events)), start, start + e.duration_ps / 1e3)
                    events.append((event, scope_of.get(e.metadata_id, "")))
    return out


def self_ms_by_scope(planes: dict, operations: int) -> dict:
    """{tf_op: self milliseconds an operation, mean over chips}: an event
    is counted without the events nested in it (`tracing.self_times`; an
    event is named by its place in its plane's list here)."""
    by_scope: dict = {}
    for events in planes.values():
        for at, ns in tracing.self_times([e for e, _ in events]).items():
            scope = events[int(at)][1]
            by_scope[scope] = by_scope.get(scope, 0.0) + ns / len(planes) / operations / 1e6
    return by_scope


def under(by_scope: dict, scope: str) -> float:
    """The milliseconds of every `tf_op` that has `scope` among its path's
    whole names (`feat/SIFTExtractor` is not `feat/SIFT`)."""
    wanted = "/" + scope.strip("/") + "/"
    return sum(ms for found, ms in by_scope.items() if wanted in "/" + found + "/")


def _probe(run, span: str) -> dict | None:
    """One trace of `PROBES` operations, reduced to {tf_op: ms}."""
    import jax

    probe = getattr(run.sut, "probe", None)
    if run.reduction is None or not run.reduction.busy_by_chip or probe is None:
        return None
    operation = probe(run)
    if operation is None:
        return None
    trace_dir = os.path.join(run.state_dir, "trace", run.cell_name + ".scopes")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for i in range(PROBES):
            with jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + span, i=i):
                operation()
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xplane(trace_dir)
    spans = tracing.spans(tracing.read_xplane(path), span)
    planes = _scoped(path, (spans[0].start, max(s.end for s in spans))) if spans else {}
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not any(scope for events in planes.values() for _, scope in events):
        return None  # no operation, or a profile without `tf_op`: nothing to tell scopes by
    return self_ms_by_scope(planes, len(spans))


def read(run, params: dict):
    cache = run.__dict__.setdefault("_scope_ms", {})
    if params["span"] not in cache:
        cache[params["span"]] = _probe(run, params["span"])
    by_scope = cache[params["span"]]
    if by_scope is None:
        return None
    value = under(by_scope, params["scope"])
    run.say(
        f"scope {params['scope']} ({params['span']}): {value:.3f} ms an operation of "
        f"{sum(by_scope.values()):.3f} ms of device self time, over {PROBES} probes"
    )
    return value
