"""From the profiler's `.xplane.pb` to busy time, idle share, top
operations and idle gaps by span.

Two halves, so that the arithmetic is checked without a chip: `read_xplane`
turns the file into plain tuples (through `jax.profiler.ProfileData`,
nothing but JAX), and everything else works on those tuples.

What the trace of a TPU looks like (read on the v5e in PR 24, jax 0.9.0):
one plane per chip named `/device:TPU:<n>`, whose line `XLA Ops` holds one
event per HLO operation run, named by the operation's whole HLO text and
nested where an operation (a `while`) runs others (the lines `XLA Modules`
and `Async XLA Ops` beside it are not read); and one plane `/host:CPU` with
a line per host thread, which holds `jax.profiler.TraceAnnotation` spans
under the names given them. Both are on one clock, in nanoseconds from the
start of the trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
TOP = 10  # entries kept in each list of the breakdown

NAME_CHARS = 120  # of an operation's or a gap's name in the breakdown
SHORT_GAP_NS = 1e6  # idle gaps under a millisecond are lumped by span
_HLO = re.compile(r"^(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")

Interval = tuple[float, float]  # start, end, in nanoseconds


def short_name(hlo: str) -> str:
    """`%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(f32[...] %x), kind=...`
    as `%fusion.7 fusion f32[8,128]`: the name, the opcode and the
    result's shape without its layout."""
    match = _HLO.match(hlo)
    if not match:
        return hlo[:NAME_CHARS]
    result = re.sub(r"\{[^}]*\}", "", match.group(2))
    return f"{match.group(1)} {match.group(3)} {result}"[:NAME_CHARS]


@dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict = field(default_factory=dict)


@dataclass
class Trace:
    device: dict[str, list[Event]]  # plane name -> events of its op line
    host: list[Event]  # every event of the host plane's lines


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_profile(profile) -> Trace:
    """A `jax.profiler.ProfileData` as plain tuples."""
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device[plane.name] = [
                        Event(short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    # the host plane holds millions of short events (futex
                    # waits); only one of half a shortest labelled gap or
                    # more can name a gap
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                    elif e.duration_ns >= SHORT_GAP_NS / 2:
                        host.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns))
    return Trace(device, host)


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    return read_profile(ProfileData.from_file(path))


# ------------------------------------------------------------- arithmetic


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list[Interval]:
    out = []
    for start, end in intervals:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """What `busy` (disjoint, sorted) leaves uncovered of [lo, hi]."""
    out, at = [], lo
    for start, end in clip(busy, lo, hi):
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: list[Event]) -> dict[str, float]:
    """Nanoseconds by operation name, each event counted without the
    events nested in it (a `while` does not count its body twice)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [event, time covered by its direct children]

    def close(entry):
        event, covered = entry
        out[event.name] = out.get(event.name, 0.0) + (event.end - event.start) - covered

    for event in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and stack[-1][0].end <= event.start:
            close(stack.pop())
        if stack:
            stack[-1][1] += min(event.end, stack[-1][0].end) - event.start
        stack.append([event, 0.0])
    while stack:
        close(stack.pop())
    return out


def spans(trace: Trace, name: Optional[str] = None) -> list[Event]:
    """The benchmark's own spans on the host plane, in order of start;
    `name` is the part after the prefix (`fit`, `apply`)."""
    want = SPAN_PREFIX + name if name else None
    found = [
        e for e in trace.host
        if e.name.startswith(SPAN_PREFIX) and (want is None or e.name == want)
    ]
    return sorted(found, key=lambda e: e.start)


@dataclass
class Reduction:
    """What the metrics read from one traced window."""

    window: Interval  # ns
    busy_by_chip: dict[str, list[Interval]]  # clipped to the window
    busy_s: float  # mean over chips
    window_s: float
    device_ops: list[list]  # [[name, seconds], ...] by self time, mean over chips
    idle_gaps: list[list]  # [[label, seconds], ...] on the busiest chip's timeline
    trace: Trace
    _ends: dict = field(default_factory=dict, repr=False)

    def busy_inside(self, start: float, end: float) -> float:
        """Mean over chips of the seconds of [start, end] (ns) in which
        an operation ran on the device."""
        if not self.busy_by_chip:
            return 0.0
        per_chip = []
        for plane, busy in self.busy_by_chip.items():
            ends = self._ends.setdefault(plane, [e for _, e in busy])
            first = bisect.bisect_right(ends, start)  # first interval that ends after `start`
            covered = 0.0
            for lo, hi in busy[first:]:
                if lo >= end:
                    break
                covered += min(hi, end) - max(lo, start)
            per_chip.append(covered)
        return sum(per_chip) / len(per_chip) / 1e9


def _label(gap: Interval, bench_spans: list[Event], starts: list[float], host: list[Event]) -> str:
    """Where an idle gap falls: the benchmark span that holds its middle
    and, for a gap of a millisecond or more, the host event (other than a
    benchmark span) that overlaps most of it, which says what the host
    was doing."""
    mid = (gap[0] + gap[1]) / 2
    at = bisect.bisect_right(starts, mid) - 1
    inside = at >= 0 and mid < bench_spans[at].end
    where = bench_spans[at].name[len(SPAN_PREFIX):] if inside else "between operations"
    if gap[1] - gap[0] < SHORT_GAP_NS:
        return f"{where}: gaps under 1 ms"
    best, best_overlap = None, 0.0
    for e in host:
        overlap = min(e.end, gap[1]) - max(e.start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = e, overlap
    if best is not None and best_overlap >= 0.5 * (gap[1] - gap[0]):
        return f"{where}: {best.name}"
    return where


def reduce(trace: Trace) -> Reduction:
    """Busy union, idle share, top operations and idle gaps of the traced
    window: from the first benchmark span's start to the last one's end,
    or the extent of the device events where there is no span."""
    bench_spans = spans(trace)
    every = [e for events in trace.device.values() for e in events]
    if bench_spans:
        window = (bench_spans[0].start, max(s.end for s in bench_spans))
    elif every:
        window = (min(e.start for e in every), max(e.end for e in every))
    else:
        window = (0.0, 0.0)
    busy_by_chip = {
        plane: clip(union((e.start, e.end) for e in events), *window)
        for plane, events in trace.device.items()
    }
    chips = max(len(busy_by_chip), 1)
    busy_s = sum(total(b) for b in busy_by_chip.values()) / chips / 1e9

    by_name: dict[str, float] = {}
    for events in trace.device.values():
        inside = [e for e in events if e.end > window[0] and e.start < window[1]]
        for name, ns in self_times(inside).items():
            by_name[name] = by_name.get(name, 0.0) + ns / chips / 1e9
    device_ops = [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]]

    by_label: dict[str, float] = {}
    if busy_by_chip:
        busiest = max(busy_by_chip.values(), key=total)
        starts = [s.start for s in bench_spans]
        others = [e for e in trace.host if not e.name.startswith(SPAN_PREFIX)]
        for gap in gaps(busiest, *window):
            label = _label(gap, bench_spans, starts, others)[:NAME_CHARS]
            by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0]) / 1e9
    idle_gaps = [[n, s] for n, s in sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]]

    return Reduction(
        window=window,
        busy_by_chip=busy_by_chip,
        busy_s=busy_s,
        window_s=(window[1] - window[0]) / 1e9,
        device_ops=device_ops,
        idle_gaps=idle_gaps,
        trace=trace,
    )
