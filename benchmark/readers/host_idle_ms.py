"""The device's idle time inside each operation, put down to the phase of
the program that the host was in: median over operations, in
milliseconds, of the idle time that fell to `params.phase`.

For each operation (the benchmark's span `bench:<params.span>`) the idle
intervals of the busiest chip inside it are cut at the borders of the
program's own spans (host events named `ks:...`, which the program's span
layer writes into the profiler's trace) and each piece goes to the
innermost program span that covers it. `params.phase` is a list of name
prefixes; `null` asks for what no program span covered. The pieces of
one operation add up to that operation's span less its busy time, the
term `host_gap_ms` takes its median of.

Nothing to read, and `None`: no device plane (a CPU run), no such
operation, or a program that writes no `ks:` span at all (a commit from
before the bridge). The harness keeps host events of 0.5 ms or more, so
a shorter program span is not seen here and its time falls to the span
around it, or to `null`.
"""

import bisect
import statistics

from benchmark.harness import trace as tracing

PROGRAM_PREFIX = "ks:"


def innermost(program, lo, hi):
    """[lo, hi] as disjoint pieces (start, end, name) in order: `name` is
    the innermost of the `program` spans that cover the piece (the one
    that started last; of two that started together, the one that ends
    first), or `None` where none does."""
    inside = [e for e in program if e.end > lo and e.start < hi]
    borders = sorted({lo, hi, *(min(max(t, lo), hi) for e in inside for t in (e.start, e.end))})
    pieces = []
    for start, end in zip(borders, borders[1:]):
        covering = [e for e in inside if e.start <= start and e.end >= end]
        best = max(covering, key=lambda e: (e.start, -e.end), default=None)
        name = best.name if best is not None else None
        if pieces and pieces[-1][2] == name:
            pieces[-1] = (pieces[-1][0], end, name)
        else:
            pieces.append((start, end, name))
    return pieces


def idle_by_span(busy, program, operation):
    """{program span name or None: idle nanoseconds} inside one operation
    (an `Event`): what `busy` (one chip's disjoint, sorted busy intervals)
    leaves idle of it, by the innermost of the `program` spans."""
    pieces = innermost(program, operation.start, operation.end)
    starts = [p[0] for p in pieces]
    out = {}
    for lo, hi in tracing.gaps(busy, operation.start, operation.end):
        at = max(bisect.bisect_right(starts, lo) - 1, 0)
        while at < len(pieces) and pieces[at][0] < hi:
            start, end, name = pieces[at]
            overlap = min(end, hi) - max(start, lo)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
            at += 1
    return out


def _falls_to(name, phase) -> bool:
    if phase is None:
        return name is None
    return name is not None and name.startswith(tuple(phase))


def read(run, params: dict):
    r = run.reduction
    if r is None or not r.busy_by_chip:
        return None
    operations = tracing.spans(r.trace, params["span"])
    program = [e for e in r.trace.host if e.name.startswith(PROGRAM_PREFIX)]
    if not operations or not program:
        return None
    busiest = max(r.busy_by_chip.values(), key=tracing.total)
    phase = params["phase"]
    fell, idle = [], 0.0
    for operation in operations:
        by_span = idle_by_span(busiest, program, operation)
        idle += sum(by_span.values())
        fell.append(sum(ns for name, ns in by_span.items() if _falls_to(name, phase)))
    share = 100.0 * sum(fell) / idle if idle else 0.0
    value = statistics.median(fell) / 1e6
    run.say(
        f"host idle ({params['span']}, {'no program span' if phase is None else ' '.join(phase)}): "
        f"median {value:.3f} ms over {len(fell)} operations, {share:.1f}% of their idle time"
    )
    return value
