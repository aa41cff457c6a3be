"""Median over operations of (the operation's span - the device's busy
time inside that span), in milliseconds: what the host code around the
kernels costs each operation. The span is the benchmark's own
TraceAnnotation (`params.span`) around its call into the program."""

import statistics

from benchmark.harness import trace as tracing


def read(run, params: dict):
    if run.reduction is None:
        return None
    spans = tracing.spans(run.reduction.trace, params["span"])
    if not spans or not run.reduction.busy_by_chip:
        return None
    gaps = [
        (s.end - s.start) / 1e9 - run.reduction.busy_inside(s.start, s.end)
        for s in spans
    ]
    return 1e3 * statistics.median(gaps)
