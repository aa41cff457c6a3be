"""keystone_tpu.obs — the unified observability layer.

One subsystem answering "where did this pipeline spend its time and
memory" end to end, replacing the three telemetry fragments the system
grew (flat per-op tracing, serving-local percentiles, the reliability
ledger's counts):

- :mod:`.spans` — hierarchical spans with trace ids, attributes, events,
  and cross-thread context handoff; every span is also a ``ks:<name>``
  annotation in the profiler's trace (the bridge), and nearly free when
  neither a session nor a profiler trace is active.
- :mod:`.metrics` — process-wide registry of labeled counters / gauges /
  histograms; :mod:`.names` declares the stable, tested name schema.
- :mod:`.device` — device/host memory sampling, per-stage peak
  attribution, the counted host-to-device upload (``h2d``).
- :mod:`.export` — Chrome trace-event JSON (Perfetto), Prometheus text,
  and a human span-tree report.
- :mod:`.store` — the persistent profile store: measurements keyed by
  structural digest + shape class + backend, persisted next to the XLA
  cache, consumed by the optimizer (autocache warm-start, measured
  knobs) and the bench-diff gate.
- :mod:`.benchdiff` — ``keystone-tpu bench-diff``: run-over-run BENCH
  comparison with a regression verdict.
- :mod:`.profile` — the ``keystone-tpu profile`` harness.

The package is stdlib-only at import time (jax is imported lazily inside
functions), so bench.py and the CLI can import it before any backend
initializes. See docs/OBSERVABILITY.md.
"""

from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    percentile,
    reset_registry,
)
from .spans import (
    NOOP_SPAN,
    Span,
    TraceSession,
    active_session,
    add_span_event,
    attach,
    current_context,
    current_span,
    record_span,
    span,
    tracing_session,
)
from .store import (
    ProfileStore,
    dataset_shape_class,
    get_store,
    set_store,
    shape_class,
    store_enabled,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "percentile", "reset_registry",
    "NOOP_SPAN", "Span", "TraceSession", "active_session", "add_span_event",
    "attach", "current_context", "current_span", "record_span", "span",
    "tracing_session",
    "ProfileStore", "get_store", "set_store", "store_enabled",
    "shape_class", "dataset_shape_class",
]
