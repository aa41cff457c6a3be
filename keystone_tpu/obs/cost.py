"""Cost observatory: measured-vs-modeled accounting for every compiled
node the plan executes.

Four cost models drive decisions in this tree — the solver ladder's
``CostModel`` rungs, ``AutoCacheRule``'s per-node linear fits,
``MeasuredKnobRule``'s recorded winners, and the tuner's ridge model —
and before this module nothing ever checked a prediction against what
XLA actually executed. A drifting model silently degrades every
decision downstream (the ROADMAP's measure-or-delete discipline). This
module closes that loop:

- **Harvest** — ``jax.stages.Lowered.cost_analysis()`` gives per-program
  flop and byte counts. ``jitted.lower(*args)`` after the function has
  executed hits the jit's trace cache: the function body does not run
  again and nothing reaches the backend compiler (re-checked on jax
  0.9.0; ``keystone_cost_harvest_compiles_total`` counts any
  violation of that invariant and must stay 0 — the explain smoke gates
  it). ``cost_analysis`` can return ``None``, a list, or a dict with
  missing keys depending on backend — every read is guarded here, and a
  KV506 lint rule keeps *all* ``cost_analysis()`` call sites in this
  module so the guarding lives exactly once.
- **Roofline** — a tiny probe pair (one matmul, one copy) measures this
  process's achievable FLOP/s and bytes/s once, cached in the
  ProfileStore under ``roofline:<backend>`` so later processes skip the
  probe. Each harvested node is classified compute-bound or
  memory-bound by its arithmetic intensity against the ridge point.
- **Perf ledger** — ``workflow/tracing.timed_execute`` opens a harvest
  frame around each node's forcing; operators note their jitted
  computations into it (fused chains, streaming chunk steps); the frame
  is finalized into one :class:`PerfLedgerEntry` joining predicted cost
  (whichever model drove the decision), measured wall, achieved rates,
  intensity, and roofline placement. Entries ride flight-recorder dumps
  and export as Perfetto counter tracks (obs/export.py).
- **Drift sentinel** — predicted-vs-measured per ``(key, shape class)``
  with a noise-tolerant ratio test (symmetric band, consecutive-miss
  sustain). Sustained drift publishes ``keystone_cost_drift_*`` metrics,
  lands a ``cost_drift`` recovery-ledger event (which the flight
  recorder rings), and marks the offending ProfileStore entry
  ``stale:`` so ``AutoCacheRule``/``MeasuredKnobRule`` re-measure
  instead of replaying a stale winner. Only *calibrated* predictions —
  ones measured under the exact (key, shape class) they are compared at
  (autocache fits, measured-knob stream winners) — are drift-scored;
  the solver ladder's constants are relative (its argmin is what
  matters), so its predictions are displayed but never flagged.

Everything is off unless the observatory is enabled
(``KEYSTONE_COST_OBS=1`` or :func:`set_cost_observatory`): harvesting
re-lowers nothing on cache hits, but the no-op path must stay a single
thread-local read for serving hot paths. The explain CLI
(``keystone-tpu explain``, workflow/explain.py), ``keystone-tpu
profile``, and bench legs turn it on for their runs.

Stdlib-only at import, like the rest of ``obs/``. docs/OBSERVABILITY.md
"Cost observatory" documents the ledger schema, calibration, and the
drift knobs.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..envknobs import env_flag, env_float, env_int
from . import names as _names
from . import spans as _spans

logger = logging.getLogger(__name__)

#: Facts cache bound: one entry per (jitted fn, input signature) —
#: generously above the live executable count of any real process.
_FACTS_CACHE_MAX = 256

#: Perf-ledger ring bound (overridable per-instance).
_LEDGER_MAX_DEFAULT = 256


# ------------------------------------------------------------------ enablement

_enabled_override: Optional[bool] = None
_enabled_lock = threading.Lock()


def cost_observatory_enabled() -> bool:
    """Master switch: ``set_cost_observatory()`` override, else the
    ``KEYSTONE_COST_OBS`` env flag (default OFF — harvesting re-traces
    nothing on cache hits, but the observatory is an analysis plane, not
    a steady-state tax; explain/profile/bench enable it for their runs)."""
    if _enabled_override is not None:
        return _enabled_override
    return env_flag("KEYSTONE_COST_OBS", False)


def set_cost_observatory(value: Optional[bool]) -> None:
    """Force the observatory on/off process-wide; ``None`` restores the
    env default."""
    global _enabled_override
    with _enabled_lock:
        _enabled_override = value


def drift_ratio_tolerance() -> float:
    """Symmetric ratio band half-width: a prediction is in-band while
    ``max(ratio, 1/ratio) <= tol``. Default 4.0 — sub-second CPU walls
    on a loaded box swing ~4× run to run (docs/OBSERVABILITY.md), and a
    drift gate tighter than the noise floor would cry wolf."""
    return max(1.0, env_float("KEYSTONE_COST_DRIFT_RATIO", 4.0))


def drift_sustain() -> int:
    """Consecutive out-of-band observations of one (key, shape) before
    the sentinel fires (``KEYSTONE_COST_DRIFT_SUSTAIN``, default 2)."""
    return max(1, env_int("KEYSTONE_COST_DRIFT_SUSTAIN", 2))


def drift_enabled() -> bool:
    return env_flag("KEYSTONE_COST_DRIFT", True)


# ----------------------------------------------------------------- predictions


@dataclass(frozen=True)
class Prediction:
    """One model's cost claim for a node, carried to the ledger join.

    ``calibrated`` marks predictions measured under the exact
    (key, shape class) they will be compared at — only those are
    drift-scored. ``seconds`` and ``rows_per_s`` are alternative units;
    whichever is set is what the sentinel compares."""

    model: str  # solver_ladder | autocache | measured_knob | tune | roofline
    key: str = ""  # the ProfileStore key that backed it ("" = none)
    shape: str = ""  # the shape class it was recorded under
    seconds: Optional[float] = None
    rows_per_s: Optional[float] = None
    calibrated: bool = False
    source: str = "observed"  # store provenance (observed | tune)
    #: Every candidate an argmin choice considered, as (name,
    #: seconds-or-None, reason) tuples — "chosen" for the winner,
    #: the rejection reason otherwise. Lets explain audit the whole
    #: ladder, not just the surviving rung.
    candidates: Tuple = ()


# Plan-scoped prediction book: node label → Prediction, filled by the
# optimizer passes that predict per-NODE costs (AutoCacheRule's linear
# fits) and read back by finalize_node when the executed operator has no
# pinned prediction of its own. Label-keyed (labels can collide across
# plans) — best-effort attribution, reset per plan by the harnesses.
_plan_predictions: Dict[str, Prediction] = {}
_plan_lock = threading.Lock()


def note_plan_prediction(label: str, prediction: Prediction) -> None:
    if not cost_observatory_enabled():
        return
    with _plan_lock:
        _plan_predictions[str(label)] = prediction


def reset_plan_predictions() -> None:
    with _plan_lock:
        _plan_predictions.clear()


def plan_prediction(label: str) -> Optional[Prediction]:
    with _plan_lock:
        return _plan_predictions.get(str(label))


# -------------------------------------------------------------------- harvest


@dataclass(frozen=True)
class CostFacts:
    """What one compiled program is, per XLA: flop count, bytes
    accessed, and the lowering digest (sha1 of the StableHLO text) that
    joins ledger entries to spans and ProfileStore keys
    deterministically."""

    flops: Optional[float]
    bytes_accessed: Optional[float]
    lowering_digest: str = ""

    @property
    def intensity(self) -> Optional[float]:
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed


# (id(fn), signature) → (fn strong ref, CostFacts). The ref pins the id
# against recycling, same discipline as fusion's chain-jit cache.
_facts_cache: "OrderedDict[Tuple[int, str], Tuple[Any, Optional[CostFacts]]]" = (
    OrderedDict()
)
_facts_lock = threading.Lock()


def _aval_signature(tree: Any) -> str:
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            shape = tuple(leaf.shape)
            dtype = getattr(leaf.dtype, "name", str(leaf.dtype))
            parts.append(f"{dtype}{list(shape)}")
        else:
            # Static/python operands (epoch counts, block sizes) are part
            # of the compiled identity — different values, different
            # programs, different flop counts.
            parts.append(repr(leaf)[:32])
    return ";".join(parts)


def _normalize_cost_analysis(raw: Any) -> Tuple[Optional[float], Optional[float]]:
    """Flops / bytes-accessed out of whatever shape ``cost_analysis``
    returned: None, a dict, or a list of per-program dicts (backends
    differ; CPU returns both keys, some TPU paths return partial or
    nothing). Missing or negative values degrade to None, never raise."""
    entries: Sequence[Any]
    if raw is None:
        return None, None
    if isinstance(raw, dict):
        entries = [raw]
    elif isinstance(raw, (list, tuple)):
        entries = [e for e in raw if isinstance(e, dict)]
    else:
        return None, None
    flops = 0.0
    bytes_accessed = 0.0
    saw_flops = saw_bytes = False
    for entry in entries:
        f = entry.get("flops")
        b = entry.get("bytes accessed")
        if isinstance(f, (int, float)) and f >= 0:
            flops += float(f)
            saw_flops = True
        if isinstance(b, (int, float)) and b >= 0:
            bytes_accessed += float(b)
            saw_bytes = True
    return (flops if saw_flops else None), (bytes_accessed if saw_bytes else None)


def _harvest_compile_counter():
    return _names.metric(_names.COST_HARVEST_COMPILES)


def harvest_cost_facts(fn: Any, args: Any = None) -> Optional[CostFacts]:
    """Flop/byte facts for one compiled computation — THE sanctioned
    ``cost_analysis()`` call site (lint rule KV506 flags any other).

    ``fn`` is a ``jax.stages.Compiled``, a ``jax.stages.Lowered``, or a
    jitted callable (then ``args`` — concrete arrays or
    ``ShapeDtypeStruct`` avals — selects the signature and
    ``fn.lower(*args)`` resolves through the jit trace cache: zero
    backend compiles when the signature already executed, asserted by
    ``keystone_cost_harvest_compiles_total``). Any failure returns None
    — a backend without cost analysis must not break a fit."""
    from ..utils.compilation_cache import compile_count

    before = compile_count()
    facts: Optional[CostFacts] = None
    try:
        lowered = fn
        if hasattr(fn, "lower") and not hasattr(fn, "cost_analysis"):
            lowered = fn.lower(*tuple(args or ()))
        raw = lowered.cost_analysis()  # the ONE call site (KV506)
        flops, bytes_accessed = _normalize_cost_analysis(raw)
        digest = ""
        try:
            text = lowered.as_text()
            digest = hashlib.sha1(text.encode()).hexdigest()[:16]
        except Exception:
            pass
        facts = CostFacts(flops, bytes_accessed, digest)
    except Exception as e:
        logger.debug("cost harvest failed (%s)", e)
        facts = None
    extra = compile_count() - before
    if extra > 0:
        # The zero-extra-compiles invariant broke (a signature was
        # lowered before it ever executed, or AOT drifted) — count it
        # loudly; the explain smoke asserts this stays 0.
        _harvest_compile_counter().inc(extra)
    return facts


def _cached_facts(fn: Any, args: Any = None, avals: Any = None) -> Optional[CostFacts]:
    """Facts for (fn, signature) through the bounded cache — the steady
    state pays one dict lookup per node execution."""
    try:
        sig = _aval_signature(avals if avals is not None else args)
    except Exception:
        return None
    key = (id(fn), sig)
    with _facts_lock:
        hit = _facts_cache.get(key)
        if hit is not None:
            _facts_cache.move_to_end(key)
            return hit[1]
    facts = harvest_cost_facts(fn, avals if avals is not None else args)
    with _facts_lock:
        _facts_cache[key] = (fn, facts)
        _facts_cache.move_to_end(key)
        while len(_facts_cache) > _FACTS_CACHE_MAX:
            _facts_cache.popitem(last=False)
    return facts


# ------------------------------------------------------------------- roofline


@dataclass(frozen=True)
class Roofline:
    """Per-backend achievable peaks, probe-measured (docs/OBSERVABILITY.md
    "Cost observatory"): the ridge point ``peak_flops/peak_bytes``
    separates compute-bound from memory-bound intensities."""

    peak_flops_per_s: float
    peak_bytes_per_s: float
    backend: str = "unknown"
    source: str = "probe"  # probe | store

    @property
    def ridge_intensity(self) -> float:
        if self.peak_bytes_per_s <= 0:
            return float("inf")
        return self.peak_flops_per_s / self.peak_bytes_per_s

    def classify(self, intensity: Optional[float]) -> Optional[str]:
        if intensity is None:
            return None
        return (
            "compute-bound" if intensity >= self.ridge_intensity
            else "memory-bound"
        )

    def predicted_seconds(
        self, flops: Optional[float], bytes_accessed: Optional[float]
    ) -> Optional[float]:
        """First-principles roofline time: max of the compute and the
        memory floor — the fallback prediction for nodes no model
        claimed."""
        terms = []
        if flops and self.peak_flops_per_s > 0:
            terms.append(flops / self.peak_flops_per_s)
        if bytes_accessed and self.peak_bytes_per_s > 0:
            terms.append(bytes_accessed / self.peak_bytes_per_s)
        return max(terms) if terms else None

    def to_json(self) -> Dict[str, Any]:
        return {
            "peak_flops_per_s": self.peak_flops_per_s,
            "peak_bytes_per_s": self.peak_bytes_per_s,
            "ridge_intensity": self.ridge_intensity,
            "backend": self.backend,
            "source": self.source,
        }


ROOFLINE_SHAPE = "probe:v1"

_roofline: Optional[Roofline] = None
_roofline_lock = threading.Lock()


def _roofline_store_key(backend: str) -> str:
    return f"roofline:{backend}"


def _probe_roofline(backend: str) -> Optional[Roofline]:
    """Measure achievable peaks with one matmul (compute roof) and one
    copy-scale (bandwidth roof): warm once, min-of-3 timed — ambient
    load inflates walls, never deflates them, so min-of-N is the
    honest calibration on a shared box. Flop/byte counts come from the
    probes' own harvested facts (self-consistent units)."""
    try:
        import jax
        import jax.numpy as jnp

        n = 384
        a = jnp.ones((n, n), jnp.float32)
        matmul = jax.jit(lambda x: x @ x)
        big = jnp.ones((4 * 1024 * 1024,), jnp.float32)  # 16 MiB
        copy = jax.jit(lambda x: x * 1.00001 + 1.0)

        def timed(fn, arg) -> float:
            fn(arg).block_until_ready()  # warm/compile
            walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn(arg).block_until_ready()
                walls.append(time.perf_counter() - t0)
            return max(min(walls), 1e-9)

        mat_wall = timed(matmul, a)
        copy_wall = timed(copy, big)
        mat_facts = harvest_cost_facts(matmul, (a,))
        copy_facts = harvest_cost_facts(copy, (big,))
        flops = (mat_facts and mat_facts.flops) or float(2 * n**3)
        traffic = (copy_facts and copy_facts.bytes_accessed) or float(
            2 * big.size * 4
        )
        return Roofline(
            peak_flops_per_s=flops / mat_wall,
            peak_bytes_per_s=traffic / copy_wall,
            backend=backend,
            source="probe",
        )
    except Exception as e:
        logger.warning("roofline probe failed (%s)", e)
        return None


def get_roofline(refresh: bool = False) -> Optional[Roofline]:
    """The process roofline: cached in-process, warm-started from the
    ProfileStore's ``roofline:<backend>`` entry (fingerprinted like any
    other measurement), probe-measured and recorded back on a cold
    store. None when no backend is importable."""
    global _roofline
    if _roofline is not None and not refresh:
        return _roofline
    with _roofline_lock:
        if _roofline is not None and not refresh:
            return _roofline
        from . import store as _store

        backend = _store.environment_fingerprint()["backend"]
        store = _store.get_store()
        if store is not None and not refresh:
            m = store.lookup(_roofline_store_key(backend), ROOFLINE_SHAPE)
            if m and m.get("peak_flops_per_s") and m.get("peak_bytes_per_s"):
                _roofline = Roofline(
                    float(m["peak_flops_per_s"]),
                    float(m["peak_bytes_per_s"]),
                    backend=backend,
                    source="store",
                )
                _publish_roofline(_roofline)
                return _roofline
        probed = _probe_roofline(backend)
        if probed is None:
            return None
        if store is not None:
            store.record(
                _roofline_store_key(backend),
                ROOFLINE_SHAPE,
                peak_flops_per_s=probed.peak_flops_per_s,
                peak_bytes_per_s=probed.peak_bytes_per_s,
            )
        _roofline = probed
        _publish_roofline(probed)
        return probed


def _publish_roofline(roofline: Roofline) -> None:
    gauge = _names.metric(_names.COST_ROOFLINE_PEAK)
    gauge.set(roofline.peak_flops_per_s, resource="flops_per_s")
    gauge.set(roofline.peak_bytes_per_s, resource="bytes_per_s")


def set_roofline(roofline: Optional[Roofline]) -> None:
    """Pin a roofline (tests); None drops the cache so the next
    :func:`get_roofline` re-resolves."""
    global _roofline
    with _roofline_lock:
        _roofline = roofline


# ------------------------------------------------------------------ the ledger


@dataclass
class PerfLedgerEntry:
    """One node execution, measured and attributed — the perf ledger's
    record (docs/OBSERVABILITY.md "Cost observatory" schema)."""

    node: str
    seconds: float
    synced: bool
    t_s: float  # perf_counter at finalize (session-relative export anchor)
    t_unix: float
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    intensity: Optional[float] = None
    flops_per_s: Optional[float] = None
    bytes_per_s: Optional[float] = None
    roofline: Optional[str] = None  # compute-bound | memory-bound | None
    bound_frac: Optional[float] = None  # achieved / peak on the binding axis
    lowering_digest: str = ""
    kinds: Tuple[str, ...] = ()
    predicted_s: Optional[float] = None
    predicted_model: Optional[str] = None
    predicted_key: str = ""
    predicted_shape: str = ""
    predicted_calibrated: bool = False
    #: (name, seconds-or-None, reason) per ladder candidate, when the
    #: prediction came from an argmin over alternatives.
    predicted_candidates: Tuple = ()
    ratio: Optional[float] = None  # measured-vs-predicted, >1 = slower
    drift: bool = False
    cold: bool = False  # compiles observed during the forcing
    rows_per_s: Optional[float] = None  # streaming folds only

    def to_json(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"node": self.node}
        for key in (
            "seconds", "synced", "t_unix", "flops", "bytes_accessed",
            "intensity", "flops_per_s", "bytes_per_s", "roofline",
            "bound_frac", "lowering_digest", "predicted_s",
            "predicted_model", "predicted_key", "predicted_shape",
            "predicted_calibrated", "ratio", "drift", "cold", "rows_per_s",
        ):
            value = getattr(self, key)
            if value is not None and value != "":
                out[key] = value
        if self.kinds:
            out["kinds"] = list(self.kinds)
        if self.predicted_candidates:
            out["predicted_candidates"] = [
                list(c) for c in self.predicted_candidates
            ]
        return out


class PerfLedger:
    """Bounded ring of :class:`PerfLedgerEntry` with a monotonic cursor
    so consumers (bench legs, flight dumps, explain) read their own
    windows."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity or env_int(
            "KEYSTONE_COST_LEDGER_MAX", _LEDGER_MAX_DEFAULT
        )
        self._lock = threading.Lock()
        self._ring: "deque[PerfLedgerEntry]" = deque(maxlen=self.capacity)
        self._seq = 0

    def record(self, entry: PerfLedgerEntry) -> None:
        with self._lock:
            self._ring.append(entry)
            self._seq += 1
        _names.metric(_names.COST_LEDGER_ENTRIES).inc(
            roofline=entry.roofline or "unknown"
        )

    def cursor(self) -> int:
        with self._lock:
            return self._seq

    def entries(self, since: int = 0) -> List[PerfLedgerEntry]:
        """Entries recorded after cursor ``since`` (ring-bounded: at most
        the last ``capacity`` survive)."""
        with self._lock:
            fresh = max(0, self._seq - since)
            return list(self._ring)[-fresh:] if fresh else []

    def tail(self, n: int) -> List[PerfLedgerEntry]:
        with self._lock:
            return list(self._ring)[-n:]

    def summary(self, since: int = 0) -> Dict[str, Any]:
        """Aggregate view for bench leg payloads: entry count, total
        flops/bytes, roofline split."""
        entries = self.entries(since)
        flops = sum(e.flops or 0.0 for e in entries)
        bytes_accessed = sum(e.bytes_accessed or 0.0 for e in entries)
        bound: Dict[str, int] = {}
        for e in entries:
            bound[e.roofline or "unknown"] = bound.get(e.roofline or "unknown", 0) + 1
        return {
            "nodes": len(entries),
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "roofline": bound,
            "drift": sum(1 for e in entries if e.drift),
        }


_ledger = PerfLedger()


def get_ledger() -> PerfLedger:
    return _ledger


# ------------------------------------------------------------- harvest frames


class _Note:
    __slots__ = ("kind", "fn", "args", "avals")

    def __init__(self, kind: str, fn: Any, args: Any, avals: Any):
        self.kind = kind
        self.fn = fn
        self.args = args
        self.avals = avals


@dataclass
class HarvestFrame:
    label: str
    notes: List[_Note] = field(default_factory=list)
    rows_per_s: Optional[float] = None
    num_examples: Optional[int] = None
    #: backend compiles observed while the node forced — a cold wall
    #: (compile-inflated) is recorded but never anchors or scores drift.
    compiles: int = 0


_frames = threading.local()


def _frame_stack() -> List[HarvestFrame]:
    stack = getattr(_frames, "stack", None)
    if stack is None:
        stack = []
        _frames.stack = stack
    return stack


def push_frame(label: str) -> HarvestFrame:
    frame = HarvestFrame(label)
    _frame_stack().append(frame)
    return frame


def pop_frame(frame: HarvestFrame) -> HarvestFrame:
    stack = _frame_stack()
    if stack and stack[-1] is frame:
        stack.pop()
    elif frame in stack:  # defensive: unwind past it
        while stack and stack.pop() is not frame:
            pass
    return frame


def current_frame() -> Optional[HarvestFrame]:
    stack = getattr(_frames, "stack", None)
    return stack[-1] if stack else None


def note_jit_call(
    kind: str, fn: Any, args: Any = None, avals: Any = None
) -> None:
    """Operators call this as they dispatch a jitted computation so the
    enclosing node's harvest frame can attribute flop/byte facts to it.
    A single thread-local read when no frame is active (serving hot
    paths never pay more). Pass ``avals`` instead of ``args`` when the
    arguments will be donated/freed before the node finalizes."""
    frame = current_frame()
    if frame is None:
        return
    frame.notes.append(_Note(kind, fn, args if avals is None else None, avals))


def note_solver_call(kind: str, fn: Any, args: Sequence[Any]) -> None:
    """Note a solver-layer jitted call, substituting avals for array
    operands (solver jits donate their inputs — the buffers may be
    deleted before the node finalizes) while passing static/python
    operands verbatim (``lower`` needs the actual static values). A
    single thread-local read when no frame is active."""
    frame = current_frame()
    if frame is None:
        return
    try:
        import jax

        lower_args = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") and hasattr(a, "dtype")
            else a
            for a in args
        )
    except Exception:
        return
    frame.notes.append(_Note(kind, fn, None, lower_args))


def note_stream_result(
    rows_per_s: Optional[float], num_examples: Optional[int] = None
) -> None:
    """The streaming fold reports its achieved throughput so a
    rows/s-denominated prediction (MeasuredKnobRule's chunk winner) can
    be drift-scored in its own unit."""
    frame = current_frame()
    if frame is None:
        return
    frame.rows_per_s = rows_per_s
    frame.num_examples = num_examples


def note_lease_result(
    name: str,
    kind: str,
    predicted_s: Optional[float],
    measured_s: Optional[float],
    source: str,
) -> None:
    """The mesh scheduler joins a retired lease's predicted wall (by
    pricing provenance — tune/store/roofline/default) to the wall it
    measured, inside whatever harvest frame is open: ``explain`` and the
    bench legs read the observatory, not the scheduler's internals
    (docs/SCHEDULING.md "Observability")."""
    frame = current_frame()
    if frame is None:
        return
    leases = getattr(frame, "leases", None)
    if leases is None:
        leases = frame.leases = []  # type: ignore[attr-defined]
    leases.append(
        {
            "name": name,
            "kind": kind,
            "predicted_s": predicted_s,
            "measured_s": measured_s,
            "source": source,
        }
    )


# --------------------------------------------------------------- the sentinel


class DriftSentinel:
    """Noise-tolerant measured-vs-expected watchdog per (key, shape).

    What it scores depends on the prediction's unit:

    - ``rows_per_s`` predictions (MeasuredKnobRule's stream winners) are
      measurements in the exact unit and shape class they are compared
      at — scored directly: ``predicted_rate / achieved_rate``.
    - ``seconds`` predictions (autocache's linear fits) are
      extrapolations — a model is allowed constant bias, so the sentinel
      baselines on REALITY instead: the first warm (compile-free)
      execution writes ``measured_wall_s`` onto the backing ProfileStore
      entry, and later fits are scored ``measured / baseline``. Drift
      means the world moved while the stored decision stood still —
      exactly when replaying it stops being defensible. A legit
      re-measurement re-records the entry without the baseline field,
      so self-correcting paths re-baseline instead of false-firing.

    Compound-key predictions (a fused chain summing member claims) are
    never scored — their walls cannot be attributed to one entry — but a
    fire on any component marks every component stale.

    One out-of-band observation is noise; ``sustain`` consecutive ones
    are drift. Firing publishes ``keystone_cost_drift_events_total``,
    records a ``cost_drift`` recovery-ledger event (flight-recorder
    ringed), marks the backing ProfileStore entry ``stale:`` (so the
    consumer rules re-measure instead of replaying a stale winner), and
    resets the streak — one sustained drift is one event until fresh
    measurements land."""

    BASELINE_FIELD = "measured_wall_s"

    def __init__(self):
        self._lock = threading.Lock()
        self._streak: Dict[Tuple[str, str], int] = {}
        #: (key, shape) already observed by THIS process. The first
        #: sight of a key re-bases its stored baseline to the wall this
        #: process just measured instead of scoring it: ms-scale CPU
        #: walls jump several-fold between processes with ambient load
        #: (the bench-diff noise floor), so cross-process baselines are
        #: noise — drift is judged within a process, where the
        #: long-running consumers (serving, the refit daemon, a
        #: multi-pass explain) actually live.
        self._seen: set = set()
        self.events: List[Dict[str, Any]] = []

    def observe(
        self,
        node: str,
        prediction: Prediction,
        measured_s: Optional[float] = None,
        measured_rate: Optional[float] = None,
    ) -> Optional[Dict[str, Any]]:
        if (
            not drift_enabled()
            or not prediction.calibrated
            or not prediction.key
            or "," in prediction.key  # compound: unattributable
        ):
            return None
        from . import store as _store

        store = _store.get_store()
        if store is None:
            return None  # the sentinel rides the store (its marks live there)
        m = store.lookup(prediction.key, prediction.shape, include_stale=True)
        if m is None or _store.is_stale(m):
            return None  # evicted, or already flagged and awaiting re-measure

        base: Optional[float] = None
        ident = (prediction.key, prediction.shape)
        if prediction.rows_per_s and measured_rate:
            ratio = prediction.rows_per_s / max(measured_rate, 1e-12)
        elif prediction.seconds is not None and measured_s:
            base = m.get(self.BASELINE_FIELD)
            with self._lock:
                first_sight = ident not in self._seen
                self._seen.add(ident)
            if (
                first_sight
                or not isinstance(base, (int, float))
                or base <= 0
            ):
                # First warm execution this process (or since a
                # re-measurement): reality becomes the baseline; no
                # drift judgment yet (see _seen — cross-process walls
                # are noise at ms scale).
                baselined = dict(m)
                baselined[self.BASELINE_FIELD] = round(measured_s, 6)
                store.record(prediction.key, prediction.shape, **baselined)
                _names.metric(_names.COST_DRIFT_RATIO).set(
                    1.0, model=prediction.model
                )
                return None
            base = float(base)
            ratio = measured_s / base
        else:
            return None

        tol = drift_ratio_tolerance()
        _names.metric(_names.COST_DRIFT_RATIO).set(
            ratio, model=prediction.model
        )
        out_of_band = max(ratio, 1.0 / max(ratio, 1e-12)) > tol
        with self._lock:
            if not out_of_band:
                self._streak.pop(ident, None)
                # In-band observations smooth the baseline toward
                # current reality (EMA): a badly-timed first baseline
                # self-corrects instead of false-firing later, at the
                # documented cost that drift *slower than the band per
                # step* is absorbed — the sentinel hunts regime changes,
                # not creep.
                if (
                    base is not None
                    and measured_s
                    and abs(measured_s - float(base)) > 0.05 * float(base)
                ):
                    smoothed = dict(m)
                    smoothed[self.BASELINE_FIELD] = round(
                        0.7 * float(base) + 0.3 * measured_s, 6
                    )
                    store.record(
                        prediction.key, prediction.shape, **smoothed
                    )
                return None
            streak = self._streak.get(ident, 0) + 1
            if streak < drift_sustain():
                self._streak[ident] = streak
                return None
            self._streak.pop(ident, None)
        return self._fire(node, prediction, ratio)

    def _fire(
        self, node: str, prediction: Prediction, ratio: float
    ) -> Dict[str, Any]:
        event = {
            "node": node,
            "model": prediction.model,
            "key": prediction.key,
            "shape": prediction.shape,
            "ratio": round(ratio, 4),
            "stale_marked": False,
        }
        _names.metric(_names.COST_DRIFT_EVENTS).inc(model=prediction.model)
        if prediction.key:
            try:
                from . import store as _store

                store = _store.get_store()
                if store is not None:
                    marked = [
                        store.mark_stale(
                            key, prediction.shape, reason="cost_drift"
                        )
                        for key in prediction.key.split(",")
                    ]
                    event["stale_marked"] = any(marked)
            except Exception:
                pass
        try:
            # The recovery ledger is the event bus the flight recorder
            # rings — a drift lands in every post-mortem dump.
            from ..reliability.recovery import get_recovery_log

            get_recovery_log().record(
                "cost_drift", node,
                model=prediction.model, key=prediction.key,
                shape=prediction.shape, ratio=event["ratio"],
                stale_marked=event["stale_marked"],
            )
        except Exception:
            pass
        _spans.add_span_event("cost_drift", **event)
        with self._lock:
            self.events.append(event)
            del self.events[:-64]
        logger.warning(
            "cost-model drift: %s predicted %s under %s ratio=%.2f "
            "(entry %smarked stale)", prediction.model, node,
            prediction.key or "<unkeyed>", ratio,
            "" if event["stale_marked"] else "NOT ",
        )
        return event

    def seen_count(self) -> int:
        """Keys this process has observed (and therefore re-based) —
        the explain CLI's gate for when a seeded corruption is
        meaningful (a corruption before any in-process baseline exists
        is clobbered by the first re-base)."""
        with self._lock:
            return len(self._seen)

    def drain_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self.events)
            self.events.clear()
        return out


_sentinel = DriftSentinel()


def get_drift_sentinel() -> DriftSentinel:
    return _sentinel


# ------------------------------------------------------------------- finalize


def _label_of(op: Any) -> str:
    return str(getattr(op, "label", type(op).__name__))


def _sum_predictions(labels: Sequence[str]) -> Optional[Prediction]:
    resolved = [plan_prediction(m) for m in labels]
    parts = [p for p in resolved if p is not None and p.seconds is not None]
    if not parts:
        return None
    # Calibrated only with FULL member coverage: a partial sum both
    # understates the chain's claim and — when it collapses to a single
    # key — would slip past the sentinel's compound-key guard and score
    # the whole chain's wall against one member's entry.
    complete = len(parts) == len(labels)
    return Prediction(
        model=parts[0].model,
        key=",".join(p.key for p in parts if p.key),
        shape=parts[0].shape,
        seconds=sum(p.seconds for p in parts),
        calibrated=complete and all(p.calibrated for p in parts),
        source=parts[0].source,
    )


def _resolve_prediction(op: Any, label: str) -> Optional[Prediction]:
    pinned = getattr(op, "predicted_cost", None)
    if isinstance(pinned, Prediction):
        return pinned
    # Fused chains: the autocache profiler predicted the MEMBERS; their
    # per-node claims sum to the chain's (same work, one dispatch).
    members = getattr(op, "member_labels", None)
    if members:
        return _sum_predictions(list(members))
    # A streaming absorb (StreamingFitOperator) replaced estimator +
    # featurize members with one node: their plan-book claims sum the
    # same way (pinned measured-knob predictions, above, win over this).
    estimator = getattr(op, "estimator", None)
    absorbed = getattr(op, "members", None)
    if estimator is not None and absorbed is not None:
        return _sum_predictions(
            [_label_of(estimator)] + [_label_of(m) for m in absorbed]
        )
    return plan_prediction(label)


def finalize_node(
    label: str,
    seconds: float,
    synced: bool,
    op: Any = None,
    span: Any = None,
    frame: Optional[HarvestFrame] = None,
) -> Optional[PerfLedgerEntry]:
    """Close one node's harvest: resolve noted computations to flop/byte
    facts (cache-hit cheap), classify against the roofline, join the
    prediction that drove the plan, drift-score it, and land the ledger
    entry (plus span attributes for the trace view). Called by
    ``timed_execute`` AFTER the wall measurement so first-shape harvest
    cost never inflates node timings. Never raises."""
    try:
        return _finalize_node(label, seconds, synced, op, span, frame)
    except Exception as e:
        logger.debug("cost finalize failed for %s (%s)", label, e)
        return None


def _finalize_node(label, seconds, synced, op, span, frame):
    notes = frame.notes if frame is not None else []
    prediction = _resolve_prediction(op, label) if op is not None else (
        plan_prediction(label)
    )
    if not notes and prediction is None and not _record_all:
        return None

    flops_total: Optional[float] = None
    bytes_total: Optional[float] = None
    digest = ""
    kinds: List[str] = []
    for note in notes:
        facts = _cached_facts(note.fn, note.args, note.avals)
        note.args = None  # drop array refs promptly
        if facts is None:
            continue
        kinds.append(note.kind)
        if facts.flops is not None:
            flops_total = (flops_total or 0.0) + facts.flops
        if facts.bytes_accessed is not None:
            bytes_total = (bytes_total or 0.0) + facts.bytes_accessed
        digest = digest or facts.lowering_digest

    intensity = (
        flops_total / bytes_total if flops_total and bytes_total else None
    )
    roofline = get_roofline() if (flops_total or bytes_total) else _roofline
    classification = roofline.classify(intensity) if roofline else None

    flops_per_s = bytes_per_s = bound_frac = None
    if synced and seconds > 0:
        if flops_total:
            flops_per_s = flops_total / seconds
        if bytes_total:
            bytes_per_s = bytes_total / seconds
        if roofline and classification == "compute-bound" and flops_per_s:
            bound_frac = flops_per_s / max(roofline.peak_flops_per_s, 1e-9)
        elif roofline and classification == "memory-bound" and bytes_per_s:
            bound_frac = bytes_per_s / max(roofline.peak_bytes_per_s, 1e-9)

    predicted_s = predicted_model = None
    predicted_key = predicted_shape = ""
    calibrated = False
    predicted_candidates: Tuple = ()
    ratio = None
    drift = False
    cold = frame is not None and frame.compiles > 0
    if prediction is not None:
        predicted_model = prediction.model
        predicted_key = prediction.key
        predicted_shape = prediction.shape
        calibrated = prediction.calibrated
        predicted_candidates = tuple(getattr(prediction, "candidates", ()))
        if prediction.seconds is not None:
            predicted_s = prediction.seconds
        elif (
            prediction.rows_per_s
            and frame is not None
            and frame.num_examples
        ):
            predicted_s = frame.num_examples / prediction.rows_per_s
        # Display ratio in the prediction's own unit, >1 = slower than
        # predicted. (The sentinel scores its own baseline-relative
        # ratio — a model is allowed constant bias; see DriftSentinel.)
        if prediction.rows_per_s and frame is not None and frame.rows_per_s:
            ratio = prediction.rows_per_s / max(frame.rows_per_s, 1e-12)
        elif prediction.seconds and synced and seconds > 0:
            ratio = seconds / prediction.seconds
        if not cold:
            drift = (
                _sentinel.observe(
                    label,
                    prediction,
                    measured_s=seconds if synced and seconds > 0 else None,
                    measured_rate=(
                        frame.rows_per_s if frame is not None else None
                    ),
                ) is not None
            )
    elif roofline is not None:
        # No model claimed this node: the roofline's first-principles
        # floor is the displayed prediction (never drift-scored).
        predicted_s = roofline.predicted_seconds(flops_total, bytes_total)
        predicted_model = "roofline" if predicted_s is not None else None

    entry = PerfLedgerEntry(
        node=label,
        seconds=round(seconds, 6),
        synced=synced,
        cold=cold,
        t_s=time.perf_counter(),
        t_unix=round(time.time(), 6),
        flops=flops_total,
        bytes_accessed=bytes_total,
        intensity=intensity,
        flops_per_s=flops_per_s,
        bytes_per_s=bytes_per_s,
        roofline=classification,
        bound_frac=bound_frac,
        lowering_digest=digest,
        kinds=tuple(kinds),
        predicted_s=predicted_s,
        predicted_model=predicted_model,
        predicted_key=predicted_key,
        predicted_shape=predicted_shape,
        predicted_calibrated=calibrated,
        predicted_candidates=predicted_candidates,
        ratio=ratio,
        drift=drift,
        rows_per_s=frame.rows_per_s if frame is not None else None,
    )
    _ledger.record(entry)

    if span is not None:
        if flops_total is not None:
            span.set_attribute("flops", flops_total)
        if bytes_total is not None:
            span.set_attribute("bytes_accessed", bytes_total)
        if classification is not None:
            span.set_attribute("roofline", classification)
        if digest:
            # The executable fingerprint: joins this span to ledger
            # entries and ProfileStore keys deterministically (the
            # fused-member-names attr alone never could).
            span.set_attribute("lowering_digest", digest)
        if predicted_s is not None:
            span.set_attribute("predicted_s", round(predicted_s, 6))
            span.set_attribute("predicted_model", predicted_model)
    return entry


# Record-all mode: explain wants a ledger entry for EVERY executed plan
# node (host-side ops included), not just harvested/predicted ones.
_record_all = False


def record_all_nodes(value: bool) -> None:
    global _record_all
    _record_all = bool(value)


# ---------------------------------------------------------------------- reset


def reset_cost_observatory() -> None:
    """Testing hook: drop ledger entries, sentinel state, plan
    predictions, facts cache, and the cached roofline."""
    global _ledger, _sentinel, _record_all
    with _facts_lock:
        _facts_cache.clear()
    reset_plan_predictions()
    set_roofline(None)
    _ledger = PerfLedger()
    _sentinel = DriftSentinel()
    _record_all = False
