"""Persistent XLA compilation cache.

First compilation of a solver or featurizer program on TPU costs tens of
seconds — on short workloads (a GMM fit, a per-class solve) that is the
dominant wall-clock, and every new process pays it again. JAX's
persistent compilation cache makes the second and later runs load the
compiled executable from disk instead.

Where the cache lives (one place per launch environment: a directory
that moves between runs never hits):

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it at import and
   this module sets NO directory in code — whoever launched the process
   (a chip tool, a deployment) owns the placement.
2. else ``KEYSTONE_COMPILATION_CACHE=<dir>`` (tests isolate with it);
   ``KEYSTONE_COMPILATION_CACHE=off`` disables the program's own set-up.
3. else one fixed, gitignored path inside the checkout:
   ``<repo>/.keystone_cache/xla-cache`` — never ``~/.cache``, which does
   not travel with the tree.
"""

from __future__ import annotations

import os

from ..envknobs import env_disabled, env_str
from typing import Callable

#: Root of the program's own persistent state (XLA cache + profile
#: store), next to the package directory: inside the checkout, listed in
#: .gitignore.
STATE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".keystone_cache",
)
_DEFAULT_DIR = os.path.join(STATE_ROOT, "xla-cache")


def resolve_cache_dir() -> "str | None":
    """The directory the persistent cache lives in under the rules in the
    module docstring; None when disabled. Pure: touches neither jax nor
    the filesystem."""
    if env_disabled("KEYSTONE_COMPILATION_CACHE"):
        return None
    return (
        env_str("JAX_COMPILATION_CACHE_DIR")
        or env_str("KEYSTONE_COMPILATION_CACHE")
        or _DEFAULT_DIR
    )


def enable_persistent_cache() -> "str | None":
    """Enable JAX's on-disk compilation cache; returns the directory (None
    when disabled). Safe to call more than once and before any backend is
    initialized (it only sets jax config values). A directory that cannot
    be created raises: a run that silently recompiles everything is a
    slower run nobody can explain."""
    import jax

    target = resolve_cache_dir()
    if target is None:
        return None
    if not env_str("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    # Cache every program: the workloads here are few large programs,
    # not thousands of tiny ones, so the default 1 MiB floor and 1 s
    # compile-time floor would skip exactly the entries we want warm.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return target


# ------------------------------------------------------- compile accounting

# Backend-compile counter. The serving layer warms a fixed bucket set and
# then asserts (in tests) / reports (in telemetry) that steady-state
# traffic triggers ZERO further XLA compiles — the counter is the
# evidence. jax.monitoring fires one
# "/jax/core/compile/backend_compile_duration" event per program that
# missed the in-memory executable cache. That INCLUDES programs then
# loaded from the persistent cache (checked on jax 0.9.0): for a steady
# state both are a stall, so both count. The persistent loads announce
# themselves with "/jax/compilation_cache/cache_hits" and are counted on
# the side, so a warm run can show how many programs it really built:
# ``compile_count() - cache_hit_count()``.
_COMPILE_EVENT_SUBSTRING = "backend_compile"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_events = {"count": 0, "cache_hits": 0}
_counter_installed = False


def install_compile_counter() -> Callable[[], int]:
    """Idempotently register the jax.monitoring listeners behind
    :func:`compile_count` (which it returns) and
    :func:`cache_hit_count`. Registration is permanent for the process
    (jax.monitoring has no unregister), which is fine: each listener is
    one string check per event. Raises if jax.monitoring cannot register
    — a dead counter would read as 'no recompiles'."""
    global _counter_installed
    if not _counter_installed:
        import jax.monitoring

        def _on_event(event: str, **kw) -> None:
            if event == _CACHE_HIT_EVENT:
                _compile_events["cache_hits"] += 1

        def _on_duration(event: str, duration: float, **kw) -> None:
            if _COMPILE_EVENT_SUBSTRING in event:
                _compile_events["count"] += 1
                # Mirror into the metrics registry so Prometheus
                # snapshots carry the compile count without callers
                # having to diff compile_count() themselves.
                from ..obs import names as _names

                _names.metric(_names.XLA_COMPILES).inc()

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _counter_installed = True
    return compile_count


def compile_count() -> int:
    """Programs that missed the in-memory executable cache since
    :func:`install_compile_counter` (0 if never installed): built by the
    backend or loaded from the persistent cache."""
    return _compile_events["count"]


def cache_hit_count() -> int:
    """The part of :func:`compile_count` that was loaded from the
    persistent cache instead of built."""
    return _compile_events["cache_hits"]
