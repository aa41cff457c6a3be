"""Device hooks: memory sampling, peak-memory attribution, and the
counted host-to-device upload.

Memory sampling prefers the accelerator's own accounting
(``Device.memory_stats()`` — bytes_in_use / peak_bytes_in_use on TPU) and
falls back to host RSS (``/proc/self/statm``, then ``resource``) on CPU
test meshes, where XLA allocates out of the process heap anyway. Either
way the snapshot says which source it used, so a reader never mistakes
RSS for HBM.

``to_device`` is the one place a host batch becomes device arrays on
the batch-apply path: an ``h2d`` span (in a profiler trace through the
span layer's bridge, obs/spans.py) and the ``keystone_h2d_*`` counters.
Its callers are ``BatchTransformer.apply_batch`` (a transformer's own
input), the graph executor (a node's output shared by several) and the
kernel solver. ``h2d`` closes when the upload is ENQUEUED; while
somebody is recording, ``watch_transfer`` hands the arrays to the
process's one watcher thread, whose ``h2d:transfer`` span runs from
there to the arrival of the last byte (the streamed fold's chunks go
the same way).

Imports jax lazily; importable before any backend initializes.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional

from . import names, spans

logger = logging.getLogger(__name__)

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096

def to_device(data: Any, site: str, consumers: int = 1) -> Any:
    """``data`` (a pytree) with its host (numpy) leaves uploaded as device
    arrays; device leaves and everything else pass through untouched
    (with no host leaf, ``data`` itself comes back). The upload is
    enqueued, never waited for, under an ``h2d`` span carrying ``site``,
    ``bytes`` and ``consumers`` (how many batch transformers will compute
    on this one copy: 1 where a transformer uploads its own input, k
    where the executor shares a node's output between k of them), and is
    counted in ``keystone_h2d_bytes_total{site}`` /
    ``keystone_h2d_transfers_total{site}`` (one transfer per leaf).
    While somebody is recording, an upload of ``_WATCH_MIN_BYTES`` or
    more is also handed to the transfer watcher, whose ``h2d:transfer``
    span ends when the bytes have arrived (:func:`watch_transfer`);
    with nobody recording that is one flag check and nothing else."""
    import numpy as np

    import jax

    host = [
        leaf for leaf in jax.tree_util.tree_leaves(data)
        if isinstance(leaf, np.ndarray)
    ]
    if not host:
        return data

    nbytes = sum(leaf.nbytes for leaf in host)
    sent = [] if watching(nbytes) else None
    with spans.span("h2d", site=site, bytes=nbytes, consumers=consumers):
        out = jax.tree_util.tree_map(
            lambda leaf: _upload(leaf, sent) if isinstance(leaf, np.ndarray) else leaf,
            data,
        )
    if sent is not None:
        watch_transfer(sent, site, nbytes)
    names.metric(names.H2D_BYTES).inc(nbytes, site=site)
    names.metric(names.H2D_TRANSFERS).inc(len(host), site=site)
    return out


#: A host array whose last dimension is narrower than this (an image
#: batch's 3 channels) goes up as (rows, everything else) and is given its
#: shape on the device. 128 is the v5e's lane width, below which the
#: device's layout puts another dimension innermost; measured at ONE
#: width only, 3 (my chip runs, PR 36: 1.2 million host transposes and
#: 160 ms a 201 MB request handed up as it is, none and 33 ms flat). The
#: TIMIT cells' 440-wide rows are past it and go up as they did.
_NARROW = 128


#: An upload of fewer bytes than this is never handed to the transfer
#: watcher. A host batch goes up at some 6 GB/s on the v5e's host (115 MB
#: in 19 ms: my chip run, PR 33), so 3 MiB are in flight for about the
#: 0.5 ms under which the benchmark's trace reduction drops a host event
#: (``benchmark/harness/trace.py::read_profile``): a shorter
#: ``h2d:transfer`` could not be read, and the serving path's small
#: batches pay nothing for it, traced or not.
_WATCH_MIN_BYTES = 3 << 20


def _upload(leaf, sent: Optional[list] = None):
    """``jnp.asarray(leaf)``. A batch of three or more dimensions with a
    narrow last one is uploaded flat and reshaped on the device: the
    device keeps such an array with a wider dimension innermost, and
    handed the array as it is the runtime makes that transpose on the
    HOST, a few hundred elements at a time (an image batch of 256 x 256 x
    256 x 3 float32: 1.2 million host transposes on four threads a
    request, each an event in a profiler's trace, which filled the 40 GiB
    of the benchmark's host in one traced window; my chip run, PR 36).
    Flat, the bytes go up as they lie and the device transposes.
    ``sent``, where given, collects the array the bytes went up as (the
    flat one: what the transfer watcher waits for is the copy, not the
    device's reshape)."""
    import jax.numpy as jnp

    narrow = leaf.ndim >= 3 and leaf.shape[-1] < _NARROW and leaf.size
    up = jnp.asarray(leaf.reshape(leaf.shape[0], -1) if narrow else leaf)
    if sent is not None:
        sent.append(up)
    return jnp.reshape(up, leaf.shape) if narrow else up


def watching(nbytes: int) -> bool:
    """Whether an upload of ``nbytes`` enqueued now is to be handed to
    :func:`watch_transfer`: it is large enough to be seen and somebody is
    recording (``spans.recording()``: a session, or a profiler trace in
    progress). With nobody recording this is all an upload pays."""
    return nbytes >= _WATCH_MIN_BYTES and spans.recording()


def watch_transfer(arrays: Any, site: str, nbytes: int) -> None:
    """Hand device arrays whose upload was just enqueued (a pytree; the
    caller has asked :func:`watching`) to the process's transfer watcher
    and return at once. The watcher, one daemon thread, takes the
    uploads in the order they were enqueued and for each holds an
    ``h2d:transfer`` span (``site``, ``bytes``; its parent the span the
    caller is in, which is its ``h2d`` span's parent) until
    ``jax.block_until_ready`` says the last byte has arrived. Uploads to
    a chip arrive in order, so a span starts at the later of its own
    enqueue and its predecessor's arrival, and the union of the spans is
    "a host batch is in flight". ``ks:h2d:transfer`` in a profiler
    trace; a recorded span under a session."""
    _WATCHER.watch(arrays, site, nbytes, spans.current_context())


class _TransferWatcher:
    """The FIFO of uploads in flight and the one thread that waits for
    them. The thread starts with the first upload handed over (so never
    in a process where nobody records), is a daemon, and outlives every
    session and trace: idle, it waits on the queue."""

    def __init__(self):
        self._lock = threading.Lock()  # the thread's start, nothing else
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None

    def watch(self, arrays, site, nbytes, parent) -> None:
        if parent is not None and not parent[1]:
            parent = None  # "the session's root": what the h2d span got
        self._queue.put((arrays, site, nbytes, parent))
        if self._thread is None:  # the first upload anybody recorded
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="keystone-h2d-watcher", daemon=True
                    )
                    self._thread.start()

    def _run(self) -> None:
        while True:
            entry = self._queue.get()
            try:
                self._await(*entry)
            except Exception:
                # the boundary: nothing in here may reach the uploader or
                # end the thread
                logger.warning("h2d:transfer watcher: an entry failed", exc_info=True)
            entry = None  # hold no upload while waiting for the next

    @staticmethod
    def _await(arrays, site, nbytes, parent) -> None:
        import jax

        with spans.span("h2d:transfer", parent=parent, site=site, bytes=nbytes) as record:
            try:
                jax.block_until_ready(arrays)
            except jax.errors.JaxRuntimeError as exc:
                # freed or donated before its turn came (its consumer ran
                # and let go of it), or the copy itself failed, which the
                # caller sees at its own first use
                gone = "deleted or donated" in str(exc)
                record.set_attribute("ended", "deleted" if gone else "error")


_WATCHER = _TransferWatcher()


def rss_bytes() -> int:
    """Resident set size of this process (0 if unavailable)."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except Exception:
        pass
    try:
        import resource

        # ru_maxrss is the PEAK, in KiB on Linux — last resort only.
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def peak_rss_bytes() -> int:
    """Process-lifetime peak RSS (0 if unavailable)."""
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def memory_snapshot() -> Dict[str, Any]:
    """Best-available memory numbers right now.

    Returns ``{"source": "device"|"rss", "bytes_in_use": int,
    "peak_bytes_in_use": int}``: the fullest local device's stats when
    the backend exposes them (TPU/GPU — CPU meshes report RSS)."""
    from ..parallel.mesh import local_memory_stats

    stats = [s for s in local_memory_stats() if "bytes_in_use" in s]
    if stats:
        return {
            "source": "device",
            "bytes_in_use": max(int(s["bytes_in_use"]) for s in stats),
            "peak_bytes_in_use": max(
                int(s.get("peak_bytes_in_use", s["bytes_in_use"])) for s in stats
            ),
        }
    return {
        "source": "rss",
        "bytes_in_use": rss_bytes(),
        "peak_bytes_in_use": peak_rss_bytes(),
    }


def per_device_snapshots() -> list:
    """One memory snapshot per local accelerator device, labeled with the
    device's stable id (``tpu:0`` …). Devices that expose no
    ``memory_stats`` (CPU meshes) collapse to a single host-RSS entry
    labeled ``host`` — per-virtual-device RSS attribution would be
    fiction. Empty list when jax is unavailable."""
    out = []
    try:
        import jax

        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats()
            except AttributeError:
                stats = None  # backend has no memory_stats: not an error
            except Exception as e:
                # The chip most likely to be OOMing/wedged is exactly the
                # one whose stats call fails — surface it as an error
                # entry instead of silently shrinking the device list.
                out.append(
                    {
                        "device": f"{dev.platform}:{dev.id}",
                        "source": "error",
                        "error": f"{type(e).__name__}: {e}",
                    }
                )
                continue
            if stats and "bytes_in_use" in stats:
                out.append(
                    {
                        "device": f"{dev.platform}:{dev.id}",
                        "source": "device",
                        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                        "peak_bytes_in_use": int(
                            stats.get(
                                "peak_bytes_in_use",
                                stats.get("bytes_in_use", 0),
                            )
                        ),
                    }
                )
    except Exception:
        return out
    if not out:
        host = memory_snapshot()
        host["device"] = "host"
        out.append(host)
    return out


def publish_memory(stage: Optional[str] = None) -> Dict[str, Any]:
    """Sample memory and publish it to the registry: the aggregate in-use
    gauge always (``device="all"``), plus per-stage peak attribution when
    ``stage`` is given. :func:`publish_per_device_memory` adds the
    per-device series."""
    snap = memory_snapshot()
    names.metric(names.MEMORY_IN_USE_BYTES).set(
        snap["bytes_in_use"], source=snap["source"], device="all"
    )
    if stage is not None:
        names.metric(names.PEAK_MEMORY_BYTES).max(
            snap["peak_bytes_in_use"], stage=stage, device="all"
        )
    return snap


def publish_per_device_memory(stage: Optional[str] = None) -> list:
    """Publish one gauge series per local device (multichip runs — one
    chip OOMing while seven idle is invisible in the aggregate) and
    return the snapshots."""
    snaps = per_device_snapshots()
    in_use = names.metric(names.MEMORY_IN_USE_BYTES)
    peak = names.metric(names.PEAK_MEMORY_BYTES)
    for snap in snaps:
        if "error" in snap:
            continue  # error entries carry no bytes to publish
        in_use.set(
            snap["bytes_in_use"], source=snap["source"], device=snap["device"]
        )
        if stage is not None:
            peak.max(
                snap["peak_bytes_in_use"], stage=stage, device=snap["device"]
            )
    return snaps


def device_obs_payload(snapshots: Optional[list] = None) -> Dict[str, Any]:
    """The per-device observability payload multichip dryruns embed in
    their artifact (MULTICHIP_r0*.json recorded parity but no telemetry):
    per-device memory plus the process compile count. Pass ``snapshots``
    (e.g. :func:`publish_per_device_memory`'s return) to reuse an
    already-taken sample — the published gauges and the embedded payload
    then agree instead of re-walking the devices twice."""
    from ..utils.compilation_cache import compile_count

    return {
        "devices": per_device_snapshots() if snapshots is None else snapshots,
        "xla_compiles": compile_count(),
    }


@contextmanager
def stage_memory(stage: str) -> Iterator[None]:
    """Attribute peak memory to a pipeline stage: snapshot before/after,
    stamp the delta and peak onto the current span, and keep the per-stage
    peak gauge. Cheap enough for per-node use only under tracing — callers
    gate on an active span session."""
    before = publish_memory(stage=stage)
    try:
        yield
    finally:
        after = publish_memory(stage=stage)
        sp = spans.current_span()
        sp.set_attribute("mem_bytes_before", before["bytes_in_use"])
        sp.set_attribute("mem_bytes_after", after["bytes_in_use"])
        sp.set_attribute("mem_peak_bytes", after["peak_bytes_in_use"])
        sp.set_attribute("mem_source", after["source"])
