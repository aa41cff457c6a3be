"""Device-mesh management.

The reference discovers cluster topology through Spark
(``getExecutorStorageStatus`` for machine counts / memory budgets,
reference: nodes/learning/LeastSquaresEstimator.scala:70-75,
workflow/AutoCacheRule.scala:572-585). The TPU equivalent is a
``jax.sharding.Mesh`` over ``jax.devices()`` plus per-device HBM
accounting.

Axis conventions used throughout the framework:

- ``data``  — example (row) sharding; every featurizer and every solver's
  Gram/gradient accumulation is data-parallel over this axis.
- ``model`` — feature/class (column) sharding for block solvers (the
  reference's ``VectorSplitter`` feature-block parallelism re-designed as a
  real mesh axis).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
# Outer axis spanning slices/hosts: collectives over (REPLICA, DATA) lower
# to a hierarchical ICI-then-DCN reduction automatically.
REPLICA_AXIS = "replica"

_current_mesh: Optional[Mesh] = None


def row_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the example (row) dimension is sharded over.

    Single-slice meshes shard rows over ``data`` only; hybrid meshes add
    the outer ``replica`` (DCN) axis. Cross-shard reductions must psum
    over all of these."""
    if REPLICA_AXIS in mesh.shape:
        return (REPLICA_AXIS, DATA_AXIS)
    return (DATA_AXIS,)


def row_shard_count(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in row_axes(mesh))


def model_axis_size(mesh: Mesh) -> int:
    """Feature-block shards the mesh carries (1 when no ``model`` axis)."""
    return mesh.shape.get(MODEL_AXIS, 1)


# One reshaped 2-D mesh per (base devices, model shards): the partitioner
# re-decides every plan, and the streaming engine's step-jit cache keys on
# mesh identity — a fresh Mesh object per plan would retrace the identical
# program every fit and break the zero-steady-state-compile guarantee.
_model_mesh_cache: dict = {}


def model_mesh(base: Mesh, model_shards: int) -> Mesh:
    """The ``(data, model)`` mesh over ``base``'s devices with the feature
    axis split ``model_shards`` ways. Cached on (device tuple, shards) so
    repeated plans hand back the SAME Mesh object (jit-cache identity).
    ``model_shards`` must divide the device count (callers gate on
    ``model-axis-indivisible`` first)."""
    devices = tuple(base.devices.flat)
    if len(devices) % model_shards != 0:
        raise ValueError(
            f"{model_shards} model shards do not divide {len(devices)} devices"
        )
    key = (devices, int(model_shards))
    hit = _model_mesh_cache.get(key)
    if hit is None:
        hit = make_mesh(
            (len(devices) // model_shards, model_shards),
            (DATA_AXIS, MODEL_AXIS),
            devices=devices,
        )
        _model_mesh_cache[key] = hit
    return hit


def make_mesh(
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = (DATA_AXIS,),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh over the available devices.

    With no arguments: a 1-D ``data`` mesh over every device.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    if math.prod(shape) != len(devices):
        raise ValueError(f"mesh shape {shape} does not cover {len(devices)} devices")
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


def make_hybrid_mesh(
    num_replicas: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(replica, data) mesh for multi-slice / multi-host scaling.

    The outer ``replica`` axis spans slices (DCN); the inner ``data`` axis
    stays within a slice (ICI). Replaces the reference's flat Spark
    cluster view with the two-tier network the hardware actually has —
    one psum over ``(replica, data)`` is lowered by XLA into an ICI
    reduce + DCN reduce (SURVEY §2.10 "hierarchical reduce").

    ``num_replicas`` defaults to the detected slice count (device
    ``slice_index`` when the platform exposes it, else process count).
    """
    devices = list(devices if devices is not None else jax.devices())
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    real_multislice = None not in slice_ids and len(slice_ids) > 1
    if num_replicas is None:
        num_replicas = len(slice_ids) if real_multislice else max(1, jax.process_count())
    if len(devices) % num_replicas != 0:
        raise ValueError(
            f"{len(devices)} devices do not divide into {num_replicas} replicas"
        )
    per_replica = len(devices) // num_replicas
    if real_multislice:
        # Slice-aware placement: mesh_utils groups each replica's devices
        # by their actual slice so the data axis rides ICI, never DCN.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            (1, per_replica), (num_replicas, 1), devices=devices
        )
    else:
        # Virtual/test meshes: jax.devices() order is contiguous per host.
        dev_array = np.array(devices).reshape(num_replicas, per_replica)
    return Mesh(np.asarray(dev_array).reshape(num_replicas, per_replica),
                (REPLICA_AXIS, DATA_AXIS))


def mesh_without(mesh: Mesh, shard_index: int) -> Mesh:
    """The shrunken mesh after losing the device at FLAT index
    ``shard_index``: a 1-D ``data`` mesh over the surviving devices. The
    flat index covers every axis — on a 1-D mesh it is the row shard, on
    a 2-D ``(data, model)`` mesh it is ``data_idx·model_shards +
    model_idx``, so a loss on either axis shrinks through the same call
    (hybrid/2-D meshes flatten — after a loss the axis grouping is stale
    anyway, and the elastic fold re-plans the layout from scratch on the
    survivors; docs/RELIABILITY.md "Durable fits")."""
    devices = [d for i, d in enumerate(mesh.devices.flat) if i != shard_index]
    if not devices:
        raise ValueError("cannot shrink a mesh below one device")
    return make_mesh(devices=devices)


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host entry point: initialize the JAX distributed runtime (the
    launcher calls this once per host before any device use; the pod-slice
    runbook is docs/MULTIHOST.md — the analog of the reference's
    EC2.md:19-29 cluster recipe).

    Explicit coordination (args, or KEYSTONE_COORDINATOR /
    KEYSTONE_NUM_HOSTS / KEYSTONE_HOST_ID env — what bin/launch-pod.sh
    sets) takes precedence; otherwise ``jax.distributed.initialize``
    auto-detects SLURM / GKE-TPU / Cloud-TPU cluster environments on its
    own. When a cluster environment is detected or explicitly configured,
    an init failure is a real error and propagates; with no cluster
    detected (plain single host) the failed auto-detection is expected
    and swallowed."""
    from ..envknobs import env_int, env_raw, env_set

    coordinator_address = coordinator_address or env_raw("KEYSTONE_COORDINATOR")
    if num_processes is None and env_set("KEYSTONE_NUM_HOSTS"):
        num_processes = env_int("KEYSTONE_NUM_HOSTS", 0)
    if process_id is None and env_set("KEYSTONE_HOST_ID"):
        process_id = env_int("KEYSTONE_HOST_ID", 0)
    explicit = coordinator_address is not None
    given = {
        "KEYSTONE_COORDINATOR": coordinator_address,
        "KEYSTONE_NUM_HOSTS": num_processes,
        "KEYSTONE_HOST_ID": process_id,
    }
    if any(v is not None for v in given.values()) and any(
        v is None for v in given.values()
    ):
        # A partial manual-cluster config (any one or two of the triplet)
        # must fail loudly with the actionable message: swallowing the
        # host-id half would run this host uncoordinated on 1/N of the
        # data, and the coordinator-only half would surface as an opaque
        # version-dependent jax init error.
        missing = sorted(k for k, v in given.items() if v is None)
        raise ValueError(
            f"partial manual-cluster config: {missing} unset — set all of "
            "KEYSTONE_COORDINATOR/KEYSTONE_NUM_HOSTS/KEYSTONE_HOST_ID "
            "(docs/MULTIHOST.md) or none"
        )

    cluster_signals = (
        "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
        "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS",
    )
    in_cluster = explicit or any(env_set(v) for v in cluster_signals)
    if jax.distributed.is_initialized():
        return
    try:
        if explicit:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        else:
            jax.distributed.initialize()
    except Exception:
        # A JaxRuntimeError here subclasses RuntimeError, so no blanket
        # RuntimeError catch: in a cluster an init failure must propagate —
        # running degraded as an uncoordinated single host is worse.
        if in_cluster:
            raise
        # single host with no cluster env: auto-detect has nothing to find


def get_mesh() -> Mesh:
    """The active mesh (a default 1-D data mesh if none was set)."""
    global _current_mesh
    if _current_mesh is None:
        _current_mesh = make_mesh()
    return _current_mesh


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _current_mesh
    _current_mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    global _current_mesh
    prev = _current_mesh
    _current_mesh = mesh
    try:
        yield mesh
    finally:
        _current_mesh = prev


def data_axis_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape.get(DATA_AXIS, 1)


def num_devices() -> int:
    return len(jax.devices())


def local_memory_stats() -> list:
    """``memory_stats()`` of every local device that reports them — one
    dict per chip, so a residency decision sees the fullest chip, not
    chip 0. Empty on backends that report none (CPU test meshes)."""
    return [s for s in (d.memory_stats() for d in jax.local_devices()) if s]


@functools.lru_cache(maxsize=None)
def _memory_limit_of(devices: tuple) -> Optional[int]:
    stats = (d.memory_stats() for d in devices)
    limits = [int(s["bytes_limit"]) for s in stats if s and "bytes_limit" in s]
    return min(limits) if limits else None


def device_memory_limit_bytes() -> Optional[int]:
    """The smallest ``bytes_limit`` over the local devices, or None where
    the backend reports no memory statistics (CPU test meshes). Asked of
    the devices once a process: a device's limit does not change, and
    ``memory_stats()`` is a call into the runtime that an entry point
    which sizes every fit by it (pipelines/timit.py) would pay each time."""
    return _memory_limit_of(tuple(jax.local_devices()))


def device_memory_budget_bytes(fraction: float = 0.75) -> int:
    """Per-device memory budget for residency planning.

    Analog of the reference's 75%-of-cluster-free-memory default cache
    budget (reference: workflow/AutoCacheRule.scala:572-585), taken on
    the local device with the least headroom. Falls back to a
    conservative constant when the platform exposes no memory stats
    (CPU test meshes).
    """
    headroom = [
        s["bytes_limit"] - s.get("bytes_in_use", 0)
        for s in local_memory_stats()
        if "bytes_limit" in s
    ]
    if headroom:
        return int(min(headroom) * fraction)
    return int(4e9 * fraction)
