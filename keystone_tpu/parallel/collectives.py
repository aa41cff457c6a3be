"""Communication layer: XLA collectives over ICI/DCN.

The reference's entire communication backend is Spark primitives —
``broadcast`` for model state, ``treeReduce`` for gradient/Gram partial
sums, ``zip``+``mapPartitions`` for aligned residual updates, shuffles for
repartitioning (reference: SURVEY §2.10; nodes/learning/LBFGS.scala:97,
nodes/learning/internal/ReWeightedLeastSquares.scala:92-103).

The TPU-native backend replaces these with XLA collectives expressed inside
``shard_map`` regions: ``psum`` (allreduce over ICI) replaces treeReduce,
sharding-annotated closures replace broadcast, ``ppermute`` ring rotation
replaces the blockwise broadcast loop of the kernel solvers, and
``all_to_all`` replaces shuffles. Multi-slice (DCN) scaling works by adding
an outer mesh axis — the same collective lowers to a hierarchical
ICI-then-DCN reduction, which XLA performs automatically for meshes whose
outer axis spans slices.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, get_mesh


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=False):
    """Thin wrapper pinning this framework's defaults."""
    mesh = mesh or get_mesh()
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def allreduce_sum(x: jnp.ndarray, axis: str = DATA_AXIS) -> jnp.ndarray:
    """``psum`` — usable only inside a shard_map/pjit region."""
    return lax.psum(x, axis)


def all_gather(x: jnp.ndarray, axis: str = DATA_AXIS, tiled: bool = False) -> jnp.ndarray:
    return lax.all_gather(x, axis, tiled=tiled)


def ring_permute(x: jnp.ndarray, axis: str = DATA_AXIS, shift: int = 1) -> jnp.ndarray:
    """Rotate shards around the ring — one ICI hop per step.

    The substrate for blockwise kernel-matrix generation (the reference's
    broadcast-a-sample-block loop, KernelGenerator.scala:90-206, re-designed
    as ring dataflow — structurally ring attention).
    """
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def reduce_scatter(x: jnp.ndarray, axis: str = DATA_AXIS, scatter_dimension: int = 0) -> jnp.ndarray:
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension, tiled=True)


def axis_index(axis: str = DATA_AXIS) -> jnp.ndarray:
    return lax.axis_index(axis)


def replicated(mesh: Optional[Mesh], x: Any) -> Any:
    """Place a pytree fully replicated on the mesh (the broadcast analog)."""
    mesh = mesh or get_mesh()
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), x
    )


def all_to_all(
    x: jnp.ndarray,
    axis: str = DATA_AXIS,
    split_axis: int = 0,
    concat_axis: int = 0,
    tiled: bool = True,
) -> jnp.ndarray:
    """Shard transpose over the mesh axis — the Spark shuffle analog
    (reference: nodes/util/Shuffler.scala:18, StupidBackoff.scala:25-46
    repartitioning; SURVEY §2.10). Each device splits its local block
    along ``split_axis`` and exchanges pieces so device i ends up with
    everyone's i-th piece concatenated along ``concat_axis``."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )
