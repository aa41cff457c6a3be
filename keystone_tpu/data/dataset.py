"""Dataset substrate: the TPU-native replacement for the reference's RDDs.

The reference moves every collection through Spark ``RDD[T]``s; featurizers
run ``mapPartitions`` over JVM objects and solvers batch partition rows into
local BLAS matrices (reference: utils/MatrixUtils.scala:17-205
``rowsToMatrixIter``; workflow/Operator.scala:10-177).

On TPU the idiomatic substrate is different, so this is a re-design, not a
port:

- ``ArrayDataset`` — a pytree of arrays with a leading example axis, the
  device-resident form. Solvers and batched featurizers consume it whole
  (one XLA computation over the sharded batch), replacing the reference's
  partition-wise GEMM idiom.
- ``ObjectDataset`` — a host-side list of Python objects (raw images,
  strings, token lists); the staging ground before padding/batching onto
  device. Replaces ``RDD[LabeledImage]``-style collections.

Both expose ``map``/``collect``/``cache`` so the untyped operator layer can
treat them uniformly. Sharding over a ``jax.sharding.Mesh`` happens when an
``ArrayDataset`` is placed with :func:`ArrayDataset.shard`; zero-row padding
makes the example count divisible by the mesh's data axis (zero rows are
harmless to Gram/gradient accumulation and are masked out of statistics via
``num_examples``).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..envknobs import env_str

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def default_ingest_workers() -> int:
    """Host-side worker count shared by every ingest-adjacent pool:
    ``ObjectDataset.map``, the archive decode pool, the streaming
    engine's prefetch pipeline, and the draw of a bank of random-feature
    branches (``CosineRandomFeatures.draw_branches``).
    ``KEYSTONE_INGEST_WORKERS`` overrides; the default derives from the
    host's core count (capped — tar decode pools past ~32 threads just
    fight the GIL/page cache)."""
    raw = env_str("KEYSTONE_INGEST_WORKERS").strip()
    if raw:
        return max(1, int(raw))
    return max(2, min(32, os.cpu_count() or 4))


def transfer_dtype(dtype) -> np.dtype:
    """The dtype a host array should CROSS the host→device link as.

    Narrow dtypes (uint8 images, int16 audio, bool masks) stay narrow —
    transfer scales with bytes, and uint8 is 4× less traffic than the
    float32 the math eventually wants (measured fact backing
    pipelines/imagenet_streaming.py); the consumer casts ON DEVICE.
    64-bit host types squeeze to 32-bit: jax (x64 disabled) would
    canonicalize them to 32-bit anyway, so shipping 8 bytes/element is
    pure waste.
    """
    dtype = np.dtype(dtype)
    if dtype == np.float64:
        return np.dtype(np.float32)
    if dtype == np.int64:
        return np.dtype(np.int32)
    if dtype == np.uint64:
        return np.dtype(np.uint32)
    if dtype == np.complex128:
        return np.dtype(np.complex64)
    return dtype


class Dataset:
    """Abstract logical collection of examples."""

    def map(self, fn: Callable[[Any], Any]) -> "Dataset":
        raise NotImplementedError

    def collect(self) -> List[Any]:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    def take(self, n: int) -> List[Any]:
        return self.collect()[:n]

    def cache(self) -> "Dataset":
        """Materialization point (reference: nodes/util/Cacher.scala:15-25).

        ``ArrayDataset`` is already materialized in HBM; ``ObjectDataset``
        forces any lazy source. Returns self for chaining.
        """
        return self

    def fetch_rows(self, start: int, stop: int) -> Any:
        """Host numpy pytree of the ``[start, stop)`` example window,
        stored dtype preserved. The one chunk-windowing primitive: both
        :meth:`iter_chunks` and the streaming engine's parallel prefetch
        workers (workflow/streaming.py) go through it, so window
        semantics can't diverge. Subclasses without a chunkable physical
        layout don't implement it — the streaming planner falls back to
        the materialized path for them."""
        raise NotImplementedError(f"{type(self).__name__} is not chunkable")

    def iter_chunks(self, chunk_rows: int) -> Iterator[Tuple[Any, int]]:
        """Yield ``(host_pytree, num_valid_rows)`` windows of at most
        ``chunk_rows`` examples, in order, as host numpy arrays with
        their stored dtype preserved (the streaming engine narrows via
        :func:`transfer_dtype` at upload time)."""
        n = len(self)
        for start in range(0, n, chunk_rows):
            stop = min(start + chunk_rows, n)
            yield self.fetch_rows(start, stop), stop - start

    @property
    def num_shards(self) -> int:
        return 1

    def per_shard_counts(self) -> List[int]:
        """Analog of the reference's ``WorkflowUtils.numPerPartition``."""
        n = len(self)
        k = self.num_shards
        base, extra = divmod(n, k)
        return [base + (1 if i < extra else 0) for i in range(k)]


class ObjectDataset(Dataset):
    """Host-side list of arbitrary Python objects."""

    def __init__(self, items: Sequence[Any], num_shards: Optional[int] = None):
        self._items = list(items)
        self._num_shards = num_shards or 1

    def map(self, fn: Callable[[Any], Any], parallel: Optional[bool] = None) -> "ObjectDataset":
        """Per-item host map, fanned over a thread pool for larger
        datasets (the RDD-map analog; pays off when ``fn`` releases the
        GIL — numpy, PIL, the native kernels — which is what host-side
        featurizer fallbacks do). Order is preserved.

        ``fn`` must be safe to call concurrently (the RDD-map contract);
        pass ``parallel=False`` for functions with shared mutable state,
        ``parallel=True`` to force the pool for small datasets. Pool
        width comes from :func:`default_ingest_workers`
        (``KEYSTONE_INGEST_WORKERS``), shared with the archive decode
        pool and the streaming prefetch pipeline."""
        if parallel is None:
            parallel = len(self._items) >= 64
        if parallel:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=default_ingest_workers()) as pool:
                return ObjectDataset(list(pool.map(fn, self._items)), self._num_shards)
        return ObjectDataset([fn(x) for x in self._items], self._num_shards)

    def collect(self) -> List[Any]:
        return list(self._items)

    def __len__(self) -> int:
        return len(self._items)

    @property
    def num_shards(self) -> int:
        return self._num_shards

    def to_arrays(self) -> "ArrayDataset":
        """Stack items (arrays or pytrees of equal shape) into an ArrayDataset."""
        if not self._items:
            raise ValueError("cannot stack an empty dataset")
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *self._items)
        return ArrayDataset(stacked)

    def fetch_rows(self, start: int, stop: int) -> Any:
        """Stack one window of items on demand — only the window is ever
        stacked, so host residency stays O(chunk) no matter the dataset
        size; the streaming prefetch workers call this concurrently."""
        window = self._items[start:stop]
        return jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *window
        )

    def __repr__(self) -> str:
        return f"ObjectDataset(n={len(self._items)}, shards={self._num_shards})"


def _leading_dim(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty pytree")
    n = leaves[0].shape[0]
    for leaf in leaves:
        if leaf.shape[0] != n:
            raise ValueError("inconsistent leading dimensions in dataset pytree")
    return n


class ArrayDataset(Dataset):
    """A pytree of arrays with a shared leading example axis.

    ``num_examples`` is the *logical* row count; the physical arrays may be
    zero-padded past it so the leading axis divides the mesh's data axis.
    """

    def __init__(self, data: Any, num_examples: Optional[int] = None):
        self.data = data
        physical = _leading_dim(data)
        self.num_examples = num_examples if num_examples is not None else physical
        if self.num_examples > physical:
            raise ValueError("num_examples exceeds physical leading dim")

    # ------------------------------------------------------------- protocol
    def __len__(self) -> int:
        return self.num_examples

    @property
    def physical_rows(self) -> int:
        return _leading_dim(self.data)

    def collect(self) -> List[Any]:
        host = jax.tree_util.tree_map(np.asarray, self.data)
        return [
            jax.tree_util.tree_map(lambda a: a[i], host) for i in range(self.num_examples)
        ]

    def map(self, fn: Callable[[Any], Any]) -> "ObjectDataset":
        """Per-item host map. Prefer :meth:`map_batched` on the device path."""
        return ObjectDataset([fn(x) for x in self.collect()])

    def map_batched(self, fn: Callable[[Any], Any], num_examples: Optional[int] = None) -> "ArrayDataset":
        """Apply ``fn`` to the whole batched pytree — one XLA computation."""
        out = fn(self.data)
        return ArrayDataset(out, num_examples if num_examples is not None else self.num_examples)

    def take(self, n: int) -> List[Any]:
        n = min(n, self.num_examples)
        host = jax.tree_util.tree_map(lambda a: np.asarray(a[:n]), self.data)
        return [jax.tree_util.tree_map(lambda a: a[i], host) for i in range(n)]

    def fetch_rows(self, start: int, stop: int) -> Any:
        """Host-side row window of the logical (unpadded) examples.
        Device-resident leaves are pulled per window, never whole —
        a chunked read of an HBM-resident dataset stays O(chunk)."""
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a[start:stop]), self.data
        )

    # ------------------------------------------------------------- sharding
    def padded_to(self, multiple: int) -> "ArrayDataset":
        """Zero-pad the leading axis up to the next multiple of ``multiple``.

        Dtype-preserving by contract: a uint8 image batch pads to uint8 —
        narrowing to the storage dtype and casting on DEVICE is what
        keeps host→device traffic at 1 byte/px (see
        :func:`transfer_dtype`); an upcast here would silently 4× it.
        """
        physical = self.physical_rows
        target = ((physical + multiple - 1) // multiple) * multiple
        if target == physical:
            return self
        pad = target - physical

        def pad_leaf(a):
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return jnp.pad(a, widths) if isinstance(a, jnp.ndarray) else np.pad(a, widths)

        return ArrayDataset(jax.tree_util.tree_map(pad_leaf, self.data), self.num_examples)

    def shard(self, mesh: jax.sharding.Mesh, axis: str = "data") -> "ArrayDataset":
        """Place on ``mesh`` sharded along the leading axis.

        Zero-pads so the leading axis divides the mesh axis size — the
        TPU-native analog of the reference's row-partitioned RDDs.
        Host leaves cross the link at :func:`transfer_dtype` width
        (uint8 stays uint8, float64 squeezes to float32) so the
        placement never silently widens the transfer.
        """
        n_dev = mesh.shape[axis]
        ds = self.padded_to(n_dev)

        def place(a):
            if isinstance(a, np.ndarray):
                narrow = transfer_dtype(a.dtype)
                if narrow != a.dtype:
                    a = a.astype(narrow)
            spec = P(axis, *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(mesh, spec))

        return ArrayDataset(jax.tree_util.tree_map(place, ds.data), self.num_examples)

    @property
    def num_shards(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.data)
        leaf = leaves[0]
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "num_devices"):
            try:
                return sharding.num_devices
            except Exception:
                return 1
        return 1

    def mask(self) -> jnp.ndarray:
        """1.0 for real rows, 0.0 for padding — shape (physical_rows,)."""
        return (jnp.arange(self.physical_rows) < self.num_examples).astype(jnp.float32)

    def __repr__(self) -> str:
        shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), self.data)
        return f"ArrayDataset(n={self.num_examples}, shapes={shapes})"


class BucketedDataset(Dataset):
    """A logical dataset physically stored as static-shape groups.

    The native-resolution path (SURVEY §7 hard part 4) groups images by
    padded size so each group is one XLA compilation; this class makes
    those groups a first-class Dataset the workflow layer can execute —
    batched transformers map per bucket, estimators consume the
    concatenation — so native-resolution pipelines flow through the
    optimizer/autocache/prefix-reuse machinery instead of a bespoke host
    loop. Example order is bucket-major and stable across ops, so labels
    aligned to ``concat()`` order stay aligned downstream.
    """

    def __init__(self, buckets: Sequence["ArrayDataset"]):
        if not buckets:
            raise ValueError("BucketedDataset needs at least one bucket")
        self.buckets = list(buckets)

    # ------------------------------------------------------------- protocol
    def __len__(self) -> int:
        return sum(len(b) for b in self.buckets)

    def collect(self) -> List[Any]:
        out: List[Any] = []
        for b in self.buckets:
            out.extend(b.collect())
        return out

    def map(self, fn: Callable[[Any], Any]) -> "ObjectDataset":
        return ObjectDataset([fn(x) for x in self.collect()])

    def map_datasets(self, fn: Callable[["ArrayDataset"], "ArrayDataset"]) -> "BucketedDataset":
        """Apply a per-bucket Dataset→Dataset function (the workflow-layer
        entry point: one static-shape computation per bucket)."""
        return BucketedDataset([fn(b) for b in self.buckets])

    def map_batched(self, fn: Callable[[Any], Any]) -> "BucketedDataset":
        return BucketedDataset([b.map_batched(fn) for b in self.buckets])

    @property
    def num_shards(self) -> int:
        return len(self.buckets)

    def per_shard_counts(self) -> List[int]:
        return [len(b) for b in self.buckets]

    def concat(self) -> "ArrayDataset":
        """Concatenate buckets along the example axis (valid once trailing
        shapes agree — e.g. after Fisher encoding collapses per-bucket
        descriptor grids to fixed-width features)."""
        datas = [
            jax.tree_util.tree_map(lambda a: a[: len(b)], b.data)
            for b in self.buckets
        ]
        joined = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *datas
        )
        return ArrayDataset(joined)

    def __repr__(self) -> str:
        return f"BucketedDataset(buckets={[len(b) for b in self.buckets]})"


def as_dataset(value: Any) -> Dataset:
    """Coerce lists/arrays into a Dataset."""
    if isinstance(value, Dataset):
        return value
    if isinstance(value, (list, tuple)):
        return ObjectDataset(list(value))
    if isinstance(value, (np.ndarray, jnp.ndarray)):
        return ArrayDataset(value)
    raise TypeError(f"cannot interpret {type(value)} as a Dataset")
