"""Statistical / elementwise vector operators.

TPU-native re-designs of the reference's stats nodes — each one is a
whole-batch XLA computation over (n, d) device arrays instead of a
per-vector Breeze loop:

- ``RandomSignNode``       (reference: nodes/stats/RandomSignNode.scala)
- ``PaddedFFT``            (reference: nodes/stats/PaddedFFT.scala:13-21)
- ``LinearRectifier``      (reference: nodes/stats/LinearRectifier.scala)
- ``NormalizeRows``        (reference: nodes/stats/NormalizeRows.scala)
- ``SignedHellingerMapper``(reference: nodes/stats/SignedHellingerMapper.scala)
- ``StandardScaler``       (reference: nodes/stats/StandardScaler.scala:16-77)
- ``Sampler``/``ColumnSampler`` (reference: nodes/stats/Sampler.scala)
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset, default_ingest_workers
from ...obs import names as _names
from ...obs import spans
from ...workflow.pipeline import BatchTransformer, Estimator, Transformer


class RandomSignNode(BatchTransformer):
    """Multiply each feature by a fixed random ±1 sign."""

    def __init__(self, signs: np.ndarray):
        self.signs = jnp.asarray(signs, dtype=jnp.float32)

    @staticmethod
    def create(size: int, seed: int = 0) -> "RandomSignNode":
        rng = np.random.default_rng(seed)
        return RandomSignNode(2.0 * rng.integers(0, 2, size=size) - 1.0)

    def apply_arrays(self, x):
        return x * self.signs


def next_power_of_two(n: int) -> int:
    return 1 << (n - 1).bit_length()


class PaddedFFT(BatchTransformer):
    """Zero-pad features to the next power of two; return the real parts of
    the first half of the Fourier transform (size p/2 output)."""

    def apply_arrays(self, x):
        d = x.shape[-1]
        p = next_power_of_two(d)
        padded = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - d)])
        # rfft returns p//2+1 coefficients; the reference keeps [0, p/2).
        return jnp.fft.rfft(padded, axis=-1).real[..., : p // 2].astype(x.dtype)


class CosineRandomFeatures(BatchTransformer):
    """Rahimi-Recht random cosine features: cos(x·Wᵀ + b)
    (reference: nodes/stats/CosineRandomFeatures.scala:19-75).

    One whole-batch GEMM on the MXU replaces the reference's
    partition-blocked Breeze GEMM; W rides along as a (d_out, d_in)
    device constant."""

    def __init__(self, w: np.ndarray, b: np.ndarray):
        if b.shape[0] != w.shape[0]:
            raise ValueError("rows of W and size of b must match")
        self.w = jnp.asarray(w, dtype=jnp.float32)
        self.b = jnp.asarray(b, dtype=jnp.float32)

    @staticmethod
    def create(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        dist: str = "gaussian",
        seed: int = 0,
    ) -> "CosineRandomFeatures":
        """W ~ gamma·dist, b ~ U[0, 2π) (reference: CosineRandomFeatures
        companion object; Cauchy variant for the TIMIT rfType flag)."""
        return CosineRandomFeatures(
            *CosineRandomFeatures.draw(
                num_input_features, num_output_features, gamma, dist, seed
            )
        )

    @staticmethod
    def draw(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        dist: str = "gaussian",
        seed: int = 0,
    ) -> "tuple[np.ndarray, np.ndarray]":
        """The host-side (W, b) that :meth:`create` wraps, so that several
        draws can be stacked into one transformer (pipelines/timit.py)
        with each row's weights unchanged."""
        rng = np.random.default_rng(seed)
        if dist == "gaussian":
            w = rng.normal(size=(num_output_features, num_input_features))
        elif dist == "cauchy":
            w = rng.standard_cauchy(size=(num_output_features, num_input_features))
        else:
            raise ValueError(f"unknown distribution {dist!r}")
        b = rng.uniform(0.0, 2.0 * np.pi, size=num_output_features)
        return w * gamma, b

    @staticmethod
    def draw_branches(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        dist: str,
        seeds: Sequence[int],
    ) -> "list[tuple[np.ndarray, np.ndarray]]":
        """:meth:`draw` for each seed of a bank of branches, in the order
        of ``seeds``, each (W, b) rounded to float32 as the constructor
        rounds it: the same bits as one :meth:`create` a seed.

        The branches have a generator each and numpy fills under
        ``nogil``, so they are drawn side by side on host threads, as
        many as the package's other host pools use
        (``default_ingest_workers``), and joined before this returns; one
        branch or one worker draws inline, with no pool. A worker rounds
        its own draw, so no more float64 copies than workers are alive.
        The span ``build:draw`` is the caller's; a worker opens none."""
        seeds = list(seeds)
        workers = max(1, min(len(seeds), default_ingest_workers()))

        def one(seed: int):
            w, b = CosineRandomFeatures.draw(
                num_input_features, num_output_features, gamma, dist, seed
            )
            return w.astype(np.float32), b.astype(np.float32)

        with spans.span("build:draw", branches=len(seeds), workers=workers):
            _names.metric(_names.FEATURE_DRAWS).inc(workers=str(workers))
            if workers == 1:
                return [one(seed) for seed in seeds]
            with ThreadPoolExecutor(workers, thread_name_prefix="keystone-draw") as pool:
                return list(pool.map(one, seeds))

    def apply_arrays(self, x):
        return jnp.cos(x @ self.w.T + self.b)


class LinearRectifier(BatchTransformer):
    """f(x) = max(max_val, x - alpha)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def apply_arrays(self, x):
        return jnp.maximum(self.max_val, x - self.alpha)


class NormalizeRows(BatchTransformer):
    """Scale each row to unit L2 norm (zero rows stay zero)."""

    def apply_arrays(self, x):
        norms = jnp.linalg.norm(x, axis=-1, keepdims=True)
        return x / jnp.where(norms == 0, 1.0, norms)


class SignedHellingerMapper(BatchTransformer):
    """x ↦ sign(x)·sqrt(|x|) (reference applies this before/after FV)."""

    def apply_arrays(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


class Clipper(BatchTransformer):
    """Elementwise clip to [lo, hi]."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def apply_arrays(self, x):
        return jnp.clip(x, self.lo, self.hi)


class StandardScalerModel(BatchTransformer):
    """Subtract column means; optionally divide by column stds."""

    def __init__(self, mean: jnp.ndarray, std: Optional[jnp.ndarray] = None):
        self.mean = jnp.asarray(mean)
        self.std = None if std is None else jnp.asarray(std)

    def apply_arrays(self, x):
        return _standardised(x, self.mean, self.std)


# The scaler's passes over x each run as ONE program, so that no (rows,
# columns) temporary is allocated beside x and the result: eagerly, the
# moments made `x * mask` twice and its square, and the model `x - mean`
# before the division, each as large as x (2.6 GB at CIFAR's 8,192 x
# 80,000), and a host running ahead of the device holds them all at once.
@jax.jit
def _masked_moments(x, mask):
    """Column sums of the masked rows and of their squares."""
    xm = x * mask
    return jnp.sum(xm, axis=0), jnp.sum(xm * xm, axis=0)


@jax.jit
def _standardised(x, mean, std):
    out = x - mean
    return out if std is None else out / std


class StandardScaler(Estimator):
    """Fit column mean/std in one masked pass over the sharded batch.

    Degenerate stds (0, NaN, inf, <eps) become 1.0, matching the
    reference's guard (StandardScaler.scala:50-56). Uses the unbiased
    (n-1) variance like MLlib's summarizer.
    """

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def out_spec(self, in_specs):
        from ...workflow.verify import elementwise_fit_spec

        return elementwise_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset) -> StandardScalerModel:
        ds = _as_array_dataset(data)
        x = ds.data
        n = ds.num_examples
        mask = ds.mask().reshape((-1,) + (1,) * (x.ndim - 1))
        s1, s2 = _masked_moments(x, mask)
        mean = s1 / n
        if not self.normalize_std_dev:
            return StandardScalerModel(mean, None)
        var = (s2 - n * mean**2) / max(n - 1, 1)
        std = jnp.sqrt(jnp.maximum(var, 0.0))
        std = jnp.where(
            jnp.isnan(std) | jnp.isinf(std) | (jnp.abs(std) < self.eps), 1.0, std
        )
        return StandardScalerModel(mean, std)


class Sampler(Transformer):
    """Random subsample of n_samples items
    (reference: nodes/stats/Sampler.scala FunctionNode via takeSample)."""

    def __init__(self, num_samples: int, seed: int = 42):
        self.num_samples = num_samples
        self.seed = seed

    def apply(self, datum):
        return datum

    def apply_batch(self, dataset: Dataset) -> Dataset:
        rng = np.random.default_rng(self.seed)
        n = len(dataset)
        take = min(self.num_samples, n)
        idx = np.sort(rng.choice(n, size=take, replace=False))
        if isinstance(dataset, ArrayDataset):
            import jax

            # a host batch is sampled on the host, a device batch on the device
            data = jax.tree_util.tree_map(lambda a: a[idx], dataset.data)
            return ArrayDataset(data, num_examples=take)
        items = dataset.collect()
        return type(dataset)([items[i] for i in idx])


class ColumnSampler(Transformer):
    """Sample descriptors from per-item (n_i, d) descriptor matrices and
    emit a flat (num_samples_total, d) dataset
    (reference: nodes/stats/ColumnSampler used by the ImageNet/VOC
    pipelines — the reference's matrices are (d, nᵢ) column-major; this
    framework's extractors emit descriptor rows, so "columns" here are the
    descriptor axis)."""

    def __init__(self, num_samples_per_item: int, seed: int = 42):
        self.num_samples_per_item = num_samples_per_item
        self.seed = seed

    def _sample(self, datum, rng) -> np.ndarray:
        mat = np.asarray(datum)
        n_desc = mat.shape[0]
        take = min(self.num_samples_per_item, n_desc)
        idx = rng.choice(n_desc, size=take, replace=False)
        return mat[idx]  # (take, d)

    def apply(self, datum):
        return self._sample(datum, np.random.default_rng(self.seed))

    def apply_batch(self, dataset: Dataset) -> ArrayDataset:
        from ...data.dataset import BucketedDataset

        if isinstance(dataset, BucketedDataset):
            # Masked/bucketed descriptors: sample on device per bucket
            # (Gumbel top-k over valid slots — no host desc[valid] fancy
            # indexing), concatenate the small sample matrices.
            parts = [
                np.asarray(self._sample_bucket(b, i).data)
                for i, b in enumerate(dataset.buckets)
            ]
            return ArrayDataset(np.concatenate(parts, axis=0))
        if isinstance(dataset, ArrayDataset) and isinstance(dataset.data, dict) \
                and "valid" in dataset.data:
            return self._sample_bucket(dataset, 0)
        if isinstance(dataset, ArrayDataset):
            return self._sample_uniform(dataset, np.random.default_rng(self.seed))
        # One rng threaded across items — re-seeding per item would sample
        # identical descriptor positions from every matrix.
        rng = np.random.default_rng(self.seed)
        rows = [self._sample(item, rng) for item in dataset.collect()]
        return ArrayDataset(np.concatenate(rows, axis=0))

    def sample_indices(self, rng, items: int, columns: int) -> np.ndarray:
        """The (items, take) columns this sampler picks from ``items``
        matrices of ``columns`` descriptors each, drawn from ``rng``:
        per-row sampling without replacement in one shot, the argsort of
        a random matrix (per-row choice() would be O(n) host calls). A
        generator fills row by row, so the rows drawn for one batch and
        for its chunks in order are the same rows."""
        take = min(self.num_samples_per_item, columns)
        return np.argsort(rng.random((items, columns)), axis=1)[:, :take]

    def _sample_uniform(self, dataset: ArrayDataset, rng) -> ArrayDataset:
        """A uniform (N, c, d) batch: one vectorized gather. Descriptors
        that are on a device stay there (the columns go up, the samples
        are gathered where the descriptors are: 2,048 images of dense SIFT
        are 13.8 GB that nobody should fetch to pick 1e6 rows of)."""
        x, n = dataset.data, dataset.num_examples
        _, c, d = x.shape
        idx = self.sample_indices(rng, n, c)
        if isinstance(x, np.ndarray):
            return ArrayDataset(x[np.arange(n)[:, None], idx].reshape(-1, d))
        if n != x.shape[0]:
            x = x[:n]
        picked = jnp.take_along_axis(x, jnp.asarray(idx)[:, :, None], axis=1)
        return ArrayDataset(picked.reshape(-1, d))

    def chunk_applier(self):
        """Uniform (N, c, d) batches only (``out_spec`` says so: nothing
        else is ever chunked): one generator through all the chunks."""
        rng = np.random.default_rng(self.seed)
        return lambda chunk: self._sample_uniform(chunk, rng)

    def out_spec(self, in_specs):
        """(N, c, d) -> (N * take, d); anything else is not for the
        verifier or the executor's row chains to reason about."""
        import jax

        from ...workflow.verify import UNKNOWN

        if not in_specs or in_specs[0] is UNKNOWN:
            return UNKNOWN
        leaves = jax.tree_util.tree_leaves(in_specs[0])
        if len(leaves) != 1 or len(leaves[0].shape) != 3:
            return UNKNOWN
        n, c, d = leaves[0].shape
        take = min(self.num_samples_per_item, c)
        return jax.ShapeDtypeStruct((n * take, d), leaves[0].dtype)

    def _sample_bucket(self, bucket: ArrayDataset, bucket_idx: int) -> ArrayDataset:
        """Uniform sample-without-replacement of valid descriptors, on
        device: Gumbel perturbation + top_k over the flattened valid slots
        (invalid slots get −inf, so they are never chosen while the take
        count stays within the valid total)."""
        import jax

        desc = jnp.asarray(bucket.data["desc"])
        valid = jnp.asarray(bucket.data["valid"])
        n = bucket.num_examples
        desc = desc[:n]
        valid = valid[:n]
        flat = desc.reshape(-1, desc.shape[-1])
        v = valid.reshape(-1).astype(bool)
        num_valid = int(jnp.sum(v))  # one scalar fetch per bucket
        take = min(self.num_samples_per_item * n, num_valid)
        if take == 0:
            return ArrayDataset(np.zeros((0, desc.shape[-1]), np.float32))
        key = jax.random.PRNGKey(self.seed + 7919 * bucket_idx)
        g = jax.random.gumbel(key, v.shape) + jnp.where(v, 0.0, -jnp.inf)
        _, idx = jax.lax.top_k(g, take)
        return ArrayDataset(flat[idx])


def _as_array_dataset(data: Dataset) -> ArrayDataset:
    if isinstance(data, ArrayDataset):
        return data
    from ...data.dataset import BucketedDataset

    if isinstance(data, BucketedDataset):
        return data.concat()
    return data.to_arrays()  # type: ignore[attr-defined]
