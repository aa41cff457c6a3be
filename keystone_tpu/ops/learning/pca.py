"""PCA family: local SVD, distributed TSQR, randomized, optimizable wrapper.

TPU-native re-design of the reference's PCA suite
(reference: nodes/learning/PCA.scala:51-247,
nodes/learning/DistributedPCA.scala:20-74,
nodes/learning/ApproximatePCA.scala:22-85).

Behavioral parity:
- Columns are mean-centered before decomposition.
- The MATLAB sign convention is enforced: each component's largest-magnitude
  coefficient is positive (PCA.scala enforceMatlabPCASignConvention).
- ``PCATransformer`` projects vectors x ↦ xᵀ·P; ``BatchPCATransformer``
  projects per-item (d, nᵢ) descriptor matrices Pᵀ·M.

TPU re-design notes: the "distributed" variant runs TSQR over the row
shards and eigendecomposes the centered d×d Gram (algebraic centering,
RᵀR − n·μμᵀ) — no centered copy, one all_gather of tiny R factors over
ICI. The randomized variant is Halko et al. alg. 4.4/5.1 with the power
iterations expressed as a lax.fori_loop of device matmuls + QRs.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset, ObjectDataset
from ...obs import spans as _spans
from ...parallel import linalg
from ...parallel.mesh import get_mesh, num_devices
from ...parallel.partitioner import fit_mesh
from ...workflow.optimize import DataStats, Optimizable
from ...workflow.pipeline import BatchTransformer, Estimator, Transformer
from .cost import DEFAULT_COST_WEIGHTS, CostModel
from ..stats.core import _as_array_dataset


def enforce_sign_convention(components: jnp.ndarray) -> jnp.ndarray:
    """Largest-|coefficient| entry of each column made positive
    (reference: PCA.scala enforceMatlabPCASignConvention)."""
    col_max = jnp.max(components, axis=0)
    col_absmax = jnp.max(jnp.abs(components), axis=0)
    signs = jnp.where(col_max == col_absmax, 1.0, -1.0)
    return components * signs


class PCATransformer(BatchTransformer):
    """Project feature vectors onto the top components: (n,d) @ (d,k)."""

    def __init__(self, components: jnp.ndarray):  # (d, k)
        self.components = jnp.asarray(components)

    def apply_arrays(self, x):
        return linalg.mm(x, self.components)


class BatchPCATransformer(Transformer):
    """Project per-item (nᵢ, d) descriptor matrices: M · P → (nᵢ, k)
    (reference: PCA.scala BatchPCATransformer — the reference holds
    descriptors as columns of (d, nᵢ) matrices; this framework's extractors
    emit descriptor-rows with the feature dim last, the TPU-friendly
    layout, so the projection is a plain right-multiply)."""

    def __init__(self, components: jnp.ndarray):
        self.components = jnp.asarray(components)

    def apply(self, mat):
        return np.asarray(mat) @ np.asarray(self.components)

    def apply_batch(self, dataset: Dataset) -> Dataset:
        from ...data.dataset import BucketedDataset

        if isinstance(dataset, BucketedDataset):
            return dataset.map_datasets(self.apply_batch)
        if isinstance(dataset, ArrayDataset):
            if isinstance(dataset.data, dict) and "valid" in dataset.data:
                # Masked descriptors: project, validity flows through
                # (zero rows stay zero under a right-multiply).
                out = jnp.einsum(
                    "ncd,dk->nck", jnp.asarray(dataset.data["desc"]),
                    self.components, precision=linalg.precision(),
                )
                return ArrayDataset(
                    {"desc": out, "valid": dataset.data["valid"]},
                    dataset.num_examples,
                )
            x = jnp.asarray(dataset.data)
            if x.ndim == 2:  # flat (n, d) descriptor rows
                out = linalg.mm(x, self.components)
            else:  # uniform (n, cols, d) stack: one batched einsum on the MXU
                with _spans.span(
                    "image:pca", rows=dataset.num_examples,
                    descriptors=int(x.shape[1]), dims=int(self.components.shape[1]),
                ):
                    out = _project_stack(x, self.components)
            return ArrayDataset(out, dataset.num_examples)
        return dataset.map(self.apply)

    def chunk_applier(self):
        """Row by row in every batch form above (a right-multiply)."""
        return self.apply_batch

    def out_spec(self, in_specs):
        """(n, c, d) or (n, d) -> the same with ``dims`` last; the masked
        dictionary and everything else is left to the verifier's default."""
        from ...workflow.verify import UNKNOWN

        if not in_specs or in_specs[0] is UNKNOWN or isinstance(in_specs[0], dict):
            return UNKNOWN
        leaves = jax.tree_util.tree_leaves(in_specs[0])
        if len(leaves) != 1 or len(leaves[0].shape) not in (2, 3):
            return UNKNOWN
        shape = tuple(leaves[0].shape[:-1]) + (int(self.components.shape[1]),)
        return jax.ShapeDtypeStruct(shape, jnp.result_type(leaves[0].dtype, self.components.dtype))


@linalg.mode_jit
def _project_stack(x, components):
    """(n, cols, d) x (d, k): one program, so that its operations carry
    the transformer's name in a device trace (an eager einsum has none)."""
    with jax.named_scope("feat/BatchPCATransformer"):
        return jnp.einsum("ncd,dk->nck", x, components, precision=linalg.precision())


class PCAEstimator(Estimator, CostModel):
    """Local (single-computation) SVD PCA (reference: PCA.scala:163-247)."""

    def __init__(self, dims: int):
        self.dims = dims

    def out_spec(self, in_specs):
        """Plan-time spec protocol (workflow/verify.py): the fitted
        projection replaces the descriptor axis with ``dims``."""
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset) -> PCATransformer:
        x = jnp.asarray(_as_array_dataset(data).data, dtype=jnp.float32)
        n = _as_array_dataset(data).num_examples
        x = x[:n]
        return PCATransformer(compute_pca(x, self.dims))

    def cost(self, n, d, k, sparsity, num_machines, w=DEFAULT_COST_WEIGHTS):
        flops = float(n) * d * d
        bytes_scanned = float(n) * d
        network = float(n) * d  # collect to one device
        return max(w.cpu * flops, w.mem * bytes_scanned) + w.network * network


@linalg.mode_jit
def _pca_svd(x):
    mu = jnp.mean(x, axis=0)
    _, _, vt = jnp.linalg.svd(x - mu, full_matrices=False)
    return enforce_sign_convention(vt.T)


def compute_pca(x: jnp.ndarray, dims: int) -> jnp.ndarray:
    return _pca_svd(x)[:, :dims]


class DistributedPCAEstimator(Estimator, CostModel):
    """TSQR-based PCA over the row-sharded sample
    (reference: DistributedPCA.scala:20-74, mlmatrix TSQR).

    Centering is algebraic: eigh(RᵀR − n·μμᵀ) gives the centered
    covariance eigenvectors without materializing A − μ.
    """

    def __init__(self, dims: int):
        self.dims = dims

    def out_spec(self, in_specs):
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset) -> PCATransformer:
        ds = _as_array_dataset(data)
        mesh = fit_mesh(self)
        x = linalg.prepare_row_sharded(jnp.asarray(ds.data, dtype=jnp.float32), mesh)
        n = ds.num_examples
        r = linalg.tsqr_r(x, mesh=mesh)
        sa = jnp.sum(x, axis=0)  # zero-padded rows are inert
        components = _centered_eig_components(r, sa, jnp.float32(n))
        return PCATransformer(components[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, w=DEFAULT_COST_WEIGHTS):
        flops = float(n) * d * d / num_machines + d * d * d
        bytes_scanned = float(n) * d / num_machines
        network = float(d) * d * np.log2(max(num_machines, 2))
        return max(w.cpu * flops, w.mem * bytes_scanned) + w.network * network


@linalg.mode_jit
def _centered_eig_components(r, sa, n):
    mu = sa / n
    cov = linalg.mm(r.T, r) - n * jnp.outer(mu, mu)
    # eigh returns ascending eigenvalues; PCA wants descending.
    _, vecs = jnp.linalg.eigh(cov)
    return enforce_sign_convention(vecs[:, ::-1])


class ApproximatePCAEstimator(Estimator, CostModel):
    """Randomized range-finder PCA (Halko/Martinsson/Tropp 2011, alg 4.4+5.1;
    reference: ApproximatePCA.scala:22-85)."""

    def __init__(self, dims: int, q: int = 10, p: int = 5, seed: int = 0):
        self.dims = dims
        self.q = q
        self.p = p
        self.seed = seed

    def out_spec(self, in_specs):
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset) -> PCATransformer:
        ds = _as_array_dataset(data)
        x = jnp.asarray(ds.data, dtype=jnp.float32)[: ds.num_examples]
        comps = _approximate_pca(x, self.dims + self.p, self.q, self.seed)
        return PCATransformer(comps[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, w=DEFAULT_COST_WEIGHTS):
        l = k + 5
        flops = float(n) * d * l * (1 + 10)
        bytes_scanned = float(n) * l
        network = float(n) * d
        return max(w.cpu * flops, w.mem * bytes_scanned) + w.network * network


def _approximate_pca(x, l, q, seed):
    return _approx_pca_jit(x, jax.random.PRNGKey(seed), l, q)


@functools.partial(linalg.mode_jit, static_argnums=(2, 3))
def _approx_pca_jit(x, key, l, q):
    mu = jnp.mean(x, axis=0)
    a = x - mu
    d = a.shape[1]
    omega = jax.random.normal(key, (d, l), dtype=a.dtype)
    y0 = linalg.mm(a, omega)
    qmat, _ = jnp.linalg.qr(y0)

    def power_iter(_, qm):
        yh = linalg.mm(qm.T, a)          # (l, d)
        qh, _ = jnp.linalg.qr(yh.T)      # (d, l)
        yj = linalg.mm(a, qh)            # (n, l)
        qn, _ = jnp.linalg.qr(yj)
        return qn

    qmat = jax.lax.fori_loop(0, q, power_iter, qmat)
    b = linalg.mm(qmat.T, a)             # (l, d)
    _, _, vt = jnp.linalg.svd(b, full_matrices=False)
    return enforce_sign_convention(vt.T)


# ------------------------------------------------- optimizable column wrapper


class LocalColumnPCAEstimator(Estimator, CostModel):
    """PCA over the descriptors of per-item (nᵢ, d) matrices, local SVD
    (reference: PCA.scala:51-73 — the reference's matrices are (d, nᵢ)
    column-major; this framework holds descriptor rows)."""

    def __init__(self, dims: int):
        self.dims = dims
        self._inner = PCAEstimator(dims)

    def out_spec(self, in_specs):
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset) -> BatchPCATransformer:
        flat = _columns_to_vectors(data)
        with _spans.span("pca:fit", method="local", samples=len(flat), dims=self.dims):
            t = self._inner.fit(flat)
        return BatchPCATransformer(t.components)

    def cost(self, *args, **kw):
        return self._inner.cost(*args, **kw)


class DistributedColumnPCAEstimator(Estimator, CostModel):
    """Descriptor PCA over per-item (nᵢ, d) matrices via distributed TSQR
    (reference: PCA.scala:75-103)."""

    def __init__(self, dims: int):
        self.dims = dims
        self._inner = DistributedPCAEstimator(dims)

    def out_spec(self, in_specs):
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset) -> BatchPCATransformer:
        flat = _columns_to_vectors(data)
        with _spans.span("pca:fit", method="tsqr", samples=len(flat), dims=self.dims):
            t = self._inner.fit(flat)
        return BatchPCATransformer(t.components)

    def cost(self, *args, **kw):
        return self._inner.cost(*args, **kw)


class ColumnPCAEstimator(Estimator, Optimizable, CostModel):
    """Cost-model-driven choice between local and distributed column PCA
    (reference: PCA.scala:105-161 ColumnPCAEstimator). Default weights were
    fit on the reference's 16-node cluster; TPU re-fit pending."""

    def __init__(self, dims: int, num_machines: Optional[int] = None,
                 weights=DEFAULT_COST_WEIGHTS):
        self.dims = dims
        self.num_machines = num_machines
        self.weights = weights
        self.local = LocalColumnPCAEstimator(dims)
        self.distributed = DistributedColumnPCAEstimator(dims)

    def out_spec(self, in_specs):
        from ...workflow.verify import projection_fit_spec

        return projection_fit_spec(in_specs, self.label, dims=self.dims)

    def fit(self, data: Dataset):
        return self.distributed.fit(data)  # the reference's default

    def optimize(self, samples: List[Dataset], stats: DataStats):
        sample = samples[0]
        items = sample.take(8)
        if not items:
            return self.distributed
        if isinstance(items[0], dict) and "valid" in items[0]:
            # Masked-descriptor items ({"desc": (n_pad, d), "valid": ...}):
            # the true per-item descriptor count is the valid total.
            cols = float(np.mean([np.asarray(m["valid"]).sum() for m in items]))
            d = int(np.asarray(items[0]["desc"]).shape[-1])
        elif np.asarray(items[0]).ndim == 1:
            # Plain feature vectors: one row per item.
            cols = 1.0
            d = int(np.asarray(items[0]).shape[0])
        else:
            cols = float(np.mean([np.asarray(m).shape[0] for m in items]))
            d = int(np.asarray(items[0]).shape[1])
        n = int(cols * stats.n_total)
        machines = self.num_machines or num_devices()
        lc = self.local.cost(n, d, self.dims, 1.0, machines, self.weights)
        dc = self.distributed.cost(n, d, self.dims, 1.0, machines, self.weights)
        return self.local if lc < dc else self.distributed


def _columns_to_vectors(data: Dataset) -> ArrayDataset:
    """Flatten per-item (nᵢ, d) descriptor matrices into one (Σnᵢ, d)
    vector dataset."""
    if isinstance(data, ArrayDataset):
        x = jnp.asarray(data.data)
        if x.ndim == 2:
            return ArrayDataset(x, data.num_examples)
        # (n, c, d) → (n·c, d)
        n, c, d = x.shape
        return ArrayDataset(x.reshape(n * c, d))
    mats = [np.asarray(m) for m in data.collect()]
    return ArrayDataset(np.concatenate(mats, axis=0))
