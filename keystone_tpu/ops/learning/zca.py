"""ZCA whitening.

TPU-native re-design of reference: nodes/learning/ZCAWhitener.scala:12-77.
Fit: the eigendecomposition C = V·diag(λ)·Vᵀ of the patches' covariance,
whitener = V·diag((λ+ε)^-½)·Vᵀ, in float64 on the host (``_zca_fit``).
Apply: (M − μ) · W for per-item patch matrices — one batched matmul when
items are uniformly shaped.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset
from ...parallel import linalg
from ...workflow.pipeline import Estimator, Transformer


class ZCAWhitener(Transformer):
    def __init__(self, whitener: jnp.ndarray, means: jnp.ndarray):
        self.whitener = jnp.asarray(whitener)  # (d, d)
        self.means = jnp.asarray(means)  # (d,)

    def apply(self, mat):
        return np.asarray((jnp.asarray(mat) - self.means) @ self.whitener)

    def apply_batch(self, dataset: Dataset) -> Dataset:
        if isinstance(dataset, ArrayDataset):
            x = jnp.asarray(dataset.data)
            out = linalg.mm(x - self.means, self.whitener)
            return ArrayDataset(out, dataset.num_examples)
        return dataset.map(self.apply)


class ZCAWhitenerEstimator(Estimator):
    """Fit on the (first / full) patch matrix
    (reference: ZCAWhitener.scala fitSingle)."""

    def __init__(self, eps: float = 0.1):
        self.eps = eps

    def out_spec(self, in_specs):
        """Plan-time spec protocol (workflow/verify.py): whitening
        preserves shape and dtype."""
        from ...workflow.verify import elementwise_fit_spec

        return elementwise_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset) -> ZCAWhitener:
        if isinstance(data, ArrayDataset):
            mat = np.asarray(data.data)[: data.num_examples]
            if mat.ndim == 3:  # dataset of matrices: use the first, like the reference
                mat = mat[0]
        else:
            mat = np.asarray(data.take(1)[0])
        return self.fit_single(mat)

    def fit_single(self, mat) -> ZCAWhitener:
        whitener, means = _zca_fit(np.asarray(mat, np.float64), self.eps)
        return ZCAWhitener(whitener.astype(np.float32), means.astype(np.float32))


def _zca_fit(mat: np.ndarray, eps: float):
    """The whitener and the means of the (n, d) patches, in float64 on
    the host: a d x d eigenproblem (108 at CIFAR's 6 x 6 x 3 patches).

    Row-normalised patches each sum to zero, so their covariance has a
    null direction, which the whitener scales by ε^-½ (316 at ε = 1e-5):
    the float32 rounding of the covariance or of an SVD lands there. A
    float32 SVD of CIFAR's 100,000 centred patches on a TPU v5e left the
    filters' max |f C f' - 1| at 5.3e-6 to 1.8e-5; a float32 covariance
    left the whitener 3.5e-5 to 4.6e-4 from C^-½ in relative Frobenius
    norm (on a CPU). In float64, stored as float32, they read 6-9e-7 and
    2.4e-8. The covariance is taken from the Gram less n·μμᵀ, one pass
    over the patches and no centred copy."""
    n = mat.shape[0]
    means = mat.mean(axis=0)
    cov = (mat.T @ mat - n * np.outer(means, means)) / (n - 1.0)
    lam, vec = np.linalg.eigh(cov)
    return (vec * (lam + eps) ** -0.5) @ vec.T, means
