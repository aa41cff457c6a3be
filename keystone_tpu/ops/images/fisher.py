"""Fisher Vector encoding from GMM posteriors.

TPU-native re-design of the reference's Scala + native enceval encoders
(reference: nodes/images/FisherVector.scala:20-94,
nodes/images/external/FisherVector.scala:17-55,
src/main/cpp/EncEval.cxx:1-100 ``calcAndGetFVs``). The encoding is pure
dense algebra — posterior-weighted moment statistics — so the whole batch
of per-image descriptor matrices is one XLA computation (two MXU GEMMs per
image via batched einsum) instead of a per-image C++ call.

Math (Sanchez et al., IJCV 2013, as implemented by the reference):
    s0 = mean_n q_nk                         (K,)
    s1 = Xᵀ q / n                            (D, K)
    s2 = (X∘X)ᵀ q / n                        (D, K)
    fv1 = (s1 − μ·diag(s0)) / (σ·diag(√w))
    fv2 = (s2 − 2μ∘s1 + (μ∘μ − σ²)·diag(s0)) / (σ²·diag(√(2w)))
    FV  = [fv1 | fv2]                        (D, 2K)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset
from ...obs import spans as _spans
from ...parallel import linalg
from ...workflow.optimize import DataStats, Optimizable
from ...workflow.pipeline import BatchTransformer, Estimator
from ..learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
    _gmm_posteriors,
)


@linalg.mode_jit
def _fisher_encode(x, means, variances, weights, weight_threshold, valid=None):
    """(N, n_desc, D) descriptors -> (N, D, 2K): the posteriors and the
    two gradients as ONE program, whose operations carry the encoder's
    name in a device trace (dispatched one by one they carry none).

    (image, descriptor) stay two axes from the first operation to the
    last: merged into one and split again they are two copies through
    linear memory, slice by slice, wherever the descriptor count is no
    multiple of the chip's 128-wide tile (13,165 is none).

    ``valid`` (N, n_desc), where given, marks an image's own descriptors
    in a padded batch: the others weigh nothing and the statistics divide
    by each image's count."""
    with jax.named_scope("feat/FisherVector"):
        x = x.astype(jnp.float32)
        means = means.astype(jnp.float32)          # (D, K)
        variances = variances.astype(jnp.float32)  # (D, K)
        weights = weights.astype(jnp.float32)      # (K,)

        q = _gmm_posteriors(x, means.T, variances.T, weights, weight_threshold)  # (N, n, K)
        if valid is None:
            count = x.shape[1]
        else:
            m = jnp.asarray(valid, jnp.float32)                       # (N, n)
            q = q * m[..., None]
            count = jnp.maximum(jnp.sum(m, axis=1), 1.0)[:, None, None]

        s0b = jnp.sum(q, axis=1, keepdims=True) / count     # (N, 1, K)
        # float32 moments on the chip too (its default rounds the inputs
        # to bfloat16, and fv2 below is a difference of near-equal terms)
        at = linalg.precision()
        s1 = jnp.einsum("bnd,bnk->bdk", x, q, precision=at) / count      # (N, D, K)
        s2 = jnp.einsum("bnd,bnk->bdk", x * x, q, precision=at) / count  # (N, D, K)

        fv1 = (s1 - means * s0b) / (jnp.sqrt(variances) * jnp.sqrt(weights))
        fv2 = (s2 - 2.0 * means * s1 + (means * means - variances) * s0b) / (
            variances * jnp.sqrt(2.0 * weights)
        )
        return jnp.concatenate([fv1, fv2], axis=2)          # (N, D, 2K)


class FisherVector(BatchTransformer):
    """Encode (N, n_desc, D) descriptor batches into (N, D, 2K) Fisher
    vectors (reference: FisherVector.scala:33-53)."""

    def __init__(self, gmm: GaussianMixtureModel):
        self.gmm = gmm

    def apply_arrays(self, x):
        return _fisher_encode(
            x, self.gmm.means, self.gmm.variances, self.gmm.weights,
            jnp.float32(self.gmm.weight_threshold),
        )

    def chunk_applier(self):
        """Every batch form of ``apply_batch`` below encodes an image from
        its own descriptors alone."""
        return self.apply_batch

    def host_span(self, dataset):
        return _spans.span(
            "image:fisher", rows=dataset.num_examples, centres=int(self.gmm.k)
        )

    def apply_arrays_masked(self, x, valid):
        """Fisher-encode ragged descriptor batches: ``x`` (N, n_pad, D)
        with per-image validity ``valid`` (N, n_pad) from the bucketed
        extractors. Invalid rows contribute nothing and the statistics
        normalize by each image's true descriptor count — equal to
        ``apply_arrays`` on the image's own valid descriptors (the
        reference encodes per-image descriptor sets of varying size,
        FisherVector.scala:33-53)."""
        return _fisher_encode(
            x, self.gmm.means, self.gmm.variances, self.gmm.weights,
            jnp.float32(self.gmm.weight_threshold), valid,
        )

    def apply_batch(self, dataset):
        """Masked-descriptor datasets ({"desc", "valid"}) encode through
        ``apply_arrays_masked`` and come out dense — the boundary where
        the native-resolution raggedness collapses to fixed-width rows."""
        from ...data.dataset import ArrayDataset, BucketedDataset

        if isinstance(dataset, BucketedDataset):
            return dataset.map_datasets(self.apply_batch)
        if (
            isinstance(dataset, ArrayDataset)
            and isinstance(dataset.data, dict)
            and "valid" in dataset.data
        ):
            out = self.apply_arrays_masked(
                dataset.data["desc"], dataset.data["valid"]
            )
            return ArrayDataset(out, dataset.num_examples)
        return super().apply_batch(dataset)


class GMMFisherVectorEstimator(Estimator, Optimizable):
    """Fit a diagonal GMM on all descriptors, return a FisherVector encoder
    (reference: FisherVector.scala:67-97 ScalaGMMFisherVectorEstimator +
    optimizable GMMFisherVectorEstimator).

    The reference's optimize() swaps in the native enceval encoder when
    k ≥ 32; both paths here lower to the same XLA computation, so
    optimize() only tunes the EM fit's sample handling.
    """

    def __init__(self, k: int, seed: int = 0):
        self.k = k
        self.seed = seed

    def fit(self, data: Dataset) -> FisherVector:
        arrays = data if isinstance(data, ArrayDataset) else data.to_arrays()
        x = jnp.asarray(arrays.data, dtype=jnp.float32)
        if x.ndim == 3:  # (N, n_desc, D) → all descriptors pooled
            x = x.reshape(-1, x.shape[-1])
        gmm = GaussianMixtureModelEstimator(self.k, seed=self.seed).fit(ArrayDataset(x))
        return FisherVector(gmm)

    def optimize(self, samples, stats: DataStats):
        return self  # single TPU implementation; see class docstring
