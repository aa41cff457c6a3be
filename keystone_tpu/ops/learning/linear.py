"""Exact linear solvers: LinearMapper / LinearMapEstimator / LocalLeastSquares.

TPU-native re-design of the reference's one-shot least-squares path
(reference: nodes/learning/LinearMapper.scala:18-161,
nodes/learning/LocalLeastSquaresEstimator.scala:16-61).

Semantics preserved: fitting centers features and labels (mean-only
StandardScaler), solves (AᵀA + λI) X = AᵀB on the centered data, and the
model applies ``(x − μ_A)ᵀ·X + μ_B``. The distributed Gram products ride
the sharded-linalg layer (per-shard MXU matmuls + one psum over ICI)
instead of mlmatrix's treeReduce of partition Grams.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset
from ...obs import spans as _spans
from ...parallel import linalg
from ...parallel.mesh import get_mesh
from ...parallel.partitioner import fit_mesh
from ...refit.state import GramStreamStateMixin
from ...workflow.pipeline import BatchTransformer, LabelEstimator
from ..stats.core import _as_array_dataset


class LinearMapper(BatchTransformer):
    """Apply a trained linear model: scores = (x − μ_A)·W + b."""

    def __init__(
        self,
        weights: jnp.ndarray,  # (d, k)
        intercept: Optional[jnp.ndarray] = None,  # (k,)
        feature_mean: Optional[jnp.ndarray] = None,  # (d,)
    ):
        self.weights = jnp.asarray(weights)
        self.intercept = None if intercept is None else jnp.asarray(intercept)
        self.feature_mean = None if feature_mean is None else jnp.asarray(feature_mean)

    def apply_arrays(self, x):
        if self.feature_mean is not None:
            x = x - self.feature_mean
        out = linalg.mm(x, self.weights)
        if self.intercept is not None:
            out = out + self.intercept
        return out


class LinearMapEstimator(GramStreamStateMixin, LabelEstimator):
    """Distributed OLS/ridge via normal equations.

    λ=None → plain least squares; otherwise ridge with strength λ
    (reference: LinearMapper.scala:75-103).
    """

    #: Chunked-fit protocol (workflow/streaming.py): exact normal
    #: equations accumulate naturally over row chunks.
    supports_fit_stream = True

    #: 2-D partitioner protocol: the Gram carry shards its feature rows
    #: (gram_stream_step.model_block_step) on a (data, model) mesh.
    supports_model_axis = True

    def __init__(self, reg: Optional[float] = None):
        self.reg = reg

    def out_spec(self, in_specs):
        """Plan-time spec protocol (workflow/verify.py): fitting (n, d)
        features against (n, k) labels yields a (m, d) → (m, k) map."""
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    def fit_stream(self, stream, state=None) -> LinearMapper:
        """Row-chunked exact fit: the same algebraic centering identity
        the fused in-core solve uses (Σ(a−μ)(a−μ)ᵀ = AᵀA − n·μμᵀ), fed
        by per-chunk Gram accumulation instead of one whole-matrix
        dispatch — O(d²) residency, feature matrix never materializes.

        ``state`` (a refit :class:`StreamState`) seeds the carry with
        previously captured statistics so this fold EXTENDS an earlier
        fit; the combined state is re-exported via
        ``export_stream_state`` (docs/REFIT.md)."""
        from ..learning.block import _stream_shapes

        def init(feat_aval, y_aval):
            d, k = _stream_shapes(feat_aval, y_aval)
            return self._seed_carry(state, d, k)

        carry, info = stream.fold(init, linalg.gram_stream_step)
        n = info["num_examples"] + (state.num_examples if state else 0)
        self._capture_state(carry, n, reg=self.reg)
        with _spans.span("stream:finish"):
            return self._finish_from_stats(carry, n)

    def _finish_from_stats(self, carry, n: int) -> LinearMapper:
        """Exact solve from accumulated statistics alone — shared by the
        streamed fit and the refit ``finish_from_state`` path."""
        gc, cc, mu_a, mu_b = linalg.gram_stream_finish(carry, n)
        w = linalg.solve_from_gram(gc, cc, reg=self.reg or 0.0)
        if not self.reg:  # singular-risk case only: fail loudly, not NaN
            linalg.check_finite(w, "LinearMapEstimator (reg=0, streaming)")
        return LinearMapper(w, intercept=mu_b, feature_mean=mu_a)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        mesh = fit_mesh(self)

        x = linalg.prepare_row_sharded(
            jnp.asarray(features.data, dtype=jnp.float32), mesh
        )
        y = linalg.prepare_row_sharded(
            jnp.asarray(targets.data, dtype=jnp.float32), mesh
        )
        n = features.num_examples

        # ONE dispatch: sharded Gram + column sums + algebraic centering
        # (Σ(a−μ)(a−μ)ᵀ = AᵀA − n·μμᵀ) + replicated Cholesky — no centered
        # copy of the data is ever materialized (matters when A fills most
        # of HBM) and no second host→device round trip for the solve.
        # KEYSTONE_SOLVER_PRECISION=refine swaps the 6-pass Gram for the
        # fast 1-pass Gram + 2 high-precision residual-correction steps
        # (cost 2·n·d·k vs n·d² — cheap when k ≪ d).
        mode = linalg.solver_mode()
        if mode == "refine":
            gram_precision, refine_steps = jax.lax.Precision.DEFAULT, 2
        else:
            # The mode's own precision, read per call — bench legs flip
            # the env var after import and must get the Gram speed they
            # asked for.
            gram_precision, refine_steps = linalg.precision_for_mode(mode), 0
        # Donate the row-sharded copies into the fused normal-equation
        # solve (frees the dominant (n, d) buffer for Gram/residual
        # temporaries) — but ONLY when prepare_row_sharded actually
        # copied: if the dataset's own device arrays came back unchanged,
        # donating would invalidate data the pipeline may re-read.
        donate = x is not features.data and y is not targets.data
        w, mu_a, mu_b = linalg.centered_solve_refined(
            x, y, n, self.reg or 0.0, mesh=mesh,
            gram_precision=gram_precision, refine_steps=refine_steps,
            donate_xy=donate,
        )
        if not self.reg:  # singular-risk case only: fail loudly, not NaN
            linalg.check_finite(w, "LinearMapEstimator (reg=0)")
        return LinearMapper(w, intercept=mu_b, feature_mean=mu_a)


class LocalLeastSquaresEstimator(LabelEstimator):
    """Single-device dense lstsq for small problems
    (reference: nodes/learning/LocalLeastSquaresEstimator.scala:16-61)."""

    def __init__(self, reg: float = 0.0):
        self.reg = reg

    def out_spec(self, in_specs):
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        x = np.asarray(jax.device_get(features.data))[: features.num_examples]
        y = np.asarray(jax.device_get(targets.data))[: targets.num_examples]
        mu_a, mu_b = x.mean(axis=0), y.mean(axis=0)
        xc, yc = x - mu_a, y - mu_b
        d = x.shape[1]
        if self.reg > 0:
            w = np.linalg.solve(xc.T @ xc + self.reg * np.eye(d), xc.T @ yc)
        else:
            w, *_ = np.linalg.lstsq(xc, yc, rcond=None)
        return LinearMapper(jnp.asarray(w), intercept=jnp.asarray(mu_b), feature_mean=jnp.asarray(mu_a))


class SparseLinearMapper(BatchTransformer):
    """Apply a dense model to host-sparse rows
    (reference: nodes/learning/SparseLinearMapper.scala:13-50)."""

    def __init__(self, weights, intercept=None):
        self.weights = jnp.asarray(weights)
        self.intercept = None if intercept is None else jnp.asarray(intercept)

    def apply_arrays(self, x):
        out = linalg.mm(x, self.weights)
        if self.intercept is not None:
            out = out + self.intercept
        return out

    def apply(self, datum):
        if hasattr(datum, "toarray"):
            datum = np.asarray(datum.toarray()).ravel()
        return super().apply(datum)

    def apply_batch(self, dataset: Dataset):
        from ..util.vectors import Densify

        if not isinstance(dataset, ArrayDataset):
            dataset = Densify().apply_batch(dataset)
        return super().apply_batch(dataset)
