"""Diagonal-covariance Gaussian mixture model fit by EM.

TPU-native re-design of
reference: nodes/learning/GaussianMixtureModelEstimator.scala:25-203 and
nodes/learning/GaussianMixtureModel.scala:19-106.

Behavioral parity with the reference's (Xerox/enceval-style) EM:
- init from one round of k-means++ (or uniform-random within column range);
- global variance lower bound max(smallVarianceThreshold·var_global,
  absoluteVarianceThreshold), re-applied each M-step;
- aggressive posterior thresholding (weights < weightThreshold → 0,
  renormalized) in both training E-steps and model application;
- stop when mean log-likelihood stops improving by tolerance, or when any
  cluster would fall under min_cluster_size (fit keeps the last good
  parameters, like the reference's largeEnoughClusters guard).

The whole EM loop is one compiled ``lax.while_loop``; E-step distances are
two MXU matmuls (X·(μ/σ²)ᵀ and X²·(1/2σ²)ᵀ) and the posterior uses a
standard logsumexp instead of the reference's incremental host loop.

The model stores means/variances as (d, k) — column per cluster — matching
the reference's layout (GaussianMixtureModel.scala:19-24), which the
Fisher-vector encoder relies on.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset
from ...obs import spans as _spans
from ...parallel import linalg
from ...workflow.pipeline import BatchTransformer, Estimator
from ..stats.core import _as_array_dataset
from .kmeans import KMeansPlusPlusEstimator, _half_sq_dists

KMEANS_PLUS_PLUS_INITIALIZATION = "kmeans++"
RANDOM_INITIALIZATION = "random"


class GaussianMixtureModel(BatchTransformer):
    """x ↦ thresholded posterior cluster assignments (n, k)."""

    def __init__(self, means, variances, weights, weight_threshold: float = 1e-4):
        self.means = jnp.asarray(means)          # (d, k)
        self.variances = jnp.asarray(variances)  # (d, k)
        self.weights = jnp.asarray(weights).ravel()  # (k,)
        self.weight_threshold = weight_threshold
        assert self.means.shape == self.variances.shape
        assert self.weights.shape[0] == self.means.shape[1]
        #: What a fit was started from and how far it went, for whoever
        #: wants to follow it (a reference implementation: the k-means++
        #: start is drawn from a seed, the rest is arithmetic): the
        #: initial (means, variances, weights), each (k, ...), and the
        #: number of EM updates applied. None on a loaded model.
        self.fit_record = None

    @property
    def k(self) -> int:
        return self.means.shape[1]

    @property
    def dim(self) -> int:
        return self.means.shape[0]

    def apply_arrays(self, x):
        return _gmm_posteriors(
            x, self.means.T, self.variances.T, self.weights,
            jnp.float32(self.weight_threshold),
        )

    @staticmethod
    def load(mean_file: str, vars_file: str, weights_file: str) -> "GaussianMixtureModel":
        """CSV warm-start (reference: GaussianMixtureModel.scala:97-105)."""
        means = np.loadtxt(mean_file, delimiter=",", ndmin=2)
        variances = np.loadtxt(vars_file, delimiter=",", ndmin=2)
        weights = np.loadtxt(weights_file, delimiter=",").ravel()
        return GaussianMixtureModel(means, variances, weights)


@linalg.mode_jit
def _gmm_log_likelihood(x, means, variances, weights):
    """Per-sample per-cluster log-likelihood over the LAST axis of x:
    (..., d) -> (..., k), the leading axes carried as they are (a batch
    of images' descriptors is never flattened: no reshape for the
    compiler to lay out again). means/vars here are (k, d)."""
    d = x.shape[-1]
    xsq = x * x
    inv_var = 1.0 / variances
    sq_mahal = (
        linalg.mm(xsq, (0.5 * inv_var).T)
        - linalg.mm(x, (means * inv_var).T)
        + 0.5 * jnp.sum(means * means * inv_var, axis=1)
    )
    log_norm = (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
    )
    return log_norm - sq_mahal


@linalg.mode_jit
def _gmm_posteriors(x, means, variances, weights, weight_threshold):
    """Thresholded posteriors over the centres, (..., d) -> (..., k)."""
    llh = _gmm_log_likelihood(x, means, variances, weights)
    llh = llh - jnp.max(llh, axis=-1, keepdims=True)
    q = jnp.exp(llh)
    q = q / jnp.sum(q, axis=-1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    return q / jnp.maximum(jnp.sum(q, axis=-1, keepdims=True), 1e-30)


class GaussianMixtureModelEstimator(Estimator):
    def __init__(
        self,
        k: int,
        max_iterations: int = 100,
        min_cluster_size: int = 40,
        stop_tolerance: float = 1e-4,
        weight_threshold: float = 1e-4,
        small_variance_threshold: float = 1e-2,
        absolute_variance_threshold: float = 1e-9,
        initialization_method: str = KMEANS_PLUS_PLUS_INITIALIZATION,
        seed: int = 0,
    ):
        assert min_cluster_size > 0 and max_iterations > 0
        self.k = k
        self.max_iterations = max_iterations
        self.min_cluster_size = min_cluster_size
        self.stop_tolerance = stop_tolerance
        self.weight_threshold = weight_threshold
        self.small_variance_threshold = small_variance_threshold
        self.absolute_variance_threshold = absolute_variance_threshold
        self.initialization_method = initialization_method
        self.seed = seed

    def out_spec(self, in_specs):
        """Plan-time spec protocol (workflow/verify.py): thresholded
        posterior cluster assignments, (m, d) -> (m, k)."""
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label, out_width=self.k)

    def fit(self, data: Dataset) -> GaussianMixtureModel:
        ds = _as_array_dataset(data)
        with _spans.span("gmm:fit", samples=ds.num_examples, centres=self.k):
            return self._fit(ds)

    def _fit(self, ds: ArrayDataset) -> GaussianMixtureModel:
        x = np.asarray(jax.device_get(ds.data), dtype=np.float32)[: ds.num_examples]
        n, d = x.shape

        if self.initialization_method == KMEANS_PLUS_PLUS_INITIALIZATION:
            km = KMeansPlusPlusEstimator(self.k, 1, seed=self.seed).fit(ArrayDataset(x))
            assign = np.asarray(km.apply_arrays(jnp.asarray(x)))
            mass = assign.sum(axis=0)
            safe = np.maximum(mass, 1.0)
            means0 = (assign.T @ x) / safe[:, None]
            vars0 = (assign.T @ (x * x)) / safe[:, None] - means0**2
            weights0 = mass / n
        else:
            rng = np.random.default_rng(self.seed)
            lo, hi = x.min(axis=0), x.max(axis=0)
            span = hi - lo
            means0 = rng.uniform(size=(self.k, d)).astype(np.float32) * span + lo
            vars0 = np.tile(0.1 * span * span, (self.k, 1)).astype(np.float32)
            weights0 = np.full(self.k, 1.0 / self.k, dtype=np.float32)

        var_global = x.var(axis=0)
        var_lb = np.maximum(
            self.small_variance_threshold * var_global, self.absolute_variance_threshold
        ).astype(np.float32)
        vars0 = np.maximum(vars0, var_lb)

        start = (
            np.asarray(means0, np.float32), np.asarray(vars0, np.float32),
            np.asarray(weights0, np.float32),
        )
        with _spans.span("gmm:em", samples=n, centres=self.k) as sp:
            means, variances, weights, iterations, updates = _gmm_em(
                jnp.asarray(x),
                *(jnp.asarray(a) for a in start),
                jnp.asarray(var_lb),
                self.max_iterations,
                jnp.float32(self.stop_tolerance),
                jnp.float32(self.weight_threshold),
                jnp.float32(self.min_cluster_size),
            )
            iterations, updates = int(iterations), int(updates)  # the loop has run
            sp.set_attribute("iterations", iterations)
        model = GaussianMixtureModel(
            means.T, variances.T, weights, self.weight_threshold
        )
        model.fit_record = {"start": start, "iterations": iterations, "updates": updates}
        return model


@functools.partial(linalg.mode_jit, static_argnums=(5,))
def _gmm_em(x, means0, vars0, weights0, var_lb, max_iterations, tol,
            weight_threshold, min_cluster_size):
    n = x.shape[0]
    xsq = x * x

    def cond(state):
        _, _, _, i, _, _, keep_going = state
        return (i < max_iterations) & keep_going

    def body(state):
        means, variances, weights, i, updates, prev_cost, _ = state
        llh = _gmm_log_likelihood(x, means, variances, weights)
        cost = jnp.mean(jax.scipy.special.logsumexp(llh, axis=1))
        improving = jnp.where(i > 0, (cost - prev_cost) >= tol * jnp.abs(prev_cost), True)

        q = llh - jnp.max(llh, axis=1, keepdims=True)
        q = jnp.exp(q)
        q = q / jnp.sum(q, axis=1, keepdims=True)
        q = jnp.where(q > weight_threshold, q, 0.0)
        q = q / jnp.maximum(jnp.sum(q, axis=1, keepdims=True), 1e-30)

        q_sum = jnp.sum(q, axis=0)
        large_enough = jnp.all(q_sum >= min_cluster_size)

        do_update = improving & large_enough
        safe = jnp.maximum(q_sum, 1e-12)[:, None]
        new_means = linalg.mm(q.T, x) / safe
        new_vars = jnp.maximum(linalg.mm(q.T, xsq) / safe - new_means**2, var_lb)
        new_weights = q_sum / n

        means = jnp.where(do_update, new_means, means)
        variances = jnp.where(do_update, new_vars, variances)
        weights = jnp.where(do_update, new_weights, weights)
        return (
            means, variances, weights, i + 1,
            updates + do_update.astype(jnp.int32), cost, do_update,
        )

    means, variances, weights, iterations, updates, *_ = jax.lax.while_loop(
        cond, body,
        (
            means0, vars0, weights0, jnp.int32(0), jnp.int32(0),
            jnp.float32(-jnp.inf), jnp.bool_(True),
        ),
    )
    return means, variances, weights, iterations, updates
