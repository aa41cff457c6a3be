"""Kernel methods: blockwise Gaussian kernel, Gauss-Seidel kernel ridge
regression, and streaming kernel-block application.

TPU-native re-design of the reference's kernel suite
(reference: nodes/learning/KernelGenerator.scala:36-206,
nodes/learning/KernelMatrix.scala:17-90,
nodes/learning/KernelRidgeRegression.scala:37-275,
nodes/learning/KernelBlockLinearMapper.scala:28-90).

This is the framework's long-context machinery: the n×n kernel matrix is
the quadratic-in-samples object (the attention-matrix analog) and is never
materialized. The re-design maps the reference's Spark dataflow onto the
mesh:

- **Training (Gauss-Seidel BCD on the dual, arXiv:1602.05310).** Train
  rows are sharded over the ``data`` axis, padded so that every column
  block lies inside one shard. Per column block: the owning shard slices
  the block's rows out and a psum hands them to the others (the broadcast
  analog; on one shard it is the slice alone), each shard computes its
  K(x_local, X_b) panel on the MXU, K_bᵀW partial products psum over ICI,
  and the b×b regularized solve runs replicated. The whole epochs×blocks
  loop is ONE compiled XLA program — the reference needed a Spark job per
  block plus RDD lineage checkpoints every 25 blocks (truncateLineage);
  with no lineage, that subsystem disappears by construction.
- **Application** (``KernelBlockLinearMapper``): ring rotation. Test rows
  stay put; (train shard, dual-weight shard) pairs rotate around the ICI
  ring via ppermute, and each step scans the visiting shard one train
  block at a time, adding K(test_local, x_block)·W_block — structurally
  ring attention, and like the reference's mapper
  (KernelBlockLinearMapper.scala:39-80) never more than test rows × one
  block of the kernel at once.

Behavioral parity: λ is applied as K_bb + λI (not λnI); per-epoch block
permutation via ``block_permuter`` seed; the last short block is handled
by zero-padding (padded rows solve to exactly zero duals).

What a fit or an apply did is in the trace: host spans ``kernel:fit`` >
``kernel:prepare``, ``kernel:solve`` and ``kernel:apply``; device scopes
``krr/gather``, ``krr/panel``, ``krr/residual``, ``krr/cholesky``,
``krr/update`` and ``kernel/panel``, ``kernel/apply``; the counters
``keystone_kernel_panels_total`` and ``keystone_kernel_panel_bytes``
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ...data.dataset import ArrayDataset, Dataset
from ...obs import names as _names
from ...obs import spans
from ...obs.device import to_device
from ...parallel import linalg
from ...parallel.collectives import shard_map
from ...parallel.mesh import DATA_AXIS, REPLICA_AXIS, get_mesh, row_axes, row_shard_count
from ...parallel.partitioner import fit_mesh
from ...workflow.pipeline import BatchTransformer, Estimator, LabelEstimator, Transformer
from ..stats.core import _as_array_dataset


# ------------------------------------------------------------------- kernels


def gaussian_kernel_block(xa, xb, gamma):
    """exp(−γ‖a−b‖²) panel via one MXU matmul + fused exp epilogue.

    Pure XLA by measurement: a hand-tiled Pallas version ran 1.6× slower
    on v5e (see ops/pallas/__init__.py for the numbers) — the emitter
    already keeps the squared-distance intermediate out of HBM."""
    an = jnp.sum(xa * xa, axis=1, keepdims=True)
    bn = jnp.sum(xb * xb, axis=1)
    sq = an - 2.0 * linalg.mm(xa, xb.T) + bn
    return jnp.exp(-gamma * jnp.maximum(sq, 0.0))


class KernelTransformer:
    """Materializes kernel blocks against fixed training data
    (reference: KernelGenerator.scala KernelTransformer + KernelMatrix)."""

    def __init__(self, train: jnp.ndarray, gamma: float, num_train: int):
        self.train = train  # (n_pad, d) row-sharded
        self.gamma = gamma
        self.num_train = num_train

    def column_block(self, start: int, size: int) -> jnp.ndarray:
        """K(X, X[start:start+size]) — (n_pad, size)."""
        xb = lax.dynamic_slice(
            self.train, (start, 0), (size, self.train.shape[1])
        )
        return gaussian_kernel_block(self.train, xb, self.gamma)

    def diag_block(self, start: int, size: int) -> jnp.ndarray:
        xb = lax.dynamic_slice(
            self.train, (start, 0), (size, self.train.shape[1])
        )
        return gaussian_kernel_block(xb, xb, self.gamma)


class BlockKernelMatrix:
    """Cache-managing view over kernel column blocks
    (reference: KernelMatrix.scala:50-90 BlockKernelMatrix). On TPU the
    cache is HBM residency of computed panels."""

    def __init__(self, transformer: KernelTransformer, cache_blocks: bool = True):
        self.transformer = transformer
        self.cache_blocks = cache_blocks
        self._cache = {}

    def __call__(self, start: int, size: int) -> jnp.ndarray:
        key = (start, size)
        if self.cache_blocks and key in self._cache:
            return self._cache[key]
        block = self.transformer.column_block(start, size)
        if self.cache_blocks:
            self._cache[key] = block
        return block

    def diag_block(self, start: int, size: int) -> jnp.ndarray:
        return self.transformer.diag_block(start, size)

    def unpersist(self) -> None:
        self._cache.clear()


class GaussianKernelGenerator(Estimator):
    """reference: KernelGenerator.scala GaussianKernelGenerator."""

    def __init__(self, gamma: float):
        self.gamma = gamma

    def fit(self, data: Dataset) -> KernelTransformer:
        ds = _as_array_dataset(data)
        mesh = fit_mesh(self)
        x = linalg.prepare_row_sharded(jnp.asarray(ds.data, jnp.float32), mesh)
        return KernelTransformer(x, self.gamma, ds.num_examples)


# ---------------------------------------------------------------------- KRR


class KernelRidgeRegression(LabelEstimator):
    """Gauss-Seidel block coordinate descent on the kernel dual."""

    def __init__(
        self,
        kernel_generator: GaussianKernelGenerator,
        reg: float,
        block_size: int,
        num_epochs: int,
        block_permuter: Optional[int] = None,
    ):
        self.kernel_generator = kernel_generator
        self.reg = reg
        self.block_size = block_size
        self.num_epochs = num_epochs
        self.block_permuter = block_permuter

    def out_spec(self, in_specs):
        """Plan-time spec protocol (workflow/verify.py): the dual
        model scores through the kernel against the training set,
        (m, d) -> (m, k) with d pinned to the training width."""
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> "KernelBlockLinearMapper":
        from ...reliability import DegradationLadder, halving_rungs

        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        n = features.num_examples

        from ...envknobs import env_int

        landmarks = env_int("KEYSTONE_KERNEL_NYSTROM", 0)
        if 0 < landmarks < n:
            return self._fit_nystrom(features, targets, landmarks)

        # OOM degradation: the live kernel panel is (n_pad, bs) — halving
        # the block halves it (and the replicated bs×bs solve) while the
        # Gauss-Seidel sweep still visits every training row.
        bs0 = min(self.block_size, n)
        ladder = DegradationLadder(
            halving_rungs(bs0, max(bs0 // 4, 1)),
            label="KernelRidgeRegression.fit",
        )
        from ...obs import solver as solver_obs

        attempts = iter(range(len(ladder.rungs)))

        def attempt(bs):
            with solver_obs.rung_span("kernel_ridge", bs, next(attempts)):
                return self._fit_with_block(features, targets, bs)

        with solver_obs.fit_span(
            "kernel_ridge", n=n, epochs=self.num_epochs
        ):
            model = ladder.run(attempt)
        if ladder.reduced:
            model.degradation = dict(ladder.record)
        return model

    def _fit_nystrom(self, features, targets, landmarks) -> "KernelBlockLinearMapper":
        """Randomized Nyström rung (``KEYSTONE_KERNEL_NYSTROM=m``, 0=off):
        m uniform landmark rows stand in for the full training set, the
        duals solve against the m×m landmark kernel, and scoring reuses
        the ring mapper with the landmarks AS the training set — exactly
        K(x, landmarks)·α. Trades the n-dual Gauss-Seidel sweep for an
        O(n·m + m³) solve; docs/SOLVERS.md has the bound."""
        from ...envknobs import env_int
        from ...obs import names as _names
        from ...obs import solver as solver_obs
        from ...sketch.solvers import nystrom_krr

        n = features.num_examples
        gamma = self.kernel_generator.gamma
        x = np.asarray(features.data, np.float32)
        y = np.asarray(targets.data, np.float32)
        with solver_obs.fit_span("kernel_nystrom", n=n, landmarks=landmarks):
            idx, duals = nystrom_krr(
                x, y, gamma, self.reg, landmarks,
                seed=env_int("KEYSTONE_SKETCH_SEED", 0),
            )
        try:
            _names.metric(_names.SKETCH_FITS).inc(variant="nystrom")
        except Exception:
            pass
        return KernelBlockLinearMapper(
            jnp.asarray(x[np.asarray(idx)]), jnp.asarray(duals), gamma,
            num_train=landmarks,
            block_size=min(self.block_size, landmarks),
        )

    def _fit_with_block(self, features, targets, bs) -> "KernelBlockLinearMapper":
        from ...reliability import probe

        probe("KernelRidgeRegression.solve")
        mesh = fit_mesh(self)
        n = features.num_examples
        gamma = self.kernel_generator.gamma
        shards = row_shard_count(mesh)
        # Rows padded so that every shard holds whole blocks: a block then
        # lies inside one shard, which slices it out (`_krr_fit`).
        n_pad = _round_up_multiple(n, bs * shards)
        num_blocks = -(-n // bs)  # blocks of padding alone are never visited
        panel_bytes = 4 * (n_pad // shards) * bs
        site = type(self).__name__

        with spans.span(
            "kernel:fit", n=n, block=bs, blocks=num_blocks,
            epochs=self.num_epochs, panel_bytes=panel_bytes, shards=shards,
        ):
            with spans.span("kernel:prepare"):
                x = to_device(features.data, site=site)
                y = to_device(targets.data, site=site)
                x = _pad_rows_to(jnp.asarray(x, jnp.float32), n_pad)
                y = _pad_rows_to(jnp.asarray(y, jnp.float32), n_pad)
                x = linalg.prepare_row_sharded(x, mesh)
                y = linalg.prepare_row_sharded(y, mesh)
                rng = np.random.default_rng(self.block_permuter)
                starts = []
                for _ in range(self.num_epochs):
                    order = np.arange(num_blocks)
                    if self.block_permuter is not None:
                        rng.shuffle(order)
                    starts.extend((order * bs).tolist())
                starts = jnp.asarray(np.asarray(starts, np.int32))
            with spans.span("kernel:solve"):
                # The panel is the caller's array, a donated workspace
                # carried through the program's scan: what a fit holds on
                # the device is then allocated, and refused where it does
                # not fit, before the program starts, and the device's
                # memory statistics count it (a program's own temporaries
                # they do not: PERF.md section 6, PR 34).
                workspace = linalg.prepare_row_sharded(jnp.zeros((n_pad, bs), jnp.float32), mesh)
                w, workspace = _krr_fit(mesh, bs)(
                    x, y, starts, jnp.float32(gamma), jnp.float32(self.reg), jnp.int32(n),
                    workspace,
                )
                del workspace  # the last block's panel: nobody needs it
                _note_panels(site, int(starts.shape[0]), panel_bytes)
            return KernelBlockLinearMapper(x, w, gamma, num_train=n, block_size=bs)


def _note_panels(site: str, panels: int, panel_bytes: int) -> None:
    """Count the column panels one fit or one request computes, and the
    size of the panel that is live while it does."""
    _names.metric(_names.KERNEL_PANELS).inc(panels, site=site)
    _names.metric(_names.KERNEL_PANEL_BYTES).set(panel_bytes, site=site)


@linalg.mode_cached()
def _krr_fit(mesh: Mesh, bs: int):
    axes = row_axes(mesh)

    def per_device(x_local, y_local, starts, gamma, lam, n, panel):
        n_local, d = x_local.shape  # whole blocks: a multiple of bs
        k = y_local.shape[1]
        first = _linear_shard_index(mesh, axes) * n_local  # this shard's first global row
        row_valid = (first + jnp.arange(n_local)) < n

        def block_rows(mat, s):
            """Rows [s, s+bs) of the global matrix, on every shard: the
            shard that holds the block slices it out and the psum hands
            it round (with one shard, the slice alone)."""
            local = lax.dynamic_slice(
                mat, (jnp.clip(s - first, 0, n_local - bs), 0), (bs, mat.shape[1])
            )
            mine = (s >= first) & (s < first + n_local)
            return lax.psum(jnp.where(mine, local, jnp.zeros((), mat.dtype)), axes)

        def step(carry, s):
            w, _ = carry  # the panel of the step before is overwritten
            with jax.named_scope("krr/gather"):
                xb = block_rows(x_local, s)                   # (bs, d) replicated
                y_b = block_rows(y_local, s)
                w_b_old = lax.dynamic_slice(w, (s, 0), (bs, k))
            col_valid = (s + jnp.arange(bs)) < n
            with jax.named_scope("krr/panel"):
                k_panel = gaussian_kernel_block(x_local, xb, gamma)
                k_panel = jnp.where(row_valid[:, None] & col_valid[None, :], k_panel, 0.0)
            with jax.named_scope("krr/residual"):
                w_rows = lax.dynamic_slice(w, (first, 0), (n_local, k))
                resid = lax.psum(linalg.mm(k_panel.T, w_rows), axes)  # (bs, k)
            with jax.named_scope("krr/cholesky"):
                kbb = gaussian_kernel_block(xb, xb, gamma)
                kbb = jnp.where(col_valid[:, None] & col_valid[None, :], kbb, 0.0)
                # a padded row's system is 1 * w = 0, whatever lam is
                system = kbb + jnp.diag(jnp.where(col_valid, lam, 1.0))
                factor = jax.scipy.linalg.cho_factor(system, lower=True)
            with jax.named_scope("krr/update"):
                rhs = y_b - (resid - linalg.mm(kbb.T, w_b_old))
                w_b_new = jax.scipy.linalg.cho_solve(factor, rhs)
                w = lax.dynamic_update_slice(w, w_b_new, (s, 0))
            return (w, k_panel), None

        w0 = jnp.zeros((n_local * row_shard_count(mesh), k), x_local.dtype)
        (w, panel), _ = lax.scan(step, (w0, panel), starts)
        return w, panel

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(), P(), P(), P(), P(axes, None)),
        out_specs=(P(), P(axes, None)),
    )
    # The seventh argument is the panel workspace, which the one caller
    # (`_fit_with_block`) allocates for this call and never reads back:
    # the program's panel lives in it.  # keystone: owns-donated
    return jax.jit(fn, donate_argnums=(6,))


# ------------------------------------------------------------------- apply


class KernelBlockLinearMapper(BatchTransformer):
    """Apply the kernel model to test data via ring rotation
    (reference: KernelBlockLinearMapper.scala:28-90, re-designed as ring
    dataflow: the train/dual shards travel the ICI ring while test rows
    stay put — the same schedule as ring attention). Each visiting shard
    is scanned ``block_size`` train rows at a time, so the live kernel
    panel is test rows × one block, never test rows × the train shard."""

    # Manages its own sharded placement + ring dispatch: composing this
    # apply_arrays inside another operator's jit would re-trace the
    # device_put/shard_map choreography — keep it a standalone dispatch.
    fusable = False
    _mesh = None  # the mesh `train` and `duals` are placed on

    def __init__(self, train: jnp.ndarray, duals: jnp.ndarray, gamma: float,
                 num_train: int, block_size: int):
        self.gamma = gamma
        self.num_train = num_train
        self.block_size = block_size
        # (n_pad, d) and (n_pad, k), zero rows at padding: whole blocks
        # on every shard of the mesh they were placed on, placed once
        # (`_placed` places them again only if the mesh changes).
        self.train, self.duals = jnp.asarray(train), jnp.asarray(duals)
        self._placed(get_mesh())

    def _placed(self, mesh: Mesh):
        if self._mesh != mesh:
            n_pad = _round_up_multiple(
                self.train.shape[0], self.block_size * row_shard_count(mesh)
            )
            self.train = linalg.prepare_row_sharded(_pad_rows_to(self.train, n_pad), mesh)
            self.duals = linalg.prepare_row_sharded(_pad_rows_to(self.duals, n_pad), mesh)
            self._mesh = mesh
        return self.train, self.duals

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_mesh"] = None  # a mesh names this process's devices
        return state

    def apply_arrays(self, x):
        mesh = get_mesh()
        shards = row_shard_count(mesh)
        train, duals = self._placed(mesh)
        m = x.shape[0]
        m_pad = _round_up_multiple(m, shards)
        bs = self.block_size
        site = type(self).__name__
        with spans.span("kernel:apply", rows=m, train_rows=self.num_train, block=bs):
            x = to_device(x, site=site)  # a host request; nothing for device rows
            xt = linalg.prepare_row_sharded(_pad_rows_to(jnp.asarray(x, jnp.float32), m_pad), mesh)
            # gamma is traced, so one compiled executable serves every gamma.
            out = _ring_kernel_apply(mesh, bs)(xt, train, duals, jnp.float32(self.gamma))
            _note_panels(site, train.shape[0] // bs, 4 * (m_pad // shards) * bs)
        return out[:m]


@linalg.mode_cached()
def _ring_kernel_apply(mesh: Mesh, bs: int):
    axes = row_axes(mesh)
    nd = mesh.shape[DATA_AXIS]
    nr = mesh.shape.get(REPLICA_AXIS, 1)
    nshards = nd * nr

    def per_device(xt_local, xs, ws, gamma):
        data_perm = [(j, (j + 1) % nd) for j in range(nd)]
        replica_perm = [(j, (j + 1) % nr) for j in range(nr)]

        def hop_replica(val):
            return lax.ppermute(val, REPLICA_AXIS, replica_perm)

        def add_block(acc, block):
            x_block, w_block = block  # (bs, d), (bs, k) of the visiting shard
            with jax.named_scope("kernel/panel"):
                panel = gaussian_kernel_block(xt_local, x_block, gamma)
            with jax.named_scope("kernel/apply"):
                return acc + linalg.mm(panel, w_block), None

        def ring_step(i, carry):
            acc, xs, ws = carry
            blocks = (
                xs.reshape(-1, bs, xs.shape[1]), ws.reshape(-1, bs, ws.shape[1])
            )
            acc, _ = lax.scan(add_block, acc, blocks)
            # inner ICI ring every step; after each full data cycle the
            # shards hop once across the DCN replica ring, so nd*nr steps
            # visit every (replica, data) shard exactly once.
            xs = lax.ppermute(xs, DATA_AXIS, data_perm)
            ws = lax.ppermute(ws, DATA_AXIS, data_perm)
            if nr > 1:
                do_hop = (i + 1) % nd == 0
                xs = lax.cond(do_hop, hop_replica, lambda v: v, xs)
                ws = lax.cond(do_hop, hop_replica, lambda v: v, ws)
            return acc, xs, ws

        acc0 = jnp.zeros((xt_local.shape[0], ws.shape[1]), xt_local.dtype)
        acc, _, _ = lax.fori_loop(0, nshards, ring_step, (acc0, xs, ws))
        return acc

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None), P()),
        out_specs=P(axes, None),
    )
    return jax.jit(fn)  # gamma (4th arg) is replicated + traced


def _linear_shard_index(mesh: Mesh, axes):
    """Row-major linear index of this device's shard over ``axes``."""
    idx = jnp.int32(0)
    for axis in axes:
        idx = idx * mesh.shape[axis] + lax.axis_index(axis)
    return idx


# -------------------------------------------------------------------- utils


def _round_up_multiple(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _pad_rows_to(a: jnp.ndarray, target: int) -> jnp.ndarray:
    if a.shape[0] == target:
        return a
    return jnp.pad(a, [(0, target - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
