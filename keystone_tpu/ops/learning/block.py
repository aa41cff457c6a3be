"""Block least-squares solvers (feature-block coordinate descent).

TPU-native re-design of the reference's block solver
(reference: nodes/learning/BlockLinearMapper.scala:22-283): features are
split into blocks (``VectorSplitter``), per-block mean-centering is
applied, and block coordinate descent minimizes ‖AW − Y‖² + λ‖W‖².

The reference materializes each block as its own RDD and treeReduces
per-block Grams to the driver; here the whole epoch×block loop is one
compiled XLA computation over the row-sharded feature matrix
(``parallel.linalg.block_coordinate_descent``) — block slicing is a
``dynamic_slice`` on the device-resident array, and per-block Gram sums
are one psum over ICI each.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...data.dataset import ArrayDataset, Dataset, ObjectDataset
from ...envknobs import env_disabled
from ...obs import names as _names
from ...obs import solver as solver_obs
from ...obs import spans as _spans
from ...parallel import linalg
from ...parallel.mesh import get_mesh
from ...parallel.partitioner import fit_mesh
from ...refit.state import GramStreamStateMixin
from ...reliability import DegradationLadder, halving_rungs, probe
from ...utils.sparse import (
    BlockSparseMatrix,
    block_density_exceeds,
    is_sparse_rows,
)
from ...workflow.pipeline import BatchTransformer, LabelEstimator
from ..stats.core import _as_array_dataset


class BlockLinearMapper(BatchTransformer):
    """Apply a block-solved linear model: (x − μ_A)·W + b.

    Equivalent to applying each feature-block's weights and summing the
    partial predictions (reference: BlockLinearMapper.scala:50-73); on TPU
    one fused matmul over the concatenated blocks is strictly better.
    """

    def __init__(
        self,
        weights: jnp.ndarray,  # (d_padded, k)
        block_size: int,
        intercept: Optional[jnp.ndarray] = None,
        feature_mean: Optional[jnp.ndarray] = None,  # (d,)
    ):
        self.weights = jnp.asarray(weights)
        self.block_size = block_size
        self.intercept = None if intercept is None else jnp.asarray(intercept)
        self.feature_mean = None if feature_mean is None else jnp.asarray(feature_mean)

    def apply_arrays(self, x):
        with jax.named_scope("mapper/apply"):
            d = x.shape[-1]
            if self.feature_mean is not None:
                x = x - self.feature_mean
            w = self.weights[:d]  # drop padded feature rows
            out = linalg.mm(x, w)
            if self.intercept is not None:
                out = out + self.intercept
            return out

    def apply_and_evaluate(self, x, evaluator):
        """Streaming per-block apply: after adding feature block i's
        contribution, call ``evaluator`` with the cumulative predictions
        (+ intercept, added per call, never into the running sum) —
        reference: BlockLinearMapper.scala:89-135 applyAndEvaluate.

        Only the running (n, k) sum and one block's partial product are
        live at a time, so predictions for all blocks are never
        materialized together — the point of the reference API, kept here
        for HBM rather than executor memory. Returns the list of
        evaluator results, one per block."""
        x = jnp.asarray(x)
        d = x.shape[-1]
        if self.feature_mean is not None:
            x = x - self.feature_mean
        w = self.weights[:d]
        results = []
        acc = None
        for start in range(0, d, self.block_size):
            xb = x[:, start : start + self.block_size]
            wb = w[start : start + self.block_size]
            part = linalg.mm(xb, wb)
            acc = part if acc is None else acc + part
            cur = acc + self.intercept if self.intercept is not None else acc
            results.append(evaluator(cur))
        return results


class BlockLeastSquaresEstimator(GramStreamStateMixin, LabelEstimator):
    """Feature-block coordinate-descent least squares
    (reference: BlockLinearMapper.scala:199-283 BlockLeastSquaresEstimator).

    ``num_iter`` full epochs over the feature blocks; λ is applied per
    block. The node is weighted for the auto-cache planner the same way the
    reference weights it: 3·num_iter + 1 passes over the data. The in-core
    program (``linalg.block_coordinate_descent``) reads each feature block
    that often too: once in the factor pass (Gram), then three times an
    epoch (residual, cross term, update); the planner's ``weight`` counts
    re-reads of the node's INPUT, which the in-core fit uploads once.
    """

    #: Chunked-fit protocol (workflow/streaming.py): this estimator can
    #: consume featurized row chunks incrementally via Gram accumulation.
    supports_fit_stream = True

    #: 2-D partitioner protocol: the Gram carry shards its feature rows
    #: (gram_stream_step.model_block_step) on a (data, model) mesh.
    supports_model_axis = True

    def __init__(
        self,
        block_size: int,
        num_iter: int = 1,
        reg: float = 0.0,
        host_streaming: Optional[bool] = None,
    ):
        self.block_size = block_size
        self.num_iter = num_iter
        self.reg = reg
        # None = auto: stream feature blocks from host RAM when the feature
        # matrix is a host array too large to sit in HBM next to its
        # centered copy and Gram workspace.
        self.host_streaming = host_streaming

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def out_spec(self, in_specs):
        from ...workflow.verify import dense_fit_spec

        return dense_fit_spec(in_specs, self.label)

    def fit_stream(self, stream, state=None) -> BlockLinearMapper:
        """Row-chunked fit: accumulate (AᵀA, AᵀY, Σx, Σy) one fused
        dispatch per chunk, then run the SAME Gauss-Seidel block updates
        as the in-core solver directly from the centered statistics
        (``linalg.bcd_from_gram``) — identical math, identical block
        order, O(d²) residency instead of O(n·d), and the feature matrix
        never exists (docs/STREAMING.md).

        ``state`` (a refit :class:`StreamState`) seeds the carry from an
        earlier fit's captured statistics; the fold then only pays for
        the NEW chunks and the extended state is re-exported via
        ``export_stream_state`` (docs/REFIT.md)."""
        probe("BlockLeastSquaresEstimator.solve")

        def init(feat_aval, y_aval):
            d, k = _stream_shapes(feat_aval, y_aval)
            return self._seed_carry(state, d, k)

        import time as _time

        t_fit = _time.perf_counter()
        with solver_obs.fit_span(
            "block_ls_stream", epochs=self.num_iter,
            **solver_obs.predicted_attrs(self),
        ):
            carry, info = stream.fold(init, linalg.gram_stream_step)
            n = info["num_examples"] + (state.num_examples if state else 0)
            self._capture_state(
                carry, n, reg=self.reg, block_size=self.block_size,
                num_iter=self.num_iter,
            )
            with _spans.span("stream:finish", epochs=self.num_iter):
                mapper = self._finish_from_stats(carry, n)
        _record_solver_observation(
            "block_ls_stream",
            rows=n,
            d=int(carry[0].shape[0]),
            block_size=mapper.block_size,
            wall_s=_time.perf_counter() - t_fit,
            rungs_attempted=1,
        )
        return mapper

    def _finish_from_stats(self, carry, n: int) -> BlockLinearMapper:
        """Gauss-Seidel block solve from accumulated statistics alone —
        shared by the streamed fit and the refit ``finish_from_state``
        path (no data pass, O(d²) inputs)."""
        gc, cc, mu_a, mu_b = linalg.gram_stream_finish(carry, n)
        d = gc.shape[0]
        block = min(self.block_size, d)
        # Same reg floor as the in-core fit: 1e-6 of the mean Gram
        # diagonal — trace(Gc)/(n·d) IS E[x²] of the centered data.
        reg = self.reg if self.reg > 0 else max(
            1e-6 * float(jnp.trace(gc)) / d, 1e-6
        )
        d_pad = _round_up(d, block)
        if d_pad != d:  # zero pad rows/cols are inert (λ keeps PD)
            gc = jnp.pad(gc, ((0, d_pad - d), (0, d_pad - d)))
            cc = jnp.pad(cc, ((0, d_pad - d), (0, 0)))
        w = linalg.bcd_from_gram(
            gc, cc, reg=reg, num_epochs=self.num_iter, block_size=block
        )
        return BlockLinearMapper(
            w, block_size=block, intercept=mu_b, feature_mean=mu_a
        )

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        # Block-sparse fast path (docs/AUTOTUNING.md, BLaST): sparse
        # featurizations (hashing-TF CSR rows, or a host matrix whose
        # nonzero structure is block-sparse) fit from BSR sufficient
        # statistics when block density falls below the TUNED threshold —
        # dense dispatch on a 10%-dense matrix wastes 90% of its MACs.
        dispatch = self._blocksparse_dispatch(data)
        if dispatch is not None:
            kind, bsr, a_dense, threshold = dispatch
            if kind == "sparse":
                targets = _as_array_dataset(labels)
                # Same OOM degradation contract as the dense paths: a
                # smaller block shrinks bcd_from_gram's per-block
                # factor/workspace, two halvings before giving up.
                block0 = min(self.block_size, bsr.shape[1])
                ladder = DegradationLadder(
                    halving_rungs(block0, max(block0 // 4, 1)),
                    label="BlockLeastSquaresEstimator.fit",
                )
                attempts = iter(range(len(ladder.rungs)))

                def attempt(block):
                    with solver_obs.rung_span(
                        "block_ls_sparse", block, next(attempts)
                    ):
                        return self._fit_blocksparse(
                            bsr, targets, threshold,
                            a_dense=a_dense, block=block,
                        )

                model = ladder.run(attempt)
                if ladder.reduced:
                    model.degradation = dict(ladder.record)
                return model
            # ObjectDataset of CSR rows that is too dense (or dispatch
            # disabled): densify once through BSR — the only way this
            # estimator can consume sparse rows. A dense ArrayDataset
            # above the threshold never reaches here: the probe is
            # mask-only and the caller's original array runs the legacy
            # path untouched.
            data = ArrayDataset(jnp.asarray(bsr.to_dense()))
        features = _as_array_dataset(data)
        targets = _as_array_dataset(labels)
        mesh = fit_mesh(self)

        raw = features.data
        stream = self.host_streaming
        if stream is None:
            # Auto-stream only on pure data meshes: the streaming solver's
            # shard_map spans the row axes only, so on a (data, model) mesh
            # it would replicate every block's work across the model axis —
            # the 2-D in-core path below owns that layout.
            stream = (
                isinstance(raw, np.ndarray)
                and raw.nbytes > _host_streaming_threshold_bytes()
                and linalg.model_axis_size(mesh) == 1
            )

        d = raw.shape[1]
        block0 = min(self.block_size, d)
        # OOM degradation: a smaller block shrinks the live Gram workspace
        # and (streaming) per-block device residency; two halvings cover
        # the realistic headroom gap before the problem itself is too big.
        ladder = DegradationLadder(
            halving_rungs(block0, max(block0 // 4, 1)),
            label="BlockLeastSquaresEstimator.fit",
        )
        fit_impl = self._fit_streaming if stream else self._fit_in_core
        attempts = iter(range(len(ladder.rungs)))

        def attempt(block):
            with solver_obs.rung_span("block_ls", block, next(attempts)):
                return fit_impl(features, targets, mesh, block)

        import time as _time

        t_fit = _time.perf_counter()
        with solver_obs.fit_span(
            "block_ls", d=d, epochs=self.num_iter, streaming=stream,
            **solver_obs.predicted_attrs(self),
        ):
            model = ladder.run(attempt)
        if ladder.reduced:
            model.degradation = dict(ladder.record)
        _record_solver_observation(
            "block_ls",
            rows=features.num_examples,
            d=d,
            block_size=model.block_size,
            wall_s=_time.perf_counter() - t_fit,
            rungs_attempted=1 + int(ladder.record.get("rung_index", 0)),
        )
        return model

    def _fit_streaming(self, features, targets, mesh, block) -> BlockLinearMapper:
        probe("BlockLeastSquaresEstimator.solve")
        raw = features.data
        reg = self.reg if self.reg > 0 else _scale_aware_reg_floor(
            np.asarray(raw[: min(features.num_examples, 4096)]),
            features.num_examples,
        )
        w, mu_a, mu_b = linalg.block_coordinate_descent_streaming(
            np.asarray(raw),
            np.asarray(targets.data, np.float32),
            reg=reg,
            num_epochs=self.num_iter,
            block_size=block,
            num_examples=features.num_examples,
            mesh=mesh,
        )
        return BlockLinearMapper(
            w, block_size=block, intercept=mu_b, feature_mean=mu_a
        )

    def _fit_in_core(self, features, targets, mesh, block) -> BlockLinearMapper:
        probe("BlockLeastSquaresEstimator.solve")
        # Host phases of the solve, as spans under `solver:fit` (in a
        # profiler trace through obs/spans.py's bridge): `solver:prepare`
        # twice (around the reg floor, which needs the centred rows before
        # padding), `solver:reg_floor`, `solver:bcd`.
        with _spans.span("solver:prepare"):
            x = jnp.asarray(features.data, dtype=jnp.float32)
            y = jnp.asarray(targets.data, dtype=jnp.float32)
            n = features.num_examples
            d = x.shape[1]
            mask = features.mask().reshape(-1, 1)
            mu_a, mu_b, xc, yc = _centred(x, y, mask, n)

        # The reg floor must see the REAL data statistics: computed here,
        # before zero-row masking dilution (first n rows only) and before
        # zero-column padding, either of which undershoots E[x²] and with
        # it the intended 1e-6 of the mean Gram diagonal.
        if self.reg > 0:
            reg = self.reg
        else:
            with _spans.span("solver:reg_floor"):  # reads a device scalar back
                reg = _scale_aware_reg_floor(xc[:n], n)

        # Pad the feature dim to a whole number of blocks (zero columns are
        # inert: their Gram rows/cols are zero and λ keeps the solve PD).
        # On a 2-D (data, model) mesh each model group needs a whole number
        # of blocks, so pad to model_axis·block columns.
        m = linalg.model_axis_size(mesh)
        with _spans.span("solver:prepare"):
            d_pad = _round_up(d, block * m)
            if d_pad != d:
                xc = jnp.pad(xc, ((0, 0), (0, d_pad - d)))
            if m > 1:
                xc = linalg.prepare_block_sharded(xc, mesh)
                yc = linalg.prepare_block_sharded(yc, mesh, fine_rows=True)
            else:
                xc = linalg.prepare_row_sharded(xc, mesh)
                yc = linalg.prepare_row_sharded(yc, mesh)
        # (the 2-D variant factors in every epoch and says nothing)
        attrs = {} if m > 1 else {
            "factor_reuse": linalg.bcd_factor_mode(self.num_iter),
            "gram_panels": str(linalg.gram_panels(block)),
        }
        with _spans.span("solver:bcd", **attrs):
            if m > 1:
                w = linalg.block_coordinate_descent_2d(
                    xc, yc, reg=reg, num_epochs=self.num_iter, block_size=block, mesh=mesh
                )
            else:
                # xc/yc are private centered copies, dead after the solve —
                # donate them so the epoch×block scan reuses their HBM for
                # the carried predictions and per-block Gram workspace
                # instead of keeping raw + centered copies both resident.
                w = linalg.block_coordinate_descent(
                    xc, yc, reg=reg, num_epochs=self.num_iter, block_size=block,
                    mesh=mesh, donate_xy=True,
                )
        return BlockLinearMapper(
            w, block_size=block, intercept=mu_b, feature_mean=mu_a
        )

    # ------------------------------------------------------- block-sparse
    def _blocksparse_dispatch(self, data):
        """The block-sparse dispatch decision for ``data``, or None for
        the legacy path untouched. Returns ``(kind, bsr, a_dense,
        threshold)`` where kind is ``"sparse"`` (fit on the BSR kernels)
        or ``"densify"`` (an ObjectDataset of CSR rows that must be
        densified through BSR regardless — the only way this estimator
        can consume them, including under ``KEYSTONE_BLOCKSPARSE=off``).
        Dense ArrayDatasets are probed with a mask-only density pass
        (no BSR is built unless the sparse path will actually run)."""
        from ...obs.store import rows_bucket, shape_class
        from ..pallas import blocksparse as _bs

        disabled = env_disabled("KEYSTONE_BLOCKSPARSE")
        if isinstance(data, ObjectDataset):
            items = data.collect()
            if not is_sparse_rows(items):
                return None
            d = int(items[0].shape[-1])
            bsr = BlockSparseMatrix.from_csr_rows(
                items, _bs.default_block_shape(d)
            )
            threshold = _bs.density_threshold(
                rows_bucket(shape_class(bsr.shape[0]))
            )
            if not disabled and bsr.density() <= threshold:
                return ("sparse", bsr, None, threshold)
            return ("densify", bsr, None, threshold)
        if disabled or not isinstance(data, ArrayDataset):
            return None
        raw = data.data
        if (
            not isinstance(raw, np.ndarray)
            or raw.ndim != 2
            or raw.shape[0] != data.num_examples  # padded rows: mask owed
            or raw.nbytes > _blocksparse_probe_bytes()
        ):
            return None
        block_shape = _bs.default_block_shape(raw.shape[1])
        threshold = _bs.density_threshold(
            rows_bucket(shape_class(raw.shape[0]))
        )
        # Banded early-exit probe: the common fully-dense fit concludes
        # after the first band instead of a full-matrix reduction.
        if block_density_exceeds(raw, block_shape, threshold):
            return None  # legacy path keeps the caller's own array
        bsr = BlockSparseMatrix.from_dense(raw, block_shape)
        return ("sparse", bsr, raw, threshold)

    def _fit_blocksparse(
        self,
        bsr: BlockSparseMatrix,
        targets,
        threshold: float,
        a_dense=None,
        block: Optional[int] = None,
    ) -> BlockLinearMapper:
        """Fit from block-sparse sufficient statistics: (AᵀA, AᵀY, Σx,
        Σy) accumulated by the BSR kernels (zero tiles skipped), then the
        SAME centered finish + Gauss-Seidel block updates as
        ``fit_stream`` (``linalg.gram_stream_finish`` + ``bcd_from_gram``)
        — identical math to the streaming fit, O(d²) residency."""
        from ..pallas import blocksparse as _bs

        probe("BlockLeastSquaresEstimator.solve")
        import time as _time

        impl = _bs.resolve_impl("auto")
        n = bsr.shape[0]
        d = bsr.shape[1]
        t_fit = _time.perf_counter()
        with solver_obs.fit_span(
            "block_ls_sparse", d=d, epochs=self.num_iter,
            density=round(bsr.density(), 4), impl=impl,
        ):
            y = jnp.asarray(targets.data, jnp.float32)[:n]
            totals = _bs.bsr_gram_totals(
                bsr, y, a_dense=a_dense, impl=impl,
                precision=linalg.precision(),
            )
            gc, cc, mu_a, mu_b = linalg.gram_stream_finish(totals, n)
            block = min(block or self.block_size, d)
            reg = self.reg if self.reg > 0 else max(
                1e-6 * float(jnp.trace(gc)) / d, 1e-6
            )
            d_pad = _round_up(d, block)
            if d_pad != d:  # zero pad rows/cols are inert (λ keeps PD)
                gc = jnp.pad(gc, ((0, d_pad - d), (0, d_pad - d)))
                cc = jnp.pad(cc, ((0, d_pad - d), (0, 0)))
            w = linalg.bcd_from_gram(
                gc, cc, reg=reg, num_epochs=self.num_iter, block_size=block
            )
        _names.metric(_names.BLOCKSPARSE_FITS).inc(impl=impl)
        _names.metric(_names.BLOCKSPARSE_BLOCKS_SKIPPED).inc(
            bsr.blocks_skipped()
        )
        _record_solver_observation(
            "block_ls_sparse",
            rows=n,
            d=d,
            block_size=block,
            wall_s=_time.perf_counter() - t_fit,
            rungs_attempted=1,
            density=round(bsr.density(), 6),
            blocks_skipped=bsr.blocks_skipped(),
            threshold=threshold,
        )
        return BlockLinearMapper(
            w, block_size=block, intercept=mu_b, feature_mean=mu_a
        )


def _blocksparse_probe_bytes() -> int:
    """Ceiling on the host feature matrix the fast path will tile-probe
    (the probe and BSR copy are O(n·d); above this the host-streaming
    path owns the fit). ``KEYSTONE_BLOCKSPARSE_PROBE_BYTES`` overrides."""
    from ...envknobs import env_int

    return env_int("KEYSTONE_BLOCKSPARSE_PROBE_BYTES", int(512e6))


def _record_solver_observation(
    solver: str,
    rows: int,
    d: int,
    block_size: int,
    wall_s: float,
    rungs_attempted: int,
    **extra,
) -> None:
    """Remember what this (block size, precision) pair cost on this shape
    class so MeasuredKnobRule can prefer the best recorded pair when the
    env knobs are unset (docs/OPTIMIZER.md). Best effort — a disabled or
    broken store never blocks a fit."""
    try:
        from ...obs import store as obs_store

        store = obs_store.get_store()
        if store is None:
            return
        mode = linalg.solver_mode()
        store.record(
            f"solver:{solver}:bs{block_size}:prec{mode}",
            obs_store.shape_class(rows, (d,), "float32"),
            wall_s=round(wall_s, 6),
            block_size=block_size,
            precision=mode,
            solver_rung=rungs_attempted,
            **extra,
        )
    except Exception:  # pragma: no cover - observability must not fail fits
        pass


def _stream_shapes(feat_aval, y_aval):
    """(d, k) from the streaming engine's featurized/label chunk avals;
    rejects non-matrix chains (the engine falls back to materialized)."""
    from ...workflow.streaming import StreamingFallback

    import jax

    leaves = jax.tree_util.tree_leaves(feat_aval)
    if len(leaves) != 1 or len(leaves[0].shape) != 2:
        raise StreamingFallback(
            f"gram streaming needs a single (rows, d) feature chunk, got "
            f"{[tuple(l.shape) for l in leaves]}"
        )
    return leaves[0].shape[1], y_aval.shape[1]


@jax.jit
def _centred(x, y, mask, n):
    """The in-core solve's centring as one program: the real rows' means
    and the centred, masked copies, and no temporary the size of the
    features (as separate operations `x * mask` and `x - mu_a` are each
    one, and a host running ahead of the device holds them at once: 2.6
    GB apiece at CIFAR's 8,192 x 80,000)."""
    with jax.named_scope("solve/centre"):
        mu_a = jnp.sum(x * mask, axis=0) / n
        mu_b = jnp.sum(y * mask, axis=0) / n
        return mu_a, mu_b, (x - mu_a) * mask, (y - mu_b) * mask


def _scale_aware_reg_floor(x_sample, n: int) -> float:
    """λ floor for an unregularized BCD solve: 1e-6 of the mean Gram
    diagonal (≈ 1e-6·n·E[x²]).

    An ABSOLUTE 1e-6 floor is invisible next to Gram entries of O(n): a
    rank-deficient block (more features than examples) then has condition
    ~n·E[x²]/1e-6 ≫ fp32's Cholesky limit and the factor silently emits
    NaNs — the model degrades to chance with no error raised. Relative to
    the data scale, the floor keeps the factor finite while acting as a
    minimum-norm tiebreak on the interpolating solution. ``x_sample`` may
    be a row subset; only E[x²] is needed.
    """
    mean_sq = float(_mean_square_about_mean(jnp.asarray(x_sample, jnp.float32)))
    return max(1e-6 * n * mean_sq, 1e-6)


@jax.jit
def _mean_square_about_mean(xs):
    """E[(x - its column means)²] as one program, with no temporary the
    size of x. The solvers fit CENTERED data; an uncentered sample with a
    large mean would overshoot the centered Gram scale by orders of
    magnitude. (Already-centered input makes the centring a no-op.)"""
    return jnp.mean(jnp.square(xs - jnp.mean(xs, axis=0, keepdims=True)))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _host_streaming_threshold_bytes() -> int:
    """Above this, a host ndarray feature matrix is streamed block-by-block
    instead of placed whole in HBM. Default 4 GB (the in-core path also
    materializes a centered copy, so real residency is ~2× + Gram
    workspace); override with KEYSTONE_STREAM_BYTES."""
    from ...envknobs import env_int

    return env_int("KEYSTONE_STREAM_BYTES", int(4e9))
