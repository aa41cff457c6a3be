"""Distributed dense linear algebra over the device mesh.

This is the first-class rebuild of the reference's external ``mlmatrix``
layer — ``RowPartitionedMatrix``, ``NormalEquations`` (treeReduce'd AᵀA/Aᵀb
+ driver-local Cholesky), ``TSQR``, ``BlockCoordinateDescent``
(reference: build.sbt:44; used at nodes/learning/LinearMapper.scala:87-95,
nodes/learning/BlockLinearMapper.scala:234-240,
nodes/learning/DistributedPCA.scala:40-57).

Design: matrices live as row-sharded device arrays over the mesh's ``data``
axis (examples × features). Partial Gram/gradient products are computed
per-shard on the MXU and combined with ``psum`` over ICI — the allreduce
that replaces Spark's treeReduce. Small (d×d) systems are solved replicated
on every device (cheaper than a gather-to-host round trip). Everything is
jitted; shapes are static.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import warnings
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import shard_map
from .mesh import DATA_AXIS, MODEL_AXIS, get_mesh, row_axes, row_shard_count


# Precision menu, measured on v5e (Gram at (1M, 1024), fp32 inputs —
# docs/PERFORMANCE.md): DEFAULT (1-pass bf16) 172 TFLOP/s, rel Frobenius
# error 5.6e-5; HIGH (3-pass) 63 TFLOP/s, 1.1e-5; HIGHEST (6-pass fp32
# emulation) 32 TFLOP/s, 1.6e-5. Linear systems are precision-sensitive
# (the reference computed in float64 Breeze), so every solver-grade
# matmul outside the refined exact solver runs at HIGHEST.
# One table for both readers below. "refine" selects the mixed-precision
# exact solver (fast Gram + high-precision iterative refinement, see
# centered_solve_refined); every other solver-grade matmul stays HIGHEST.
# "refine" is the DEFAULT for the exact solver on measured evidence
# (docs/PERFORMANCE.md): at (500k, 1024, 138) with Gram cond 1e4 on v5e,
# fast-Gram + 2 IR steps lands 540x closer to the converged solution than
# the 6-pass HIGHEST Cholesky (3.4e-8 vs 1.8e-5 weight error) at ~1.4x
# less compute — IR corrects the factorization's own rounding too.
_PRECISION_MODES = {
    "highest": lax.Precision.HIGHEST,
    "high": lax.Precision.HIGH,
    "default": lax.Precision.DEFAULT,
    "refine": lax.Precision.HIGHEST,
}


# Measured-knob override (workflow/knobs.py MeasuredKnobRule): replaces
# the DEFAULT precision mode only — an explicit KEYSTONE_SOLVER_PRECISION
# always wins, so an operator's pinned choice can never be overridden by
# a measurement. Read per call like the env var, so the mode-keyed
# compilation caches below key on it correctly. THREAD-LOCAL: the knob
# rule scopes its override to the fit it planned (solver_mode_scope), so
# a concurrent fit on another thread must not observe it.
_mode_override_local = threading.local()


def set_solver_mode_override(mode: "str | None") -> None:
    """Install (or clear, with None) the measured default-precision mode
    for the CURRENT THREAD. Raises on unknown modes — a bad stored
    observation must fail loudly at decision time, not mislead every
    subsequent solve. Prefer :func:`solver_mode_scope` — an unscoped
    install leaks into every later solve on the thread."""
    if mode is not None and mode not in _PRECISION_MODES:
        raise ValueError(
            f"solver mode override {mode!r}: expected one of "
            f"{sorted(_PRECISION_MODES)}"
        )
    _mode_override_local.mode = mode


@contextlib.contextmanager
def solver_mode_scope(mode: "str | None"):
    """Scoped default-precision override: installed on entry, restored on
    exit, thread-local throughout. ``None`` is a no-op scope. This is how
    MeasuredKnobRule's per-operator precision choice is applied — only
    around the planned fit, never as lingering process state, so a solve
    that was never planned under the measurement (direct ``fit_datasets``
    calls, another pipeline on another thread) keeps its own default."""
    if mode is None:
        yield
        return
    prev = getattr(_mode_override_local, "mode", None)
    set_solver_mode_override(mode)
    try:
        yield
    finally:
        _mode_override_local.mode = prev


def solver_mode() -> str:
    """The KEYSTONE_SOLVER_PRECISION mode, read PER CALL — one lifetime
    for the whole knob (r4 verdict item 8: an import-frozen ``PRECISION``
    global meant flipping the env mid-process changed the exact solver
    but silently not BCD/kernel/TSQR matmuls). Every solver-grade matmul
    reads this at trace time, and every compiled-function cache in this
    package keys on it (``mode_jit`` / the ``_*_fn`` factories), so a
    flip re-traces instead of silently reusing the old precision.

    Resolution order: explicit env var > measured override
    (:func:`set_solver_mode_override`) > the shipped "refine" default."""
    from ..envknobs import env_raw

    env = env_raw("KEYSTONE_SOLVER_PRECISION")
    override = getattr(_mode_override_local, "mode", None)
    if env is not None:
        name = env.lower()
    elif override is not None:
        name = override
    else:
        name = "refine"
    if name not in _PRECISION_MODES:  # loud, not silent: a typo'd "fast
        raise ValueError(  # mode" that silently ran 6-pass would mislead
            f"KEYSTONE_SOLVER_PRECISION={name!r}: expected one of "
            f"{sorted(_PRECISION_MODES)}"
        )
    return name


def precision_for_mode(mode: str) -> lax.Precision:
    """Matmul precision for a KEYSTONE_SOLVER_PRECISION mode name."""
    return _PRECISION_MODES[mode]


def _solver_precision() -> lax.Precision:
    return _PRECISION_MODES[solver_mode()]


def precision() -> lax.Precision:
    """Current solver-grade matmul precision (per-call read; use inside
    traced code for einsums that can't route through ``mm``)."""
    return _solver_precision()


def mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Solver-grade matmul at the CURRENT KEYSTONE_SOLVER_PRECISION mode
    (read at trace time; mode-keyed compilation caches make the read
    effective even after a mid-process flip)."""
    return jnp.matmul(a, b, precision=_solver_precision())


# The narrowest diagonal block `gram_sym` halves a width down to. Measured
# on one v5e chip at the two shapes the benchmark's cells run (PR 31; float32
# at HIGHEST; ms a product, by this constant):
#   rows 16,384 x width 16,384 (a streamed chunk's step on one chip): one
#     full product 283.5; 2048 (8 panels) 165.8; 1024 (16) 167.8; 512 (32) 179.4
#   rows 32,768 x width 4,096 (an in-core block): one full product 38.9;
#     2048 (2 panels) 28.5; 1024 (4) 22.5; 512 (8) 21.1
# 1024 is within 2 ms of the best at the first shape and takes 16.4 of the
# 17.8 ms the second has to give. Narrower than that the small blocks on
# and beside the diagonal lose the matmul's efficiency faster than halving
# again saves work; below 2048 columns nothing is halved at all.
_GRAM_SYM_PANEL = 1024


def gram_panels(d: int) -> int:
    """How many diagonal blocks (column panels) :func:`gram_sym` halves a
    width of ``d`` into: the largest power of two that leaves them
    ``_GRAM_SYM_PANEL`` wide or wider, and 1, the single full product,
    below twice that. A function of the width alone: the ``panels`` label
    of ``keystone_gram_symmetric_total`` and the ``gram_panels`` attribute
    of the ``stream:fold`` and ``solver:bcd`` spans read it too."""
    return 1 << max(0, (d // _GRAM_SYM_PANEL).bit_length() - 1)


def gram_sym(x: jnp.ndarray) -> jnp.ndarray:
    """``xᵀx`` at the solver precision from its upper block triangle
    alone: every entry is the dot product over the same rows that
    ``mm(x.T, x)`` takes, computed once where the full product computes
    it twice. With m panels the matmuls do (m+1)/(2m) of the full
    product's work, and the result is symmetric to the bit (the full
    product on a TPU is not). Narrow widths (``gram_panels`` 1) get the
    single matmul this replaces."""
    m = gram_panels(x.shape[1])
    return _gram_sym_panels(x, m) if m > 1 else mm(x.T, x)


def _gram_sym_panels(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """:func:`gram_sym` at a given power-of-two panel count (tests and the
    chip measurement force m; callers go through the width rule)."""
    d = x.shape[1]
    if m <= 1 or d < 2:
        # A diagonal block in full, its upper triangle mirrored: whatever
        # order the matmul sums its passes in, G[r, c] is G[c, r].
        g = mm(x.T, x)
        row = lax.broadcasted_iota(jnp.int32, g.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, g.shape, 1)
        return jnp.where(row <= col, g, g.T)
    # [[A, B], [Bᵀ, C]]: one matmul for the block above the diagonal, its
    # transpose below, and the two diagonal blocks by the same halving.
    h = -(-d // 2)
    left, right = x[:, :h], x[:, h:]
    above = mm(left.T, right)
    return jnp.block([
        [_gram_sym_panels(left, m // 2), above],
        [above.T, _gram_sym_panels(right, m // 2)],
    ])


def mode_jit(fn=None, **jit_kwargs):
    """``jax.jit`` whose compiled-executable cache is ALSO keyed on the
    solver-precision mode: the wrapped function re-traces (and ``mm``
    re-reads the mode) when KEYSTONE_SOLVER_PRECISION changes
    mid-process. Use for any jitted function that transitively calls
    ``mm``/``precision`` — a plain ``jax.jit`` would silently replay the
    executable compiled under the old mode."""
    def deco(f):
        jitted: dict = {}

        def fresh_callable():
            # jax's jit cache keys on the underlying callable OBJECT:
            # jax.jit(f) twice shares one trace cache, so each mode needs
            # a distinct pass-through callable or the first mode's traces
            # would be replayed under every later mode.
            def g(*args, **kwargs):
                return f(*args, **kwargs)

            return g

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            mode = solver_mode()
            if mode not in jitted:
                jitted[mode] = jax.jit(fresh_callable(), **jit_kwargs)
            jf = jitted[mode]
            if not kwargs:
                # Cost-observatory attribution (obs/cost.py): one
                # thread-local read when no harvest frame is active.
                from ..obs import cost as _cost

                _cost.note_solver_call(f.__name__, jf, args)
            return jf(*args, **kwargs)

        return wrapper

    return deco(fn) if fn is not None else deco


def _mode_cached(maxsize=None):
    """``functools.lru_cache`` that additionally keys on the
    solver-precision mode, so a mid-process KEYSTONE_SOLVER_PRECISION
    flip builds fresh compiled functions instead of replaying ones traced
    under the old mode. Positional-args-only (every factory here is)."""
    def deco(f):
        @functools.lru_cache(maxsize=maxsize)
        def cached(mode, *args):
            return f(*args)

        @functools.wraps(f)
        def wrapper(*args):
            return cached(solver_mode(), *args)

        return wrapper

    return deco


mode_cached = _mode_cached  # public name for other modules' compiled-fn factories


_DONATION_WARNING_RE = "Some donated buffers were not usable"


def _quiet_unused_donation_warnings() -> None:
    """Ensure a filter for jax's "Some donated buffers were not usable"
    warning is present. This package DELIBERATELY marks whole data
    matrices as donors for the solves' temporaries (jax.buffer_donor);
    backends that can't exploit that (host CPU aliasing is input→output
    only) warn per compile, which would read as a bug to an operator
    when it is the documented best-effort behavior. Called from the
    donating code paths — not at import — so a process that never uses
    these solvers keeps jax's diagnostic for its own donations. The
    presence check is against the live filter list (not a once-flag):
    pytest/catch_warnings scopes restore the list behind our back, and
    a stale flag would leave later compiles un-silenced."""
    for f in warnings.filters:
        if f[0] == "ignore" and f[1] is not None and f[1].pattern == _DONATION_WARNING_RE:
            return
    warnings.filterwarnings("ignore", message=_DONATION_WARNING_RE)


def _row_sharded(mesh: Mesh, a: jnp.ndarray) -> jnp.ndarray:
    spec = P(row_axes(mesh), *([None] * (a.ndim - 1)))
    target = NamedSharding(mesh, spec)
    current = getattr(a, "sharding", None)
    # Skip the placement when the array is already laid out correctly —
    # a redundant device_put of a multi-GB matrix is pure HBM traffic.
    if current is not None:
        try:
            if current.is_equivalent_to(target, a.ndim):
                return a
        except Exception:
            pass
    return jax.device_put(a, target)


def _pad_rows(a, multiple: int):
    n = a.shape[0]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return a
    pad = np.pad if isinstance(a, np.ndarray) else jnp.pad
    return pad(a, [(0, target - n)] + [(0, 0)] * (a.ndim - 1))


def prepare_row_sharded(a, mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """Zero-pad rows to the mesh data-axis size and place sharded. Host
    input stays on the host until the placement, so each device receives
    only its own row block (``jnp.asarray`` first would land the whole
    matrix on device 0 and reshard from there)."""
    mesh = mesh or get_mesh()
    if not isinstance(a, jax.Array):
        a = np.asarray(a)
    return _row_sharded(mesh, _pad_rows(a, row_shard_count(mesh)))


# ------------------------------------------------------------------ gram/solve


# Compiled-function caches: shard_map closures are rebuilt per call site,
# which would defeat jax.jit's cache and recompile on every invocation —
# a multi-second tax per solver call. Cache keyed on (mesh, static config).


@_mode_cached()
def _gram_fn(mesh: Mesh):
    axes = row_axes(mesh)

    def f(a_local):
        return lax.psum(mm(a_local.T, a_local), axes)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P(axes, None), out_specs=P()))


def _gram2_raw(mesh: Mesh):
    """Un-jitted shard_map computing (AᵀA, AᵀB) with one psum each at the
    solver precision — the shared kernel under gram() and
    normal_equations_solve. (The fused centered solve keeps its own
    variant: it also needs column sums in the same pass and a per-mode
    Gram precision.)"""
    axes = row_axes(mesh)

    def f2(a_local, b_local):
        ata = lax.psum(mm(a_local.T, a_local), axes)
        atb = lax.psum(mm(a_local.T, b_local), axes)
        return ata, atb

    return shard_map(
        f2,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None)),
        out_specs=(P(), P()),
    )


@_mode_cached()
def _gram2_fn(mesh: Mesh):
    return jax.jit(_gram2_raw(mesh))


def gram(
    a: jnp.ndarray,
    b: Optional[jnp.ndarray] = None,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """AᵀA (and AᵀB) via per-shard MXU matmul + psum over ICI.

    Zero-padded rows contribute nothing, so callers may pass padded arrays.
    (Replaces mlmatrix ``NormalEquations``' treeReduce of partition Grams.)

    ``a`` may also be a host-side
    :class:`~keystone_tpu.utils.sparse.BlockSparseMatrix`: the Gram then
    runs on the block-sparse kernels (``ops/pallas/blocksparse.py``),
    skipping zero tiles entirely — single-device (no mesh reduce; the
    block-sparse tier is below the partitioner's row floors today).
    """
    from ..utils.sparse import BlockSparseMatrix

    if isinstance(a, BlockSparseMatrix):
        from ..ops.pallas.blocksparse import bsr_gram_totals

        zeros = jnp.zeros((a.shape[0], 1), jnp.float32) if b is None else b
        g, c, _sa, _sb = bsr_gram_totals(a, zeros, precision=precision())
        return g, (None if b is None else c)
    mesh = mesh or get_mesh()
    if b is None:
        return _gram_fn(mesh)(a), None
    return _gram2_fn(mesh)(a, b)


@_mode_cached()
def _centered_solve_fused_fn(
    mesh: Mesh,
    gram_precision: lax.Precision,
    refine_steps: int,
    resid_precision: lax.Precision,
    gram_perturb: float = 0.0,
    donate_xy: bool = False,
):
    """ONE jitted computation: sharded Gram + algebraic centering +
    replicated Cholesky solve + optional mixed-precision iterative
    refinement. One dispatch: the gram→solve split it replaced paid the
    host's per-dispatch latency twice.

    Refinement (classic mixed-precision IR): the Gram runs at a fast
    precision, the Cholesky factor of that approximate Gram becomes the
    preconditioner, and each step recomputes the TRUE normal-equations
    residual from A itself at ``resid_precision`` — cost 2·n·d·k flops
    per step vs n·d² for the Gram, cheap whenever k ≪ d. The residual of
    the *centered* system is computed without materializing centered
    data: with S = B − A·W (padded zero rows contribute nothing),

        A_cᵀ(B_c − A_c·W) = AᵀS − μ_a·(1ᵀS)      (the n·μ_a·cᵀ terms cancel)

    so each step is one sharded pass producing (AᵀS, 1ᵀS) + a psum.

    Divergence guard (when the fast Gram can be worse than HIGHEST): IR
    contracts the error by ~cond(Gram)·ε_gram per step, so on badly
    conditioned systems the steps can stall or diverge and the refined
    weights would silently be WORSE than a HIGHEST-precision solve. The
    FINAL iterate's true residual norm is therefore measured (one extra
    2·n·d·k pass) and — still inside the same compiled program, via
    ``lax.cond`` — the whole solve is redone from a HIGHEST-precision
    Gram whenever that final residual is not at least half the initial
    one (r4 advisor: judging on the best norm across steps let a
    halve-then-diverge trajectory return a bad final iterate). Healthy
    IR shrinks the residual by orders of magnitude, so the fallback
    branch compiles always but executes only on conditioning failures.

    ``gram_perturb`` is a TEST SEAM: a deterministic rank-one corruption
    of the fast Gram, letting tests exercise the guard on backends where
    matmul precision flags are no-ops (host CPU). Always 0.0 in
    production paths.
    """
    axes = row_axes(mesh)

    def _gram_shard(precision):
        def gram_part(a_local, b_local):
            g = lambda p, q: jnp.matmul(p, q, precision=precision)
            ata = lax.psum(g(a_local.T, a_local), axes)
            atb = lax.psum(g(a_local.T, b_local), axes)
            sa = lax.psum(jnp.sum(a_local, axis=0), axes)
            sb = lax.psum(jnp.sum(b_local, axis=0), axes)
            return ata, atb, sa, sb

        return shard_map(
            gram_part, mesh=mesh,
            in_specs=(P(axes, None), P(axes, None)),
            out_specs=(P(), P(), P(), P()),
        )

    gram_raw = _gram_shard(gram_precision)
    guarded = refine_steps > 0 and gram_precision != lax.Precision.HIGHEST
    gram_highest = _gram_shard(lax.Precision.HIGHEST) if guarded else None

    def resid_part(a_local, b_local, w):
        r = lambda p, q: jnp.matmul(p, q, precision=resid_precision)
        s = b_local - r(a_local, w)
        ats = lax.psum(r(a_local.T, s), axes)
        ssum = lax.psum(jnp.sum(s, axis=0), axes)
        return ats, ssum

    resid_raw = shard_map(
        resid_part, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P()),
        out_specs=(P(), P()),
    )

    def _solve_from_gram(ata, atb, sa, sb, n, reg):
        mu_a, mu_b = sa / n, sb / n
        d = ata.shape[0]
        ata_c = ata - n * jnp.outer(mu_a, mu_a)
        atb_c = atb - n * jnp.outer(mu_a, mu_b)
        factor = jax.scipy.linalg.cho_factor(
            ata_c + reg * jnp.eye(d, dtype=ata.dtype), lower=True
        )
        return jax.scipy.linalg.cho_solve(factor, atb_c), mu_a, mu_b, factor, atb_c

    def run(x, y, n, reg):
        ata, atb, sa, sb = gram_raw(x, y)
        if gram_perturb:
            d = ata.shape[0]
            scale = jnp.trace(ata) / d
            ata = ata + gram_perturb * scale * jnp.ones_like(ata)
        w, mu_a, mu_b, factor, atb_c = _solve_from_gram(ata, atb, sa, sb, n, reg)
        if refine_steps == 0:
            return w, mu_a, mu_b

        def resid(w):
            ats, ssum = resid_raw(x, y, w)
            r = ats - jnp.outer(mu_a, ssum) - reg * w
            return r, jnp.linalg.norm(r)

        # Healthy IR returns the final iterate exactly as before; the
        # FINAL residual norm decides failure (r4 advisor: judging on the
        # best norm across steps let a trajectory that halved the
        # residual on step 1 then diverged pass the guard while the
        # returned final iterate was worse than the unrefined solve).
        # Near convergence fp32 residual norms sit at the roundoff floor;
        # the `floor` term below keeps that noise from firing the guard.
        r, n0 = resid(w)
        final_n = n0
        for _ in range(refine_steps):
            w = w + jax.scipy.linalg.cho_solve(factor, r)
            r, final_n = resid(w)
        if not guarded:
            return w, mu_a, mu_b

        def highest_fallback(_):
            ata_h, atb_h, sa_h, sb_h = gram_highest(x, y)
            w_h, _, _, factor_h, _ = _solve_from_gram(ata_h, atb_h, sa_h, sb_h, n, reg)
            for _ in range(refine_steps):
                r_h, _ = resid(w_h)
                w_h = w_h + jax.scipy.linalg.cho_solve(factor_h, r_h)
            return w_h

        # No-fallback floor: when the unrefined residual already sits at
        # fp32 roundoff relative to the gradient scale (well-conditioned
        # data, or backends where DEFAULT==HIGHEST), refinement cannot
        # halve noise and the guard must not fire — the solve is done.
        floor = 1e-5 * (jnp.linalg.norm(atb_c) + reg * jnp.linalg.norm(w))
        failed = (final_n > 0.5 * n0) & (n0 > floor)
        w_final = lax.cond(failed, highest_fallback, lambda _: w, None)
        return w_final, mu_a, mu_b

    # donate_xy: the (n, d)/(n, k) inputs dominate HBM during the solve;
    # when the caller owns them (fresh row-sharded copies, as in
    # LinearMapEstimator.fit) donation frees their buffers into the
    # computation for Gram/residual temporaries. The normal-equation
    # update passes (IR residual recomputation) still read x/y — XLA
    # keeps the storage live exactly as long as needed; only the caller's
    # handle dies.  # keystone: owns-donated
    return jax.jit(
        run, donate_argnums=(0, 1) if donate_xy else ()
    )


def centered_solve_refined(
    x: jnp.ndarray,
    y: jnp.ndarray,
    n: int,
    reg: float,
    mesh: Optional[Mesh] = None,
    gram_precision: lax.Precision = None,
    refine_steps: int = 0,
    resid_precision: lax.Precision = lax.Precision.HIGHEST,
    donate_xy: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Centered ridge solve (w, μ_a, μ_b) in one dispatch, with optional
    mixed-precision iterative refinement (see _centered_solve_fused_fn).

    ``x``/``y`` must be row-sharded (zero-padded rows allowed); ``n`` is
    the true (unpadded) row count. ``donate_xy=True`` donates the data
    buffers into the solve — only when the caller owns them (their
    handles are invalidated).
    """
    mesh = mesh or get_mesh()
    if gram_precision is None:
        gram_precision = _solver_precision()
    if donate_xy:
        _quiet_unused_donation_warnings()
    fn = _centered_solve_fused_fn(
        mesh, gram_precision, int(refine_steps), resid_precision,
        float(_TEST_GRAM_PERTURB), bool(donate_xy),
    )
    return fn(x, y, jnp.float32(n), jnp.float32(reg))


# Test seam for the refine-mode divergence guard (see
# _centered_solve_fused_fn): host-CPU matmuls ignore precision flags, so
# tests set this to corrupt the fast Gram deterministically and check the
# guard recovers the HIGHEST-precision solution. Never set in production.
_TEST_GRAM_PERTURB: float = 0.0


def check_finite(w: jnp.ndarray, context: str) -> None:
    """Raise loudly when a solve produced non-finite weights.

    An unregularized normal-equations solve of a rank-deficient system
    makes Cholesky emit NaNs that silently flow into garbage predictions
    (chance-level error with no hint why). The reference failed loudly
    here (Breeze cholesky throws NotSymmetricPositiveDefinite); match
    that. Callers gate this on reg==0 — the only singular-risk case — so
    regularized fits pay no extra device round trip.
    """
    if not bool(jnp.isfinite(jnp.sum(w))):
        raise FloatingPointError(
            f"{context}: solution contains non-finite values — the normal "
            "equations are singular (more features than examples, or "
            "linearly dependent features) and no regularization was "
            "applied. Pass reg > 0."
        )


def solve_spd(ata: jnp.ndarray, atb: jnp.ndarray, reg=0.0) -> jnp.ndarray:
    """Solve (AᵀA + reg·I) x = Aᵀb by Cholesky (the reference's local solve).

    ``reg`` may be a traced scalar (it participates in jit caches as a
    value, not a shape).
    """
    d = ata.shape[0]
    lhs = ata + reg * jnp.eye(d, dtype=ata.dtype)
    factor = jax.scipy.linalg.cho_factor(lhs, lower=True)
    return jax.scipy.linalg.cho_solve(factor, atb)


@_mode_cached()
def _normal_equations_fn(mesh: Mesh):
    gram_raw = _gram2_raw(mesh)

    def run(a, b, reg):
        ata, atb = gram_raw(a, b)
        return solve_spd(ata, atb, reg=reg)

    return jax.jit(run)


def normal_equations_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    reg: float = 0.0,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """One-shot distributed least squares: x = (AᵀA + λI)⁻¹ Aᵀb.

    Gram + replicated Cholesky fused into ONE dispatch. Callers
    that own private copies of the data and want them donated into the
    solve should use :func:`centered_solve_refined` with ``donate_xy``
    (the exact-solver path LinearMapEstimator takes).
    """
    mesh = mesh or get_mesh()
    return _normal_equations_fn(mesh)(a, b, jnp.float32(reg))


# ------------------------------------------------------------------------ TSQR


def tsqr_r(a: jnp.ndarray, mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """R factor of a row-sharded tall-skinny matrix.

    Local QR per shard → all_gather the small R factors → QR of the stack.
    Rebuild of mlmatrix ``TSQR`` (used by the reference's DistributedPCA,
    nodes/learning/DistributedPCA.scala:40-57) with the tree reduction
    realized as one ICI all_gather (device counts are small enough that a
    single gather beats a multi-level tree on-slice).
    """
    mesh = mesh or get_mesh()
    return _tsqr_fn(mesh)(a)


@_mode_cached()
def _tsqr_fn(mesh: Mesh):
    axes = row_axes(mesh)

    def f(a_local):
        d = a_local.shape[1]
        r_local = jnp.linalg.qr(a_local, mode="r")
        stacked = lax.all_gather(r_local, axes)  # (n_shards, min(n_local,d), d)
        return jnp.linalg.qr(stacked.reshape(-1, d), mode="r")

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P(axes, None), out_specs=P()))


@jax.jit
def _svd_of_r(r):
    _, s, vt = jnp.linalg.svd(r, full_matrices=False)
    return s, vt


def tsqr_svd(
    a: jnp.ndarray, mesh: Optional[Mesh] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Singular values and right singular vectors of a row-sharded matrix,
    via SVD of the TSQR R factor: A = QR, R = UΣVᵀ ⇒ A's (Σ, V) = R's."""
    return _svd_of_r(tsqr_r(a, mesh=mesh))


# ---------------------------------------------------------------------- BCD
#
# Block Gauss-Seidel is written once: the epoch scan `_block_gauss_seidel`
# runs `_block_update` over a `_BlockSource`, the one thing the forms
# differ in; the one-block programs and the 2-D form call `block_update`.
# The parts of the update are `jax.named_scope`s, each opened at one site
# (`_block_update`, `_gram`, `_cholesky`), so a device trace of any form
# splits Gram against Cholesky against the rest. Metadata only.
BCD_RESIDUAL = "bcd/residual"
BCD_GRAM = "bcd/gram"
BCD_CROSS = "bcd/cross"
BCD_CHOLESKY = "bcd/cholesky"
BCD_UPDATE = "bcd/update"
BCD_SCOPES = (BCD_RESIDUAL, BCD_GRAM, BCD_CROSS, BCD_CHOLESKY, BCD_UPDATE)


class _BlockSource(NamedTuple):
    """Where block b's system (A_bᵀA_b + λI) W_b = A_bᵀ(Y − P + A_b W_b)
    comes from. ``block(b, start)`` is what the others read for block b;
    ``gram`` is A_bᵀA_b summed over the mesh; ``residual`` then ``cross``
    make the right-hand side; ``update`` moves the carried state (from
    ``init()``) to the new block weights."""

    block: Callable
    gram: Callable
    residual: Callable
    cross: Callable
    update: Callable
    init: Callable


def _panel_source(panel, y_local, axes) -> _BlockSource:
    """Block b as this shard's rows of its panel of A (``panel(b, start)``;
    unused by the one-block programs), the predictions P carried."""
    return _BlockSource(
        block=panel,
        gram=lambda a_b: lax.psum(gram_sym(a_b), axes),
        residual=lambda a_b, p_local, w, w_b: y_local - p_local + mm(a_b, w_b),
        cross=lambda a_b, r_local, w_b: lax.psum(mm(a_b.T, r_local), axes),
        update=lambda a_b, p_local, w_b, w_b_new: p_local + mm(a_b, w_b_new - w_b),
        init=lambda: jnp.zeros_like(y_local),
    )


def _statistics_source(gc, cc, block_size: int) -> _BlockSource:
    """Block b as slices of the centred statistics Gc = AᵀA, Cc = AᵀY:
    A_bᵀA_b is Gc's diagonal block, and the right-hand side is
    (AᵀY)_b − (AᵀA·W)_b + A_bᵀA_b·W_b. Nothing is carried."""
    d, k = cc.shape

    def diagonal(start):
        return lax.dynamic_slice(gc, (start, start), (block_size, block_size))

    return _BlockSource(
        block=lambda b, start: start,
        gram=diagonal,
        # A_bᵀP, the predictions as block b sees them
        residual=lambda start, _, w, w_b: mm(lax.dynamic_slice(gc, (start, 0), (block_size, d)), w),
        cross=lambda start, a_bt_p, w_b: (
            lax.dynamic_slice(cc, (start, 0), (block_size, k)) - a_bt_p + mm(diagonal(start), w_b)
        ),
        update=lambda start, state, w_b, w_b_new: state,
        init=tuple,
    )


def _gram(source: _BlockSource, blk):
    with jax.named_scope(BCD_GRAM):
        return source.gram(blk)


def _cholesky(g, reg, eye, rhs=None, factor=None):
    """The lower factor of ``g + λI`` (or ``factor``), solved against ``rhs`` if given."""
    with jax.named_scope(BCD_CHOLESKY):
        if factor is None:
            factor = jax.scipy.linalg.cho_factor(g + reg * eye, lower=True)[0]
        if rhs is None:
            return factor
        return jax.scipy.linalg.cho_solve((factor, True), rhs)


def _block_update(source, blk, state, w, w_b, start, reg, eye, factors=None, b=None):
    """One block's update in every form; returns ``(w, state)`` with ``w_b``'s
    rows of ``w`` (from ``start``) rewritten. The factor is ``factors[b]``
    where a factor pass made it, else made here from the block's Gram."""
    with jax.named_scope(BCD_RESIDUAL):
        r = source.residual(blk, state, w, w_b)
    g = _gram(source, blk) if factors is None else None
    with jax.named_scope(BCD_CROSS):
        c = source.cross(blk, r, w_b)
    factor = None if factors is None else lax.dynamic_index_in_dim(factors, b, keepdims=False)
    w_b_new = _cholesky(g, reg, eye, c, factor)
    with jax.named_scope(BCD_UPDATE):
        state = source.update(blk, state, w_b, w_b_new)
        w = lax.dynamic_update_slice(w, w_b_new, (start, 0))
    return w, state


def _block_gauss_seidel(source, num_blocks: int, block_size: int, num_epochs: int, reg, k: int, dtype):
    """One scan over epoch x block of ``source``'s blocks; returns the
    (num_blocks · block_size, k) weights. No factor depends on the
    iterate, so with ``num_epochs > 1`` a factor pass ahead of the scan
    makes each once, stacked (``d · block_size`` floats a device: 256 MiB
    at d = 16384, block 4096); with one epoch the step factors in place."""
    eye = jnp.eye(block_size, dtype=dtype)
    w0 = jnp.zeros((num_blocks * block_size, k), dtype=dtype)
    state0 = source.init()
    factors = lax.map(
        lambda b: _cholesky(_gram(source, source.block(b, b * block_size)), reg, eye),
        jnp.arange(num_blocks),
    ) if bcd_factor_mode(num_epochs) == "reused" else None

    def step(carry, b):
        w, state = carry
        start = b * block_size
        blk = source.block(b, start)
        w_b = lax.dynamic_slice(w, (start, 0), (block_size, k))
        return _block_update(source, blk, state, w, w_b, start, reg, eye, factors, b), None

    blocks = jnp.tile(jnp.arange(num_blocks), num_epochs)
    (w, _), _ = lax.scan(step, (w0, state0), blocks)
    return w


def block_update(a_b, y_local, p_local, w_b, reg, axes):
    """``(w_b_new, p_local)`` from block panel ``a_b`` (this shard's rows in a
    ``shard_map`` over ``axes``), factored in place: the step of the
    one-block programs (streamed BCD, conv_block.py) and the 2-D form."""
    eye = jnp.eye(a_b.shape[1], dtype=a_b.dtype)
    return _block_update(_panel_source(None, y_local, axes), a_b, p_local, w_b, w_b, 0, reg, eye)


def bcd_factor_mode(num_epochs: int) -> str:
    """Which form of the epoch scan ``num_epochs`` compiles to: the ``mode``
    label of ``keystone_bcd_factor_reuse_total`` and the ``factor_reuse``
    attribute of the ``solver:bcd`` span."""
    return "reused" if num_epochs > 1 else "single_pass"


def _count_factor_rule(num_epochs: int) -> None:
    from ..obs import names as _names

    _names.metric(_names.BCD_FACTOR_REUSE).inc(mode=bcd_factor_mode(num_epochs))


def block_coordinate_descent(
    a: jnp.ndarray,
    y: jnp.ndarray,
    reg: float,
    num_epochs: int,
    block_size: int,
    mesh: Optional[Mesh] = None,
    donate_xy: bool = False,
) -> jnp.ndarray:
    """Least-squares block coordinate descent over feature blocks.

    Rebuild of mlmatrix ``BlockCoordinateDescent.solveLeastSquaresWithL2``
    (driving the reference's BlockLeastSquaresEstimator,
    nodes/learning/BlockLinearMapper.scala:234-240): per block b, solve

        (A_bᵀA_b + λI) W_b = A_bᵀ (Y − P + A_b W_b)

    where P are current predictions. Per-shard products ride the MXU;
    cross-shard sums are one psum per block; the whole solve is a single
    compiled program — no host round trips inside training: the epoch
    scan :func:`_block_gauss_seidel` over the panels of ``a``.

    ``a`` is (n, d) row-sharded (rows may be zero-padded), ``y`` is (n, k).
    ``d`` must be a multiple of ``block_size`` (pad features if needed).
    Returns the (d, k) weight matrix, replicated.

    ``donate_xy=True`` donates the ``a``/``y`` buffers into the solve
    (caller's handles are invalidated) — pass it when they are private
    centered copies (block.py's in-core fit does), so the epoch×block
    scan can reuse their HBM for the carried predictions and Gram
    workspace instead of holding the copies alive beside them.
    """
    mesh = mesh or get_mesh()
    n, d = a.shape
    if d % block_size != 0:
        raise ValueError(f"d={d} not divisible by block_size={block_size}")
    if donate_xy:
        _quiet_unused_donation_warnings()
    fn = _bcd_fn(mesh, num_epochs, block_size, bool(donate_xy))
    reg_arr = jnp.asarray(reg, dtype=a.dtype)
    # Cost-observatory attribution (obs/cost.py): avals, not the arrays
    # — a/y may be donated into the solve below.
    from ..obs import cost as _cost

    _count_factor_rule(num_epochs)
    _count_gram_panels(block_size)
    _cost.note_solver_call("solver_bcd", fn, (a, y, reg_arr))
    return fn(a, y, reg_arr)


@_mode_cached()
def _bcd_fn(mesh: Mesh, num_epochs: int, block_size: int, donate_xy: bool = False):
    axes = row_axes(mesh)

    def per_device(a_local, y_local, reg):
        rows, d = a_local.shape
        source = _panel_source(
            lambda b, start: lax.dynamic_slice(a_local, (0, start), (rows, block_size)),
            y_local, axes,
        )
        return _block_gauss_seidel(
            source, d // block_size, block_size, num_epochs, reg, y_local.shape[1], a_local.dtype
        )

    return jax.jit(
        shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(axes, None), P(axes, None), P()),
            out_specs=P(),
        ),
        # x/y donated only when the caller passes owned copies
        # (donate_xy contract above).  # keystone: owns-donated
        donate_argnums=(0, 1) if donate_xy else (),
    )


def _linear_row_index(axes, mesh: Mesh):
    """Combined linear shard index over the (possibly multiple) row axes."""
    if isinstance(axes, str):
        return lax.axis_index(axes)
    idx = jnp.int32(0)
    for name in axes:
        idx = idx * mesh.shape[name] + lax.axis_index(name)
    return idx


@_mode_cached(maxsize=16)
def _bcd_remat_fn(mesh: Mesh, num_epochs: int, block_size: int,
                  num_blocks: int, block_fn):
    """Cache is keyed on ``block_fn`` IDENTITY: pass a module-level or
    otherwise long-lived callable for cache hits — a closure re-created
    per call recompiles every time. Bounded (not maxsize=None like the
    shape-keyed caches above) precisely because per-call closures would
    otherwise pin compiled executables forever."""
    axes = row_axes(mesh)

    def per_device(y_local, reg):
        rows, k = y_local.shape
        offset = _linear_row_index(axes, mesh) * rows
        source = _panel_source(lambda b, start: block_fn(b, offset, rows), y_local, axes)
        return _block_gauss_seidel(source, num_blocks, block_size, num_epochs, reg, k, y_local.dtype)

    return jax.jit(
        shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axes, None), P()), out_specs=P(),
        )
    )


def block_coordinate_descent_rematerialized(
    block_fn,
    y: jnp.ndarray,
    reg: float,
    num_epochs: int,
    block_size: int,
    num_blocks: int,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """BCD where feature blocks are COMPUTED on device inside the update
    instead of read from anywhere — for feature matrices too large for
    HBM *and* host RAM (TIMIT-wide at full n is 144 GB; the streaming
    path needs it in host RAM, this path needs only a generator).

    The epoch scan of :func:`block_coordinate_descent` over panels made
    by ``block_fn`` (its factor pass makes each once more; the conv-block
    solver applies the idea with a conv featurizer — ops/learning/
    conv_block.py). ``block_fn(b, row_offset, rows)`` must return the
    local (rows, block_size) panel of block ``b`` for the shard whose
    global row range starts at ``row_offset``, as a pure traceable
    function (e.g. seeded ``jax.random`` generation). ``y`` is
    row-sharded; returns the replicated (num_blocks·block_size, k) weights.
    """
    mesh = mesh or get_mesh()
    fn = _bcd_remat_fn(mesh, int(num_epochs), int(block_size),
                       int(num_blocks), block_fn)
    _count_factor_rule(num_epochs)
    _count_gram_panels(block_size)
    return fn(y, jnp.asarray(reg, dtype=jnp.float32))


# -------------------------------------------------------------- streaming BCD


@_mode_cached()
def _bcd_stream_step_fn(mesh: Mesh):
    axes = row_axes(mesh)

    # Donation (same idea as conv_block.py's donate_argnums=(3,)): the
    # streaming caller ping-pongs the (n, k) predictions and the (bs, k)
    # block weights through this step — the old buffers are dead the
    # moment the step returns — and the (n, bs) feature panel is a fresh
    # per-block transfer consumed exactly once. Donating all three lets
    # XLA alias p/w outputs onto their inputs and reuse the panel's HBM
    # for temporaries, so per-step residency stays one panel + one
    # predictions buffer instead of two of each.
    def per_device(a_b_local, mask_local, mu_block, y_local, p_local, w_b, reg):
        # Center on device (padding rows stay exactly zero via the mask).
        a_b = (a_b_local - mu_block) * mask_local
        return block_update(a_b, y_local, p_local, w_b, reg, axes)

    return jax.jit(
        shard_map(
            per_device,
            mesh=mesh,
            in_specs=(
                P(axes, None), P(axes, None), P(), P(axes, None),
                P(axes, None), P(), P(),
            ),
            out_specs=(P(), P(axes, None)),
        ),
        # panel + ping-pong carries are loop-owned (built by the stream
        # driver, threaded only through this step; alias asserted by
        # tests/ops/test_donation.py).  # keystone: owns-donated
        donate_argnums=(0, 4, 5),
    )


def block_coordinate_descent_streaming(
    x_host: np.ndarray,
    y: jnp.ndarray,
    reg: float,
    num_epochs: int,
    block_size: int,
    num_examples: Optional[int] = None,
    center: bool = True,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """BCD least squares for feature matrices too large for HBM.

    The reference streams each feature block out of the RDD cache per BCD
    iteration (mlmatrix BlockCoordinateDescent over VectorSplitter blocks,
    reference: nodes/learning/BlockLinearMapper.scala:234-240); the TPU
    analog keeps ``x_host`` in host RAM and transfers one (n, block_size)
    feature block to the mesh per update, so device residency is one block
    panel + the (n, k) predictions — independent of d. Mean-centering
    happens on device per block (the full centered copy of X never exists
    anywhere).

    Returns ``(w, mu_a, mu_b)``: weights (d, k) and the feature/label
    means used for centering (zeros when ``center=False``).
    """
    mesh = mesh or get_mesh()
    x_host = np.asarray(x_host)
    n_rows, d = x_host.shape
    n = num_examples if num_examples is not None else n_rows
    k = y.shape[1]
    bs = min(block_size, d)
    num_blocks = -(-d // bs)

    y_arr = jnp.asarray(y, jnp.float32)
    if center:
        # One streaming pass for the feature means; label mean is cheap.
        mu_a = np.zeros((d,), np.float64)
        for start in range(0, d, bs):
            mu_a[start : start + bs] = (
                np.asarray(x_host[:n, start : start + bs], np.float64).sum(axis=0) / n
            )
        mu_a = mu_a.astype(np.float32)
        mu_b = jnp.sum(y_arr[:n], axis=0) / n
        y_arr = y_arr.at[:n].add(-mu_b)
        y_arr = y_arr.at[n:].set(0.0)
    else:
        mu_a = np.zeros((d,), np.float32)
        mu_b = jnp.zeros((k,), jnp.float32)

    y_dev = prepare_row_sharded(y_arr, mesh)
    n_pad = y_dev.shape[0]
    mask = np.zeros((n_pad, 1), np.float32)
    mask[:n] = 1.0
    mask_dev = prepare_row_sharded(mask, mesh)
    p_dev = prepare_row_sharded(jnp.zeros((n_pad, k), jnp.float32), mesh)

    _quiet_unused_donation_warnings()  # the step donates its spent panel
    step = _bcd_stream_step_fn(mesh)
    reg_dev = jnp.float32(reg)
    w_blocks = [jnp.zeros((bs, k), jnp.float32) for _ in range(num_blocks)]
    # The step donates its ping-pong carries (predictions + block
    # weights, aliased in place) and the spent feature panel — the old
    # handles die with each call, which is exactly the intent here.
    for _ in range(num_epochs):
        for b in range(num_blocks):
            start = b * bs
            xb = x_host[:, start : start + bs]
            if xb.shape[1] < bs:  # short last block: zero-pad columns
                xb = np.pad(xb, ((0, 0), (0, bs - xb.shape[1])))
            xb_dev = prepare_row_sharded(
                np.ascontiguousarray(xb, np.float32), mesh
            )
            mu_blk = mu_a[start : start + bs]
            if mu_blk.shape[0] < bs:
                mu_blk = np.pad(mu_blk, (0, bs - mu_blk.shape[0]))
            w_blocks[b], p_dev = step(
                xb_dev, mask_dev, jnp.asarray(mu_blk), y_dev, p_dev,
                w_blocks[b], reg_dev,
            )
    w = jnp.concatenate(w_blocks, axis=0)[:d]
    return w, jnp.asarray(mu_a), mu_b


# --------------------------------------------- streaming gram (chunked fit)
#
# The row-chunked counterpart of the feature-block streaming above: the
# workflow streaming engine (workflow/streaming.py) feeds featurized row
# chunks through ``gram_stream_step`` — fused into the SAME dispatch as
# the featurize chain, carries donated ping-pong style like
# ``_bcd_stream_step_fn`` — so only O(d²) sufficient statistics ever
# exist; the (n, d) feature matrix never materializes on host or device.
# ``solve_from_gram`` / ``bcd_from_gram`` then finish the fit from the
# statistics alone: the Gauss-Seidel block update only needs A_bᵀA_b,
# (AᵀA·W)_b and (AᵀY)_b, all slices of the accumulated Gram.


def gram_stream_init(d: int, k: int, dtype=jnp.float32):
    """Zero sufficient statistics (G=AᵀA, C=AᵀY, Σx, Σy) for a streaming
    least-squares fit. The carry the engine donates through every chunk."""
    return (
        jnp.zeros((d, d), dtype),
        jnp.zeros((d, k), dtype),
        jnp.zeros((d,), dtype),
        jnp.zeros((k,), dtype),
    )


def gram_stream_step(carry, x, y):
    """One chunk's contribution to the sufficient statistics (traceable;
    the engine composes it after the featurize chain inside ONE jit).
    Pad rows must be exactly zero — the engine's re-zero mask and the
    framework-wide BatchTransformer invariant guarantee it — so no mask
    multiply is needed here."""
    g, c, sa, sb = carry
    with jax.named_scope("gram/step"):
        x = x.astype(g.dtype)
        y = y.astype(g.dtype)
        return (
            g + gram_sym(x),
            c + mm(x.T, y),
            sa + jnp.sum(x, axis=0),
            sb + jnp.sum(y, axis=0),
        )


def _count_gram_panels(width: int) -> str:
    """One count of ``keystone_gram_symmetric_total`` for a fit whose
    Gram products are ``width`` wide; returns the ``panels`` label."""
    from ..obs import names as _names

    panels = str(gram_panels(width))
    _names.metric(_names.GRAM_SYMMETRIC).inc(panels=panels)
    return panels


def _note_gram_fold(carry) -> dict:
    """``ChunkStream.fold`` calls this once a fold with the estimator's
    carry: counts the fold by the panels :func:`gram_stream_step` will
    cut its Gram into, and returns the same as the ``gram_panels``
    attribute of ``stream:fold``."""
    return {"gram_panels": _count_gram_panels(carry[0].shape[0])}


def gram_stream_block_step(carry, x, y, block_index):
    """Model-axis (feature-sharded) variant of :func:`gram_stream_step`:
    this device's carry holds only the ``block_index``-th row block of G
    (and of C, Σx) — (d/p_model, d) instead of (d, d) — so the per-device
    Gram state shrinks p_model×. Each block still sees the FULL chunk x
    (rows already data-sharded by the engine) and takes its own column
    slice; Σy is feature-free, so only block 0 accumulates it (the
    finish-time model reduction SUMS non-feature leaves)."""
    g, c, sa, sb = carry
    b = g.shape[0]  # static block height; block_index is traced
    x = x.astype(g.dtype)
    y = y.astype(g.dtype)
    xb = lax.dynamic_slice_in_dim(x, block_index * b, b, axis=1)
    on0 = (block_index == 0).astype(g.dtype)
    return (
        g + mm(xb.T, x),
        c + mm(xb.T, y),
        sa + jnp.sum(xb, axis=0),
        sb + on0 * jnp.sum(y, axis=0),
    )


# Blocked-carry protocol (workflow/streaming.py 2-D layouts): which axis
# of each carry leaf is the feature axis (None = feature-free, kept full
# shape and accumulated only on model block 0).
gram_stream_step.model_layout = (0, 0, 0, None)
gram_stream_step.model_block_step = gram_stream_block_step
gram_stream_step.note_fold = _note_gram_fold


@_mode_cached()
def _gram_finish_fn():
    def run(g, c, sa, sb, n):
        # Algebraic centering (Σ(x−μ)(x−μ)ᵀ = G − n·μμᵀ), same identity
        # as the exact solver's fused path — no centered copy exists.
        with jax.named_scope("gram/finish"):
            mu_a = sa / n
            mu_b = sb / n
            gc = g - n * jnp.outer(mu_a, mu_a)
            cc = c - n * jnp.outer(mu_a, mu_b)
            return gc, cc, mu_a, mu_b

    return jax.jit(run)


def gram_stream_finish(carry, n: int):
    """Centered Gram/cross products + column means from the accumulated
    statistics: ``(Gc, Cc, mu_a, mu_b)``."""
    g, c, sa, sb = carry
    return _gram_finish_fn()(g, c, sa, sb, jnp.asarray(n, g.dtype))


def solve_from_gram(gc, cc, reg) -> jnp.ndarray:
    """Exact ridge solve from centered sufficient statistics — the
    streaming analog of the normal-equation rung."""
    return solve_spd(gc, cc, reg=reg)


@_mode_cached()
def _bcd_gram_fn(num_epochs: int, block_size: int):
    def run(gc, cc, reg):
        source = _statistics_source(gc, cc, block_size)
        return _block_gauss_seidel(
            source, gc.shape[0] // block_size, block_size, num_epochs, reg, cc.shape[1], gc.dtype
        )

    return jax.jit(run)


def bcd_from_gram(
    gc: jnp.ndarray,
    cc: jnp.ndarray,
    reg: float,
    num_epochs: int,
    block_size: int,
) -> jnp.ndarray:
    """Feature-block Gauss-Seidel least squares driven entirely by the
    centered Gram statistics — the epoch scan of
    :func:`block_coordinate_descent` (the same update, block order and
    factor pass) over blocks sliced from the statistics, so a streaming
    fit matches the materialized fit to accumulation rounding. ``gc``
    must be (d_pad, d_pad) with d_pad a multiple of ``block_size`` (zero
    pad rows/cols are inert: λ keeps the factor PD, exactly as the
    in-core solver's zero column padding). Returns (d_pad, k) weights.
    """
    d = gc.shape[0]
    if d % block_size != 0:
        raise ValueError(f"d={d} not divisible by block_size={block_size}")
    fn = _bcd_gram_fn(int(num_epochs), int(block_size))
    _count_factor_rule(num_epochs)
    return fn(gc, cc, jnp.asarray(reg, dtype=gc.dtype))


# ------------------------------------------------------------------- 2-D BCD


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)


def prepare_block_sharded(
    a, mesh: Optional[Mesh] = None, fine_rows: bool = False
) -> jnp.ndarray:
    """Place a matrix for the 2-D (data, model) solver path.

    ``fine_rows=False``: rows sharded over the row axes, columns sharded
    over ``model`` (the layout for A — each device holds an
    (n/D, d/M) tile, so A is never column-replicated).
    ``fine_rows=True``: rows sharded over (row axes, model) jointly, columns
    replicated (the layout for Y and the carried predictions — M× finer row
    shards than the 1-D path, relieving the per-device residual HBM
    pressure the 1-D solver pays).
    """
    mesh = mesh or get_mesh()
    if not isinstance(a, jax.Array):
        a = np.asarray(a)  # host blocks upload to their own devices
    multiple = row_shard_count(mesh) * model_axis_size(mesh)
    a = _pad_rows(a, multiple)
    if fine_rows:
        spec = P(row_axes(mesh) + (MODEL_AXIS,), *([None] * (a.ndim - 1)))
    else:
        spec = P(row_axes(mesh), MODEL_AXIS, *([None] * (a.ndim - 2)))
    return jax.device_put(a, NamedSharding(mesh, spec))


def block_coordinate_descent_2d(
    a: jnp.ndarray,
    y: jnp.ndarray,
    reg: float,
    num_epochs: int,
    block_size: int,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Gauss-Seidel feature-block coordinate descent on a 2-D
    (data, model) mesh — same math as :func:`block_coordinate_descent`
    (reference: mlmatrix BlockCoordinateDescent via
    nodes/learning/BlockLinearMapper.scala:234-240, feature-block layout
    per nodes/util/VectorSplitter.scala:10-37), different sharding:

    - A is (row, model)-tiled: each device stores an (n/D, d/M) tile, so
      the feature matrix is never column-replicated (the reference keeps
      each feature block as its own RDD; here each model group owns a
      contiguous d/M slice of columns = its blocks).
    - W comes back sharded d-wise over ``model`` (never replicated).
    - The carried predictions/residuals are (n/(D·M), k) per device — M×
      smaller than the 1-D path's per-device residual.
    - Every device computes on EVERY block: one ``all_to_all`` over the
      ``model`` axis per block-column re-shards the owner group's
      (n/D, b) block into (n/(D·M), b) row-refined tiles on all devices,
      so per-block Gram compute rides the full mesh, then one psum over
      (row axes, model) reduces it. The all_to_all moves n·b floats per
      block vs the n·b·b/(D·M) extra FLOPs it spreads — bandwidth-cheap
      for the reference's block sizes (b≥1024).

    Block update order is (local block, model group)-major — a fixed
    permutation of the reference's sequential order with the identical
    fixed point (AᵀA+λI)W = AᵀY. The loop is its own (one ``all_to_all``
    serves a local block's M updates); each is :func:`block_update`.

    ``a`` must be laid out by ``prepare_block_sharded(a)`` and ``y`` by
    ``prepare_block_sharded(y, fine_rows=True)``. d must divide into
    M·block_size. Returns (d, k) sharded P(model, None).
    """
    mesh = mesh or get_mesh()
    n, d = a.shape
    m = model_axis_size(mesh)
    if m < 2:
        return block_coordinate_descent(a, y, reg, num_epochs, block_size, mesh)
    if d % (m * block_size) != 0:
        raise ValueError(
            f"d={d} not divisible by model_axis·block_size={m}·{block_size}"
        )
    fn = _bcd2d_fn(mesh, num_epochs, block_size)
    return fn(a, y, jnp.asarray(reg, dtype=a.dtype))


@_mode_cached()
def _bcd2d_fn(mesh: Mesh, num_epochs: int, block_size: int):
    raxes = row_axes(mesh)
    all_axes = raxes + (MODEL_AXIS,)
    m = mesh.shape[MODEL_AXIS]

    def per_device(a_local, y_fine, reg):
        n_loc, d_loc = a_local.shape
        k = y_fine.shape[1]
        num_local_blocks = d_loc // block_size
        j = lax.axis_index(MODEL_AXIS)
        w0 = jnp.zeros((d_loc, k), dtype=a_local.dtype)
        p0 = jnp.zeros_like(y_fine)

        def outer_step(carry, lb):
            w_local, p = carry
            start = lb * block_size
            a_lb = lax.dynamic_slice(a_local, (0, start), (n_loc, block_size))
            # Row-refine the M blocks at local index lb across the model
            # axis: refined[:, j'*b:(j'+1)*b] is this device's fine row
            # chunk of model group j's block.
            refined = lax.all_to_all(
                a_lb, MODEL_AXIS, split_axis=0, concat_axis=1, tiled=True
            )
            for jp in range(m):  # static unroll; model axes are small
                a_j = lax.dynamic_slice(
                    refined, (0, jp * block_size), (n_loc // m, block_size)
                )
                w_b_own = lax.dynamic_slice(w_local, (start, 0), (block_size, k))
                # Broadcast the owner group's current block weights.
                w_b_old = lax.psum(
                    jnp.where(j == jp, w_b_own, jnp.zeros_like(w_b_own)),
                    MODEL_AXIS,
                )
                w_b_new, p = block_update(a_j, y_fine, p, w_b_old, reg, all_axes)
                w_local = jnp.where(
                    j == jp,
                    lax.dynamic_update_slice(w_local, w_b_new, (start, 0)),
                    w_local,
                )
            return (w_local, p), None

        blocks = jnp.tile(jnp.arange(num_local_blocks), num_epochs)
        (w_local, _), _ = lax.scan(outer_step, (w0, p0), blocks)
        return w_local

    return jax.jit(
        shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(raxes, MODEL_AXIS), P(raxes + (MODEL_AXIS,), None), P()),
            out_specs=P(MODEL_AXIS, None),
        )
    )


@_mode_cached()
def _apply_2d_fn(mesh: Mesh):
    raxes = row_axes(mesh)

    def f(x_local, w_local):
        return lax.psum(mm(x_local, w_local), MODEL_AXIS)

    return jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=(P(raxes, MODEL_AXIS), P(MODEL_AXIS, None)),
            out_specs=P(raxes, None),
        )
    )


def block_sharded_apply(
    x: jnp.ndarray, w: jnp.ndarray, mesh: Optional[Mesh] = None
) -> jnp.ndarray:
    """Predictions for a column-sharded X against a model-sharded W:
    the per-group partial products Σ_j X_j·W_j summed with one psum over
    ``model`` (the reference's sum-of-per-block-predictions,
    BlockLinearMapper.scala:50-73, as a collective). X via
    ``prepare_block_sharded``; result is row-sharded, fully formed."""
    mesh = mesh or get_mesh()
    if model_axis_size(mesh) < 2:
        return mm(x, w)
    return _apply_2d_fn(mesh)(x, w)
