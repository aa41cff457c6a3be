#!/usr/bin/env python
"""Solver comparison sweep + cost-constant refit.

Parity with the reference's benchmarking workflow: the reference shipped
measured solver comparisons (reference: scripts/solver-comparisons-final.csv
— Amazon/TIMIT shapes on 16 r3.4xlarge nodes) and an R script fitting the
cost-model constants from them (reference: scripts/constantEstimator.R).
This script regenerates both on the current hardware: it times each
least-squares solver over a shape grid, writes the comparison CSV, then
least-squares-fits the (cpu, mem, network) weights of the cost model to
the measurements so `LeastSquaresEstimator`'s auto-selection reflects the
machine it actually runs on.

Usage:
    python scripts/solver_comparison.py --out solver-comparisons.csv \
        [--fit-constants] [--preset quick|full]

Run on TPU for real constants; `--preset quick` is CPU-safe for CI.
"""

from __future__ import annotations

import argparse
import csv
import os
import sys
import time

import numpy as np

# Runnable as `python scripts/solver_comparison.py` from anywhere: put the
# repo root (the script's parent's parent) ahead of scripts/ on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


QUICK_GRID = [
    # (n, d, k, sparsity)
    (20_000, 256, 8, 1.0),
    (20_000, 512, 8, 1.0),
    (40_000, 256, 8, 1.0),
    (20_000, 1024, 8, 0.01),
]

FULL_GRID = [
    # TIMIT-like dense column (reference csv rows: n=2.2M, k=138)
    (500_000, 1024, 138, 1.0),
    (500_000, 2048, 138, 1.0),
    (1_000_000, 1024, 138, 1.0),
    # Amazon-like sparse shapes (reference csv: n=65M, k=2, sparsity=0.005;
    # d=16384 is the reference's widest measured sparse column, csv:12-13)
    (1_000_000, 1024, 2, 0.005),
    (1_000_000, 4096, 2, 0.005),
    (1_000_000, 16384, 2, 0.005),
]

# Dense-materialization ceiling: sparse problems above this many logical
# elements only run the sparse solver (the dense ones would need the
# densified matrix in memory).
DENSE_ELEMS_LIMIT = 2e8


def make_problem(n, d, k, sparsity, seed=0):
    """Returns (x, y) — x is a scipy CSR matrix for sparse shapes (never
    densified at generation time), a dense float32 array otherwise."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    if sparsity < 1.0:
        import scipy.sparse as sp

        # Fixed nnz per row with replacement — O(nnz) construction.
        # (sp.random's no-replacement sampling takes tens of minutes at
        # 82M nnz; duplicate column hits within a row are harmless for
        # solver timing — CSR matvec sums them.)
        per_row = max(1, round(d * sparsity))
        indices = rng.integers(0, d, size=n * per_row, dtype=np.int32)
        indptr = np.arange(0, n * per_row + 1, per_row, dtype=np.int64)
        data = rng.random(n * per_row, dtype=np.float32)
        x = sp.csr_matrix((data, indices, indptr), shape=(n, d))
        y = np.asarray(x @ w_true, dtype=np.float32)
        y += 0.1 * rng.normal(size=(n, k)).astype(np.float32)
        return x, y
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = x @ w_true + 0.1 * rng.normal(size=(n, k)).astype(np.float32)
    return x, y


def time_solver(name, fit, x, y):
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    from keystone_tpu.data.dataset import ArrayDataset, ObjectDataset

    is_sparse = sp.issparse(x)
    if name == "sparse_lbfgs":
        # Host-resident CSR is the sparse solver's native form; its
        # host-side work is part of what the cost model must rank.
        xd = ObjectDataset([x if is_sparse else sp.csr_matrix(x)])
        yd = ArrayDataset(y)
    else:
        # Pre-place dense problems on device BEFORE the clock: the
        # host→device upload is identical for every dense solver on a
        # given problem, so it carries no signal for solver selection.
        xa = jnp.asarray(np.asarray(x.todense()) if is_sparse else x)
        ya = jnp.asarray(y)
        float(jnp.sum(xa[..., -1]) + jnp.sum(ya[..., -1]))  # force placement
        xd = ArrayDataset(xa)
        yd = ArrayDataset(ya)
    # Warm-up fit eats XLA compilation, then the timed fit measures
    # steady-state execution. The cost model is linear in (flops, elems,
    # moved); a ~30 s compile-time constant offset at these (deliberately
    # small) measurement shapes would swamp the signal and extrapolate
    # nonsense to the real problem sizes auto-selection serves. The
    # sparse solver is host-resident scipy — nothing to compile, so a
    # warm-up would only double a minutes-long measurement.
    def run():
        model = fit(xd, yd)
        jax.block_until_ready(model.weights)
        return model

    if name != "sparse_lbfgs":
        run()
    start = time.perf_counter()
    model = run()
    seconds = time.perf_counter() - start
    # Cap the densified eval slice by ELEMENTS, not rows: 65536 rows at
    # d=16384 is a 4.3 GB dense block — enough to OOM the host mid-sweep.
    head = min(x.shape[0], 65536, max(1024, int(1e8 / x.shape[1])))
    xh = np.asarray(x[:head].todense()) if is_sparse else x[:head]
    pred = np.asarray(model.apply_arrays(xh))
    err = float(np.mean((pred - y[:head]) ** 2))
    return seconds * 1000.0, err


def solvers(reg=1e-3, sparsity=1.0, n=0, d=0):
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.learning.lbfgs import (
        DenseLBFGSEstimator,
        SparseLBFGSEstimator,
    )
    from keystone_tpu.ops.learning.linear import LinearMapEstimator

    out = {}
    if sparsity >= 1.0 or n * d <= DENSE_ELEMS_LIMIT:
        out.update(
            {
                "exact": lambda xd, yd: LinearMapEstimator(reg).fit(xd, yd),
                "block": lambda xd, yd: BlockLeastSquaresEstimator(
                    1024, num_iter=3, reg=reg
                ).fit(xd, yd),
                "lbfgs": lambda xd, yd: DenseLBFGSEstimator(
                    num_iterations=20, reg=reg
                ).fit(xd, yd),
            }
        )
    if sparsity < 1.0:
        out["sparse_lbfgs"] = lambda xd, yd: SparseLBFGSEstimator(
            num_iterations=20, reg=reg
        ).fit(xd, yd)
    return out


def cost_features(name, n, d, k, sparsity, num_machines):
    """Per-solver (flops, elements scanned, elements moved) — the EXACT
    expressions the CostModel classes use
    (keystone_tpu/ops/learning/least_squares.py:_ExactCost/_BlockSolveCost/
    _DenseLBFGSCost; keep in sync), in the raw units CostWeights carries
    (ms per flop / per fp32 element). Fitting ms ≈ cpu·flops + mem·elems
    + net·moved is the linearization of cost()'s max(cpu·flops,
    mem·elems) + net·moved — exact whenever one term dominates, which it
    does at the measured shapes."""
    m = num_machines
    log_m = np.log2(max(2, m))
    if name == "exact":
        flops = n * d * (d + k) / m + d * d * d
        elems = n * d / m + d * d
        moved = d * (d + k)
    elif name == "block":
        b = 1024
        iters = 3 * max(d // b, 1)
        flops = iters * (n * b * (b + k)) / m
        elems = iters * n * b / m
        moved = iters * (b * b + b * k) * log_m
    else:  # lbfgs / sparse_lbfgs (cost: _DenseLBFGSCost with sparsity)
        iters = 20
        sp_ = max(sparsity, 1e-12)
        flops = iters * n * d * k * sp_ / m
        elems = iters * n * d * sp_ / m
        moved = iters * d * k * log_m
    return flops, elems, moved


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="solver-comparisons.csv")
    parser.add_argument("--preset", choices=("quick", "full"), default="quick")
    parser.add_argument("--fit-constants", action="store_true")
    parser.add_argument(
        "--fit-only", action="store_true",
        help="skip measurement entirely: load --merge-csv rows and refit "
        "(refit committed on-chip rows with updated bounds/model without "
        "touching the chip)",
    )
    parser.add_argument(
        "--constants-out", default=None,
        help="where to write fitted constants (default: the in-package "
        "tpu_cost_constants.json, the commit-and-ship workflow)",
    )
    parser.add_argument("--reg", type=float, default=1e-3)
    parser.add_argument(
        "--grid", choices=("all", "dense", "sparse"), default="all",
        help="measure only the dense or sparse subset of the preset grid "
        "(the sparse solver is host-side, so its rows can be re-measured "
        "on CPU without re-claiming the TPU for the dense rows)",
    )
    parser.add_argument(
        "--merge-csv", default=None,
        help="CSV of previously measured rows to merge in before writing/"
        "fitting; freshly measured rows win on (solver, n, d, k, sparsity)",
    )
    parser.add_argument(
        "--fitted-on", default=None,
        help="override the fitted_on provenance string (e.g. when dense "
        "rows came from a TPU run and sparse rows from the host)",
    )
    args = parser.parse_args(argv)

    import jax

    grid = QUICK_GRID if args.preset == "quick" else FULL_GRID
    if args.grid == "dense":
        grid = [g for g in grid if g[3] >= 1.0]
    elif args.grid == "sparse":
        grid = [g for g in grid if g[3] < 1.0]
    if args.fit_only:
        grid = []
        if not args.merge_csv:
            parser.error("--fit-only needs --merge-csv (the rows to refit)")
    num_machines = len(jax.devices())
    rows = []
    for n, d, k, sparsity in grid:
        x, y = make_problem(n, d, k, sparsity)
        for name, fit in solvers(args.reg, sparsity=sparsity, n=n, d=d).items():
            ms, err = time_solver(name, fit, x, y)
            rows.append(
                {
                    "solver": name, "n": n, "d": d, "k": k,
                    "sparsity": sparsity, "ms": round(ms, 2),
                    "train_mse": round(err, 6),
                    # Per-row so merged rows from another device keep the
                    # device count they were measured with (the cost fit
                    # divides flops/elems by it).
                    "machines": num_machines,
                }
            )
            print(rows[-1], flush=True)

    if args.merge_csv:
        fresh = {(r["solver"], r["n"], r["d"], r["k"], r["sparsity"]) for r in rows}
        with open(args.merge_csv) as f:
            for r in csv.DictReader(f):
                r = {
                    "solver": r["solver"], "n": int(r["n"]), "d": int(r["d"]),
                    "k": int(r["k"]), "sparsity": float(r["sparsity"]),
                    "ms": float(r["ms"]), "train_mse": float(r["train_mse"]),
                    "machines": int(r.get("machines") or num_machines),
                }
                if (r["solver"], r["n"], r["d"], r["k"], r["sparsity"]) not in fresh:
                    rows.append(r)

    with open(args.out, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
        writer.writeheader()
        writer.writerows(rows)
    print(f"wrote {args.out} ({len(rows)} measurements)")

    if args.fit_constants:
        # Bounded LS fit of ms ≈ c₀ + cpu·flops + mem·elems + net·moved in
        # the raw units cost() consumes (the reference's
        # constantEstimator.R equivalent), per DOMAIN:
        #
        # - Dense rows run on the chip. Lower-bounding each weight at its
        #   first-principles value (a chip cannot beat its own peak —
        #   r3's unbounded fit drove cpu to 2e16 flop/s) and adding a
        #   per-solve intercept c₀ (the attachment's dispatch round trip,
        #   measured ~66 ms, which the unbounded fit was smearing into
        #   the per-flop rate) yields physical constants with ≲20%
        #   per-row residuals. c₀ is reported but NOT shipped in
        #   CostWeights: every solver here is one fused computation, so
        #   the constant cancels in the argmin cost() exists to serve.
        # - Sparse rows run on the HOST (scipy route); one chip triple
        #   cannot describe them, so they get their own (cpu, c₀),
        #   recorded for provenance/ranking sanity only.
        from scipy.optimize import lsq_linear

        from keystone_tpu.ops.learning.cost import tpu_weights

        def features(r):
            return cost_features(
                r["solver"], r["n"], r["d"], r["k"], r["sparsity"],
                r.get("machines", num_machines),
            )

        dense_rows = [r for r in rows if r["sparsity"] >= 1.0]
        sparse_rows = [r for r in rows if r["sparsity"] < 1.0]
        if not dense_rows:
            print("no dense rows to fit; not persisting")
            return 1

        fp = tpu_weights()
        A = np.asarray([list(features(r)) + [1.0] for r in dense_rows])
        t = np.asarray([r["ms"] for r in dense_rows])
        fit = lsq_linear(
            A, t,
            bounds=([fp.cpu, fp.mem, fp.network, 0.0], [np.inf] * 4),
        )
        w = fit.x[:3]
        intercept = float(fit.x[3])
        pred = A @ fit.x
        rel = np.abs(pred - t) / np.maximum(t, 1e-9)
        per_row = {
            f"{r['solver']}_n{r['n']}_d{r['d']}": round(float(e), 3)
            for r, e in zip(dense_rows, rel)
        }
        residual = float(np.sqrt(np.mean((pred - t) ** 2)))

        host_sparse = None
        if sparse_rows:
            A2 = np.asarray([[features(r)[0], 1.0] for r in sparse_rows])
            t2 = np.asarray([r["ms"] for r in sparse_rows])
            fit2 = lsq_linear(A2, t2, bounds=([0.0, 0.0], [np.inf] * 2))
            pred2 = A2 @ fit2.x
            host_sparse = {
                "cpu": float(fit2.x[0]),
                "intercept_ms": float(fit2.x[1]),
                "per_row_rel_residual": {
                    f"{r['solver']}_n{r['n']}_d{r['d']}": round(
                        float(abs(p - m) / max(m, 1e-9)), 3
                    )
                    for r, p, m in zip(sparse_rows, pred2, t2)
                },
            }

        print(
            "fitted CostWeights(cpu=%.3e, mem=%.3e, network=%.3e)  "
            "# ms per flop / fp32 element; dispatch intercept %.1f ms; "
            "max dense per-row rel residual %.1f%%"
            % (w[0], w[1], w[2], intercept, 100 * rel.max())
        )
        # Committing the in-package file makes the measured constants the
        # default on TPU (cost.measured_tpu_weights). On CPU nothing is
        # persisted unless --constants-out names an explicit destination.
        import json

        from keystone_tpu.ops.learning.cost import MEASURED_CONSTANTS_PATH

        on_accelerator = jax.default_backend() != "cpu"
        out_path = args.constants_out or (
            MEASURED_CONSTANTS_PATH if on_accelerator else None
        )
        if out_path is not None:
            payload = {
                "cpu": float(w[0]),
                "mem": float(w[1]),
                "network": float(w[2]),
                "dispatch_intercept_ms": intercept,
                "fitted_on": args.fitted_on
                or getattr(jax.devices()[0], "device_kind", "unknown"),
                "preset": args.preset,
                "fit_residual_ms": float(residual),
                "per_row_rel_residual": per_row,
                "physical_lower_bounds": {
                    "cpu": fp.cpu, "mem": fp.mem, "network": fp.network,
                },
            }
            if host_sparse is not None:
                payload["host_sparse"] = host_sparse
            try:
                with open(out_path, "w") as f:
                    json.dump(payload, f, indent=1)
                print(f"wrote {out_path}")
            except OSError as e:
                print(f"could not write {out_path} ({e}); constants printed above")
        else:
            print("cpu backend and no --constants-out: constants printed only")
    return 0


if __name__ == "__main__":
    sys.exit(main())
