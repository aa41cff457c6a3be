#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children. Drives the main path once through the entry
points a user calls, at the published width of TIMIT (input 440,
d = 4 x 4096 = 16384 cosine features, 147 classes, block 4096 —
pipelines/timit.py, BASELINE.md) on seeded synthetic data:

  stream   timit.build_pipeline(...).fit() at a size whose features do not
           fit the devices (65,536 rows on one chip): the single chain,
           4096-row chunks folded into a 1 GiB Gram carry a chip
  shipped  timit.build_pipeline(...).fit() at a size that fits: the gather
           of branches, materialised features, in-core block coordinate
           descent
  serve    FittedPipeline.save, then `keystone-tpu serve` in-process with
           64 single-row requests on stdin
  kernel   the block-sparse Pallas kernel, compiled, against the lax path

It fails (exit != 0, no result line) when JAX finds no TPU. `--tiny` is
the CPU rehearsal: the same code path at small widths; its output says
`rehearsal` and is never a pass. Walls printed here are smoke walls
(compilation included), not benchmark numbers.

    python chip_smoke.py            # on the chip, through the chip tool
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 123
SERVE_REQUESTS = 64

#: Error bounds for the full-width run, fixed from the CPU float32 run of
#: the same seeds and shapes (these phases under JAX_PLATFORMS=cpu,
#: 2026-09-26): (error on the first 4096 training rows, held-out error)
#: was (0.0, 0.9586) for the shipped form and (0.0, 0.9103) streamed.
#: synthetic_timit's labels are the argmax of a 440-dim linear rule over
#: 147 classes, which a Gaussian-kernel fit of this size barely
#: generalises (held-out sits near the 0.993 of chance), so the training
#: rows carry the check that the solve worked and the held-out rows the
#: check that it did not blow up. The margin covers the chip's
#: single-pass bf16 featurizer matmul (CosineRandomFeatures.apply_arrays
#: runs at the MXU default).
ERROR_BOUNDS = {"shipped": (0.02, 0.975), "stream": (0.02, 0.93)}
#: Rehearsal bounds: 512 random features cannot separate 147 classes; the
#: rehearsal only checks the fits are better than chance.
TINY_ERROR_BOUNDS = {"shipped": (0.9, 0.99), "stream": (0.9, 0.99)}
TRAIN_CHECK_ROWS = 4096

#: ell_matmul, compiled kernel against the lax path. Both contract at
#: Precision.HIGHEST (the kernel passes `precision` to Mosaic's fp32
#: contraction), so what separates them is float32 summation order: the
#: kernel adds one ELL slot at a time, the lax einsum reduces slots and
#: tile rows together. 1e-5 of the largest output is the bound the CPU
#: parity gate already holds interpret-vs-lax to.
KERNEL_REL_TOL = 1e-5


def _sizes(tiny: bool) -> dict:
    if tiny:
        return dict(
            num_cosines=2, cosine_features=256, n_shipped=2048, n_test=512,
            n_stream=16 * 4096, mm_docs=256, mm_d=4096, gram_docs=256,
            gram_d=512, bounds=TINY_ERROR_BOUNDS,
        )
    return dict(
        num_cosines=4, cosine_features=4096, n_shipped=2 * 16384,
        n_test=8192, n_stream=16 * 4096, mm_docs=2048, mm_d=131072,
        gram_docs=2048, gram_d=4096, bounds=ERROR_BOUNDS,
    )


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _block_mapper(fitted):
    """The fitted BlockLinearMapper inside a FittedPipeline graph (fusion
    may have folded it into a fused chain's members)."""
    from keystone_tpu.ops.learning.block import BlockLinearMapper

    found = [
        m
        for op in fitted.graph.operators.values()
        for m in getattr(op, "members", (op,))
        if isinstance(m, BlockLinearMapper)
    ]
    assert len(found) == 1, f"expected one BlockLinearMapper, found {len(found)}"
    return found[0]


def _check_fit(fitted, train, test, bounds, d: int) -> dict:
    """No silent recovery, finite weights of the expected shape, and
    training and held-out errors under their bounds."""
    import numpy as np

    from keystone_tpu import reliability
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.data.loaders.timit import NUM_CLASSES
    from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator

    mapper = _block_mapper(fitted)
    degradation = getattr(mapper, "degradation", None)
    assert degradation is None, f"fit degraded: {degradation}"
    events = reliability.get_recovery_log().summary()["events"]
    assert not events, f"recovery log not empty after fit: {events}"
    w = np.asarray(mapper.weights)
    assert w.shape == (d, NUM_CLASSES), w.shape
    assert np.isfinite(w).all(), "non-finite weights"

    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    head = slice(0, TRAIN_CHECK_ROWS)
    sets = {
        "train_error": (
            ArrayDataset(np.asarray(train.data.data)[head]),
            ArrayDataset(np.asarray(train.labels.data)[head]),
        ),
        "heldout_error": (test.data, test.labels),
    }
    errors = {}
    for (name, (x, y)), bound in zip(sets.items(), bounds):
        error = evaluator.evaluate(fitted.apply_batch(x), y).total_error
        assert error <= bound, f"{name} {error:.4f} over bound {bound}"
        errors[name] = round(float(error), 4)
    return errors


def phase_stream(cfg, sizes, test) -> dict:
    """A trainer whose features do not fit the devices: the shipped entry
    point then yields the single chain, and `Pipeline.fit` streams it."""
    from keystone_tpu.parallel.mesh import (
        get_mesh,
        local_memory_stats,
        row_shard_count,
    )
    from keystone_tpu.pipelines import timit
    from keystone_tpu.workflow.streaming import last_stream_report, stream_chunk_rows

    d = cfg.num_cosines * cfg.num_cosine_features
    rehearsal = timit.device_memory_limit_bytes() is None
    if rehearsal:
        # A backend that reports no memory (the CPU) is taken to hold any
        # size: the rehearsal tells the entry point of a device that holds
        # a megabyte, so that it takes the same path as on the chip.
        real_limit = timit.device_memory_limit_bytes
        timit.device_memory_limit_bytes = lambda: 1 << 20
    try:
        # The fewest rows, from n_stream up, whose features do not fit the
        # devices there are: n_stream on one chip, four times it on four.
        n = sizes["n_stream"]
        while timit.features_fit_in_core(n, d):
            n *= 2
        train = timit.synthetic_timit(n, seed=SEED + 2)
        pipeline = timit.build_pipeline(cfg, train)
    finally:
        if rehearsal:
            timit.device_memory_limit_bytes = real_limit
    fitted = pipeline.fit()
    report = last_stream_report()
    assert report is not None, "the single-chain form did not stream"
    shards = row_shard_count(get_mesh())
    want_chunks = n // stream_chunk_rows()
    assert report.chunks == want_chunks, (report.chunks, want_chunks)
    assert report.compiles_first_chunk == 1, report.compiles_first_chunk
    assert report.compiles_steady_state == 0, report.compiles_steady_state
    assert report.shards == shards, (report.shards, shards)
    bounds = sizes["bounds"]["stream"]
    if n != sizes["n_stream"]:
        # The bound on the training rows was fixed at n_stream = 4 d, where
        # the fit interpolates them; with more rows than that it does not.
        bounds = (1.0, bounds[1])
    errors = _check_fit(fitted, train, test, bounds, d)

    facts = {
        "rows": n,
        "chunks": report.chunks,
        "shards": report.shards,
        "carry_bytes_per_device": report.state_bytes_per_device,
        "compiles_first_chunk": report.compiles_first_chunk,
        "compiles_steady_state": report.compiles_steady_state,
        **errors,
    }
    peaks = [s["peak_bytes_in_use"] for s in local_memory_stats()]
    if peaks:  # CPU backends report no memory statistics
        facts["peak_bytes_in_use"] = peaks
        _say(f"per-device peak_bytes_in_use after the streamed fit: {peaks}")
        if len(peaks) > 1:
            # Device 0 legitimately holds the fold's seed carry beside its
            # own block; anything beyond that was built there by mistake.
            excess = peaks[0] - max(peaks[1:])
            allowed = int(1.1 * report.state_bytes_per_device)
            assert excess <= allowed, (
                f"device 0 peaked {excess} bytes over the other devices; "
                f"the carry it seeds accounts for {allowed}"
            )
    return facts


def phase_shipped(cfg, sizes, test):
    """The shipped form: a gather of branches, which does not stream."""
    from keystone_tpu.pipelines import timit

    train = timit.synthetic_timit(sizes["n_shipped"], seed=SEED)
    fitted = timit.build_pipeline(cfg, train).fit()
    d = cfg.num_cosines * cfg.num_cosine_features
    errors = _check_fit(fitted, train, test, sizes["bounds"]["shipped"], d)
    return fitted, {"rows": sizes["n_shipped"], **errors}


def phase_serve(fitted, test, workdir: str) -> dict:
    """The real CLI, in-process: serve_from_args, the registry, warm-up
    buckets and the batcher all run."""
    import numpy as np

    from keystone_tpu import cli
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.utils.compilation_cache import compile_count

    path = os.path.join(workdir, "timit.fitted")
    fitted.save(path)
    rows = np.asarray(test.data.data)[:SERVE_REQUESTS]
    requests = "".join(
        json.dumps({"id": i, "x": row.tolist()}) + "\n" for i, row in enumerate(rows)
    )
    out = io.StringIO()
    stdin = sys.stdin
    sys.stdin = io.StringIO(requests)
    try:
        with contextlib.redirect_stdout(out):
            rc = cli.main([
                "serve", "--model", path, "--max-batch", "8",
                "--queue-depth", "256",
            ])
    finally:
        sys.stdin = stdin
    assert rc == 0, f"serve exited {rc}"
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    stats_lines = [ln for ln in lines if ln.startswith("SERVE_STATS:")]
    assert len(stats_lines) == 1, f"{len(stats_lines)} SERVE_STATS lines"
    stats = json.loads(stats_lines[0][len("SERVE_STATS:"):])
    answers = [json.loads(ln) for ln in lines if not ln.startswith("SERVE_STATS:")]
    errors = [a for a in answers if "error" in a]
    assert not errors, f"{len(errors)} error lines, first: {errors[0]}"
    assert len(answers) == SERVE_REQUESTS, len(answers)
    by_id = {a["id"]: a["y"] for a in answers}
    # The reference comes AFTER the stats line was read: an out-of-band
    # apply at a fresh shape would count against the compile counter.
    for i, row in enumerate(rows):
        want = np.asarray(fitted.apply_batch(ArrayDataset(row[None])).data)[0]
        assert np.array_equal(np.asarray(by_id[i]), want), (i, by_id[i], want)
    for key in ("sheds", "timeouts", "retries", "failures"):
        assert stats[key] == 0, f"{key}={stats[key]}"
    assert stats["served"] == SERVE_REQUESTS, stats["served"]
    assert stats["xla_compiles_since_warmup"] == 0, stats["xla_compiles_since_warmup"]
    assert compile_count() > 0, "the process compile counter is dead"
    return {
        "served": stats["served"],
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "batch_occupancy": stats["batch_occupancy"],
        "xla_compiles_since_warmup": stats["xla_compiles_since_warmup"],
    }


def _hashed_docs(rng, docs: int, d: int, terms: int):
    """A hashing-TF-shaped matrix: each document has `terms` term counts
    at hashed (uniform) feature ids."""
    import numpy as np

    a = np.zeros((docs, d), np.float32)
    cols = rng.integers(0, d, size=(docs, terms))
    np.add.at(a, (np.arange(docs)[:, None], cols), 1.0)
    return a


def phase_kernel(sizes, interpret: bool) -> dict:
    """ell_matmul at the default 8x128 feature tile, in the two forms the
    estimator fast path sends: A·W (8x128 tiles against a (d, k) operand)
    and the Gram totals AᵀA, AᵀY (the transposed 128x8 tiles against the
    dense rows)."""
    import numpy as np

    from keystone_tpu.data.loaders.timit import NUM_CLASSES
    from keystone_tpu.ops.pallas import blocksparse as bs
    from keystone_tpu.utils.sparse import BlockSparseMatrix

    rng = np.random.default_rng(SEED)

    def rel(got, want):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape, (got.shape, want.shape)
        assert np.isfinite(got).all()
        return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))

    a = _hashed_docs(rng, sizes["mm_docs"], sizes["mm_d"], terms=16)
    bsr = BlockSparseMatrix.from_dense(a, bs.DEFAULT_BLOCK_SHAPE)
    w = rng.normal(size=(sizes["mm_d"], NUM_CLASSES)).astype(np.float32)
    err_mm = rel(
        bs.bsr_matmul(bsr, w, impl="pallas", interpret=interpret),
        bs.bsr_matmul(bsr, w, impl="lax"),
    )
    assert err_mm <= KERNEL_REL_TOL, f"A·W kernel vs lax: {err_mm}"
    density_mm = bsr.density()

    a = _hashed_docs(rng, sizes["gram_docs"], sizes["gram_d"], terms=2)
    bsr = BlockSparseMatrix.from_dense(a, bs.DEFAULT_BLOCK_SHAPE)
    y = rng.normal(size=(sizes["gram_docs"], NUM_CLASSES)).astype(np.float32)
    got = bs.bsr_gram_totals(bsr, y, a_dense=a, impl="pallas", interpret=interpret)
    want = bs.bsr_gram_totals(bsr, y, a_dense=a, impl="lax")
    err_gram = max(rel(g, r) for g, r in zip(got, want))
    assert err_gram <= KERNEL_REL_TOL, f"Gram totals kernel vs lax: {err_gram}"
    if not interpret:
        assert bs.resolve_impl("auto") == "pallas"
    return {
        "compiled": not interpret,
        "matmul_rel_err": err_mm,
        "matmul_block_density": round(density_mm, 4),
        "gram_rel_err": err_gram,
        "gram_block_density": round(bsr.density(), 4),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--tiny", action="store_true",
        help="CPU rehearsal at small widths; never a pass",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _say(f"jax {jax.__version__} on {device}")
    if device["platform"] != "tpu" and not args.tiny:
        print(
            f"chip_smoke: no TPU (platform {device['platform']!r}); "
            "--tiny is the CPU rehearsal", file=sys.stderr,
        )
        return 3

    from keystone_tpu.pipelines import timit
    from keystone_tpu.utils.compilation_cache import (
        cache_hit_count,
        compile_count,
        enable_persistent_cache,
        install_compile_counter,
    )

    cache_dir = enable_persistent_cache()
    want_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".keystone_cache", "xla-cache"
    )
    assert cache_dir == want_dir, (cache_dir, want_dir)
    assert jax.config.jax_compilation_cache_dir == want_dir, (
        jax.config.jax_compilation_cache_dir
    )
    entries_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    _say(f"compile cache {cache_dir} ({entries_before} entries)")
    install_compile_counter()

    sizes = _sizes(args.tiny)
    cfg = timit.TimitConfig(
        num_cosines=sizes["num_cosines"],
        num_cosine_features=sizes["cosine_features"],
        seed=SEED,
    )
    test = timit.synthetic_timit(sizes["n_test"], seed=SEED + 1)

    summary = {
        "rehearsal": args.tiny,
        "jax": jax.__version__,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n_devices": device["count"],
        "d": cfg.num_cosines * cfg.num_cosine_features,
        "cache_dir": cache_dir,
        "smoke_wall_s": {},
        "compiles": {},
    }

    def run(name, fn, *fn_args):
        t0, c0, h0 = time.perf_counter(), compile_count(), cache_hit_count()
        out = fn(*fn_args)
        wall = time.perf_counter() - t0
        loaded = cache_hit_count() - h0
        summary["smoke_wall_s"][name] = round(wall, 2)
        summary["compiles"][name] = {
            "built": compile_count() - c0 - loaded, "loaded_from_cache": loaded,
        }
        _say(f"{name} ok in {wall:.1f}s (smoke wall) {summary['compiles'][name]}")
        return out

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        summary["stream"] = run("stream", phase_stream, cfg, sizes, test)
        fitted, summary["shipped"] = run("shipped", phase_shipped, cfg, sizes, test)
        summary["serve"] = run("serve", phase_serve, fitted, test, workdir)
        summary["kernel"] = run(
            "kernel", phase_kernel, sizes, device["platform"] != "tpu"
        )

    entries = len(os.listdir(cache_dir))
    loaded = cache_hit_count()
    assert entries > 0 and (entries > entries_before or loaded > 0), (
        f"no compile-cache entries written to {cache_dir}"
    )
    summary["cache_entries"] = entries
    summary["compiles"]["total"] = {
        "built": compile_count() - loaded, "loaded_from_cache": loaded,
    }
    print("CHIP_SMOKE:" + json.dumps(summary), flush=True)
    verdict = {"ok": False, "rehearsal": True} if args.tiny else {"ok": True}
    print(json.dumps({**verdict, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
