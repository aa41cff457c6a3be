"""Failure-path coverage (round-2 verdict item 8): OOM adaptation in the
bench helpers, masked extractors at degenerate sizes, solver validation
on misconfigured meshes/shapes, and where the compile cache lives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.parallel import linalg
from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh, use_mesh


# ------------------------------------------------------------ bench helpers


def test_imagenet_bench_ladder_reduces_on_oom(monkeypatch):
    """The imagenet_fv bench walks its reduction ladder on
    RESOURCE_EXHAUSTED and marks the result."""
    import bench

    calls = []

    def fake_at(n_img, size, num_classes, small):
        calls.append((n_img, size, num_classes))
        if size > 64:
            raise RuntimeError("RESOURCE_EXHAUSTED: fake OOM")
        return {"num_images": n_img, "image_size": size}

    monkeypatch.setattr(bench, "_imagenet_fv_at", fake_at)
    out = bench._bench_imagenet_fv(small=False)
    assert out["extrapolated"] is True
    assert out["reduced_from"]["image_size"] == 256
    assert out["reduced_from"]["num_classes"] == 1000
    assert out["num_classes"] == 16
    assert "RESOURCE_EXHAUSTED" in out["reduction_reason"]
    assert len(calls) == 5  # walked every >64 rung before succeeding


def test_imagenet_bench_ladder_reraises_non_oom(monkeypatch):
    import bench

    def fake_at(n_img, size, num_classes, small):
        raise ValueError("not an OOM")

    monkeypatch.setattr(bench, "_imagenet_fv_at", fake_at)
    with pytest.raises(ValueError):
        bench._bench_imagenet_fv(small=False)


def test_bench_workload_registry_consistent():
    import bench

    assert set(bench.WORKLOADS) == set(bench._workload_registry())


# -------------------------------------------------- masked degenerate sizes


def test_masked_sift_image_smaller_than_grid():
    """A bucket member far smaller than the padded shape must yield zero
    valid descriptors at scales its native size can't host, and the valid
    count must match its native-size run."""
    from keystone_tpu.ops.images.sift import SIFTExtractor

    ext = SIFTExtractor(scale_step=1)
    rng = np.random.default_rng(0)
    big, small = 96, 24
    img_small = rng.random((small, small)).astype(np.float32)
    padded = np.pad(img_small, ((0, big - small), (0, big - small)), mode="edge")
    batch = jnp.asarray(padded[None])
    dims = jnp.asarray([[small, small]], jnp.int32)
    desc, valid = ext.apply_arrays_masked(batch, dims)
    native = np.asarray(ext.apply_arrays(jnp.asarray(img_small[None])))
    assert int(valid.sum()) == native.shape[1]
    got = np.asarray(desc)[0][np.asarray(valid)[0]]
    np.testing.assert_allclose(got, native[0], atol=1.0)
    # 99.5%-within-1, the reference's own tolerance (VLFeatSuite.scala:47-52)
    close = np.abs(got - native[0]) <= 1.0
    assert close.mean() > 0.995


def test_masked_lcs_degenerate_size():
    from keystone_tpu.ops.images.lcs import LCSExtractor

    ext = LCSExtractor(stride=4, stride_start=16, sub_patch_size=6)
    rng = np.random.default_rng(1)
    small = 40  # barely above the 2*border minimum
    img = rng.random((small, small, 3)).astype(np.float32)
    padded = np.pad(img, ((0, 24), (0, 24), (0, 0)), mode="edge")
    desc, valid = ext.apply_arrays_masked(
        jnp.asarray(padded[None]), jnp.asarray([[small, small]], jnp.int32)
    )
    native = np.asarray(ext.apply_arrays(jnp.asarray(img[None])))
    assert int(valid.sum()) == native.shape[1]


def test_bucketize_rejects_nothing_but_groups_consistently():
    from keystone_tpu.data.buckets import bucketize_images

    rng = np.random.default_rng(2)
    recs = [
        {"image": rng.random((17, 23, 3)).astype(np.float32), "label": 0},
        {"image": rng.random((17, 23, 3)).astype(np.float32), "label": 1},
        {"image": rng.random((64, 64, 3)).astype(np.float32), "label": 2},
    ]
    buckets = bucketize_images(recs, granularity=32)
    assert sorted(b.bucket_shape for b in buckets) == [(32, 32), (64, 64)]
    assert sum(len(b) for b in buckets) == 3


# ------------------------------------------------------- solver validation


def test_bcd_rejects_non_dividing_block():
    mesh = make_mesh(devices=jax.devices()[:8])
    rng = np.random.default_rng(3)
    a = rng.normal(size=(16, 10)).astype(np.float32)
    y = rng.normal(size=(16, 2)).astype(np.float32)
    with use_mesh(mesh):
        with pytest.raises(ValueError, match="not divisible"):
            linalg.block_coordinate_descent(
                linalg.prepare_row_sharded(a, mesh),
                linalg.prepare_row_sharded(y, mesh),
                reg=0.1, num_epochs=1, block_size=3, mesh=mesh,
            )


def test_bcd2d_rejects_non_dividing_model_blocks():
    mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS), devices=jax.devices()[:8])
    rng = np.random.default_rng(4)
    a = rng.normal(size=(16, 12)).astype(np.float32)
    y = rng.normal(size=(16, 2)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        linalg.block_coordinate_descent_2d(
            linalg.prepare_block_sharded(a, mesh),
            linalg.prepare_block_sharded(y, mesh, fine_rows=True),
            reg=0.1, num_epochs=1, block_size=8, mesh=mesh,
        )


def test_conv_block_estimator_rejects_bad_block_size():
    from keystone_tpu.ops.images import (
        Convolver,
        FusedConvFeaturizer,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.ops.learning.conv_block import (
        ConvBlockLeastSquaresEstimator,
    )

    rng = np.random.default_rng(5)
    fz = FusedConvFeaturizer(
        Convolver(rng.normal(size=(8, 108)).astype(np.float32), 3),
        SymmetricRectifier(alpha=0.25),
        Pooler(13, 14, None, "sum"),
    )
    est = ConvBlockLeastSquaresEstimator(fz, block_size=12)  # 12 % 8 != 0
    mesh = make_mesh(devices=jax.devices()[:8])
    with use_mesh(mesh):
        with pytest.raises(ValueError, match="not divisible"):
            est.fit(
                ArrayDataset(rng.random((16, 32, 32, 3)).astype(np.float32)),
                ArrayDataset(rng.normal(size=(16, 2)).astype(np.float32)),
            )


def test_streaming_threshold_env_override(monkeypatch):
    from keystone_tpu.ops.learning import block as block_mod

    monkeypatch.setenv("KEYSTONE_STREAM_BYTES", "123")
    assert block_mod._host_streaming_threshold_bytes() == 123


def test_solver_precision_env_knob(monkeypatch):
    """KEYSTONE_SOLVER_PRECISION is read per call; invalid values raise
    (a typo'd 'fast mode' must not silently run 6-pass)."""
    import jax.numpy as jnp

    from keystone_tpu.parallel import linalg

    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "default")
    assert linalg.precision() == jax.lax.Precision.DEFAULT
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "highest")
    assert linalg.precision() == jax.lax.Precision.HIGHEST
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "bf16")
    with pytest.raises(ValueError, match="KEYSTONE_SOLVER_PRECISION"):
        linalg.solver_mode()
    # Unset → the shipped default: refine mode for the exact solver,
    # HIGHEST for every other solver-grade matmul.
    monkeypatch.delenv("KEYSTONE_SOLVER_PRECISION", raising=False)
    assert linalg.solver_mode() == "refine"
    assert linalg.precision() == jax.lax.Precision.HIGHEST


def test_solver_precision_flips_mid_process(monkeypatch):
    """r4 verdict item 8 'Done' criterion: one lifetime for the precision
    knob. Flipping KEYSTONE_SOLVER_PRECISION mid-process must flow into
    (a) ``mm`` itself, (b) the lru-cached compiled-fn factories (mode in
    the cache key — distinct executables per mode, cache hits within a
    mode), and (c) ``mode_jit``-wrapped solver entry points (re-trace on
    flip). Verified structurally via the lowered HLO (numeric checks
    can't see precision on the CPU backend, where every matmul is fp32)."""
    import jax.numpy as jnp

    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import make_mesh

    a = jnp.ones((8, 4))
    b = jnp.ones((4, 4))

    # (a) mm reads the mode at trace time. Fresh jit instances per lower:
    # a SINGLE jax.jit object would replay its cached trace across the
    # flip — which is exactly why every jitted mm caller must go through
    # mode_jit (part c) rather than bare jax.jit.
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "highest")
    assert "HIGHEST" in jax.jit(lambda p, q: linalg.mm(p, q)).lower(a, b).as_text().upper()
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "default")
    assert "HIGHEST" not in jax.jit(lambda p, q: linalg.mm(p, q)).lower(a, b).as_text().upper()

    # (b) factory caches key on the mode: distinct per mode, hit within.
    mesh = make_mesh(devices=jax.devices()[:8])
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "highest")
    f_hi = linalg._gram_fn(mesh)
    assert "HIGHEST" in f_hi.lower(a).as_text().upper()
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "default")
    f_def = linalg._gram_fn(mesh)
    assert f_def is not f_hi
    assert "HIGHEST" not in f_def.lower(a).as_text().upper()
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "highest")
    assert linalg._gram_fn(mesh) is f_hi

    # (c) mode_jit re-traces on a flip (and caches within a mode).
    traces = []

    @linalg.mode_jit
    def probe(x):
        traces.append(linalg.solver_mode())
        return linalg.mm(x, x)

    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "highest")
    probe(b)
    probe(b)
    assert traces == ["highest"]
    monkeypatch.setenv("KEYSTONE_SOLVER_PRECISION", "default")
    probe(b)
    assert traces == ["highest", "default"]


def test_persistent_compilation_cache_resolution(tmp_path, monkeypatch):
    """Where the compile cache lives (utils/compilation_cache.py): with
    JAX_COMPILATION_CACHE_DIR set the launcher owns the placement and the
    program sets NO directory in code; the KEYSTONE knob's "off" disables
    and its path is honored otherwise; with neither, one fixed path
    inside the checkout — never the home directory."""
    import os

    import jax

    import keystone_tpu
    from keystone_tpu.utils import compilation_cache as cc

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)),
    )
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(keystone_tpu.__file__)))
    fixed = os.path.join(repo, ".keystone_cache", "xla-cache")
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("KEYSTONE_COMPILATION_CACHE", "off")
        assert cc.enable_persistent_cache() is None

        target = str(tmp_path / "xla-cache")
        monkeypatch.setenv("KEYSTONE_COMPILATION_CACHE", target)
        assert cc.enable_persistent_cache() == target
        assert os.path.isdir(target)
        assert jax.config.jax_compilation_cache_dir == target

        monkeypatch.delenv("KEYSTONE_COMPILATION_CACHE")
        assert cc.resolve_cache_dir() == fixed
        assert not fixed.startswith(os.path.expanduser("~/.cache"))

        # The launcher's variable wins over everything, and with it set
        # the only config the program touches is the two thresholds.
        launcher = str(tmp_path / "launcher-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", launcher)
        monkeypatch.setenv("KEYSTONE_COMPILATION_CACHE", target)
        del updates[:]
        assert cc.enable_persistent_cache() == launcher
        assert "jax_compilation_cache_dir" not in updates
        assert sorted(updates) == [
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        ]
        assert not os.path.exists(launcher)  # jax creates it, not the program
    finally:  # global jax config: restore so later tests don't write a cache
        real_update("jax_compilation_cache_dir", saved[0])
        real_update("jax_persistent_cache_min_entry_size_bytes", saved[1])
        real_update("jax_persistent_cache_min_compile_time_secs", saved[2])


def test_dryrun_perturbation_makes_legs_fail():
    """r4 verdict item 4 'Done' criterion: a seeded numeric perturbation
    must make dryrun legs report non-ok — proving the MULTICHIP artifact
    certifies numeric correctness, not just that sharded code executes."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["KEYSTONE_DRYRUN_PERTURB"] = "1000.0"
    proc = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(2)"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode != 0, proc.stdout[-1500:]
    out = proc.stdout + proc.stderr
    assert "DRYRUN_LEGS" in out, out[-1500:]
    assert out.count("FAIL") >= 5, out[-1500:]  # most legs carry invariants
    assert "rel_err" in out, out[-1500:]


def test_bench_workload_filter_validation(monkeypatch):
    """KEYSTONE_BENCH_WORKLOADS restricts the run; unknown names fail
    loudly (a typo'd leg name must not silently run everything)."""
    import bench

    monkeypatch.setenv("KEYSTONE_BENCH_WORKLOADS", "gram_mfu, ingest")
    assert bench._selected_workloads() == ["gram_mfu", "ingest"]
    monkeypatch.setenv("KEYSTONE_BENCH_WORKLOADS", "timit_exact,nope")
    with pytest.raises(SystemExit, match="nope"):
        bench._selected_workloads()
    # set-but-empty ("", " ", ",") must not silently select ZERO legs (a
    # zero-leg bench run exiting 0 would look like a green measurement) —
    # and an accidentally-empty wrapper var must not run the FULL bench
    for empty in ("", " , ", " "):
        monkeypatch.setenv("KEYSTONE_BENCH_WORKLOADS", empty)
        with pytest.raises(SystemExit, match="no workloads"):
            bench._selected_workloads()
    monkeypatch.delenv("KEYSTONE_BENCH_WORKLOADS")
    assert bench._selected_workloads() == list(bench.WORKLOADS)

