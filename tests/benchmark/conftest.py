"""Shared by the benchmark's tests: the real tree and the tiny one.

The tests reach the harness through function arguments (`Bench(search=...)`,
`run_cell(require_platform="cpu")`) and the tiny configuration files under
`tests/benchmark/tiny/`, never through a flag or variable of the command.
"""

import os

import pytest

from bench_paths import ROOT, TINY


@pytest.fixture(scope="session")
def bench():
    from benchmark.harness.manifest import Bench

    return Bench(ROOT)


@pytest.fixture(scope="session")
def tiny_bench():
    from benchmark.harness.manifest import Bench

    return Bench(
        ROOT,
        manifest_path=os.path.join(TINY, "BENCHMARK.json"),
        search=[TINY, os.path.join(ROOT, "benchmark")],
    )
