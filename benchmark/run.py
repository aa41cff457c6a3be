#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json `command`).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the TPU this process is started on and prints, as the
last line of its standard output, one JSON object with the cell's
metrics. It exits non-zero, with no result line, when JAX finds no TPU or
fewer chips than the cell asks for.
"""

import time

T0 = time.time()  # set-up is counted from here: before any import

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # the checkout: `keystone_tpu` and `benchmark`

if __name__ == "__main__":
    from benchmark.harness.runner import main

    sys.exit(main(sys.argv[1:], t0=T0, root=ROOT))
