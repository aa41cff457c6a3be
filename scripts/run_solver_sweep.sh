#!/usr/bin/env bash
# Solver comparison sweep + cost-constant fit — the ONE canonical
# invocation. On the chip it goes through the chip tool, one stage per
# process (one process per chip):
#   - dense rows measured on the current accelerator;
#   - sparse rows + the constant fit on host CPU (the sparse solver IS
#     host scipy; fitting on CPU also keeps --fitted-on provenance
#     honest), merging the fresh dense rows in;
#   - writes scripts/solver-comparisons-tpu.csv and the in-package
#     keystone_tpu/ops/learning/tpu_cost_constants.json.
# Run from the repo root. One TPU process at a time.
set -u
cd "$(dirname "$0")/.."

python scripts/solver_comparison.py \
    --out scripts/solver-comparisons-tpu-dense.csv --preset full --grid dense \
    2>&1 | tee /tmp/sweep_tpu.log | tail -5 || echo "sweep failed (see /tmp/sweep_tpu.log)"
JAX_PLATFORMS=cpu python scripts/solver_comparison.py \
    --out scripts/solver-comparisons-tpu.csv --preset full --grid sparse \
    --merge-csv scripts/solver-comparisons-tpu-dense.csv --fit-constants \
    --constants-out keystone_tpu/ops/learning/tpu_cost_constants.json \
    --fitted-on "TPU v5 lite (dense rows) + host scipy (sparse rows)" \
    2>&1 | tee /tmp/sweep_cpu.log | tail -5 || echo "sparse/fit failed (see /tmp/sweep_cpu.log)"
