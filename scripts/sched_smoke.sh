#!/usr/bin/env bash
# Co-scheduler smoke (docs/SCHEDULING.md "The demo"): one
# `keystone-tpu explain --schedule` run drives the cosched demo —
# serving and refit folds co-resident on one mesh — and asserts the
# whole admission/preemption contract from its evidence JSON:
#
#   - serving p99 stays inside the SLO while background folds run in
#     the trace's idle gaps (≥2 rounds publish co-resident)
#   - the seeded mid-fold SLO pressure preempts EXACTLY ONE fold at a
#     chunk boundary; the round defers and the next round resumes from
#     the durable cursor (sched_preempt + sched_resume in the ledger)
#   - the resumed chain matches the serialize-everything baseline
#     daemon to ≤1e-6 (preempt→resume ≡ uninterrupted fold)
#   - ZERO dropped serving requests across both phases
#   - zero steady-state compiles after the settle round
#   - the co-scheduled wall beats the serial wall outright (<1.0) —
#     the harvested idle is real, not bookkeeping
#
# This is the CI face of tests/sched/ (unit + preemption correctness)
# and the `cosched` bench leg (same demo, diff-gated counts).
#
# Budget: <90 s on CPU (small shapes, one serving pipeline).
#
# Usage: scripts/sched_smoke.sh [out_dir]
set -euo pipefail

cd "$(dirname "$0")/.."
OUT="${1:-$(mktemp -d)}"
mkdir -p "$OUT"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

timeout -k 10 420 python -m keystone_tpu explain --schedule --json \
    --out "$OUT/sched.json" 2>&1 | tee "$OUT/sched.log"
rc=${PIPESTATUS[0]}
if [[ "$rc" -ne 0 ]]; then
    echo "SCHED SMOKE: FAIL (explain --schedule rc=$rc)" >&2
    exit 1
fi

python - "$OUT/sched.log" <<'EOF'
import json, sys

body = None
with open(sys.argv[1]) as f:
    for line in f:
        if line.startswith("SCHED_JSON:"):
            body = json.loads(line[len("SCHED_JSON:"):])
assert body is not None, "no SCHED_JSON line in smoke log"

fails = []
def check(cond, msg):
    (fails.append(msg) if not cond else None)

check(body["p99_within_slo"],
      f"p99 {body['p99_ms_worst']}ms breached SLO {body['slo_target_ms']}ms")
check(body["publishes"] >= 2,
      f"expected >=2 co-resident publishes, got {body['publishes']}")
check(body["preemptions"] == 1,
      f"expected exactly 1 seeded preemption, got {body['preemptions']}")
check(body["preempted_at_chunk"] is not None,
      "preemption did not land at a chunk boundary")
check("sched_preempt" in body["ledger_kinds"],
      f"sched_preempt missing from ledger kinds {body['ledger_kinds']}")
check("sched_resume" in body["ledger_kinds"],
      f"sched_resume missing from ledger kinds {body['ledger_kinds']}")
check(body["parity_ok"],
      f"resume parity {body['parity_max_abs_diff']:.3e} > 1e-6")
check(body["dropped"] == 0, f"{body['dropped']} serving requests dropped")
check(body["compiles_steady_state_post_settle"] == 0,
      f"{body['compiles_steady_state_post_settle']} steady-state compiles")
check(body["cosched_faster"],
      f"co-scheduled wall not faster: ratio "
      f"{body['cosched_vs_serial_ratio']}")

if fails:
    for m in fails:
        print(f"SCHED SMOKE: FAIL — {m}")
    sys.exit(1)
print(
    "SCHED SMOKE: OK "
    f"ratio={body['cosched_vs_serial_ratio']} "
    f"p99={body['p99_ms_worst']}ms/{body['slo_target_ms']}ms "
    f"publishes={body['publishes']} preempted_at_chunk="
    f"{body['preempted_at_chunk']} parity={body['parity_max_abs_diff']:.1e} "
    f"dropped={body['dropped']}"
)
EOF
