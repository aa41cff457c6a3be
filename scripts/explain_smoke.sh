#!/usr/bin/env bash
# Cost-observatory smoke (docs/OBSERVABILITY.md "Cost observatory"):
# three `keystone-tpu explain` runs against one profile store.
#
#   run 1 (clean)   — populates autocache/stream entries + the roofline
#                     probe; JSON must carry per-node predicted cost,
#                     measured wall, intensity, and roofline
#                     classification for every compiled plan node, with
#                     ZERO extra XLA compiles from harvesting.
#   run 2 (seeded)  — one stored autocache entry corrupted 10×: the
#                     drift sentinel must fire EXACTLY ONE drift event
#                     (metric + cost_drift ledger event + `stale:` mark
#                     on the entry) and exit 2.
#   run 3 (clean)   — the stale entry was re-measured (autocache
#                     re-profiled live), the store is fresh again, and
#                     the accurate model stays quiet.
#
# Budget: <30 s on CPU (tiny synthetic shapes, warm XLA cache after
# run 1).
#
# Usage: scripts/explain_smoke.sh [out_dir]
set -euo pipefail

cd "$(dirname "$0")/.."
OUT="${1:-$(mktemp -d)}"
mkdir -p "$OUT"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export KEYSTONE_PROFILE_STORE="$OUT/profile-store.jsonl"

# Shapes sized for walls in the tens of milliseconds: large enough that
# ambient CI load can't swing them across the 4x drift band, small
# enough to keep the whole 3-run smoke under 30 s.
EXPLAIN="python -m keystone_tpu explain --pipeline synthetic \
    --rows 2048 --dim 96 --classes 4 --json"

run() { # run <n> <expected_rc> [extra flags...]
    local n="$1" want="$2"; shift 2
    local rc=0
    timeout -k 10 120 $EXPLAIN --out "$OUT/r$n.json" "$@" \
        > "$OUT/r$n.stdout.txt" 2> "$OUT/r$n.stderr.txt" || rc=$?
    if [ "$rc" != "$want" ]; then
        echo "explain run $n: expected rc=$want got rc=$rc" >&2
        tail -20 "$OUT/r$n.stderr.txt" >&2
        exit 1
    fi
}

run 1 0
run 2 2 --seed-drift 10
run 3 0

python - "$OUT" <<'EOF'
import json, os, sys

out = sys.argv[1]
runs = [json.load(open(os.path.join(out, f"r{i}.json"))) for i in (1, 2, 3)]
r1, r2, r3 = runs

for i, r in enumerate(runs, 1):
    # Harvesting rides the jit trace cache: ZERO extra XLA compiles.
    assert r["harvest_compiles"] == 0, (i, r["harvest_compiles"])
    assert r["roofline"] is not None and r["roofline"]["peak_flops_per_s"] > 0
    assert r["nodes"], f"run {i}: empty ledger"
    # Every compiled plan node reports the full cost picture.
    compiled = [n for n in r["nodes"] if n.get("flops")]
    assert compiled, f"run {i}: no harvested nodes"
    for n in compiled:
        assert n.get("seconds") is not None, n
        assert n.get("predicted_s") is not None, n
        assert n.get("intensity") is not None, n
        assert n.get("roofline") in ("compute-bound", "memory-bound"), n
        assert n.get("lowering_digest"), n

# Roofline calibration is paid once: runs 2-3 warm-start from the store.
assert r1["roofline"]["source"] == "probe", r1["roofline"]
assert r2["roofline"]["source"] == "store", r2["roofline"]

# Clean runs stay quiet across 3 consecutive executions each.
assert r1["drift_events"] == [], r1["drift_events"]
assert r3["drift_events"] == [], r3["drift_events"]
assert r3["store"]["stale_entries"] == 0, r3["store"]

# The seeded 10x mis-prediction fires EXACTLY ONE drift event, marks
# the entry stale, and the next plan re-measures it.
assert r2["seeded_corruptions"] == 1, r2["seeded_corruptions"]
assert len(r2["drift_events"]) == 1, r2["drift_events"]
event = r2["drift_events"][0]
assert event["model"] == "autocache", event
assert event["stale_marked"] is True, event
assert event["key"].startswith("autocache:"), event
assert r2["store"]["stale_entries"] >= 1, r2["store"]
assert event["key"] in r2["store"]["stale_keys"], r2["store"]

print("EXPLAIN_SMOKE_OK", {
    "drift_key": event["key"][:24],
    "ratio": event["ratio"],
    "nodes": len(r3["nodes"]),
    "harvest_compiles": [r["harvest_compiles"] for r in runs],
})
EOF

echo "explain smoke OK (artifacts in $OUT)"
