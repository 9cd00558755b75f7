#!/usr/bin/env bash
# Run the pod-scale input-pipeline bench with a hard timeout and
# crash diagnostics:
#
#   1. the input grid (simulated hosts x shards)
#      (experiments/input_bench.py -> experiments/results/input.json
#       + the BENCH_INPUT.md section);
#   2. the fast multi-shard reader suite (tests/test_sharded_corpus.py
#      — the cursor-law pins the bench numbers rest on).
#
# Usage: scripts/run_input_bench.sh [extra args passed to the bench]
set -u -o pipefail

cd "$(dirname "$0")/.."

RUN_DIR="$(mktemp -d "${TMPDIR:-/tmp}/c2v-input.XXXXXX")"
LOG="$RUN_DIR/bench.log"
export C2V_CHAOS_DIAG_DIR="$RUN_DIR"

# Wall-clock backstops: the grid is 9 arms x best-of-3 short runs
# (~2 min on a dev CPU). The timeouts catch a hang, not a slow run.
BENCH_BUDGET=900
TEST_BUDGET=300
rc=0

echo "=== input grid (budget ${BENCH_BUDGET}s) ==="
timeout -k 20 "$BENCH_BUDGET" \
    env JAX_PLATFORMS=cpu python experiments/input_bench.py "$@" \
    2>&1 | tee "$LOG"
bench_rc=${PIPESTATUS[0]}
if [ "$bench_rc" -eq 124 ] || [ "$bench_rc" -eq 137 ]; then
    echo "BENCH TIMED OUT (rc=$bench_rc)" \
        | tee -a "$LOG"
fi
[ "$bench_rc" -ne 0 ] && rc=$bench_rc

echo "=== multi-shard reader suite (budget ${TEST_BUDGET}s) ==="
timeout -k 20 "$TEST_BUDGET" \
    env JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    tests/test_sharded_corpus.py 2>&1 | tee -a "$LOG"
test_rc=${PIPESTATUS[0]}
[ "$test_rc" -ne 0 ] && rc=$test_rc

if [ "$rc" -ne 0 ]; then
    echo "=== input bench FAILED (rc=$rc): dumping diagnostics ==="
    find "$RUN_DIR" -maxdepth 4 -type f \
        \( -name '*heartbeat*.json' -o -name 'hb*.json' \
           -o -name '*.prom' -o -name '*metrics*' \) 2>/dev/null \
        | while read -r f; do
        echo "--- $f ---"
        cat "$f"
        echo
    done
    echo "full log: $LOG"
else
    rm -rf "$RUN_DIR"
fi
exit "$rc"
