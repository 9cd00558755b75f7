#!/usr/bin/env bash
# Run the serving load-generator bench with a hard timeout and crash
# diagnostics, matching scripts/run_chaos.sh conventions.
#
# The bench drives a real HTTP server + warm extractor pool + batcher;
# a serving bug tends to surface as a HANG (a request waiting on a dead
# worker or a stuck batcher dispatch), so the run is wall-clock bounded
# and, on failure, any metrics/heartbeat snapshots the bench left under
# the run dir are dumped so "where was the server when it stopped" is
# answerable from CI logs alone.
#
# Usage: scripts/run_serving_bench.sh [extra args passed to the bench]
#        scripts/run_serving_bench.sh resilience   # PR-9 overload +
#        kill-replica scenarios -> results/serving_resilience.json
#        scripts/run_serving_bench.sh tenants      # PR-20 tenancy
#        overhead + hot-tenant fairness drill -> results/serving_tenants.json
set -u -o pipefail

cd "$(dirname "$0")/.."

RUN_DIR="$(mktemp -d "${TMPDIR:-/tmp}/c2v-serving.XXXXXX")"
LOG="$RUN_DIR/bench.log"
# The bench exports a Prometheus snapshot here at exit; on failure the
# dump below surfaces it (SLO histograms, pool/cache/batcher counters).
export C2V_CHAOS_DIAG_DIR="$RUN_DIR"

# Wall-clock backstop: the bench itself finishes in ~2 minutes on a
# laptop CPU; 600s catches a pool/batcher/drain hang, not a slow run.
BUDGET=600

echo "=== serving bench (budget ${BUDGET}s) ==="
timeout -k 20 "$BUDGET" \
    env JAX_PLATFORMS=cpu python experiments/serving_bench.py "$@" \
    2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
if [ "$rc" -eq 124 ] || [ "$rc" -eq 137 ]; then
    echo "BENCH TIMED OUT (rc=$rc): likely a serving hang" | tee -a "$LOG"
fi

if [ "$rc" -ne 0 ]; then
    echo "=== serving bench FAILED (rc=$rc): dumping diagnostics ==="
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
