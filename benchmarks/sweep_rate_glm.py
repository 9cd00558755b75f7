#!/usr/bin/env python3
"""`sweep_rate_lm.py` for a cell of runner kind `serve_lm_ctx`: find,
once, the highest request rate the context-scoring server sustains
without a growing backlog, on the chip. One process, one warm server
with the mix's contexts registered, one open-loop window per rate, each
over questions no earlier window sent.

    python3 benchmarks/sweep_rate_glm.py --workload <cell> --rates 8,16,24 --seconds 10

A rate is sustained when nothing failed and the last third of the window
is no slower than twice the first third. The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen, readers  # noqa: E402
from benchmarks.runners import serve, serve_lm_ctx  # noqa: E402


def window_row(drove: dict) -> dict:
    """What one driven window says of the server: the percentiles, the
    thirds, the phase means and the dispatcher's busy share."""
    got = serve.summarize(drove["results"], drove["plan"]["deadline_ms"])
    ok = [r["latency_ms"] for r in drove["results"] if r and r["ok"]]
    third = max(len(ok) // 3, 1)
    first, last = ok[:third] or [0.0], ok[-third:] or [0.0]
    row = {"attempted": got["attempted"], "failed": got["failed"],
           "p50_ms": got["request_p50_ms"], "p95_ms": got["request_p95_ms"],
           "p50_first_third_ms": readers.percentile(first, 50),
           "p50_last_third_ms": readers.percentile(last, 50),
           "late_p95_ms": readers.percentile(got["late_ms"], 95),
           "window_s": drove["window_s"]}
    row["sustained"] = (got["failed"] == 0 and row["p50_last_third_ms"]
                        <= 2.0 * row["p50_first_third_ms"])
    registry = drove["registry"]
    for phase in ("batch_wait", "device"):
        h = registry.histogram("serving_request_seconds", {"phase": phase})
        row[phase + "_mean_ms"] = None if h is None else 1e3 * h[0] / h[1]
    busy = registry.histogram("serving_dispatcher_seconds",
                              {"state": "dispatch"})
    row["dispatcher_busy_pct"] = (
        None if busy is None else 100.0 * busy[0] / drove["window_s"])
    rows = registry.histogram("serving_batch_fill_ratio", {"dim": "rows"})
    row["steps"] = None if rows is None else rows[1]
    row["requests_per_step"] = (None if rows is None
                                else got["attempted"] / rows[1])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2_600_000_000)
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm_ctx.ServingCtx(cell, args.seed)
    used, rows = 0, []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_per_s=rate)
            arrivals = loadgen.schedule(args.seed, args.seconds, traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            if used > int(traffic["request_pool"]):
                break
            row = dict({"rate": rate}, **window_row(serving.drive(arrivals)))
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        serving.close()
    good = [r["rate"] for r in rows if r["sustained"]]
    print("KNEE " + json.dumps({"highest_sustained": max(good, default=None),
                                "memory_peak_bytes":
                                    common.memory_peak_bytes()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
