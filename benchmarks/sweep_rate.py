#!/usr/bin/env python3
"""Find, once, the highest request rate a serve cell sustains without a
growing backlog, on the chip; the traffic file then carries 0.8 of it as
a number. One process, one warm server, one open-loop window per rate,
each over bodies no earlier window sent (so the cache never hits).

    python3 benchmarks/sweep_rate.py --workload <cell> --rates 20,40,80 --seconds 8

A rate is sustained when nothing failed and the last third of the
window is no slower than twice the first third (the backlog is not
growing). The benchmark's own runs never run this.
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
from benchmarks.runners import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2_600_000_000)
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve.Serving(cell, args.seed)
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
            drove = serving.drive(arrivals)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            ok = [r["latency_ms"] for r in drove["results"] if r and r["ok"]]
            third = max(len(ok) // 3, 1)
            first, last = ok[:third], ok[-third:]
            row = {"rate": rate, "attempted": got["attempted"],
                   "failed": got["failed"],
                   "p50_ms": got["request_p50_ms"],
                   "p95_ms": got["request_p95_ms"],
                   "p50_first_third_ms": readers.percentile(first, 50),
                   "p50_last_third_ms": readers.percentile(last, 50),
                   "late_p95_ms": readers.percentile(got["late_ms"], 95),
                   "window_s": drove["window_s"]}
            row["sustained"] = (got["failed"] == 0 and
                                row["p50_last_third_ms"]
                                <= 2.0 * row["p50_first_third_ms"])
            for phase in ("queue_wait", "extract", "batch_wait", "device"):
                h = drove["registry"].histogram("serving_request_seconds",
                                                {"phase": phase})
                row[phase + "_mean_ms"] = (None if h is None
                                           else 1e3 * h[0] / h[1])
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
