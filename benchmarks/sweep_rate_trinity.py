#!/usr/bin/env python3
"""`sweep_rate_glm.py` for a cell of runner kind `serve_lm_paged_ctx`:
find, once, the highest request rate the context-scoring server
sustains without a growing backlog, on the chip. One process, one warm
server with the mix's contexts registered, one open-loop window per
rate, each over questions no earlier window sent.

    python3 benchmarks/sweep_rate_trinity.py --workload <cell> --rates 4,8,12 --seconds 10

A rate is sustained when nothing failed and the last third of the window
is no slower than twice the first third (`sweep_rate_glm.window_row`).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen  # noqa: E402
from benchmarks.runners import serve_lm_paged_ctx  # noqa: E402
from benchmarks.sweep_rate_glm import window_row  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2_600_000_000)
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm_paged_ctx.ServingPagedCtx(cell, args.seed)
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
