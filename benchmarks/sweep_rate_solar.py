#!/usr/bin/env python3
"""`sweep_rate_glm.py` for a cell of runner kind `serve_lm_session_ctx`:
find, once, the highest rate of kept turns the server sustains without a
growing backlog, on the chip. One process, one warm server, one
open-loop window a rate, each over bodies no earlier window sent.

    python3 benchmarks/sweep_rate_solar.py --workload <cell> --rates 4,8,12 --seconds 10

Every window makes the sessions longer, so a rate's window does not
start where the one before it did: the sessions are REGISTERED ANEW
before a window that could not otherwise end with a twentieth of the
pool free (`--fresh 1`: before every window), and a row says how many
tokens its sessions held when it began. A rate is sustained when nothing
failed and the last third of the window is no slower than twice the
first third (`sweep_rate_glm.window_row`). The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.runners import serve_lm_session_ctx as runner  # noqa: E402
from benchmarks.sweep_rate_glm import window_row  # noqa: E402


def register_anew(serving) -> None:
    """An empty book, and the mix's sessions registered as set-up
    registers them (the arrays keep what they held: nothing reads what
    the book does not name)."""
    model = serving.model
    model.contexts = model.contexts.fresh()
    serving.session_ids = [serving.register(tokens)
                           for tokens in serving.pool["sessions"]]
    serving.session_tokens = [[t] for t in serving.pool["sessions"]]
    serving.turns = [[] for _ in serving.session_ids]
    serving.named = set(serving.session_ids)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2_600_000_000)
    ap.add_argument("--fresh", type=int, default=0)
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = runner.ServingSessions(cell, args.seed)
    book = serving.model.contexts
    longest = int(cell.traffic["length"]["max"])
    used, rows = 0, []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_per_s=rate)
            arrivals = runner.turn_schedule(args.seed, args.seconds, traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            if used > int(traffic["request_pool"]):
                break
            book = serving.model.contexts
            grow = sum(a["length"] for a in arrivals) // book.page_tokens \
                + len(serving.session_ids)
            if args.fresh or len(book._free_pages) - grow < book.pages // 20:
                register_anew(serving)
            held = sum(serving.length_of(s)
                       for s in range(len(serving.session_ids)))
            drove = serving.drive(arrivals)
            book = serving.model.contexts
            row = dict({"rate": rate, "tokens_held_before": held,
                        "pool_fill_after":
                            1.0 - len(book._free_pages) / book.pages,
                        "refused": sum(1 for r in drove["results"]
                                       if r and r["status"] in (404, 409))},
                       **window_row(drove))
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
