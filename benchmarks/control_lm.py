#!/usr/bin/env python3
"""The readings the limits of a `serve_lm` cell's `correct` are set from,
on the chip, at the cell's own size, several seeds in one process:

    python3 benchmarks/control_lm.py --workload <cell> --seeds 6 --control-seeds 3

One warm server; for each seed the model is given that seed's weights
and a short open-loop window at the cell's own rate is driven over bodies
no earlier window sent. The sampled answers are held against the float32
reference (the SOUND readings); for the first `--control-seeds` seeds the
same sequences also go through the CONTROL (`reference_lm.forward(...,
lower=True)`: int8 matmul operands; router, state and logits bfloat16),
whose own answers are held against the reference the same way. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen, reference_lm  # noqa: E402
from benchmarks.runners import serve, serve_lm  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm.ServingLM(cell, args.first_seed)
    used, out = 0, []
    try:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            if i:
                serving.seed_weights(seed)
            arrivals = loadgen.schedule(seed, args.seconds, cell.traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            checked = set(range(min(len(arrivals), int(
                cell.traffic["checked_requests"]))))
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                     drove["plan"]["deadline_ms"])
            served = serve_lm.served_answers(drove["results"], drove["plan"])
            ref = reference_lm.forward(seed, cell.config, served["sequences"])
            sound = reference_lm.served_gap(ref["logits"], served["ids"],
                                            served["logits"])
            row = {"seed": seed, "failed": got["failed"],
                   "attempted": got["attempted"],
                   "p50_ms": got["request_p50_ms"],
                   "requests": len(served["sequences"]),
                   "tokens": sum(len(s) for s in served["sequences"]),
                   "malformed": served["malformed"],
                   "served_top_logit_gap": sound["top_gap"],
                   "served_score_gap": sound["score_gap"],
                   "served_score_gap_median": sound["score_gap_median"],
                   "expert_sets_equal_share": reference_lm.same_expert_sets(
                       served["routing"], ref["chosen_last"])}
            if i < args.control_seeds:
                low = reference_lm.forward(seed, cell.config,
                                           served["sequences"], lower=True)
                gap = reference_lm.served_gap(
                    ref["logits"], *reference_lm.own_answers(
                        low["logits"], served["ids"].shape[1]))
                row["control_top_logit_gap"] = gap["top_gap"]
                row["control_score_gap"] = gap["score_gap"]
                row["control_score_gap_median"] = gap["score_gap_median"]
                row["control_expert_sets_equal_share"] = \
                    reference_lm.same_expert_sets(low["chosen_last"],
                                                  ref["chosen_last"])
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        serving.close()
    summary = {"workload": cell.name, "seeds": len(out)}
    for name in ("top_logit_gap", "score_gap", "score_gap_median"):
        summary["served_" + name] = {
            "sound_max": max(r["served_" + name] for r in out),
            "control_min": min((r["control_" + name] for r in out
                                if "control_" + name in r), default=None)}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
