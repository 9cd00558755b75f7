#!/usr/bin/env python3
"""The readings the limits of a `serve_lm_ctx` cell's `correct` are set
from, on the chip, at the cell's own size, several seeds in one process:

    python3 benchmarks/control_glm.py --workload <cell> --seeds 4 --control-seeds 2

One warm server; for each seed the model is given that seed's weights,
the mix's contexts are registered anew (the cache holds the OLD weights'
latents otherwise) and a short open-loop window at the cell's own rate
is driven over questions no earlier window sent. The sampled answers are
held against the float32 reference's one full forward over context ++
question (the SOUND readings). For the first `--control-seeds` seeds the
same sequences also go through the CONTROL (`reference_glm.forward(...,
lower=True)`: int8 matmul operands; router and logits bfloat16; the
latent rounded to 3 mantissa bits), whose own answers are held against
the reference the same way, and through two FAULTS a cache can have: the
served answers held against the reference over ANOTHER context (a row
that read the wrong slot) and over the context less its first token
(positions off by one). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen, reference_glm, reference_lm  # noqa: E402
from benchmarks.runners import serve, serve_lm_ctx  # noqa: E402

NAMES = (("top_logit_gap", "top_gap"), ("score_gap", "score_gap"),
         ("score_gap_median", "score_gap_median"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-requests", type=int, default=6,
                    help="of a control seed's checked requests, how many "
                         "go through the control and the faults too")
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm_ctx.ServingCtx(cell, args.first_seed)
    pool, model, used, out = serving.pool, serving.model, 0, []
    try:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            if i:
                # other weights empty the program's slot book; the ids are
                # the same, so the bodies stand, and the arrays freed for
                # the last seed's reference are made again
                serving.seed_weights(seed)
                model.cache = model.module.init_cache(
                    model.lm, model.contexts.slots, model.contexts.capacity)
                for tokens in pool["contexts"]:
                    serving.register(tokens)
            arrivals = loadgen.schedule(seed, args.seconds, cell.traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            checked = set(serve_lm_ctx.pick_checked(seed, arrivals, pool,
                                                    cell.traffic))
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            served = serve_lm_ctx.served_answers(
                drove["results"], drove["plan"], pool, serving.context_ids)
            for layer in model.cache:       # the reference needs the room
                layer.delete()
            ref = reference_glm.forward(seed, cell.config,
                                        served["sequences"])
            sound = reference_lm.served_gap(ref["logits"], served["ids"],
                                            served["logits"])
            row = {"seed": seed, "failed": got["failed"],
                   "attempted": got["attempted"],
                   "p50_ms": got["request_p50_ms"],
                   "requests": len(served["sequences"]),
                   "contexts": len(set(served["contexts"])),
                   "tokens": sum(len(s) for s in served["sequences"]),
                   "malformed": served["malformed"],
                   "expert_sets_equal_share": reference_lm.same_expert_sets(
                       served["routing"], ref["chosen_last"])}
            for name, key in NAMES:
                row["served_" + name] = sound[key]
            if i < args.control_seeds:
                n = args.control_requests
                some = served["sequences"][:n]
                low = reference_glm.forward(seed, cell.config, some,
                                            lower=True)
                gap = reference_lm.served_gap(
                    ref["logits"][:n], *reference_lm.own_answers(
                        low["logits"], served["ids"].shape[1]))
                n_ctx = len(pool["contexts"])
                faults = {
                    "wrong_slot": [np.concatenate(
                        [pool["contexts"][(c + 1) % n_ctx],
                         s[len(pool["contexts"][c]):]])
                        for s, c in zip(some, served["contexts"])],
                    "stale_length": [np.concatenate(
                        [s[:len(pool["contexts"][c]) - 1],
                         s[len(pool["contexts"][c]):]])
                        for s, c in zip(some, served["contexts"])]}
                for name, key in NAMES:
                    row["control_" + name] = gap[key]
                row["control_expert_sets_equal_share"] = \
                    reference_lm.same_expert_sets(low["chosen_last"],
                                                  ref["chosen_last"][:n])
                for fault, sequences in faults.items():
                    other = reference_glm.forward(seed, cell.config,
                                                  sequences)
                    bad = reference_lm.served_gap(
                        other["logits"], served["ids"][:n],
                        served["logits"][:n])
                    for name, key in NAMES:
                        row[f"{fault}_{name}"] = bad[key]
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        serving.close()
    summary = {"workload": cell.name, "seeds": len(out)}
    for name, _ in NAMES:
        summary["served_" + name] = {
            "sound_max": max(r["served_" + name] for r in out),
            **{kind + "_min": min((r[f"{kind}_{name}"] for r in out
                                   if f"{kind}_{name}" in r), default=None)
               for kind in ("control", "wrong_slot", "stale_length")}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
