#!/usr/bin/env python3
"""The readings the limits of a `serve_lm_state_ctx` cell's `correct`
are set from, on the chip, at the cell's own size, several seeds in one
process (as `control_glm.py`):

    python3 benchmarks/control_brumby.py --workload <cell> --seeds 3 --control-seeds 1

One warm server; for each seed the model is given that seed's weights,
the mix's contexts are registered anew (the slots hold the OLD weights'
states otherwise) and a short open-loop window of bursts at the cell's
own rate is driven over questions no earlier window sent. The sampled
answers are held against the float32 reference's one full forward over
context ++ question (the SOUND readings). For the first
`--control-seeds` seeds the first `--control-requests` of those
sequences also go through

  control   `reference_brumby.forward(..., lower=True)`: int8 matmul
            operands, logits bfloat16, the retention's state and
            normaliser HELD in bfloat16 from chunk to chunk. Its OWN
            answers against the reference. It must fail a limit, so that
            a later change that stores the states in bfloat16 and calls
            it a speed-up is caught.

and the served answers are held against the reference computed with one
FAULT a state cache or the layer can have; each must fail a limit:

  wrong_slot          the reference over another context (a row that
                      read another row's slot);
  stale_length        the reference over the context less its last token
                      (a state one token short, positions off by one);
  last_chunk_alone    the keys before the context's last registration
                      chunk count for nothing (the state was not CARRIED
                      from chunk to chunk);
  gates_ignored       g = 1: nothing is ever forgotten;
  normaliser_dropped  the weighted sum not divided by the weights' sum;
  power_one           p = 1 in the place of 2.

The benchmark's own runs never run this.
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

from benchmarks import common, reference_brumby, reference_lm  # noqa: E402
from benchmarks.control_glm import NAMES  # noqa: E402
from benchmarks.runners import serve, serve_lm_state_ctx  # noqa: E402

FAULTS = ("wrong_slot", "stale_length", "last_chunk_alone",
          "gates_ignored", "normaliser_dropped", "power_one")


def fault_runs(pool, sequences, contexts, register_chunk: int):
    """{fault: keyword arguments of `reference_brumby.forward`}: the
    question behind the NEXT context of the pool, behind its own context
    less one token, the sound sequence with the keys before the last
    registration chunk masked, and the three faults of the layer."""
    n_ctx = len(pool["contexts"])
    moved, short, first = [], [], []
    for s, c in zip(sequences, contexts):
        own = len(pool["contexts"][c])
        moved.append(np.concatenate(
            [pool["contexts"][(c + 1) % n_ctx], s[own:]]))
        short.append(np.concatenate([s[:own - 1], s[own:]]))
        first.append((own - 1) // register_chunk * register_chunk)
    runs = {"wrong_slot": dict(sequences=moved),
            "stale_length": dict(sequences=short),
            "last_chunk_alone": dict(sequences=sequences, first_key=first)}
    for fault in reference_brumby.FAULTS:
        runs[fault] = dict(sequences=sequences, fault=fault)
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control-requests", type=int, default=3,
                    help="of a control seed's checked requests, how many "
                         "go through the control and the faults too")
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm_state_ctx.ServingStateCtx(cell, args.first_seed)
    pool, model, used, out = serving.pool, serving.model, 0, []
    chunk = int(cell.config["serve"]["context_cache"]["register_chunk"])
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
            arrivals = serve_lm_state_ctx.burst_schedule(
                seed, args.seconds, cell.traffic, first_question=used)
            used += 1 + max(a["burst"] for a in arrivals)
            checked = set(serve_lm_state_ctx.pick_checked(
                seed, arrivals, pool, cell.traffic))
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            served = serve_lm_state_ctx.served_answers(
                drove["results"], drove["plan"], pool, serving.context_ids)
            for layer in model.cache:       # the reference needs the room
                layer.delete()
            ref = reference_brumby.forward(seed, cell.config,
                                           served["sequences"])
            sound = reference_lm.served_gap(ref["logits"], served["ids"],
                                            served["logits"])
            row = {"seed": seed, "failed": got["failed"],
                   "attempted": got["attempted"],
                   "p50_ms": got["request_p50_ms"],
                   "requests": len(served["sequences"]),
                   "contexts": len(set(served["contexts"])),
                   "tokens": sum(len(s) for s in served["sequences"]),
                   "malformed": served["malformed"]}
            for name, key in NAMES:
                row["served_" + name] = sound[key]
            if i < args.control_seeds:
                n = args.control_requests
                some = served["sequences"][:n]
                low = reference_brumby.forward(seed, cell.config, some,
                                               lower=True)
                gap = reference_lm.served_gap(
                    ref["logits"][:n], *reference_lm.own_answers(
                        low["logits"], served["ids"].shape[1]))
                for name, key in NAMES:
                    row["control_" + name] = gap[key]
                runs = fault_runs(pool, some, served["contexts"][:n], chunk)
                for fault in FAULTS:
                    other = reference_brumby.forward(seed, cell.config,
                                                     **runs[fault])
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
               for kind in ("control",) + FAULTS}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
