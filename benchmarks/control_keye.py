#!/usr/bin/env python3
"""The readings the limits of a `serve_lm_sparse_ctx` cell's `correct`
are set from, on the chip, at the cell's own size, several seeds in one
process (as `control_glm.py`):

    python3 benchmarks/control_keye.py --workload <cell> --seeds 4 --control-seeds 1

One warm server; for each seed the model is given that seed's weights,
the mix's contexts are registered anew (the caches hold the OLD weights'
state otherwise) and a short open-loop window at the cell's own rate is
driven over questions no earlier window sent. The sampled answers are
held against the float32 reference's one full forward over context ++
question (the SOUND readings). For the first `--control-seeds` seeds the
first `--control-requests` of those sequences also go through

  control        `reference_keye.forward(..., lower=True)`: int8 matmul
                 operands; router, logits and index scores bfloat16;
                 cached keys, values and index keys rounded to 3
                 mantissa bits. Its OWN answers against the reference.

and the served answers are held against the reference computed with one
FAULT a selecting cache can have:

  dense          the selection ignored: every visible key attended;
  foreign_index  the selection made from ANOTHER context's index keys
                 (a row that read another slot's index cache);
  wrong_slot     the reference over another context (a row that read
                 another slot altogether);
  stale_length   the reference over the context less its last token (a
                 slot read one token short).

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

from benchmarks import common, loadgen, reference_keye, reference_lm  # noqa: E402
from benchmarks.control_glm import NAMES  # noqa: E402
from benchmarks.runners import serve, serve_lm_sparse_ctx  # noqa: E402

FAULTS = ("dense", "foreign_index", "wrong_slot", "stale_length")


def fault_sequences(pool, sequences, contexts):
    """The sequences the wrong-slot, stale-length and foreign-index
    faults run: each question behind the NEXT context of the pool (cut or
    repeated to the right context's length for the foreign index, so
    that positions line up), and behind its own context less one
    token."""
    n_ctx = len(pool["contexts"])
    moved, short, foreign = [], [], []
    for s, c in zip(sequences, contexts):
        own = len(pool["contexts"][c])
        other = pool["contexts"][(c + 1) % n_ctx]
        moved.append(np.concatenate([other, s[own:]]))
        short.append(np.concatenate([s[:own - 1], s[own:]]))
        foreign.append(np.concatenate([np.resize(other, own), s[own:]]))
    return moved, short, foreign


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control-requests", type=int, default=4,
                    help="of a control seed's checked requests, how many "
                         "go through the control and the faults too")
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    serving = serve_lm_sparse_ctx.ServingSparseCtx(cell, args.first_seed)
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
            checked = set(serve_lm_sparse_ctx.pick_checked(
                seed, arrivals, pool, cell.traffic))
            serving.ask_selected = {arrivals[j]["body_index"]
                                    for j in checked}
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            served = serve_lm_sparse_ctx.served_answers(
                drove["results"], drove["plan"], pool, serving.context_ids)
            import jax
            for leaf in jax.tree.leaves(model.cache):   # room for the
                leaf.delete()                           # reference
            ref = reference_keye.forward(seed, cell.config,
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
                       served["routing"], ref["chosen_last"]),
                   "selected_sets_overlap_share":
                       reference_keye.selected_overlap(
                           served["selected"], ref["selected_last"])}
            for name, key in NAMES:
                row["served_" + name] = sound[key]
            if i < args.control_seeds:
                n = args.control_requests
                some = served["sequences"][:n]
                low = reference_keye.forward(seed, cell.config, some,
                                             lower=True)
                gap = reference_lm.served_gap(
                    ref["logits"][:n], *reference_lm.own_answers(
                        low["logits"], served["ids"].shape[1]))
                for name, key in NAMES:
                    row["control_" + name] = gap[key]
                row["control_expert_sets_equal_share"] = \
                    reference_lm.same_expert_sets(low["chosen_last"],
                                                  ref["chosen_last"][:n])
                row["control_selected_sets_overlap_share"] = \
                    reference_keye.selected_overlap(
                        [[np.flatnonzero(m).tolist() for m in kept]
                         for kept in low["selected_last"]],
                        ref["selected_last"][:n])
                moved, short, foreign = fault_sequences(
                    pool, some, served["contexts"][:n])
                runs = {"dense": dict(sequences=some, fault="dense"),
                        "foreign_index": dict(sequences=some,
                                              fault="foreign_index",
                                              other=foreign),
                        "wrong_slot": dict(sequences=moved),
                        "stale_length": dict(sequences=short)}
                for fault in FAULTS:
                    other = reference_keye.forward(seed, cell.config,
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
