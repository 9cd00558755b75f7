#!/usr/bin/env python3
"""The readings the limits of `correct` are set from, on the chip, at the
cell's own size, many seeds in one process:

    python3 benchmarks/control.py --workload <cell> --seeds 12 --control-seeds 4

A serve cell: one warm server; for each seed the model is given that
seed's weights and a short open-loop window at the cell's own rate is
driven over bodies no earlier window sent; the sampled answers are held
against the reference, and the same contexts against the control and each of its halves.
A train cell: for each seed, the program's own compiled step, fed through the
program's own transfer, is driven from the seed's weights through the
followed steps; then, the program's state freed, the float32 reference
follows the same batches, and for the first `--control-seeds` seeds so
does the CONTROL (the reference in the precision below the
configuration's: parameters in bfloat16, matmul operands int8) and each
of its two halves alone (`reference.LOWER`). Printed per seed: every
number `correct` compares, for the program against the reference (sound)
and for the control and its halves against the reference. The
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

from benchmarks import common, datagen, reference  # noqa: E402
from benchmarks.runners import train  # noqa: E402


def gaps(a: reference.Followed, ref: reference.Followed) -> dict:
    return {c["name"]: c["value"]
            for c in train.compare(a, ref, {"loss_gap": 0.0,
                                            "first_grad_norm_gap": 0.0,
                                            "param_change_norm_gap": 0.0})}


def serve_readings(cell, args) -> int:
    import random
    from benchmarks import loadgen
    from benchmarks.runners import serve
    serving = serve.Serving(cell, args.first_seed)
    tables = serve.lookup_tables(cell.config, serving.data["head"])
    used, out = 0, []
    try:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            if i:
                serving.program.seed_state(seed)
            arrivals = loadgen.schedule(seed, args.seconds, cell.traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            rng = random.Random(seed)
            checked = set(rng.sample(range(len(arrivals)), min(
                int(cell.traffic["checked_requests"]), len(arrivals))))
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            served = serve.served_arrays(cell, serving.exe, tables,
                                         drove["results"], drove["plan"])
            params = reference.make_params(seed, serving.program.dims)
            row = {"seed": seed, "failed": got["failed"],
                   "attempted": got["attempted"],
                   "p50_ms": got["request_p50_ms"],
                   "mismatched": served["mismatched"],
                   "unknown": served["unknown"],
                   "methods": served["methods"]}
            for half in reference.LOWER:
                gap = reference.served_gap(
                    params, *served["contexts"], served["ids"],
                    served["logp"], control=half)
                row["served_top_logit_gap"] = gap["top_gap"]
                row["served_score_gap"] = gap["score_gap"]
                row[f"control_top_logit_gap.{half}"] = gap["control_top_gap"]
                row[f"control_score_gap.{half}"] = gap["control_score_gap"]
            del params
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        serving.close()
    summary = {"workload": cell.name, "seeds": len(out)}
    for sound in ("served_top_logit_gap", "served_score_gap"):
        summary[sound] = {"sound_max": max(r[sound] for r in out)}
        for half in reference.LOWER:
            key = sound.replace("served", "control") + "." + half
            summary[sound]["control_min." + half] = min(r[key] for r in out)
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="a serve cell's window per seed")
    args = ap.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    if cell.runner == "serve":
        return serve_readings(cell, args)
    common.configure_jax()
    common.require_chips(cell.chips)
    import jax
    from code2vec_tpu.data.reader import EstimatorAction
    from code2vec_tpu.training.step import device_put_batch

    data = datagen.prepare_train_data(cell.work, cell.config, cell.traffic)
    program = train.Program(cell, data["prefix"], args.first_seed)
    model, config = program.model, program.config
    for leaf in jax.tree.leaves(model.state):
        leaf.delete()
    rows = cell.config["batch_rows_per_chip"] * cell.chips
    corpus = model._train_corpus()
    block = cell.traffic.get("reference_block_rows", 256)
    keep = cell.config["dropout_keep"]
    out = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        short = seed % (2 ** 31 - 1)
        it = corpus.iter_batches(rows, EstimatorAction.Train, seed=short)
        feed = train.Feed(it, train.FOLLOWED_STEPS)
        state = program.fresh_state(seed)
        follower = train.Follower(program, seed)
        rng = jax.random.key(short + 2, impl=config.dropout_prng_impl)
        for n in range(1, train.FOLLOWED_STEPS + 1):
            arrays = device_put_batch(next(feed), model.mesh)
            state, loss = program.train_step(state, *arrays, rng)
            follower.after_step(n, state, loss)
        got = follower.read()
        for leaf in jax.tree.leaves(state):
            leaf.delete()
        ref = reference.follow_steps(seed, program.dims, feed.kept,
                                     keep=keep, block_rows=block)
        row = {"seed": seed, "sound": gaps(got, ref),
               "losses": got.losses, "ref_losses": ref.losses}
        if i < args.control_seeds:
            row["control"] = {
                half: gaps(reference.follow_steps(
                    seed, program.dims, feed.kept, keep=keep, lower=half,
                    block_rows=block), ref)
                for half in reference.LOWER}
            # what a second mask alone moves: the reference again, its
            # blocks (so its mask) cut differently
            again = reference.follow_steps(seed, program.dims, feed.kept,
                                           keep=keep, block_rows=block // 2)
            row["other_mask"] = gaps(again, ref)
        print(json.dumps(row), flush=True)
        out.append(row)
    names = list(out[0]["sound"])
    summary = {"workload": cell.name, "seeds": len(out)}
    for name in names:
        summary[name] = {
            "sound_max": max(r["sound"][name] for r in out),
            "other_mask_max": max((r["other_mask"][name] for r in out
                                   if "other_mask" in r), default=None)}
        for half in reference.LOWER:
            summary[name]["control_min." + half] = min(
                (r["control"][half][name] for r in out if "control" in r),
                default=None)
    print("SUMMARY " + json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": out, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
