#!/usr/bin/env python3
"""The readings the limits of a `serve_lm_paged_ctx` cell's `correct`
are set from, on the chip, at the cell's own size and over the cell's
own number of checked requests, several seeds in one process (as
`control_keye.py`):

    python3 benchmarks/control_trinity.py --workload <cell> --seeds 2 --stop-after 1300

One warm server; for each seed the model is given that seed's weights,
the mix's contexts are registered anew (the rings and pages hold the OLD
weights' state otherwise) and a short open-loop window at the cell's own
rate is driven over questions no earlier window sent. The answers the
cell would check (`pick_checked`: `checked_requests` of them,
`checked_modules` on modules) are held against the float32 reference's
one full forward over context ++ question: the SOUND reading. Then, over
the SAME requests, one reading a variant, each passed through the
runner's own `check_answers` with the cell's limits, so that a row says
what a run of the cell would have said (`correct`, `fails`):

  control        `reference_trinity.forward(..., lower=True)`: int8
                 matmul operands; router and logits bfloat16; cached
                 keys and values rounded to 3 mantissa bits. Its OWN
                 answers against the reference.

and the served answers against the reference computed with one FAULT the
cache of two geometries or the layers can have:

  one_token_short    the reference over the context less its last token;
  foreign_pages      the full layers read ANOTHER context's pages (a row
                     given another row's page list), the rings its own;
                     and `foreign_pages_modules_only`, from the same
                     forward: the fault in the requests on modules alone
                     (the median over twelve is the files' to decide);
  ring_first         the window layers' ring holds the context's FIRST
                     2,048 tokens (written without `p mod 2048`);
  rotary_everywhere  the full layers rotated too;
  no_attn_gate       the attention's output gate dropped;
  no_post_norms      the two post-norms of every layer dropped;
  no_route_scale     `route_scale` dropped.

The last seed reads every variant, those nearest a limit first (`ORDER`);
the seeds before it the first `--nearest` of them. No forward starts
after `--stop-after` seconds: what was left out is said. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen, reference_lm, reference_trinity  # noqa: E402
from benchmarks.runners import serve, serve_lm_ctx, serve_lm_paged_ctx  # noqa: E402

FAULTS = ("one_token_short", "foreign_pages", "ring_first",
          "rotary_everywhere", "no_attn_gate", "no_post_norms",
          "no_route_scale")
# the order they are read in: those nearest a limit first
ORDER = ("rotary_everywhere", "foreign_pages", "no_route_scale", "control",
         "one_token_short", "no_attn_gate", "no_post_norms", "ring_first")


def fault_runs(pool, sequences, contexts):
    """What `reference_trinity.forward` is given for each fault: the
    sequences, each one's context tokens, and for the two faults of the
    cache other tokens: the context less its last token, and the NEXT
    context of the sequence's class (cut or repeated to the right
    context's length, so that positions line up) for the foreign
    pages."""
    held = [len(pool["contexts"][c]) for c in contexts]
    short, foreign = [], []
    for s, c, own in zip(sequences, contexts, held):
        same = [k for k, cls in enumerate(pool["class_of"])
                if cls == pool["class_of"][c]]
        other = pool["contexts"][same[(same.index(c) + 1) % len(same)]]
        short.append(np.concatenate([s[:own - 1], s[own:]]))
        foreign.append(np.concatenate([np.resize(other, own), s[own:]]))
    runs = {"one_token_short": dict(sequences=short,
                                    context=[n - 1 for n in held]),
            "foreign_pages": dict(sequences=sequences, context=held,
                                  fault="foreign_pages", other=foreign)}
    for fault in FAULTS[2:]:
        runs[fault] = dict(sequences=sequences, context=held, fault=fault)
    return runs


def request_gaps(reference, served) -> list:
    """Each request's own widest score difference (what
    `served_score_gap_median` is the median of)."""
    return [reference_lm.served_gap(
        reference["logits"][i:i + 1], served["ids"][i:i + 1],
        served["logits"][i:i + 1])["score_gap"]
        for i in range(len(served["sequences"]))]


def reading(cell, seed, served, pool, limits, reference) -> dict:
    """`served` against `reference` as a run of the cell reads it: the
    runner's own checks, limits and counts."""
    checks = serve_lm_paged_ctx.check_answers(cell, seed, served, limits,
                                              pool, reference=reference)
    row = {c["name"]: c["value"] for c in checks if c["name"] in limits}
    row["correct"] = all(c["ok"] for c in checks)
    row["fails"] = [c["name"] for c in checks if not c["ok"]]
    row["score_gap_by_request"] = request_gaps(reference, served)
    return row


def variant_readings(cell, seed, served, pool, limits, ref, variants,
                     go_on=lambda: True):
    """(variant, reading) for each of `variants` while `go_on()`; a
    variant it did not reach reads None. `ref`: the sound reference of
    `served["sequences"]`."""
    last = len(cell.traffic["context_classes"]) - 1
    on_module = np.asarray([pool["class_of"][c] == last
                            for c in served["contexts"]])
    runs = fault_runs(pool, served["sequences"], served["contexts"])
    for variant in variants:
        if not go_on():
            yield variant, None
            continue
        if variant == "control":
            low = reference_trinity.forward(seed, cell.config,
                                            served["sequences"], lower=True)
            ids, logits = reference_lm.own_answers(low["logits"],
                                                   served["ids"].shape[1])
            yield variant, reading(
                cell, seed, dict(served, ids=ids, logits=logits,
                                 routing=low["chosen_last"]), pool, limits,
                ref)
            continue
        bad = reference_trinity.forward(seed, cell.config, **runs[variant])
        yield variant, reading(cell, seed, served, pool, limits, bad)
        if variant == "foreign_pages":
            mixed = {k: np.where(
                on_module.reshape((-1,) + (1,) * (ref[k].ndim - 1)),
                bad[k], ref[k]) for k in ("logits", "chosen_last")}
            yield variant + "_modules_only", reading(
                cell, seed, served, pool, limits, mixed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--nearest", type=int, default=4,
                    help="how many of ORDER every seed but the last reads")
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--stop-after", type=float, default=float("inf"))
    args = ap.parse_args(argv)
    began = time.perf_counter()

    def go_on():
        return time.perf_counter() - began < args.stop_after
    cell = common.Cell(ROOT, args.workload)
    limits = cell.limits()
    serving = serve_lm_paged_ctx.ServingPagedCtx(cell, args.first_seed)
    pool, model, used, out = serving.pool, serving.model, 0, []
    try:
        for i in range(args.seeds):
            if not go_on():
                break
            seed = args.first_seed + 7919 * i
            if i:
                # the weights and the cache were freed for the last seed's
                # reference: this seed's are made, the book starts empty
                # (the ids are the same, so the bodies stand)
                model.set_params({
                    name: reference_trinity.make_leaf(
                        seed, cell.config, name, shape, dtype, init)
                    for name, shape, dtype, init
                    in reference_trinity.all_leaves(cell.config)})
                model.cache = model.module.init_cache(
                    model.lm, model.contexts.slots, model.contexts.pages,
                    model.register_chunk)
                for tokens in pool["contexts"]:
                    serving.register(tokens)
            arrivals = loadgen.schedule(seed, args.seconds, cell.traffic)
            for a in arrivals:
                a["body_index"] += used
            used += len(arrivals)
            checked = set(serve_lm_paged_ctx.pick_checked(
                seed, arrivals, pool, cell.traffic))
            drove = serving.drive(arrivals, checked)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
            served = serve_lm_ctx.served_answers(
                drove["results"], drove["plan"], pool, serving.context_ids)
            # room for the reference: 12 GB of weights leave none
            for leaf in list(model.params.values()) + list(model.cache):
                leaf.delete()
            ref = reference_trinity.forward(seed, cell.config,
                                            served["sequences"])
            row = dict(
                {"seed": seed, "variant": "sound", "failed": got["failed"],
                 "attempted": got["attempted"],
                 "p50_ms": got["request_p50_ms"],
                 "requests": len(served["sequences"]),
                 "tokens": sum(len(s) for s in served["sequences"]),
                 "class_by_request": [pool["class_of"][c]
                                      for c in served["contexts"]],
                 "expert_sets_equal_share": reference_lm.same_expert_sets(
                     served["routing"], ref["chosen_last"])},
                **reading(cell, seed, served, pool, limits, ref))
            print(json.dumps(row), flush=True)
            out.append(row)
            variants = (ORDER if i == args.seeds - 1
                        else ORDER[:args.nearest])
            for variant, seen in variant_readings(
                    cell, seed, served, pool, limits, ref, variants, go_on):
                row = dict({"seed": seed, "variant": variant,
                            "at_s": round(time.perf_counter() - began, 1)},
                           **(seen or {"left_out": True}))
                print(json.dumps(row), flush=True)
                out.append(row)
    finally:
        # what the last reference freed is neither described nor freed
        # again by the drain
        model.params, model.cache = {}, ()
        serving.close()
    read = [r for r in out if "left_out" not in r]
    summary = {"workload": cell.name, "limits": limits,
               "seeds": sorted({r["seed"] for r in read}),
               "left_out": [(r["seed"], r["variant"]) for r in out
                            if "left_out" in r],
               "read_correct": sorted({r["variant"] for r in read
                                       if r["correct"]})}
    for name in limits:
        sound = [r[name] for r in read if r["variant"] == "sound"]
        other = {r["variant"] for r in read} - {"sound"}
        summary[name] = {
            "sound_max": max(sound, default=None),
            **{v + "_min": min(r[name] for r in read if r["variant"] == v)
               for v in sorted(other)}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
