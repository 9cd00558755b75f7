#!/usr/bin/env python3
"""The readings the limits of a `serve_lm_session_ctx` cell's `correct`
are set from, on the chip, at the cell's own widths (as
`control_trinity.py`):

    python3 benchmarks/control_solar.py --workload <cell> --seeds 1 --stop-after 1300

One warm server a seed; the mix's sessions registered, a short open-loop
window of kept turns at the cell's own rate, then the plain questions.
The answers the cell would check (`pick_checked`, over a SMALLER budget
of reference tokens, `--reference-tokens`, so that ten forwards fit a
call) are held against the float32 reference's one forward a session:
the SOUND reading. Then, one reading a variant, each through the
runner's own `check_answers` with the cell's limits (`fails` names the
LIMITS a run of the cell would have failed by):

  control   `reference_solar.forward(..., lower=True)`: int8 matmul
            operands; router and logits bfloat16; the state held in
            bfloat16 from token to token; pages at 3 mantissa bits. Its
            OWN answers against the reference.

and the served answers against the reference computed with one FAULT
(`reference_solar.FAULTS`: the nine of its docstring). A fault is read at
a sequence's END, so each checked read becomes a sequence of its own,
cut behind the read, with the starts of the kept turns up to it; the
foreign state is the NEXT session's first `--foreign-tokens` tokens.

No forward starts after `--stop-after` seconds: what was left out is
said. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, reference_lm, reference_solar  # noqa: E402
from benchmarks.runners import serve  # noqa: E402
from benchmarks.runners import serve_lm_session_ctx as runner  # noqa: E402

# the order they are read in: those expected nearest a limit first
ORDER = ("control", "tails_zeroed", "head_decay", "b_not_doubled",
         "state_not_written", "page_start", "no_gqa_gate", "foreign_state",
         "no_erase", "no_qk_norm")


def by_read(served: dict, pool: dict, foreign_tokens: int) -> dict:
    """Each checked read as a sequence of its own, cut behind the read:
    what a fault's forward is given."""
    sequences, starts, others = [], [], []
    n = len(pool["sessions"])
    for sequence, reads, begun, s in zip(
            served["sequences"], served["read_at"], served["starts"],
            served["sessions"]):
        for at in reads:
            sequences.append(sequence[:at + 1])
            starts.append([b for b in begun if b <= at] or [0])
            others.append(pool["sessions"][(s + 1) % n][:foreign_tokens])
    return {"sequences": sequences, "starts": starts, "others": others}


def reading(cell, seed, served, limits, reference) -> dict:
    """`served` against `reference` as a run of the cell reads it: the
    runner's own checks and limits."""
    checks = runner.check_answers(cell, seed, served, limits,
                                  reference=reference)
    row = {c["name"]: c["value"] for c in checks if c["name"] in limits}
    row["fails"] = [c["name"] for c in checks
                    if not c["ok"] and c["name"] in limits]
    row["score_gap_by_read"] = [reference_lm.served_gap(
        reference["logits"][i:i + 1], served["ids"][i:i + 1],
        served["logits"][i:i + 1])["score_gap"]
        for i in range(len(served["ids"]))]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--reference-tokens", type=int, default=120_000)
    ap.add_argument("--foreign-tokens", type=int, default=16_384)
    ap.add_argument("--variants", default=",".join(ORDER))
    ap.add_argument("--stop-after", type=float, default=float("inf"))
    args = ap.parse_args(argv)
    began = time.perf_counter()

    def go_on():
        return time.perf_counter() - began < args.stop_after
    cell = common.Cell(ROOT, args.workload)
    limits = cell.limits()
    # fewer and shorter sessions than a run checks: ten forwards a seed
    cell.traffic = dict(cell.traffic, checked_sessions=2, checked_turns=4,
                        checked_last=1,
                        reference_tokens=args.reference_tokens)
    out = []
    for i in range(args.seeds):
        if not go_on():
            break
        seed = args.first_seed + 7919 * i
        serving = runner.ServingSessions(cell, seed)
        try:
            arrivals = runner.turn_schedule(seed, args.seconds, cell.traffic)
            drove = serving.drive(arrivals)
            picked = runner.pick_checked(seed, serving, drove["results"],
                                         cell.traffic)
            spare = (int(cell.traffic["request_pool"])
                     + int(cell.traffic["warm_requests"]))
            s = next(iter(picked))
            asked = serving.pool["blocks"][spare][
                :int(cell.traffic["length"]["median"])]
            questions = {s: (asked, serving.ask(s, asked, keep=False))}
            served = runner.served_answers(serving, drove["results"], picked,
                                           questions)
            got = serve.summarize(drove["results"],
                                  drove["plan"]["deadline_ms"])
        finally:
            serving.close()
        ref = reference_solar.forward(seed, cell.config, served["sequences"],
                                      served["read_at"])
        row = dict(
            {"seed": seed, "variant": "sound", "failed": got["failed"],
             "attempted": got["attempted"], "p50_ms": got["request_p50_ms"],
             "reads": len(served["ids"]),
             "tokens": sum(len(q) for q in served["sequences"]),
             "expert_sets_equal_share": reference_lm.same_expert_sets(
                 served["routing"], ref["chosen_last"])},
            **reading(cell, seed, served, limits, ref))
        print(json.dumps(row), flush=True)
        out.append(row)
        cut = by_read(served, serving.pool, args.foreign_tokens)
        for variant in args.variants.split(","):
            if not go_on():
                out.append({"seed": seed, "variant": variant,
                            "left_out": True})
                print(json.dumps(out[-1]), flush=True)
                continue
            if variant == "control":
                low = reference_solar.forward(
                    seed, cell.config, served["sequences"],
                    served["read_at"], lower=True)
                ids, logits = reference_lm.own_answers(
                    low["logits"], served["ids"].shape[1])
                seen = reading(cell, seed, dict(
                    served, ids=ids, logits=logits,
                    routing=low["chosen_last"]), limits, ref)
            else:
                bad = reference_solar.forward(
                    seed, cell.config, cut["sequences"], fault=variant,
                    starts=cut["starts"], others=cut["others"])
                seen = reading(cell, seed, served, limits, bad)
            row = dict({"seed": seed, "variant": variant,
                        "at_s": round(time.perf_counter() - began, 1)},
                       **seen)
            print(json.dumps(row), flush=True)
            out.append(row)
        jax.clear_caches()
    read = [r for r in out if "left_out" not in r]
    summary = {"workload": cell.name, "limits": limits,
               "left_out": [(r["seed"], r["variant"]) for r in out
                            if "left_out" in r],
               "read_correct": sorted({r["variant"] for r in read
                                       if not r["fails"]})}
    for name in limits:
        sound = [r[name] for r in read if r["variant"] == "sound"]
        other = sorted({r["variant"] for r in read} - {"sound"})
        summary[name] = {
            "sound_max": max(sound, default=None),
            **{v + "_min": min(r[name] for r in read if r["variant"] == v)
               for v in other}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
