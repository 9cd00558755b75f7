"""Runner kind `serve_lm_state_ctx`: `serve_lm_ctx`'s deployment
(contexts registered through `POST /contexts` during set-up, then
open-loop `POST /score` against them) for a model that holds a context
as a STATE OF FIXED SIZE (configuration `brumby-14b-pp8`, power
retention), under traffic that arrives in BURSTS: one question sent at
one instant as `burst_rows` requests, each on another registered
context (a reranker scoring its best-retrieved files against a query).

From `runners/serve_lm_ctx.py` and `serve_lm.py`, unchanged: the
contexts and questions of the mix (`make_pool`), the drive, the facts of
a window (`registry_total`, `counter_delta`) and `served_answers`.
Written here: the burst schedule and its bodies, and what names the
model (`ServingStateCtx.__init__`, `seed_weights`, `check_answers`,
`retention_facts`, `run`: PERF.md section 7 lists the copies for the
`benchmark` issue that gives the runner kinds a model hook).

`correct`: once the window has closed and the program's arrays are
freed, `checked_requests` of the requests it finished, over at least
`checked_contexts` distinct contexts and with the longest context asked
among them, are each scored ONCE by the float32 reference
(`benchmarks/reference_brumby.py`) as one full forward over context ++
question in the QUADRATIC form: no state, no feature map, no chunks, no
cache, no slot. Compared are the numbers `serve_lm` compares
(`reference_lm.served_gap`). A state carried wrongly from chunk to
chunk, a slot that leaks what it held, a row that read another row's
slot, a wrong position offset or a state rounded on its way is another
sequence's answer.

README.serve_lm_state_ctx.md has the traffic file's keys.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks import common, loadgen, readers
from benchmarks.runners import serve_lm, serve_lm_ctx
from benchmarks.runners.serve import TRACE_WINDOW_S, summarize
from benchmarks.runners.serve_lm_ctx import registry_total, served_answers

PROGRAM = serve_lm_ctx.PROGRAM
RETENTION = ("retention_intra", "retention_state_read",
             "retention_state_update")
SCOPES = RETENTION + ("retention_proj", "dense_mlp", "lm_head")


# ------------------------------------------------------------- the traffic

def burst_contexts(traffic: Dict, burst: int) -> List[int]:
    """The contexts burst number `burst` (in due order) asks: distinct,
    drawn without replacement with Zipf weights, the same for every
    seed."""
    n = int(traffic["contexts"])
    p = 1.0 / np.arange(1, n + 1) ** float(traffic["context_zipf"])
    rng = np.random.default_rng([int(traffic["schedule_seed"]), int(burst)])
    return rng.choice(n, size=int(traffic["burst_rows"]), replace=False,
                      p=p / p.sum()).tolist()


def burst_schedule(seed: int, seconds: float, traffic: Dict,
                   first_question: int = 0) -> List[Dict]:
    """`round(rate * seconds)` bursts at `loadgen.schedule`'s instants
    (Poisson, the same for every seed), burst k the question that
    schedule deals it (the seed's order) on `burst_contexts(k)`. One
    arrival a request: [{"due_s", "body_index", "burst", "question",
    "context"}], `body_index` naming the (question, context) pair."""
    n_ctx = int(traffic["contexts"])
    out = []
    for k, burst in enumerate(loadgen.schedule(seed, seconds, traffic)):
        question = first_question + burst["body_index"]
        for c in burst_contexts(traffic, k):
            out.append({"due_s": burst["due_s"], "burst": k,
                        "question": question, "context": c,
                        "body_index": question * n_ctx + c})
    return out


def pick_checked(seed: int, arrivals: List[Dict], pool: Dict,
                 traffic: Dict) -> List[int]:
    """`serve_lm_ctx.pick_checked` (the longest context asked first, then
    new contexts, then any) over arrivals that name their context
    themselves."""
    asked = {a["body_index"]: a["context"] for a in arrivals}
    return serve_lm_ctx.pick_checked(seed, arrivals,
                                     dict(pool, context_of=asked), traffic)


# ------------------------------------------------------------- the program

class ServingStateCtx(serve_lm_ctx.ServingCtx):
    """The system under test, up and warm, its contexts registered."""

    def __init__(self, cell: common.Cell, seed: int,
                 require_tpu: bool = True):
        try:
            from code2vec_tpu.lm_facade import MODEL_MODULES, ScoringModel
            ScoringModel.register_context
            MODEL_MODULES[cell.config["model_type"]]
        except (ImportError, AttributeError, KeyError) as e:
            # a tree without the model or its cache ends here, at once
            raise common.NoResult(
                f"this checkout's program does not run the "
                f"configuration's model: {e!r}")
        common.configure_jax()
        self.device = common.require_chips(cell.chips, require_tpu)
        from code2vec_tpu.cli import config_from_args
        from code2vec_tpu.serving.server import PredictionServer
        from benchmarks import reference_brumby
        self.cell, self.seed = cell, seed
        common.program_log_to(os.path.join(cell.work, "program.log"))
        self.pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
        argv = serve_lm.program_argv(cell, seed)
        saved = os.path.join(cell.work, "checkpoint", "saved")
        if not os.path.isdir(saved):    # committed by a rename
            common.say("writing the deployment's checkpoint (first run in "
                       "this checkout)")
            first = ScoringModel(config_from_args(argv + ["--save", saved]))
            first.save()
            self._free(first)
            del first
        self.config = config_from_args(argv + ["--load", saved])
        self.model = ScoringModel(self.config)
        want = reference_brumby.all_leaves(cell.config)
        have = [(leaf.name, tuple(leaf.shape), leaf.dtype)
                for leaf in self.model.module.leaf_specs(self.model.lm)]
        if [w[:3] for w in want] != have:
            raise common.NoResult("the program's leaves are not the "
                                  "configuration file's")
        self.seed_weights(seed)
        self.server = PredictionServer(self.model, self.config)
        self.port = self.server.start(0, "127.0.0.1")
        t = time.perf_counter()
        self.model.warmup()
        common.say(f"{self.model.predict_compile_count()} shapes and the "
                   f"registration chunk warm in "
                   f"{time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        self.context_ids = [self.register(tokens)
                            for tokens in self.pool["contexts"]]
        common.say(f"{len(self.context_ids)} contexts of "
                   f"{sum(len(c) for c in self.pool['contexts'])} tokens "
                   f"registered in {time.perf_counter() - t:.1f}s")
        held = self.model.contexts.held()
        if sorted(held) != sorted(set(self.context_ids)):
            raise common.NoResult("the cache does not hold the mix's "
                                  "contexts: too few slots")
        self.bodies = {"dir": self._body_dir(),
                       "lengths": self.pool["lengths"]}
        n_pool = int(cell.traffic["request_pool"])
        n_ctx = len(self.context_ids)
        for i in range(int(cell.traffic["warm_requests"])):
            text, params = self.body((n_pool + i) * n_ctx + i % n_ctx)
            self.server.handle("score", text, params=params)

    def _body_dir(self) -> str:
        """Where the bodies lie; emptied when the ids the server gave are
        not those of the files there."""
        import shutil
        data = os.path.join(self.cell.work, "data")
        done = os.path.join(data, "context_ids.json")
        if not (os.path.exists(done)
                and common.load_json(done) == self.context_ids):
            shutil.rmtree(data, ignore_errors=True)
            os.makedirs(data)
            with open(done, "w") as f:
                json.dump(self.context_ids, f)
        return data

    def file(self, index: int) -> str:
        """The body of one (question, context) pair, written when first
        named: a window sends a few thousand of the pool's 131,072."""
        question, c = divmod(index, len(self.context_ids))
        path = os.path.join(self.bodies["dir"],
                            f"{question:05d}_{c:02d}.json")
        if not os.path.exists(path):
            with open(path, "w") as f:
                json.dump({"context": self.context_ids[c],
                           "ids": self.pool["questions"][question].tolist(),
                           "top_k": int(self.cell.traffic["top_k"]),
                           "return_routing": True}, f)
        return path

    def seed_weights(self, seed: int) -> None:
        """The benchmark's weights from the seed in place of the restored
        ones, leaf by leaf, each freed before its successor is made."""
        from benchmarks import reference_brumby
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_brumby.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_brumby.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})


# --------------------------------------------------------------- the facts

def retention_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                    traced: common.RegistryWindow, sent: List[Dict],
                    pool: Dict) -> Dict[str, float]:
    """`retention_read_roofline`, `retention_share_of_step_pct` and
    `score_step_mfu`, in percent, from the TRACED window: the floors of
    benchmarks/roofline_brumby.py at the window's mean counts a step
    (steps and real rows from the histogram `serving_batch_rows`, rows
    that read a state from the counter `retention_states_read_total`,
    real tokens and own pairs from the questions `sent`) over the mean
    device time a step of the scopes and of the whole program. Nothing
    where the trace or the program's series give nothing to read."""
    from benchmarks import roofline_brumby, trace_reduce, trace_scopes
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES)
    rows = traced.histogram("serving_batch_rows")
    states = serve_lm.counter_delta(traced, "retention_states_read_total")
    if got is None or not rows or not states:
        return {}
    whole = trace_reduce.program_time(trace_reduce.load_xplane(trace_dir),
                                      PROGRAM)
    if whole is None or whole["seconds_per_run"] <= 0:
        return {}
    layers, steps = int(cell.config["layers"]), rows[1]
    lengths = [pool["lengths"][a["question"]] for a in sent]
    tokens = sum(lengths) / steps
    pairs = sum(n * (n + 1) // 2 for n in lengths) / steps
    reading = states / (layers * steps)
    step_s = whole["seconds_per_run"]
    out = {"steps_traced": float(steps), "rows_per_step": rows[0] / steps,
           "step_device_ms": step_s * 1e3}
    for scope in SCOPES:
        out[scope + "_scope_ms_per_step"] = (
            got["seconds"][scope] / got["runs"] * 1e3)
    inside = sum(got["seconds"][s] for s in RETENTION) / got["runs"]
    out["retention_share_of_step_pct"] = 100.0 * inside / step_s
    read_s = sum(got["seconds"][s] for s in (
        "retention_state_read", "retention_intra")) / (got["runs"] * layers)
    floor = roofline_brumby.retention_read_floor(
        cell.config, reading, tokens, pairs, device_kind)
    if read_s > 0:
        out["retention_read_roofline"] = 100.0 * floor["seconds"] / read_s
        out["retention_read_ms_per_layer"] = read_s * 1e3
        out["retention_read_floor_bound"] = floor["bound"]
    step = roofline_brumby.score_step_floor(
        cell.config, rows[0] / steps, reading, tokens, pairs, device_kind)
    out["score_step_mfu"] = 100.0 * step["seconds"] / step_s
    out["score_step_floor_ms"] = step["seconds"] * 1e3
    out["score_step_floor_bound"] = step["bound"]
    return out


# -------------------------------------------------------------- the checks

def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict
                  ) -> List[Dict]:
    from benchmarks import reference_brumby, reference_lm
    n, distinct = len(served["sequences"]), len(set(served["contexts"]))
    checks = [{"name": "answers_malformed", "value": served["malformed"],
               "limit": 0, "ok": served["malformed"] == 0, "note": ""}]
    for name, value, key in (("served_requests_checked", n,
                              "checked_requests"),
                             ("served_contexts_checked", distinct,
                              "checked_contexts")):
        least = int(cell.traffic[key])
        checks.append({"name": name, "value": value, "limit": least,
                       "ok": value >= least, "note": "at least"})
    if not n:
        return checks
    ref = reference_brumby.forward(seed, cell.config, served["sequences"])
    got = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    tokens = sum(len(s) for s in served["sequences"])
    note = (f"{n} served requests on {distinct} contexts, {tokens} tokens "
            f"through the reference, the longest "
            f"{max(len(s) for s in served['sequences'])}")
    for name, key in (("served_top_logit_gap", "top_gap"),
                      ("served_score_gap", "score_gap"),
                      ("served_score_gap_median", "score_gap_median")):
        checks.append({"name": name, "value": got[key],
                       "limit": limits[name],
                       "ok": bool(got[key] <= limits[name]), "note": note})
    return checks


# ------------------------------------------------------------------ the run

def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    serving = ServingStateCtx(cell, seed, require_tpu)
    try:
        arrivals = burst_schedule(seed, seconds, cell.traffic)
        checked = set(pick_checked(seed, arrivals, serving.pool,
                                   cell.traffic))
        drove = serving.drive(arrivals, checked)
        trace_dir, tail, tail_drove = None, [], None
        if trace:
            # a short window of its own, over questions the timed window
            # did not send (runners/serve_lm.py says why)
            trace_dir = os.path.join(cell.work, "trace")
            bursts = 1 + max(a["burst"] for a in arrivals)
            tail = burst_schedule(seed, TRACE_WINDOW_S, cell.traffic,
                                  first_question=bursts)
            if 1 + max(a["question"] for a in tail) > int(
                    cell.traffic["request_pool"]):
                raise common.NoResult("the pool of questions is too small "
                                      "for the timed and the traced window")
            tail_drove = serving.drive(tail, trace_dir=trace_dir)
        registered = registry_total("context_register_seconds")
    finally:
        serving.close()
    results, plan = drove["results"], drove["plan"]
    got = summarize(results, plan["deadline_ms"])
    values = {"request_p50_ms": got["request_p50_ms"],
              "request_p95_ms": got["request_p95_ms"],
              "setup_s": drove["setup_s"]}
    common.say(f"window {drove['window_s']:.2f}s, {got['attempted']} "
               f"requests, {got['failed']} failed, p50 "
               f"{values['request_p50_ms']:.2f} ms, p95 "
               f"{values['request_p95_ms']:.2f} ms, generator late p95 "
               f"{readers.percentile(got['late_ms'], 95):.3f} ms")
    t_check = time.perf_counter()
    checks = check_answers(
        cell, seed, served_answers(results, plan, serving.pool,
                                   serving.context_ids), cell.limits())
    common.say(f"sampled answers scored by the reference in "
               f"{time.perf_counter() - t_check:.1f}s")
    checks.append({"name": "compiled_inside_window",
                   "value": drove["compiled_in_window"], "limit": 0,
                   "ok": drove["compiled_in_window"] == 0, "note": ""})
    correct = all(c["ok"] for c in checks)
    device = serving.device
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": drove["memory_peak"]}
    result = {"correct": correct, "checks": checks, "values": values,
              "device": dev, "attempted": got["attempted"],
              "failed": got["failed"]}
    if not emit:
        return result
    breakdown = None
    if trace:
        facts = {"request_p95_ms": got["request_p95_ms"]}
        if registered:
            facts["context_register_ms"] = 1e3 * registered[0] / registered[1]
        facts.update(retention_facts(cell, device["kind"], trace_dir,
                                     tail_drove["registry"], tail,
                                     serving.pool))
        traced = readers.read_traced(
            cell, device["kind"], drove["registry"], drove["window_s"],
            trace_dir, late_ms=got["late_ms"], facts=facts)
        dev.update(traced["device"])
        values, breakdown = traced["values"], traced["breakdown"]
        if "score_step_mfu" in facts:
            common.say(
                f"traced: {facts['steps_traced']:.0f} steps of "
                f"{facts['rows_per_step']:.2f} rows, "
                f"{facts['step_device_ms']:.3f} ms of device time a step "
                f"(floor {facts['score_step_floor_ms']:.3f} ms, bound by "
                f"{facts['score_step_floor_bound']}); device ms a step by "
                f"scope: " + ", ".join(
                    f"{s} {facts[s + '_scope_ms_per_step']:.3f}"
                    for s in SCOPES))
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, got["attempted"], got["failed"],
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result
