"""Runner kind `serve_lm_paged_ctx`: `serve_lm_ctx`'s deployment (contexts
registered through `POST /contexts` during set-up, then open-loop `POST
/score` against them) for a model that holds a context in TWO
geometries, a ring of its last tokens in the window layers and pages of
all its tokens in the full layers (configuration `trinity-mini-pp4`),
under traffic of two CLASSES of context in one queue: a few long ones
(modules) that most questions ask, and many short ones (files).

From `runners/serve_lm_ctx.py` and `serve_lm.py`, unchanged: the drive,
the body files (`write_bodies`), the facts of a window
(`registry_total`, `counter_delta`, `router_facts`) and
`served_answers`. Written here: the two-class pool, the picking of
checked requests by class, and what names the model
(`ServingPagedCtx.__init__`, `seed_weights`, `check_answers`,
`paged_facts`, `run`: PERF.md section 7 lists the copies for the
`benchmark` issue that gives the runner kinds a model hook).

`correct`: once the window has closed and the program's arrays are
freed, `checked_requests` of the requests it finished, `checked_modules`
of them on contexts of the LAST class (the longest asked among them) and
the rest on the others, over at least `checked_contexts` distinct
contexts, are each scored ONCE by the float32 reference
(`benchmarks/reference_trinity.py`) as one full forward over context ++
question: no cache, no ring, no pages, no chunks, the window a mask.
Compared are the numbers `serve_lm` compares (`reference_lm.served_gap`).
A ring that did not wrap, a foreign or a missing page, a row that read
another row's list, a wrong position offset or a slot read one token
short is another sequence's answer.

README.serve_lm_paged_ctx.md has the traffic file's keys.
"""

from __future__ import annotations

import os
import random
import time
from typing import Dict, List

import numpy as np

from benchmarks import common, loadgen, readers
from benchmarks.runners import serve_lm, serve_lm_ctx
from benchmarks.runners.serve import TRACE_WINDOW_S, summarize
from benchmarks.runners.serve_lm_ctx import (
    _lengths, _zipf_cdf, registry_total, served_answers,
)

PROGRAM = serve_lm_ctx.PROGRAM
ATTENTION = ("gqa_proj", "window_attend", "full_attend", "attn_gate")
SCOPES = ATTENTION + ("moe_route", "moe_experts", "moe_shared", "dense_mlp",
                      "lm_head")
KERNELS = serve_lm_ctx.KERNELS


# ------------------------------------------------------------- the traffic

def make_pool(config: Dict, traffic: Dict) -> Dict:
    """The mix's contexts and questions from its own `corpus_seed`: the
    contexts class by class in the file's order (`class_of` a context),
    lengths log-normal, clipped; token ids Zipf over the vocabulary rows
    held (id = rank - 1); a question's class by the classes' `share`,
    its context Zipf within the class (the class's first context the
    most asked). The same for every seed."""
    rng = np.random.default_rng(int(traffic["corpus_seed"]))
    rows = int(config["vocab_rows"])
    ids = _zipf_cdf(rows, traffic["id_zipf"])

    def tokens(length):
        return np.minimum(np.searchsorted(ids, rng.random(int(length))),
                          rows - 1).astype(np.int32)
    classes = traffic["context_classes"]
    contexts, class_of, first = [], [], []
    for k, spec in enumerate(classes):
        first.append(len(contexts))
        for n in _lengths(rng, int(spec["contexts"]), spec["length"]):
            contexts.append(tokens(n))
            class_of.append(k)
    n = int(traffic["request_pool"]) + int(traffic["warm_requests"])
    lengths = _lengths(rng, n, traffic["length"])
    shares = np.cumsum([float(spec["share"]) for spec in classes])
    which = np.minimum(np.searchsorted(shares / shares[-1], rng.random(n)),
                       len(classes) - 1)
    within = rng.random(n)
    cdfs = [_zipf_cdf(int(spec["contexts"]), traffic["context_zipf"])
            for spec in classes]
    context_of = [first[k] + min(int(np.searchsorted(cdfs[k], u)),
                                 len(cdfs[k]) - 1)
                  for k, u in zip(which, within)]
    return {"contexts": contexts, "class_of": class_of,
            "lengths": lengths.tolist(), "context_of": context_of,
            "questions": [tokens(length) for length in lengths]}


def pick_checked(seed: int, arrivals: List[Dict], pool: Dict,
                 traffic: Dict) -> List[int]:
    """Arrivals whose answers are kept: `checked_modules` on contexts of
    the LAST class (the first of them on the longest context asked, the
    others on other contexts of the class while there are any), the rest
    on the other classes, new contexts first; from a shuffle by the
    seed."""
    last = len(traffic["context_classes"]) - 1
    context_of = [pool["context_of"][a["body_index"]] for a in arrivals]
    order = list(range(len(arrivals)))
    random.Random(int(seed) ^ 0x5EED).shuffle(order)
    long_ones = [i for i in order if pool["class_of"][context_of[i]] == last]
    others = [i for i in order if pool["class_of"][context_of[i]] != last]
    long_ones.sort(key=lambda i: -len(pool["contexts"][context_of[i]]))

    def novel_first(candidates, want):
        picked, seen = [], set()
        for novel_only in (True, False):
            for i in candidates:
                if len(picked) >= want:
                    break
                if i in picked or (novel_only and context_of[i] in seen):
                    continue
                picked.append(i)
                seen.add(context_of[i])
        return picked
    modules = novel_first(long_ones, int(traffic["checked_modules"]))
    return modules + novel_first(
        others, int(traffic["checked_requests"]) - len(modules))


# ------------------------------------------------------------- the program

class ServingPagedCtx(serve_lm_ctx.ServingCtx):
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
        from benchmarks import reference_trinity
        self.cell, self.seed = cell, seed
        common.program_log_to(os.path.join(cell.work, "program.log"))
        self.pool = make_pool(cell.config, cell.traffic)
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
        want = reference_trinity.all_leaves(cell.config)
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
        book = self.model.contexts
        common.say(f"{len(self.context_ids)} contexts of "
                   f"{sum(len(c) for c in self.pool['contexts'])} tokens "
                   f"registered in {time.perf_counter() - t:.1f}s: "
                   f"{sum(len(h.pages) for h in book.held().values())} of "
                   f"{book.pages} pages, {len(book.held())} of {book.slots} "
                   f"ring slots")
        if sorted(book.held()) != sorted(set(self.context_ids)):
            raise common.NoResult("the cache does not hold the mix's "
                                  "contexts: too few ring slots or pages")
        self.bodies = {"dir": serve_lm_ctx.write_bodies(
            cell.work, self.pool, self.context_ids,
            int(cell.traffic["top_k"])), "lengths": self.pool["lengths"]}
        n_pool = int(cell.traffic["request_pool"])
        for i in range(int(cell.traffic["warm_requests"])):
            text, params = self.body(n_pool + i)
            self.server.handle("score", text, params=params)

    def seed_weights(self, seed: int) -> None:
        """The benchmark's weights from the seed in place of the restored
        ones, leaf by leaf, each freed before its successor is made."""
        from benchmarks import reference_trinity
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_trinity.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_trinity.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})


# --------------------------------------------------------------- the facts

def step_counts(cell: common.Cell, sent: List[Dict], pool: Dict) -> Dict:
    """Summed over the requests `sent`: real question tokens, and the
    visible keys and (query, visible key) pairs a layer of each kind
    (`roofline_trinity.row_counts`)."""
    from benchmarks import roofline_trinity
    window = int(cell.config["sliding_window"])
    total = {"tokens": 0, "full_keys": 0, "full_pairs": 0,
             "window_keys": 0, "window_pairs": 0}
    for a in sent:
        q = pool["lengths"][a["body_index"]]
        held = len(pool["contexts"][pool["context_of"][a["body_index"]]])
        total["tokens"] += q
        for key, n in roofline_trinity.row_counts(held, q, window).items():
            total[key] += n
    return total


def pool_facts(registry: common.RegistryWindow) -> Dict[str, float]:
    """Of one window, from the program's counters: pages the full
    layers' loop walked over pages the rows held; 1.0 when no row rode a
    longer row's trips."""
    visited = serve_lm.counter_delta(registry, "score_pages_visited_total")
    needed = serve_lm.counter_delta(registry, "score_pages_needed_total")
    if not visited or not needed:
        return {}
    return {"pages_visited_over_needed": visited / needed}


def paged_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                traced: common.RegistryWindow, sent: List[Dict], pool: Dict
                ) -> Dict[str, float]:
    """`full_attend_roofline`, `window_attend_roofline`,
    `moe_gated_experts_roofline`, `attention_share_of_step_pct` and
    `score_step_mfu`, in percent, from the TRACED window: the floors of
    benchmarks/roofline_trinity.py at the window's mean counts a step
    (steps and real rows from the histogram `serving_batch_rows`, the
    keys and pairs from the questions `sent` and their contexts'
    lengths, the experts' from the router's series) over the mean device
    time a step of the scopes and of the whole program. Nothing where
    the trace or the program's series give nothing to read."""
    from benchmarks import (roofline_glm, roofline_trinity, trace_reduce,
                            trace_scopes)
    rows = traced.histogram("serving_batch_rows")
    router = serve_lm.router_facts(traced)
    if not rows or not sent or "assignments_per_step_layer" not in router:
        return {}
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES, KERNELS)
    if got is None:
        return {}
    whole = trace_reduce.program_time(trace_reduce.load_xplane(trace_dir),
                                      PROGRAM)
    if whole is None or whole["seconds_per_run"] <= 0:
        return {}
    steps, step_s = rows[1], whole["seconds_per_run"]
    kinds = cell.config["layer_types"][:int(cell.config["layers"])]
    layers = {"window": kinds.count("sliding_attention"),
              "full": kinds.count("full_attention")}
    experts = int(cell.config["layers"]) - min(
        int(cell.config["num_dense_layers"]), int(cell.config["layers"]))
    mean = {k: v / steps for k, v in step_counts(cell, sent, pool).items()}
    out = {"steps_traced": float(steps), "rows_per_step": rows[0] / steps,
           "step_device_ms": step_s * 1e3}
    for scope in SCOPES:
        out[scope + "_scope_ms_per_step"] = (
            got["seconds"][scope] / got["runs"] * 1e3)
    out["attention_share_of_step_pct"] = 100.0 * sum(
        got["seconds"][s] for s in ATTENTION) / got["runs"] / step_s
    floors = {
        kind + "_attend": (kind + "_attend", layers[kind],
                           roofline_trinity.attend_floor(
            cell.config, mean["tokens"], mean[kind + "_keys"],
            mean[kind + "_pairs"], device_kind))
        for kind in ("window", "full")}
    floors["moe_gated_experts"] = (
        "moe_experts", experts, roofline_glm.moe_gated_experts_floor(
            cell.config, router["assignments_per_step_layer"],
            router["experts_hit_per_step_layer"], device_kind))
    for name, (scope, count, floor) in floors.items():
        measured = got["seconds"][scope] / (got["runs"] * max(count, 1))
        if measured > 0 and count:
            out[name + "_roofline"] = 100.0 * floor["seconds"] / measured
            out[name + "_ms_per_layer"] = measured * 1e3
            out[name + "_floor_bound"] = floor["bound"]
    step = roofline_trinity.score_step_floor(
        cell.config, rows[0] / steps, mean["tokens"],
        {"keys": mean["window_keys"], "pairs": mean["window_pairs"]},
        {"keys": mean["full_keys"], "pairs": mean["full_pairs"]},
        router["assignments_per_step_layer"],
        router["experts_hit_per_step_layer"], device_kind)
    out["score_step_mfu"] = 100.0 * step["seconds"] / step_s
    out["score_step_floor_ms"] = step["seconds"] * 1e3
    out["score_step_floor_bound"] = step["bound"]
    return out


# -------------------------------------------------------------- the checks

def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict,
                  pool: Dict, reference: Dict = None) -> List[Dict]:
    """The checks of `correct` on the sampled answers. `reference` is
    what they are held against, `reference_trinity.forward`'s output for
    `served["sequences"]`: computed here unless handed in
    (benchmarks/control_trinity.py hands in the reference computed with
    a fault, so that a fault's reading passes through the very limits
    and counts a run's does)."""
    from benchmarks import reference_lm, reference_trinity
    n, distinct = len(served["sequences"]), len(set(served["contexts"]))
    last = len(cell.traffic["context_classes"]) - 1
    modules = sum(pool["class_of"][c] == last for c in served["contexts"])
    checks = [{"name": "answers_malformed", "value": served["malformed"],
               "limit": 0, "ok": served["malformed"] == 0, "note": ""}]
    for name, value, key in (("served_requests_checked", n,
                              "checked_requests"),
                             ("served_contexts_checked", distinct,
                              "checked_contexts"),
                             ("served_modules_checked", modules,
                              "checked_modules")):
        least = int(cell.traffic[key])
        checks.append({"name": name, "value": value, "limit": least,
                       "ok": value >= least, "note": "at least"})
    if not n:
        return checks
    ref = reference or reference_trinity.forward(seed, cell.config,
                                                 served["sequences"])
    got = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    tokens = sum(len(s) for s in served["sequences"])
    note = (f"{n} served requests on {distinct} contexts ({modules} on "
            f"modules), {tokens} tokens through the reference, the longest "
            f"{max(len(s) for s in served['sequences'])}")
    for name, key in (("served_top_logit_gap", "top_gap"),
                      ("served_score_gap", "score_gap"),
                      ("served_score_gap_median", "score_gap_median")):
        checks.append({"name": name, "value": got[key],
                       "limit": limits[name],
                       "ok": bool(got[key] <= limits[name]), "note": note})
    same = reference_lm.same_expert_sets(served["routing"],
                                         ref["chosen_last"])
    # reported, no limit: near-ties flip under bfloat16
    print(f"note expert_sets_equal_share: {same!r} over "
          f"{served['routing'].shape[0] * served['routing'].shape[1]} "
          f"(request, expert layer) choices at the last position",
          flush=True)
    return checks


# ------------------------------------------------------------------ the run

def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    serving = ServingPagedCtx(cell, seed, require_tpu)
    try:
        arrivals = loadgen.schedule(seed, seconds, cell.traffic)
        checked = set(pick_checked(seed, arrivals, serving.pool,
                                   cell.traffic))
        drove = serving.drive(arrivals, checked)
        trace_dir, tail, tail_drove = None, [], None
        if trace:
            # a short window of its own, over questions the timed window
            # did not send (runners/serve_lm.py says why)
            trace_dir = os.path.join(cell.work, "trace")
            tail = loadgen.schedule(seed, TRACE_WINDOW_S, cell.traffic)
            for a in tail:
                a["body_index"] += len(arrivals)
            if len(arrivals) + len(tail) > int(cell.traffic["request_pool"]):
                raise common.NoResult("the pool of bodies is too small for "
                                      "the timed and the traced window")
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
                                   serving.context_ids), cell.limits(),
        serving.pool)
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
        facts.update(pool_facts(drove["registry"]))
        facts.update(paged_facts(cell, device["kind"], trace_dir,
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
                f"{facts['score_step_floor_bound']}: score_step_mfu "
                f"{facts['score_step_mfu']:.2f} %; "
                f"moe_gated_experts_roofline "
                f"{facts.get('moe_gated_experts_roofline', 0.0):.2f} %); "
                f"device ms a step by scope: " + ", ".join(
                    f"{s} {facts[s + '_scope_ms_per_step']:.3f}"
                    for s in SCOPES))
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, got["attempted"], got["failed"],
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result
