"""Runner kind `serve_lm_sparse_ctx`: `serve_lm_ctx`'s deployment and
traffic (contexts registered through `POST /contexts` during set-up,
then open-loop `POST /score` against them) for a model whose attention
attends a learned SELECTION of each context's keys (configuration
`keye-vl2-pp8`).

From `runners/serve_lm_ctx.py`, unchanged: the traffic (`make_pool`,
`write_bodies`), the drive, the picking of checked requests and the
facts of a window. Written here: what names the model. That file names
`reference_glm` inside `ServingCtx.__init__`, `seed_weights`,
`check_answers` and `roofline_facts`, so those four (and `run`, which
calls them) are this model's copies (PERF.md section 7 lists them for
the `benchmark` issue that gives the runner kind a model hook).

`correct`: once the window has closed and the program's arrays are
freed, `checked_requests` of the requests it finished, over at least
`checked_contexts` distinct contexts and with the longest context among
them, are each scored ONCE by the float32 reference
(`benchmarks/reference_keye.py`) as one full forward over context ++
question: no cache, no chunks, no slot, `lax.top_k` over each whole
causal row of index scores. Compared are the numbers `serve_lm`
compares (`reference_lm.served_gap`). The checked requests also ask for
the keys their last position attended (`return_selected`); the share of
the reference's kept keys that the served step kept too is printed
beside the share of equal expert sets, neither with a limit.

README.serve_lm_sparse_ctx.md has the traffic file's keys.
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
from benchmarks.runners.serve_lm_ctx import (
    attention_facts, pick_checked, registry_total,
)

PROGRAM = serve_lm_ctx.PROGRAM
SCOPES = ("index_score", "index_select", "sparse_attend", "moe_experts")
KERNELS = serve_lm_ctx.KERNELS


# ------------------------------------------------------------- the program

class ServingSparseCtx(serve_lm_ctx.ServingCtx):
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
        from benchmarks import reference_keye
        self.cell, self.seed = cell, seed
        self.ask_selected: set = set()
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
        want = reference_keye.all_leaves(cell.config)
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
        from benchmarks import reference_keye
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_keye.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_keye.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})

    def file(self, index: int) -> str:
        """A body's file; for the bodies of `ask_selected`, a copy that
        also asks for the keys the last position attended (the copy is
        written when first named)."""
        plain = super().file(index)
        if index not in self.ask_selected:
            return plain
        asking = plain[:-len(".json")] + ".sel.json"
        if not os.path.exists(asking):
            with open(asking, "w") as f:
                json.dump(dict(common.load_json(plain),
                               return_selected=True), f)
        return asking

    @staticmethod
    def _free(model) -> None:
        import jax
        for leaf in list(model.params.values()) + jax.tree.leaves(
                getattr(model, "cache", ())):
            leaf.delete()


# --------------------------------------------------------------- the facts

def selection_facts(registry: common.RegistryWindow) -> Dict[str, float]:
    """Of one window: `attention_facts`, and the selection's counts a
    step, summed over the layers: (query, key) pairs the indexer scored
    and keys kept; `keys_selected_pct`, kept over visible."""
    out = attention_facts(registry)
    steps = registry.histogram("serving_batch_tokens_fill_ratio")
    scored = serve_lm.counter_delta(registry,
                                    "score_index_pairs_scored_total")
    visible = serve_lm.counter_delta(registry, "score_keys_visible_total")
    kept = serve_lm.counter_delta(registry, "score_keys_selected_total")
    if steps and scored and visible and kept:
        out["index_pairs_per_step"] = scored / steps[1]
        out["selected_pairs_per_step"] = kept / steps[1]
        out["keys_selected_pct"] = 100.0 * kept / visible
    return out


def roofline_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                   traced: common.RegistryWindow) -> Dict[str, float]:
    """`index_select_roofline`, `sparse_attend_roofline` and
    `moe_gated_experts_roofline`, in percent: the floor of one (step,
    layer) at the traced window's mean counts over the scopes' mean
    device time a (step, layer). `keys_selected_pct` needs no trace.
    Nothing where the trace or the counters give nothing to read."""
    from benchmarks import roofline_glm, roofline_keye, trace_scopes
    facts = selection_facts(traced)
    out = {k: facts[k] for k in ("keys_selected_pct",) if k in facts}
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES, KERNELS)
    if got is None or "index_pairs_per_step" not in facts:
        return out
    layers = int(cell.config["layers"])
    queries, keys = facts["tokens_per_step"], facts["latents_per_step"]
    floors = {
        # the indexer's work is scoring AND selecting: both scopes
        "index_select": (("index_score", "index_select"),
                         roofline_keye.index_select_floor(
            cell.config, queries, keys,
            facts["index_pairs_per_step"] / layers, device_kind)),
        "sparse_attend": (("sparse_attend",),
                          roofline_keye.sparse_attend_floor(
            cell.config, queries, keys,
            facts["selected_pairs_per_step"] / layers, device_kind)),
        "moe_gated_experts": (("moe_experts",),
                              roofline_glm.moe_gated_experts_floor(
            cell.config, facts["assignments_per_step_layer"],
            facts["experts_hit_per_step_layer"], device_kind))}
    for name, (scopes, floor) in floors.items():
        measured = sum(got["seconds"][s] for s in scopes) / (
            got["runs"] * layers)
        if measured > 0:
            out[name + "_roofline"] = 100.0 * floor["seconds"] / measured
            out[name + "_ms_per_layer"] = measured * 1e3
            out[name + "_floor_bound"] = floor["bound"]
    for scope in SCOPES:
        out[scope + "_scope_ms_per_step"] = (
            got["seconds"][scope] / got["runs"] * 1e3)
    return out


# -------------------------------------------------------------- the checks

def served_answers(results: List[Dict], plan: Dict, pool: Dict,
                   context_ids: List[str]) -> Dict:
    """`serve_lm_ctx.served_answers`, result by result, so that each
    answer kept there has its `selected_last` beside it here."""
    kept, selected, malformed = [], [], 0
    for r in results:
        one = serve_lm_ctx.served_answers([r], plan, pool, context_ids)
        malformed += one["malformed"]
        if one["sequences"]:
            kept.append(one)
            selected.append(json.loads(r["body"]).get("selected_last"))
    out = {"sequences": [k["sequences"][0] for k in kept],
           "contexts": [k["contexts"][0] for k in kept],
           "malformed": malformed, "selected": selected}
    for name, dtype in (("ids", np.int64), ("logits", np.float32),
                        ("routing", np.int32)):
        out[name] = np.asarray([k[name][0] for k in kept], dtype)
    return out


def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict
                  ) -> List[Dict]:
    from benchmarks import reference_keye, reference_lm
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
    ref = reference_keye.forward(seed, cell.config, served["sequences"])
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
    # reported, no limit: near-ties flip under bfloat16
    same = reference_lm.same_expert_sets(served["routing"],
                                         ref["chosen_last"])
    print(f"note expert_sets_equal_share: {same!r} over "
          f"{served['routing'].shape[0] * served['routing'].shape[1]} "
          f"(request, layer) choices at the last position", flush=True)
    if all(s is not None for s in served["selected"]):
        share = reference_keye.selected_overlap(served["selected"],
                                                ref["selected_last"])
        print(f"note selected_sets_overlap_share: {share!r} of the "
              f"reference's kept keys at the last position, over "
              f"{n * int(cell.config['layers'])} (request, layer) "
              f"selections", flush=True)
    return checks


# ------------------------------------------------------------------ the run

def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    serving = ServingSparseCtx(cell, seed, require_tpu)
    try:
        arrivals = loadgen.schedule(seed, seconds, cell.traffic)
        checked = set(pick_checked(seed, arrivals, serving.pool,
                                   cell.traffic))
        serving.ask_selected = {arrivals[i]["body_index"] for i in checked}
        drove = serving.drive(arrivals, checked)
        serving.ask_selected = set()
        trace_dir, tail_drove = None, None
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
        facts.update(roofline_facts(cell, device["kind"], trace_dir,
                                    tail_drove["registry"]))
        traced = readers.read_traced(
            cell, device["kind"], drove["registry"], drove["window_s"],
            trace_dir, late_ms=got["late_ms"], facts=facts)
        dev.update(traced["device"])
        values, breakdown = traced["values"], traced["breakdown"]
        for key in ("index_select", "sparse_attend", "moe_gated_experts"):
            if key + "_roofline" in facts:
                common.say(f"{key}: {facts[key + '_ms_per_layer']:.3f} ms a "
                           f"layer and step, floor bound by "
                           f"{facts[key + '_floor_bound']}")
        common.say("device ms a step by scope: " + ", ".join(
            f"{s} {facts[s + '_scope_ms_per_step']:.3f}" for s in SCOPES
            if s + "_scope_ms_per_step" in facts))
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, got["attempted"], got["failed"],
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result

