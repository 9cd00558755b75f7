"""Runner kind `serve_lm_ctx`: a language model served for scoring
AGAINST REGISTERED CONTEXTS. `runners/serve_lm.py`'s deployment (the
program's own `save` on a checkout's first run, `--load` on every run,
then the seed's weights leaf by leaf; an in-process `PredictionServer`
over `code2vec.py serve --model_config <file> --load <checkpoint>`'s
facade; the JAX-free open-loop child of `loadgen_lm.py`), with one more
phase of set-up: the mix's contexts are registered through the server
(`POST /contexts`), each landing in a slot of the program's device-
resident latent cache, and the ids that come back are written into the
question bodies. Nothing is registered inside the window.

`correct`: once the window has closed and the program's arrays are
freed, `checked_requests` of the requests it finished, over at least
`checked_contexts` distinct contexts and with the longest context among
them, are each scored ONCE by the float32 reference
(`benchmarks/reference_glm.py`) as one full forward over context ++
question: no cache, no chunks, no slot. Compared are the numbers
`serve_lm` compares (`reference_lm.served_gap`). A cache written wrongly,
a wrong position offset, a stale slot or a row that read another row's
slot is another sequence's answer.

README.serve_lm_ctx.md has the traffic file's keys and the facts the
runner hands the readers.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import time
from typing import Dict, List

import numpy as np

from benchmarks import common, loadgen, readers
from benchmarks.runners import serve_lm
from benchmarks.runners.serve import TRACE_WINDOW_S, summarize

PROGRAM = r"^jit_ctx_score_step\("
SCOPES = ("mla_attend", "moe_experts")
# the grouped-matmul custom calls carry no scope of their own
KERNELS = {"moe_experts": r"^(ragged-dot|gmm)"}


# ------------------------------------------------------------- the traffic

def _lengths(rng: np.random.Generator, n: int, spec: Dict) -> np.ndarray:
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _zipf_cdf(n: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** float(exponent)
    return np.cumsum(p / p.sum())


def make_pool(config: Dict, traffic: Dict) -> Dict:
    """The mix's contexts and questions from its own `corpus_seed`:
    token ids Zipf over the vocabulary rows held (id = rank - 1), lengths
    log-normal, clipped; a question's context Zipf over the contexts
    (context 0 the most asked)."""
    rng = np.random.default_rng(int(traffic["corpus_seed"]))
    rows = int(config["vocab_rows"])
    ids = _zipf_cdf(rows, traffic["id_zipf"])

    def tokens(length):
        return np.minimum(np.searchsorted(ids, rng.random(int(length))),
                          rows - 1).astype(np.int32)
    n_ctx = int(traffic["contexts"])
    contexts = [tokens(n) for n in _lengths(rng, n_ctx,
                                            traffic["context_length"])]
    n = int(traffic["request_pool"]) + int(traffic["warm_requests"])
    lengths = _lengths(rng, n, traffic["length"])
    which = np.minimum(np.searchsorted(
        _zipf_cdf(n_ctx, traffic["context_zipf"]), rng.random(n)), n_ctx - 1)
    return {"contexts": contexts, "lengths": lengths.tolist(),
            "context_of": which.tolist(),
            "questions": [tokens(length) for length in lengths]}


def write_bodies(work: str, pool: Dict, context_ids: List[str],
                 top_k: int) -> str:
    """One JSON file a question, naming its context by the id the server
    gave; written anew when the ids are not those of the files there."""
    data = os.path.join(work, "data")
    done = os.path.join(data, "context_ids.json")
    if os.path.exists(done) and common.load_json(done) == context_ids:
        return data
    shutil.rmtree(data, ignore_errors=True)
    os.makedirs(data)
    for i, (question, c) in enumerate(zip(pool["questions"],
                                          pool["context_of"])):
        with open(os.path.join(data, f"{i:05d}.json"), "w") as f:
            json.dump({"context": context_ids[c], "ids": question.tolist(),
                       "top_k": top_k, "return_routing": True}, f)
    with open(done, "w") as f:
        json.dump(context_ids, f)
    return data


# ------------------------------------------------------------- the program

class ServingCtx(serve_lm.ServingLM):
    """The system under test, up and warm, its contexts registered."""

    def __init__(self, cell: common.Cell, seed: int,
                 require_tpu: bool = True):
        try:
            from code2vec_tpu.lm_facade import ScoringModel
            ScoringModel.register_context
        except (ImportError, AttributeError) as e:
            # a tree without the model or its cache ends here, at once
            raise common.NoResult(
                f"this checkout's program registers no contexts: {e}")
        common.configure_jax()
        self.device = common.require_chips(cell.chips, require_tpu)
        from code2vec_tpu.cli import config_from_args
        from code2vec_tpu.serving.server import PredictionServer
        from benchmarks import reference_glm
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
        want = reference_glm.all_leaves(cell.config)
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
        self.bodies = {"dir": write_bodies(
            cell.work, self.pool, self.context_ids,
            int(cell.traffic["top_k"])), "lengths": self.pool["lengths"]}
        n_pool = int(cell.traffic["request_pool"])
        for i in range(int(cell.traffic["warm_requests"])):
            text, params = self.body(n_pool + i)
            self.server.handle("score", text, params=params)

    def register(self, tokens: np.ndarray) -> str:
        body = {"ids": tokens.tolist()}
        answer = json.loads(self.server.handle(
            "contexts", json.dumps(body), params=body))
        if answer["tokens"] != len(tokens):
            raise common.NoResult(f"a context of {len(tokens)} tokens was "
                                  f"registered as {answer['tokens']}")
        return answer["context"]

    def seed_weights(self, seed: int) -> None:
        """The benchmark's weights from the seed in place of the restored
        ones, leaf by leaf, each freed before its successor is made."""
        from benchmarks import reference_glm
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_glm.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_glm.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})

    @staticmethod
    def _free(model) -> None:
        for leaf in list(model.params.values()) + list(
                getattr(model, "cache", ())):
            leaf.delete()

    def close(self) -> None:
        """Stop serving and free the program's parameters and cache: the
        reference needs the room."""
        super().close()
        self._free(self.model)


# --------------------------------------------------------------- the facts

def registry_total(name: str):
    """(sum, count) of a histogram of the program over the whole process
    (set-up included), None where the program has none."""
    from code2vec_tpu import obs
    total, count, seen = 0.0, 0, False
    for metric in obs.default_registry().collect().get(name, {}).values():
        if hasattr(metric, "sum") and hasattr(metric, "count"):
            total, count, seen = (total + float(metric.sum),
                                  count + int(metric.count), True)
    return (total, count) if seen and count else None


def attention_facts(registry: common.RegistryWindow) -> Dict[str, float]:
    """Of one window, a scoring step: real question tokens, latents read
    and (query, key) pairs a layer; assignments and experts hit a (step,
    expert layer)."""
    steps = registry.histogram("serving_batch_tokens_fill_ratio")
    keys = serve_lm.counter_delta(registry, "score_latents_read_total")
    pairs = serve_lm.counter_delta(registry, "score_attended_pairs_total")
    out = serve_lm.router_facts(registry)
    if steps and keys and pairs and "tokens_per_step" in out:
        out["latents_per_step"] = keys / steps[1]
        out["pairs_per_step"] = pairs / steps[1]
    return out


def roofline_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                   traced: common.RegistryWindow) -> Dict[str, float]:
    """`mla_attend_roofline` and `moe_gated_experts_roofline`, in percent:
    the floor of one (step, layer) at the traced window's mean counts
    over the scope's mean device time a (step, layer). Nothing where the
    trace or the counters give nothing to read."""
    from benchmarks import roofline_glm, trace_scopes
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES, KERNELS)
    facts = attention_facts(traced)
    if got is None or "pairs_per_step" not in facts:
        return {}
    layers = int(cell.config["layers"])
    dense = min(int(cell.config["first_k_dense_replace"]), layers)
    floors = {
        "mla_attend": ("mla_attend", layers, roofline_glm.mla_attend_floor(
            cell.config, facts["tokens_per_step"], facts["latents_per_step"],
            facts["pairs_per_step"], device_kind)),
        "moe_gated_experts": ("moe_experts", layers - dense,
                              roofline_glm.moe_gated_experts_floor(
            cell.config, facts["assignments_per_step_layer"],
            facts["experts_hit_per_step_layer"], device_kind))}
    out = {}
    for name, (scope, count, floor) in floors.items():
        measured = got["seconds"][scope] / (got["runs"] * count)
        if measured > 0:
            out[name + "_roofline"] = 100.0 * floor["seconds"] / measured
            out[name + "_ms_per_layer"] = measured * 1e3
            out[name + "_floor_bound"] = floor["bound"]
    return out


# -------------------------------------------------------------- the checks

def pick_checked(seed: int, arrivals: List[Dict], pool: Dict,
                 traffic: Dict) -> List[int]:
    """Arrivals whose answers are kept: the first one on the longest
    context among those asked, then from a shuffle by the seed those
    that add a context until `checked_contexts` are in, then any, up to
    `checked_requests`."""
    context_of = [pool["context_of"][a["body_index"]] for a in arrivals]
    longest = max(set(context_of), key=lambda c: len(pool["contexts"][c]))
    picked = [context_of.index(longest)]
    order = list(range(len(arrivals)))
    random.Random(int(seed) ^ 0x5EED).shuffle(order)
    seen = {longest}
    for novel_only in (True, False):
        for i in order:
            if len(picked) >= int(traffic["checked_requests"]):
                break
            if i in picked or (novel_only and (
                    context_of[i] in seen
                    or len(seen) >= int(traffic["checked_contexts"]))):
                continue
            picked.append(i)
            seen.add(context_of[i])
    return picked


def served_answers(results: List[Dict], plan: Dict, pool: Dict,
                   context_ids: List[str]) -> Dict:
    """The sampled answers beside the whole sequences they answer:
    context ++ question."""
    sequences, contexts, ids, logits, routing, malformed = (
        [], [], [], [], [], 0)
    for r in results:
        if r is None or "body" not in r:
            continue
        with open(plan["requests"][r["i"]]["file"]) as f:
            body = json.load(f)
        answer = json.loads(r["body"])
        top = answer.get("top", [])
        c = context_ids.index(body["context"])
        context = pool["contexts"][c]
        if (len(top) != body["top_k"] or "routing_last" not in answer
                or answer.get("tokens") != len(body["ids"])
                or answer.get("context_tokens") != len(context)):
            malformed += 1
            continue
        sequences.append(np.concatenate(
            [context, np.asarray(body["ids"], np.int32)]))
        contexts.append(c)
        ids.append([t["id"] for t in top])
        logits.append([t["logit"] for t in top])
        routing.append(answer["routing_last"])
    return {"sequences": sequences, "contexts": contexts,
            "malformed": malformed, "ids": np.asarray(ids, np.int64),
            "logits": np.asarray(logits, np.float32),
            "routing": np.asarray(routing, np.int32)}


def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict
                  ) -> List[Dict]:
    from benchmarks import reference_glm, reference_lm
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
    ref = reference_glm.forward(seed, cell.config, served["sequences"])
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
    serving = ServingCtx(cell, seed, require_tpu)
    try:
        arrivals = loadgen.schedule(seed, seconds, cell.traffic)
        checked = set(pick_checked(seed, arrivals, serving.pool,
                                   cell.traffic))
        drove = serving.drive(arrivals, checked)
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
        for key in ("mla_attend", "moe_gated_experts"):
            if key + "_roofline" in facts:
                common.say(f"{key}: {facts[key + '_ms_per_layer']:.3f} ms a "
                           f"layer and step, floor bound by "
                           f"{facts[key + '_floor_bound']}")
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, got["attempted"], got["failed"],
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result
