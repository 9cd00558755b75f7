"""Runner kind `serve_lm`: a language model served for scoring. An
in-process `PredictionServer` over the program's scoring facade
(`code2vec.py serve --model_config <configuration file> --load
<checkpoint>` builds the same), every `(rows, length)` shape warmed
before the window, and the open-loop generator of `loadgen.py` in a
JAX-free child that sends `POST /score` (`benchmarks/loadgen_lm.py`).

The deployment starts as `runners/serve.py`'s does: on a checkout's
first run the program's own `save` writes the checkpoint (parameters
only), every run restores it through `--load` (leaf by leaf, straight
into place), and only then do the seed's weights take the restored
ones' place, each freed before its successor is made.

`correct`: once the window has closed, 32 of the requests it finished
(drawn from the seed, the longest among them) go through the float32
reference (`benchmarks/reference_lm.py`) with the seed's weights, layer
by layer; compared are the served top-k logit differences with the
reference's for the same tokens (the widest, and the median over the
requests of each request's widest), and how far the served top token's
reference logit lies below the reference's best. The share of (request,
expert layer) expert sets at the last position that equal the
reference's is printed beside them; it has no limit (near-ties flip
under bfloat16).

README.serve_lm.md has the traffic file's keys and the facts the runner
hands the readers.
"""

from __future__ import annotations

import json
import os
import random
import select
import shutil
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import common, loadgen, readers
from benchmarks.runners.serve import (
    TRACE_WINDOW_S, _trace_slice, summarize,
)

PROGRAM = r"^jit_lm_score_step\("
SCOPES = ("moe_experts", "ssd_scan")
# the grouped-matmul custom calls carry no scope of their own
KERNELS = {"moe_experts": r"^(ragged-dot|gmm)"}


# ------------------------------------------------------------- the traffic

def request_lengths(rng: np.random.Generator, n: int, traffic: Dict
                    ) -> np.ndarray:
    spec = traffic["length"]
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def prepare_bodies(work: str, config: Dict, traffic: Dict) -> Dict:
    """On the cell's first run: the pool of request bodies, one JSON file
    each, from the traffic file's own seed. Lengths log-normal, clipped;
    ids Zipf over the vocabulary rows held (id = rank - 1)."""
    data = os.path.join(work, "data")
    done = os.path.join(data, "bodies.json")
    made = not os.path.exists(done)
    n = int(traffic["request_pool"]) + int(traffic["warm_requests"])
    if made:
        shutil.rmtree(data, ignore_errors=True)
        os.makedirs(data)
        rng = np.random.default_rng(int(traffic["corpus_seed"]))
        lengths = request_lengths(rng, n, traffic)
        rows = int(config["vocab_rows"])
        p = 1.0 / np.arange(1, rows + 1) ** float(traffic["id_zipf"])
        cdf = np.cumsum(p / p.sum())
        for i, length in enumerate(lengths):
            ids = np.minimum(np.searchsorted(cdf, rng.random(int(length))),
                             rows - 1)
            with open(os.path.join(data, f"{i:05d}.json"), "w") as f:
                json.dump({"ids": ids.tolist(),
                           "top_k": int(traffic["top_k"]),
                           "return_routing": True}, f)
        with open(done, "w") as f:
            json.dump({"lengths": lengths.tolist()}, f)
    with open(done) as f:
        lengths = json.load(f)["lengths"]
    return {"dir": data, "lengths": lengths, "made": made}


# ------------------------------------------------------------- the program

def program_argv(cell: common.Cell, seed: int) -> List[str]:
    serve = cell.config.get("serve", {})
    return (["serve", "--serve_port", "0",
             "--model_config", config_path(cell),
             "--serve_token_budget", str(int(serve["token_budget"])),
             "--seed", str(int(seed) % (2 ** 31 - 1))]
            + list(cell.traffic.get("program_args", [])))


def config_path(cell: common.Cell) -> str:
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.entry["config"])
    return os.path.join(cell.root, entry["file"])


class ServingLM:
    """The system under test, up and warm."""

    def __init__(self, cell: common.Cell, seed: int,
                 require_tpu: bool = True):
        try:
            # the program's model entry first: a tree without the model
            # ends here, at once, with no result
            from code2vec_tpu.lm_facade import ScoringModel
        except ImportError as e:
            raise common.NoResult(
                f"this checkout's program has no scoring model: {e}")
        common.configure_jax()
        self.device = common.require_chips(cell.chips, require_tpu)
        from code2vec_tpu.cli import config_from_args
        from code2vec_tpu.models import hybrid_lm
        from code2vec_tpu.serving.server import PredictionServer
        from benchmarks import reference_lm
        self.cell, self.seed = cell, seed
        common.program_log_to(os.path.join(cell.work, "program.log"))
        self.bodies = prepare_bodies(cell.work, cell.config, cell.traffic)
        common.say(f"pool of bodies "
                   f"{'made' if self.bodies['made'] else 'found'}")
        argv = program_argv(cell, seed)
        saved = os.path.join(cell.work, "checkpoint", "saved")
        if not os.path.isdir(saved):    # committed by a rename
            common.say("writing the deployment's checkpoint (first run in "
                       "this checkout)")
            first = ScoringModel(config_from_args(argv + ["--save", saved]))
            first.save()
            for leaf in first.params.values():
                leaf.delete()
            del first
        self.config = config_from_args(argv + ["--load", saved])
        self.model = ScoringModel(self.config)
        want = reference_lm.all_leaves(cell.config)
        have = [(leaf.name, tuple(leaf.shape), leaf.dtype, leaf.init)
                for leaf in hybrid_lm.leaf_specs(self.model.lm)]
        if [w[:3] for w in want] != [h[:3] for h in have]:
            raise common.NoResult("the program's leaves are not the "
                                  "configuration file's")
        self.seed_weights(seed)
        self.server = PredictionServer(self.model, self.config)
        self.port = self.server.start(0, "127.0.0.1")
        t = time.perf_counter()
        self.model.warmup()
        common.say(f"{self.model.predict_compile_count()} shapes warm in "
                   f"{time.perf_counter() - t:.1f}s")
        n_pool = int(cell.traffic["request_pool"])
        for i in range(int(cell.traffic["warm_requests"])):
            text, params = self.body(n_pool + i)
            self.server.handle("score", text, params=params)

    def seed_weights(self, seed: int) -> None:
        """The benchmark's weights from the seed in place of the restored
        ones, leaf by leaf, each freed before its successor is made: the
        device never holds two sets."""
        from benchmarks import reference_lm
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_lm.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_lm.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})

    def file(self, index: int) -> str:
        return os.path.join(self.bodies["dir"], f"{index:05d}.json")

    def body(self, index: int):
        with open(self.file(index)) as f:
            text = f.read()
        return text, json.loads(text)

    def drive(self, arrivals: List[Dict], checked=(), trace_dir=None) -> Dict:
        """One open-loop window: the child sends `arrivals`, the parent
        waits. Returns the child's results and the window's facts."""
        import jax
        from code2vec_tpu import obs
        cell = self.cell
        plan = {"port": self.port,
                "deadline_ms": float(self.config.serve_deadline_ms),
                "threads": int(cell.traffic["generator_threads"]),
                "requests": [{"due_s": a["due_s"],
                              "file": self.file(a["body_index"]),
                              "keep_body": i in checked}
                             for i, a in enumerate(arrivals)]}
        plan_path = os.path.join(cell.work, "plan.json")
        out_path = os.path.join(cell.work, "results.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        if os.path.exists(out_path):
            os.remove(out_path)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "TPU_", "XLA_"))}
        compiled_before = self.model.predict_compile_count()
        child = subprocess.Popen(
            [sys.executable, os.path.join(common.HOME, "loadgen_lm.py"),
             "--plan", plan_path, "--out", out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        try:
            if child.stdout.readline().strip() != "READY":
                raise common.NoResult("the load generator did not come up")
            registry = common.RegistryWindow(obs.default_registry())
            tracer = None
            if trace_dir:
                tracer = threading.Thread(target=_trace_slice,
                                          args=(trace_dir,), daemon=True)
            registry.open()
            t0 = time.time() + 0.25
            child.stdin.write(f"GO {t0!r}\n")
            child.stdin.flush()
            if tracer:
                tracer.start()
            status = ""
            while not status and child.poll() is None:
                # in slices, so that a trace started meanwhile sees them
                with jax.profiler.TraceAnnotation("bench.serve_window"):
                    ready, _, _ = select.select([child.stdout], [], [], 0.25)
                if ready:
                    status = child.stdout.readline().strip()
            child.wait(timeout=60)
            registry.close()
            window_s = time.time() - t0
            if tracer:
                tracer.join(timeout=60)
            if status != "DONE" or child.returncode != 0:
                raise common.NoResult(f"the load generator failed: "
                                      f"{status!r} rc={child.returncode}")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(out_path) as f:
            results = json.load(f)
        return {"results": results, "plan": plan, "registry": registry,
                "window_s": window_s, "setup_s": t0 - common.PROCESS_START,
                "memory_peak": common.memory_peak_bytes(),
                "compiled_in_window":
                    self.model.predict_compile_count() - compiled_before}

    def close(self) -> None:
        self.server.drain(timeout=10.0)


# --------------------------------------------------------------- the facts

def counter_delta(registry: common.RegistryWindow, name: str
                  ) -> Optional[float]:
    """What a counter of the program grew by inside the window."""
    total, seen = 0.0, False
    for (n, labels), (value, count) in registry._close.items():
        if n == name and count is None:
            total += value - registry._open.get((n, labels), (0.0, None))[0]
            seen = True
    return total if seen else None


def router_facts(registry: common.RegistryWindow) -> Dict[str, float]:
    """Of one window: the share of (token, expert layer) pairs with no
    held expert, and the means a (step, expert layer) that the floors
    take."""
    routed = counter_delta(registry, "moe_tokens_routed_total")
    unserved = counter_delta(registry,
                             "moe_tokens_without_local_expert_total")
    out: Dict[str, float] = {}
    if routed:
        out["tokens_without_local_expert_pct"] = 100.0 * unserved / routed
    layers = registry.histogram("moe_expert_load_max_over_mean")
    if routed and layers:
        n = layers[1]
        out["tokens_per_step"] = routed / n
        out["assignments_per_step_layer"] = counter_delta(
            registry, "moe_local_assignments_total") / n
        out["experts_hit_per_step_layer"] = counter_delta(
            registry, "moe_experts_hit_total") / n
    return out


def roofline_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                   traced: common.RegistryWindow) -> Dict[str, float]:
    """`moe_experts_roofline` and `ssd_scan_roofline`, in percent: the
    floor of one (step, layer) at the traced window's mean counts over
    the scope's mean device time a (step, layer). Nothing where the
    trace or the counters give nothing to read."""
    from benchmarks import roofline_lm, trace_scopes
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES, KERNELS)
    facts = router_facts(traced)
    if got is None or "assignments_per_step_layer" not in facts:
        return {}
    count = {k: cell.config["pattern"].count(k) for k in "ME"}
    out = {}
    floors = {
        "moe_experts": (count["E"], roofline_lm.moe_experts_floor(
            cell.config, facts["tokens_per_step"],
            facts["assignments_per_step_layer"],
            facts["experts_hit_per_step_layer"], device_kind)),
        "ssd_scan": (count["M"], roofline_lm.ssd_scan_floor(
            cell.config, facts["tokens_per_step"], device_kind))}
    for scope, (layers, floor) in floors.items():
        measured = got["seconds"][scope] / (got["runs"] * layers)
        if measured > 0:
            out[scope + "_roofline"] = 100.0 * floor["seconds"] / measured
            out[scope + "_ms_per_layer"] = measured * 1e3
            out[scope + "_floor_bound"] = floor["bound"]
    return out


# -------------------------------------------------------------- the checks

def served_answers(results: List[Dict], plan: Dict) -> Dict:
    """The sampled answers beside the bodies they answer."""
    sequences, ids, logits, routing, malformed = [], [], [], [], 0
    for r in results:
        if r is None or "body" not in r:
            continue
        with open(plan["requests"][r["i"]]["file"]) as f:
            body = json.load(f)
        answer = json.loads(r["body"])
        top = answer.get("top", [])
        if (len(top) != body["top_k"] or answer.get("tokens")
                != len(body["ids"]) or "routing_last" not in answer):
            malformed += 1
            continue
        sequences.append(np.asarray(body["ids"], np.int32))
        ids.append([t["id"] for t in top])
        logits.append([t["logit"] for t in top])
        routing.append(answer["routing_last"])
    return {"sequences": sequences, "malformed": malformed,
            "ids": np.asarray(ids, np.int64),
            "logits": np.asarray(logits, np.float32),
            "routing": np.asarray(routing, np.int32)}


def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict
                  ) -> List[Dict]:
    from benchmarks import reference_lm
    checks = [{"name": "answers_malformed", "value": served["malformed"],
               "limit": 0, "ok": served["malformed"] == 0, "note": ""}]
    n = len(served["sequences"])
    if not n:
        checks.append({"name": "served_requests_checked", "value": 0,
                       "limit": 1, "ok": False,
                       "note": "nothing to compare"})
        return checks
    ref = reference_lm.forward(seed, cell.config, served["sequences"])
    got = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    tokens = sum(len(s) for s in served["sequences"])
    note = f"{n} served requests, {tokens} tokens"
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
    serving = ServingLM(cell, seed, require_tpu)
    try:
        arrivals = loadgen.schedule(seed, seconds, cell.traffic)
        rng = random.Random(int(seed) ^ 0x5EED)
        checked = set(rng.sample(
            range(len(arrivals)),
            min(int(cell.traffic["checked_requests"]) - 1, len(arrivals))))
        lengths = serving.bodies["lengths"]
        checked.add(max(range(len(arrivals)),
                        key=lambda i: lengths[arrivals[i]["body_index"]]))
        drove = serving.drive(arrivals, checked)
        trace_dir, tail_drove = None, None
        if trace:
            # a short window of its own, the same mix over bodies the
            # timed window did not send: stopping a trace stalls the
            # host for seconds, which inside the timed window would be
            # read as the server's own tail
            trace_dir = os.path.join(cell.work, "trace")
            tail = loadgen.schedule(seed, TRACE_WINDOW_S, cell.traffic)
            for a in tail:
                a["body_index"] += len(arrivals)
            if len(arrivals) + len(tail) > int(cell.traffic["request_pool"]):
                raise common.NoResult("the pool of bodies is too small for "
                                      "the timed and the traced window")
            tail_drove = serving.drive(tail, trace_dir=trace_dir)
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
    checks = check_answers(cell, seed, served_answers(results, plan),
                           cell.limits())
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
        facts.update(router_facts(drove["registry"]))
        facts.update(roofline_facts(cell, device["kind"], trace_dir,
                                    tail_drove["registry"]))
        traced = readers.read_traced(
            cell, device["kind"], drove["registry"], drove["window_s"],
            trace_dir, late_ms=got["late_ms"], facts=facts)
        dev.update(traced["device"])
        values, breakdown = traced["values"], traced["breakdown"]
        for key in ("moe_experts", "ssd_scan"):
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
