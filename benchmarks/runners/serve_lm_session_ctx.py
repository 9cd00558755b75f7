"""Runner kind `serve_lm_session_ctx`: `serve_lm_ctx`'s deployment
(sessions registered through `POST /contexts` during set-up) for a model
whose contexts GROW (configuration `solar-open2-ep8`: a delta-rule state
in three layers of four, pages in the fourth), under traffic of TURNS:
every request is one `POST /score {context, ids, top_k, keep: true}`
that is scored AND kept, names the id its session's previous turn
answered with, and is answered with the session's next id
(`benchmarks/loadgen_sessions.py`).

From `runners/serve_lm_ctx.py`, `serve_lm.py` and `serve.py`, unchanged:
the deployment's checkpoint and restore, `register`, the facts of a
window (`registry_total`, `counter_delta`, `router_facts`), `summarize`,
the traced slice. Written here: the sessions and the pool of turn
tokens, the schedule of turns (instants, sessions and lengths the same
for every seed), the drive of the chained generator, the laying of every
session's tokens end to end as the server kept them, the picking of the
checked turns and what names the model (`ServingSessions.__init__`,
`seed_weights`, `check_answers`, `session_facts`, `run`: PERF.md section
7 lists the copies for the `benchmark` issue that gives the runner kinds
a model hook).

`correct`: once the windows have closed and the program's arrays are
freed, `checked_turns` finished turns on at least `checked_sessions`
sessions, among them the LAST finished turn of `checked_last` sessions
(every earlier write-back of those sessions lies under it), and one
plain `/score` question (no `keep`) on each of those `checked_last`
sessions, sent after the windows closed, are scored by the float32
reference (`benchmarks/reference_solar.py`): ONE forward a session over
its tokens as registered ++ every kept turn, read at the end of each
checked turn (the model is causal); no cache, no state carried, no
pages, no chunks. The sessions are chosen so that these forwards sum to
at most `reference_tokens`. Compared are the numbers `serve_lm` compares
(`reference_lm.served_gap`). A state not written back, conv inputs
lost, keys written at the wrong place of a page, another session's
state, a turn kept twice or not at all is another sequence's answer.
Besides: nothing evicted, no turn refused, no id named twice.

README.serve_lm_session_ctx.md has the traffic file's keys.
"""

from __future__ import annotations

import json
import os
import random
import select
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import common, loadgen, readers
from benchmarks.runners import serve_lm, serve_lm_ctx
from benchmarks.runners.serve import TRACE_WINDOW_S, _trace_slice, summarize
from benchmarks.runners.serve_lm_ctx import (
    _lengths, _zipf_cdf, registry_total,
)

PROGRAM = r"^jit_ctx_extend_step\("
DELTA = ("kda_proj", "kda_conv", "kda_gates", "kda_chunk", "kda_out",
         "state_write")
SCOPES = DELTA + ("gqa_proj", "full_attend", "attn_gate", "page_write",
                  "moe_route", "moe_experts", "moe_shared", "lm_head")
KERNELS = serve_lm_ctx.KERNELS


# ------------------------------------------------------------- the traffic

def make_pool(config: Dict, traffic: Dict) -> Dict:
    """The mix's sessions and the pool of turn tokens from its own
    `corpus_seed`: lengths log-normal, clipped; token ids Zipf over the
    vocabulary rows held (id = rank - 1). `blocks[b]`: the tokens of
    body `b`, as long as the longest turn (a turn takes as many as ITS
    length). The same for every seed."""
    rng = np.random.default_rng(int(traffic["corpus_seed"]))
    rows = int(config["vocab_rows"])
    ids = _zipf_cdf(rows, traffic["id_zipf"])

    def tokens(length):
        return np.minimum(np.searchsorted(ids, rng.random(int(length))),
                          rows - 1).astype(np.int32)
    sessions = [tokens(n) for n in _lengths(
        rng, int(traffic["sessions"]), traffic["session_length"])]
    n = int(traffic["request_pool"]) + int(traffic["warm_requests"]) \
        + int(traffic["checked_last"])
    longest = int(traffic["length"]["max"])
    return {"sessions": sessions,
            "blocks": tokens(n * longest).reshape(n, longest)}


def turn_schedule(seed: int, seconds: float, traffic: Dict) -> List[Dict]:
    """`loadgen.schedule`'s arrivals (the same instants for every seed,
    the bodies in another order) with, for arrival i, a `length` and a
    `session` that are the same for EVERY seed: the length log-normal
    from `schedule_seed`; the session drawn by Zipf(`session_zipf`) over
    the sessions' ranks among those whose last turn was due at least
    `session_gap_s` before (an agent's tool call takes that long)."""
    arrivals = loadgen.schedule(seed, seconds, traffic)
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    lengths = _lengths(rng, len(arrivals), traffic["length"])
    draws = rng.random(len(arrivals))
    n = int(traffic["sessions"])
    weight = 1.0 / np.arange(1, n + 1) ** float(traffic["session_zipf"])
    gap = float(traffic["session_gap_s"])
    last_due = np.full((n,), -np.inf)
    for a, length, u in zip(arrivals, lengths, draws):
        free = last_due <= a["due_s"] - gap
        if not free.any():
            raise ValueError("no session is free for a turn: too few "
                             "sessions for this rate and gap")
        cdf = np.cumsum(np.where(free, weight, 0.0))
        s = int(min(np.searchsorted(cdf / cdf[-1], u), n - 1))
        while not free[s]:      # u on an edge of a zero-weight stretch
            s += 1
        a["session"], a["length"] = s, int(length)
        last_due[s] = a["due_s"]
    return arrivals


# ------------------------------------------------------------- the program

class ServingSessions(serve_lm_ctx.ServingCtx):
    """The system under test, up and warm, its sessions registered; and
    the runner's own account of what each session holds: its tokens end
    to end, its id, and where each kept turn lies."""

    def __init__(self, cell: common.Cell, seed: int,
                 require_tpu: bool = True):
        try:
            from code2vec_tpu.lm_facade import MODEL_MODULES, ScoringModel
            MODEL_MODULES[cell.config["model_type"]].ctx_extend_step
        except (ImportError, AttributeError, KeyError) as e:
            # a tree without the model or its kept turns ends here, at once
            raise common.NoResult(
                f"this checkout's program does not run the "
                f"configuration's model: {e!r}")
        common.configure_jax()
        self.device = common.require_chips(cell.chips, require_tpu)
        from code2vec_tpu.cli import config_from_args
        from code2vec_tpu.serving.server import PredictionServer
        from benchmarks import reference_solar
        self.cell, self.seed = cell, seed
        # the counter is the process's: what THIS server evicts is the
        # difference
        self.evicted_before = counter_total("latent_cache_evictions_total")
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
        want = reference_solar.all_leaves(cell.config)
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
        common.say(f"{self.model.predict_compile_count()} programs (scoring, "
                   f"extending and the registration shape) warm in "
                   f"{time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        self.session_ids = [self.register(tokens)
                            for tokens in self.pool["sessions"]]
        self.session_tokens = [[tokens] for tokens in self.pool["sessions"]]
        self.turns: List[List[int]] = [[] for _ in self.session_ids]
        self.named = set(self.session_ids)
        self.renamed = 0        # ids an answer carried that were not new
        book = self.model.contexts
        common.say(f"{len(self.session_ids)} sessions of "
                   f"{sum(len(c) for c in self.pool['sessions'])} tokens "
                   f"registered in {time.perf_counter() - t:.1f}s: "
                   f"{sum(len(h.pages) for h in book.held().values())} of "
                   f"{book.pages} pages, {len(book.held())} of {book.slots} "
                   f"state slots")
        if sorted(book.held()) != sorted(set(self.session_ids)):
            raise common.NoResult("the cache does not hold the mix's "
                                  "sessions: too few state slots or pages")
        # a few kept turns through the server's whole path, one a session
        n_pool = int(cell.traffic["request_pool"])
        for i in range(int(cell.traffic["warm_requests"])):
            s = i % len(self.session_ids)
            self.ask(s, self.pool["blocks"][n_pool + i][
                :int(cell.traffic["length"]["median"])], keep=True)
        common.say(f"{int(cell.traffic['warm_requests'])} warm turns kept")

    def length_of(self, session: int) -> int:
        return sum(len(t) for t in self.session_tokens[session])

    def kept(self, session: int, tokens: np.ndarray, context: str) -> None:
        """The server answered a kept turn of `tokens` on `session` with
        the id `context`."""
        self.turns[session].append(self.length_of(session))
        self.session_tokens[session].append(np.asarray(tokens, np.int32))
        self.session_ids[session] = context
        self.renamed += context in self.named
        self.named.add(context)

    def ask(self, session: int, tokens: np.ndarray, keep: bool) -> Dict:
        """One request on `session` through the server, in this
        process."""
        body = {"context": self.session_ids[session],
                "ids": [int(t) for t in tokens],
                "top_k": int(self.cell.traffic["top_k"]),
                "return_routing": True, **({"keep": True} if keep else {})}
        answer = json.loads(self.server.handle(
            "score", json.dumps(body), params=body))
        if keep:
            self.kept(session, tokens, answer["context"])
        return answer

    def seed_weights(self, seed: int) -> None:
        """The benchmark's weights from the seed in place of the restored
        ones, leaf by leaf, each freed before its successor is made."""
        from benchmarks import reference_solar
        params = dict(self.model.params)
        for name, shape, dtype, init in reference_solar.all_leaves(
                self.cell.config):
            params.pop(name).delete()
            params[name] = reference_solar.make_leaf(
                seed, self.cell.config, name, shape, dtype, init)
        self.model.set_params({name: params[name] for name in
                               self.model.params})

    @staticmethod
    def _free(model) -> None:
        import jax
        for leaf in jax.tree.leaves((model.params,
                                     getattr(model, "cache", ()))):
            leaf.delete()

    def drive(self, arrivals: List[Dict], trace_dir: Optional[str] = None
              ) -> Dict:
        """One open-loop window of turns: the child sends `arrivals`,
        chaining each session's ids; the parent waits, then lays the
        kept turns behind their sessions in the order the server kept
        them. Every answer's body comes back (which turns are checked is
        chosen among those that FINISHED)."""
        import jax
        from code2vec_tpu import obs
        cell = self.cell
        blocks = self.pool["blocks"]
        plan = {"port": self.port,
                "deadline_ms": float(self.config.serve_deadline_ms),
                "threads": int(cell.traffic["generator_threads"]),
                "sessions": list(self.session_ids),
                "requests": [{
                    "due_s": a["due_s"], "session": a["session"],
                    "ids": blocks[a["body_index"]][:a["length"]].tolist(),
                    "top_k": int(cell.traffic["top_k"]), "keep": True,
                    "keep_body": True} for a in arrivals]}
        plan_path = os.path.join(cell.work, "plan.json")
        out_path = os.path.join(cell.work, "results.json")
        os.makedirs(cell.work, exist_ok=True)
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        if os.path.exists(out_path):
            os.remove(out_path)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "TPU_", "XLA_"))}
        compiled_before = self.model.predict_compile_count()
        child = subprocess.Popen(
            [sys.executable, os.path.join(common.HOME,
                                          "loadgen_sessions.py"),
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
        # a session's turns answer in the order they were sent (each
        # waits for the one before): the order of the plan
        for r, a in zip(results, arrivals):
            if r is None:
                continue
            r["held"] = self.length_of(a["session"])
            r["length"] = a["length"]
            if r["status"] == 200 and isinstance(r.get("context"), str):
                self.kept(a["session"],
                          blocks[a["body_index"]][:a["length"]],
                          r["context"])
                r["turn"] = len(self.turns[a["session"]]) - 1
        return {"results": results, "plan": plan, "registry": registry,
                "window_s": window_s, "setup_s": t0 - common.PROCESS_START,
                "memory_peak": common.memory_peak_bytes(),
                "compiled_in_window":
                    self.model.predict_compile_count() - compiled_before}


# --------------------------------------------------------------- the facts

def step_counts(results: List[Dict]) -> Dict[str, float]:
    """Summed over the finished turns of `results`: real tokens, and the
    visible keys and (query, visible key) pairs of the G layer
    (`roofline_solar.turn_counts`)."""
    from benchmarks import roofline_solar
    total = {"tokens": 0, "keys": 0, "pairs": 0, "turns": 0}
    for r in results:
        if r is None or "turn" not in r:
            continue
        total["tokens"] += r["length"]
        total["turns"] += 1
        for key, n in roofline_solar.turn_counts(r["held"],
                                                 r["length"]).items():
            total[key] += n
    return total


def session_facts(cell: common.Cell, device_kind: str, trace_dir: str,
                  traced: common.RegistryWindow, results: List[Dict]
                  ) -> Dict[str, float]:
    """`delta_rule_roofline`, `session_attend_roofline`,
    `held_experts_roofline`, `extend_step_mfu` and
    `delta_share_of_step_pct`, in percent, from the TRACED window: the
    floors of benchmarks/roofline_solar.py at the window's mean counts a
    step (steps and real rows from the histogram `serving_batch_rows`,
    tokens, keys and pairs from the turns it finished and what their
    sessions held, the experts' from the router's series) over the mean
    device time a step of the scopes and of the whole program. Nothing
    where the trace or the program's series give nothing to read."""
    from benchmarks import (roofline_glm, roofline_solar, trace_reduce,
                            trace_scopes)
    rows = traced.histogram("serving_batch_rows")
    router = serve_lm.router_facts(traced)
    counts = step_counts(results)
    if (not rows or not counts["turns"]
            or "assignments_per_step_layer" not in router):
        return {}
    got = trace_scopes.scope_seconds(trace_dir, PROGRAM, SCOPES, KERNELS)
    if got is None:
        return {}
    whole = trace_reduce.program_time(trace_reduce.load_xplane(trace_dir),
                                      PROGRAM)
    if whole is None or whole["seconds_per_run"] <= 0:
        return {}
    steps, step_s = rows[1], whole["seconds_per_run"]
    kinds = ["G" if i in cell.config["gqa_layers"] else "K"
             for i in range(int(cell.config["layers"]))]
    mean = {k: v / steps for k, v in counts.items()}
    out = {"steps_traced": float(steps), "rows_per_step": rows[0] / steps,
           "step_device_ms": step_s * 1e3}
    for scope in SCOPES:
        out[scope + "_scope_ms_per_step"] = (
            got["seconds"][scope] / got["runs"] * 1e3)
    out["delta_share_of_step_pct"] = 100.0 * sum(
        got["seconds"][s] for s in DELTA) / got["runs"] / step_s
    floors = {
        "delta_rule": ("kda_chunk", kinds.count("K"),
                       roofline_solar.delta_rule_floor(
            cell.config, rows[0] / steps, mean["tokens"], device_kind)),
        "session_attend": ("full_attend", kinds.count("G"),
                           roofline_solar.attend_floor(
            cell.config, mean["tokens"], mean["keys"], mean["pairs"],
            device_kind)),
        "held_experts": ("moe_experts", len(kinds),
                         roofline_glm.moe_gated_experts_floor(
            cell.config, router["assignments_per_step_layer"],
            router["experts_hit_per_step_layer"], device_kind))}
    for name, (scope, count, floor) in floors.items():
        measured = got["seconds"][scope] / (got["runs"] * max(count, 1))
        if measured > 0 and count:
            out[name + "_roofline"] = 100.0 * floor["seconds"] / measured
            out[name + "_ms_per_layer"] = measured * 1e3
            out[name + "_floor_bound"] = floor["bound"]
    step = roofline_solar.extend_step_floor(
        cell.config, rows[0] / steps, mean["tokens"], mean["keys"],
        mean["pairs"], router["assignments_per_step_layer"],
        router["experts_hit_per_step_layer"], device_kind)
    out["extend_step_mfu"] = 100.0 * step["seconds"] / step_s
    out["extend_step_floor_ms"] = step["seconds"] * 1e3
    out["extend_step_floor_bound"] = step["bound"]
    return out


# -------------------------------------------------------------- the checks

def pick_checked(seed: int, serving: ServingSessions, results: List[Dict],
                 traffic: Dict) -> Dict[int, List[int]]:
    """{session: the indices (into its kept turns) of its checked
    turns}: sessions from a shuffle by the seed, taken while their
    tokens (as they stand, every one a reference forward) stay within
    `reference_tokens`, up to `checked_sessions`; the LAST kept turn of
    the first `checked_last` of them, one finished turn of each other,
    then other finished turns of the window on them, from the shuffle,
    up to `checked_turns`."""
    rng = random.Random(int(seed) ^ 0x5EED)
    finished: Dict[int, List[int]] = {}
    for r in results:
        if r is not None and "turn" in r and r["ok"]:
            finished.setdefault(r["session"], []).append(r["turn"])
    order = sorted(finished)
    rng.shuffle(order)
    budget = int(traffic["reference_tokens"])
    want = int(traffic["checked_sessions"])
    sessions, spent = [], 0
    for by_length in (False, True):
        if len(sessions) >= want:
            break
        if by_length:       # the shuffle's did not fit: the shortest do
            order = sorted(finished, key=serving.length_of)
            sessions, spent = [], 0
        for s in order:
            if len(sessions) < want and spent + serving.length_of(s) <= budget:
                sessions.append(s)
                spent += serving.length_of(s)
    picked = {s: [] for s in sessions}
    for s in sessions[:int(traffic["checked_last"])]:
        picked[s].append(len(serving.turns[s]) - 1)
    for s in sessions[int(traffic["checked_last"]):]:
        # every session taken is read at least once
        picked[s].append(rng.choice(finished[s]))
    rest = [(s, t) for s in sessions for t in finished[s]
            if t not in picked[s]]
    rng.shuffle(rest)
    total = sum(len(v) for v in picked.values())
    for s, t in rest:
        if total >= int(traffic["checked_turns"]):
            break
        picked[s].append(t)
        total += 1
    return picked


def served_answers(serving: ServingSessions, results: List[Dict],
                   picked: Dict[int, List[int]], questions: Dict[int, Dict]
                   ) -> Dict:
    """The sampled answers beside the sequences they answer: a session's
    tokens end to end (and the plain question behind them) and the
    positions read."""
    by_turn = {(r["session"], r["turn"]): r for r in results
               if r is not None and "turn" in r}
    out = {"sequences": [], "read_at": [], "starts": [], "sessions": [],
           "ids": [], "logits": [], "routing": [], "malformed": 0,
           "turns": 0, "last_turns": 0, "questions": 0}
    top_k = int(serving.cell.traffic["top_k"])

    def take(answer, tokens, held) -> bool:
        top = answer.get("top", [])
        if (len(top) != top_k or "routing_last" not in answer
                or answer.get("tokens") != tokens
                or answer.get("context_tokens") != held):
            out["malformed"] += 1
            return False
        out["ids"].append([t["id"] for t in top])
        out["logits"].append([t["logit"] for t in top])
        out["routing"].append(answer["routing_last"])
        return True
    for s, turns in picked.items():
        parts = list(serving.session_tokens[s])
        starts = list(serving.turns[s])
        reads = []
        for t in sorted(set(turns)):
            r = by_turn.get((s, t))
            begin = starts[t]
            end = (starts[t + 1] if t + 1 < len(starts)
                   else serving.length_of(s))
            if r is None or "body" not in r:
                # the session's last kept turn fell outside the windows
                # (a warm-up turn): nothing was kept of its answer
                continue
            if take(json.loads(r["body"]), end - begin, begin):
                reads.append(end - 1)
                out["turns"] += 1
                out["last_turns"] += t == len(starts) - 1
        if s in questions:
            asked, answer = questions[s]
            if take(answer, len(asked), serving.length_of(s)):
                parts.append(np.asarray(asked, np.int32))
                reads.append(serving.length_of(s) + len(asked) - 1)
                out["questions"] += 1
        if reads:
            out["sequences"].append(np.concatenate(parts))
            out["read_at"].append(reads)
            out["starts"].append(starts)
            out["sessions"].append(s)
    out["ids"] = np.asarray(out["ids"], np.int64)
    out["logits"] = np.asarray(out["logits"], np.float32)
    out["routing"] = np.asarray(out["routing"], np.int32)
    return out


def check_answers(cell: common.Cell, seed: int, served: Dict, limits: Dict,
                  reference: Dict = None) -> List[Dict]:
    """The checks of `correct` on the sampled answers. `reference` is
    what they are held against, `reference_solar.forward`'s output for
    `served["sequences"]` read at `served["read_at"]`: computed here
    unless handed in (benchmarks/control_solar.py hands in the reference
    computed with a fault, so that a fault's reading passes through the
    very limits and counts a run's does)."""
    from benchmarks import reference_lm, reference_solar
    checks = [{"name": "answers_malformed", "value": served["malformed"],
               "limit": 0, "ok": served["malformed"] == 0, "note": ""}]
    for name, value, key in (
            ("served_turns_checked", served["turns"], "checked_turns"),
            ("served_sessions_checked", len(served["sessions"]),
             "checked_sessions"),
            ("served_last_turns_checked", served["last_turns"],
             "checked_last"),
            ("served_questions_checked", served["questions"],
             "checked_last")):
        least = int(cell.traffic[key])
        checks.append({"name": name, "value": value, "limit": least,
                       "ok": value >= least, "note": "at least"})
    if not len(served["ids"]):
        return checks
    ref = reference or reference_solar.forward(
        seed, cell.config, served["sequences"], served["read_at"])
    got = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    tokens = sum(len(s) for s in served["sequences"])
    note = (f"{served['turns']} kept turns ({served['last_turns']} a "
            f"session's last) and {served['questions']} plain questions on "
            f"{len(served['sessions'])} sessions, {tokens} tokens through "
            f"the reference, the longest "
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


def counter_total(name: str) -> float:
    from code2vec_tpu import obs
    return sum(m.value for m in
               obs.default_registry().collect().get(name, {}).values())


# ------------------------------------------------------------------ the run

def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    serving = ServingSessions(cell, seed, require_tpu)
    try:
        arrivals = turn_schedule(seed, seconds, cell.traffic)
        drove = serving.drive(arrivals)
        results = list(drove["results"])
        trace_dir, tail_drove = None, None
        if trace:
            # a short window of its own, over bodies the timed window
            # did not send (runners/serve_lm.py says why)
            trace_dir = os.path.join(cell.work, "trace")
            tail = turn_schedule(seed, TRACE_WINDOW_S, cell.traffic)
            for a in tail:
                a["body_index"] += len(arrivals)
            if len(arrivals) + len(tail) > int(cell.traffic["request_pool"]):
                raise common.NoResult("the pool of bodies is too small for "
                                      "the timed and the traced window")
            tail_drove = serving.drive(tail, trace_dir=trace_dir)
            common.say(f"the traced window: {len(tail)} turns")
        # the checked turns: among ALL the finished ones (a session's
        # last kept turn may lie in the traced window)
        finished = results + (tail_drove["results"] if trace else [])
        picked = pick_checked(seed, serving, finished, cell.traffic)
        # the windows have closed: a plain question (no keep) behind the
        # sessions whose last turn is checked
        spare = (int(cell.traffic["request_pool"])
                 + int(cell.traffic["warm_requests"]))
        questions = {}
        for j, s in enumerate(list(picked)[:int(cell.traffic[
                "checked_last"])]):
            asked = serving.pool["blocks"][spare + j][
                :int(cell.traffic["length"]["median"])]
            questions[s] = (asked, serving.ask(s, asked, keep=False))
        registered = registry_total("context_register_seconds")
        book = serving.model.contexts
        pool_fill = 1.0 - len(book._free_pages) / book.pages
        evicted = (counter_total("latent_cache_evictions_total")
                   - serving.evicted_before)
        served = served_answers(serving, finished, picked, questions)
    finally:
        serving.close()
    plan = drove["plan"]
    got = summarize(results, plan["deadline_ms"])
    values = {"request_p50_ms": got["request_p50_ms"],
              "request_p95_ms": got["request_p95_ms"],
              "setup_s": drove["setup_s"]}
    grown = sum(r["length"] for r in results if r and "turn" in r)
    common.say(f"window {drove['window_s']:.2f}s, {got['attempted']} turns, "
               f"{got['failed']} failed, p50 "
               f"{values['request_p50_ms']:.2f} ms, p95 "
               f"{values['request_p95_ms']:.2f} ms, generator late p95 "
               f"{readers.percentile(got['late_ms'], 95):.3f} ms; the "
               f"sessions grew by {grown} tokens, pool {pool_fill:.1%} full")
    t_check = time.perf_counter()
    checks = check_answers(cell, seed, served, cell.limits())
    common.say(f"sampled answers scored by the reference in "
               f"{time.perf_counter() - t_check:.1f}s")
    refused = sum(1 for r in results if r and r["status"] in (404, 409))
    for name, value in (
            ("compiled_inside_window", drove["compiled_in_window"]),
            ("sessions_evicted", int(evicted)),
            ("turns_refused_or_lost", refused),
            ("ids_named_twice", int(serving.renamed))):
        checks.append({"name": name, "value": value, "limit": 0,
                       "ok": value == 0, "note": ""})
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
        extended = serve_lm.counter_delta(drove["registry"],
                                          "context_extend_tokens_total")
        if extended is not None:
            facts["extend_tokens_per_s"] = extended / drove["window_s"]
        facts.update(session_facts(cell, device["kind"], trace_dir,
                                   tail_drove["registry"],
                                   tail_drove["results"]))
        traced = readers.read_traced(
            cell, device["kind"], drove["registry"], drove["window_s"],
            trace_dir, late_ms=got["late_ms"], facts=facts)
        dev.update(traced["device"])
        values, breakdown = traced["values"], traced["breakdown"]
        if "extend_step_mfu" in facts:
            common.say(
                f"traced: {facts['steps_traced']:.0f} steps of "
                f"{facts['rows_per_step']:.2f} rows, "
                f"{facts['step_device_ms']:.3f} ms of device time a step "
                f"(floor {facts['extend_step_floor_ms']:.3f} ms, bound by "
                f"{facts['extend_step_floor_bound']}: extend_step_mfu "
                f"{facts['extend_step_mfu']:.2f} %); device ms a step by "
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
