"""Runner kind `serve`: an in-process `PredictionServer` on the process
that holds the chip, every context bucket warmed before the window, the
C++ extractor pool as shipped, and an open-loop generator in a JAX-free
child (`benchmarks/loadgen.py`).

`correct`: once the window has closed, for a sample of the requests it
finished (drawn from the seed, the largest file among them) the
benchmark parses the same sources itself (the extractor binary, then its
own path hashing, truncation and dictionary look-up), runs the float32
reference once over those contexts with the seed's weights, and reads
the widest gap by which a served top name's reference logit lies below
the reference's best. The extractor itself is the system's; tier-1's
byte-level goldens hold it.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import random
import select
import shutil
import subprocess
import sys
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks import common, datagen, javagen, loadgen, readers, reference
from benchmarks.runners import train

TRACE_WINDOW_S = 5.0    # a traced run's extra window, after the timed one
TRACE_AFTER_S = 1.0     # the trace starts this far into it
TRACE_FOR_S = 3.0       # and lasts this long


# ------------------------------------------------------- the benchmark's parse

def java_hashcode(s: str) -> int:
    """Java's String#hashCode, as the training data hashes its paths."""
    h = 0
    for ch in s:
        h = (31 * h + ord(ch)) & 0xFFFFFFFF
    return h - (1 << 32) if h >= (1 << 31) else h


def extractor_path(root: str) -> str:
    return os.path.join(root, "cpp", "build", "c2v-extract")


def ensure_extractor(root: str) -> str:
    """The C++ extractor, built on a checkout's first run (cpp/build is
    git-ignored)."""
    exe = extractor_path(root)
    if not os.path.exists(exe):
        common.say("building cpp/ (first run in this checkout)")
        subprocess.run(["make", "-C", os.path.join(root, "cpp"),
                        f"-j{os.cpu_count() or 1}"], check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    return exe


def extract_methods(exe: str, path: str, max_contexts: int
                    ) -> List[Tuple[str, List[Tuple[str, str, str]]]]:
    """[(method name, [(token, hashed path, token)] cut to max_contexts)]
    of a Java file, as the serving path prepares them."""
    out = subprocess.run(
        [exe, "--max_path_length", "8", "--max_path_width", "2",
         "--file", path, "--no_hash"], capture_output=True, text=True,
        timeout=120, check=True).stdout
    methods = []
    for line in out.splitlines():
        parts = line.rstrip().split(" ")
        contexts = []
        for c in parts[1:][:max_contexts]:
            if c:
                w1, p, w2 = c.split(",")
                contexts.append((w1, str(java_hashcode(p)), w2))
        methods.append((parts[0], contexts))
    return methods


def prepare_serve_data(root: str, work: str, config: Dict, traffic: Dict
                       ) -> Dict:
    """On the cell's first run: the pool of Java files (seeded, 1-9
    methods each, heavy-tailed), and dictionaries at the published row
    counts whose most frequent words are the real tokens, paths and
    names of the pool's first `vocabulary_files` files (the rest of the
    pool brings words the model has never seen, as new code does)."""
    data = os.path.join(work, "data")
    java = os.path.join(work, "java")
    prefix = os.path.join(data, "serve")
    head_path = os.path.join(data, "vocab_head.json")
    made = not os.path.exists(head_path)
    if made:
        os.makedirs(data, exist_ok=True)
        shutil.rmtree(java, ignore_errors=True)
        os.makedirs(java)
        rng = random.Random(int(traffic["corpus_seed"]))
        lo, hi = traffic["methods_per_file"]
        files = []
        for i in range(int(traffic["request_pool"])
                       + int(traffic["warm_requests"])):
            # heavy tail: most files are small, a few have `hi` methods
            n = min(hi, lo + int(rng.expovariate(1.0 / 2.0)))
            name = javagen.cap(rng.choice(javagen.NOUNS)) + "Service" + str(i)
            path = os.path.join(java, f"{i:05d}.java")
            with open(path, "w") as f:
                f.write(javagen.generate_class(
                    rng, javagen.NOUNS, name, "com.gen.bench", n))
            files.append(path)
        exe = extractor_path(root)
        counts: Dict[str, Dict[str, int]] = {"token": {}, "path": {},
                                             "target": {}}
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            for methods in pool.map(
                    lambda p: extract_methods(exe, p, config["max_contexts"]),
                    files[:int(traffic["vocabulary_files"])]):
                for name, contexts in methods:
                    counts["target"][name] = counts["target"].get(name, 0) + 1
                    for w1, p, w2 in contexts:
                        for kind, w in (("token", w1), ("path", p),
                                        ("token", w2)):
                            counts[kind][w] = counts[kind].get(w, 0) + 1
        head = {k: sorted(v, key=lambda w: (-v[w], w))
                for k, v in counts.items()}
        datagen.write_dictionaries(prefix + ".dict.c2v", config, 1, head)
        with open(prefix + ".train.c2v.num_examples", "w") as f:
            f.write("1\n")
        with open(head_path, "w") as f:
            json.dump(head, f)
    with open(head_path) as f:
        head = json.load(f)
    return {"prefix": prefix, "java": java, "head": head, "made": made}


def lookup_tables(config: Dict, head: Dict) -> Dict[str, Dict[str, int]]:
    """word -> id as the dictionaries give it (id 0 is the special word,
    word k of the list is k + 1). Tokens and paths: the real words only,
    since a synthetic fill word never comes out of the extractor; target
    names: all of them, since the model may serve any."""
    names = datagen.vocabulary(head["target"], config["target_rows"],
                               datagen._target_word)
    return {"token": {w: i + 1 for i, w in enumerate(head["token"])},
            "path": {w: i + 1 for i, w in enumerate(head["path"])},
            "target": {w: i + 1 for i, w in enumerate(names)}}


def contexts_to_arrays(methods, tables: Dict, max_contexts: int):
    n = len(methods)
    src = np.zeros((n, max_contexts), np.int32)
    pth = np.zeros((n, max_contexts), np.int32)
    tgt = np.zeros((n, max_contexts), np.int32)
    for r, (_, contexts) in enumerate(methods):
        for c, (w1, p, w2) in enumerate(contexts):
            src[r, c] = tables["token"].get(w1, 0)
            pth[r, c] = tables["path"].get(p, 0)
            tgt[r, c] = tables["token"].get(w2, 0)
    # a context is real when any of its three parts is in the vocabulary
    # (the published model's mask: the special word doubles as padding)
    mask = ((src != 0) | (pth != 0) | (tgt != 0)).astype(np.float32)
    return src, pth, tgt, mask


# ------------------------------------------------------------- the run

def warm_line(contexts: int, max_contexts: int, head: Dict) -> str:
    """A predict line with exactly `contexts` real contexts (words of
    the vocabulary: the bucket follows the deepest valid context)."""
    ctx = " ".join([f"{head['token'][0]},{head['path'][0]},"
                    f"{head['token'][0]}"] * contexts)
    return "warm|up " + ctx + " " * (max_contexts - contexts)


def served_arrays(cell, exe, tables, results, plan) -> Dict:
    """The sampled answers beside the benchmark's own parse of the same
    files: contexts as id arrays, the served names' ids and
    log-probabilities (N, K), and what did not line up."""
    cap = cell.config["max_contexts"]
    methods, served, mismatched = [], [], 0
    for r in results:
        if r is None or "body" not in r:
            continue
        own = extract_methods(exe, plan["requests"][r["i"]]["file"], cap)
        answer = json.loads(r["body"])["methods"]
        if [m["original_name"] for m in answer] != [n for n, _ in own]:
            mismatched += 1
            continue
        for (name, contexts), m in zip(own, answer):
            if contexts and m["predictions"]:
                methods.append((name, contexts))
                served.append([(tables["target"].get("|".join(p["name"]), -1),
                                p["probability"]) for p in m["predictions"]])
    out = {"mismatched": mismatched, "methods": len(methods),
           "unknown": sum(1 for row in served for i, _ in row if i < 0),
           "requests": sum(1 for r in results if r and "body" in r)}
    if methods:
        k = max(len(row) for row in served)
        ids = np.full((len(served), k), -1, np.int32)
        logp = np.zeros((len(served), k), np.float32)
        for r, row in enumerate(served):
            for c, (i, prob) in enumerate(row):
                ids[r, c], logp[r, c] = i, np.log(max(prob, 1e-30))
        out["contexts"] = contexts_to_arrays(methods, tables, cap)
        out["ids"], out["logp"] = ids, logp
    return out


def check_answers(served: Dict, dims, seed, limits) -> List[Dict]:
    """The sampled answers against the reference."""
    checks = [{"name": "answers_with_other_methods",
               "value": served["mismatched"], "limit": 0,
               "ok": served["mismatched"] == 0, "note": ""},
              {"name": "served_names_unknown", "value": served["unknown"],
               "limit": 0, "ok": served["unknown"] == 0, "note": ""}]
    if not served["methods"] or served["unknown"]:
        checks.append({"name": "served_methods_checked", "value": 0,
                       "limit": 1, "ok": False, "note": "nothing to compare"})
        return checks
    got = reference.served_gap(reference.make_params(seed, dims),
                               *served["contexts"], served["ids"],
                               served["logp"])
    note = f"{served['methods']} served methods of {served['requests']} requests"
    for name, key in (("served_top_logit_gap", "top_gap"),
                      ("served_score_gap", "score_gap")):
        checks.append({"name": name, "value": got[key],
                       "limit": limits[name],
                       "ok": bool(got[key] <= limits[name]), "note": note})
    return checks


def ensure_checkpoint(cell: common.Cell, prefix: str, argv: List[str]) -> str:
    """The checkpoint the deployment starts from, written on the cell's
    first run in a checkout by the program's own `save` (Orbax state
    with optimizer moments, dictionaries, manifest: what a training run
    leaves behind). Every run then starts as `serve --load` does."""
    base = os.path.join(cell.work, "checkpoint", "saved")
    if os.path.isdir(base):       # committed by a rename: whole or absent
        return base
    import jax
    common.say("writing the deployment's checkpoint (first run in this "
               "checkout)")
    first = train.Program(cell, prefix, 0, argv=argv + ["--data", prefix],
                          with_train_step=False)
    path = first.model.save(base)
    for leaf in jax.tree.leaves(first.model.state):
        leaf.delete()
    return path


class Serving:
    """The system under test, up and warm: the model with the seed's
    weights, the `PredictionServer` on a local port, every context
    bucket compiled, the extractor pool exercised."""

    def __init__(self, cell: common.Cell, seed: int, require_tpu: bool = True):
        common.configure_jax()
        self.device = common.require_chips(cell.chips, require_tpu)
        from code2vec_tpu.serving.server import PredictionServer
        self.cell, self.seed = cell, seed
        cfg, traffic = cell.config, cell.traffic
        self.exe = ensure_extractor(cell.root)
        self.data = prepare_serve_data(cell.root, cell.work, cfg, traffic)
        common.say(f"pool and dictionaries "
                   f"{'made' if self.data['made'] else 'found'}")
        argv = (["serve", "--serve_port", "0",
                 "--max_contexts", str(cfg["max_contexts"]),
                 "--seed", str(int(seed) % (2 ** 31 - 1))]
                + list(traffic.get("program_args", [])))
        # the deployment path: the facade restores a checkpoint into its
        # fresh initial state (`serve --load`), and the memory peak is
        # the program's own; only then do the seed's weights take the
        # restored parameters' place, those freed first
        saved = ensure_checkpoint(cell, self.data["prefix"], argv)
        self.program = train.Program(cell, self.data["prefix"], seed,
                                     argv=argv + ["--load", saved],
                                     with_train_step=False)
        self.program.seed_state(seed)
        self.model, self.config = self.program.model, self.program.config
        self.server = PredictionServer(self.model, self.config)
        self.port = self.server.start(0, "127.0.0.1")
        for bucket in self.model.context_buckets:
            self.model.predict(
                [warm_line(bucket, cfg["max_contexts"], self.data["head"])],
                batch_size=self.config.serve_batch_size,
                with_code_vectors=True)
        n_pool = int(traffic["request_pool"])
        for i in range(int(traffic["warm_requests"])):
            self.server.handle("predict", self.body(n_pool + i))

    def file(self, index: int) -> str:
        return os.path.join(self.data["java"], f"{index:05d}.java")

    def body(self, index: int) -> str:
        with open(self.file(index)) as f:
            return f.read()

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
            [sys.executable, os.path.join(common.HOME, "loadgen.py"),
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


def summarize(results: List[Dict], deadline_ms: float) -> Dict:
    """p50 and p95 over ALL requests (a failed one misses every
    percentile: it counts as infinitely late), and the failures."""
    failed = sum(1 for r in results if r is None or not r["ok"])
    latencies = [(r["latency_ms"] if r and r["ok"] else float("inf"))
                 for r in results]
    sentinel = 10.0 * deadline_ms

    def finite(x):
        return x if np.isfinite(x) else sentinel
    return {"request_p50_ms": finite(readers.percentile(latencies, 50)),
            "request_p95_ms": finite(readers.percentile(latencies, 95)),
            "failed": failed, "attempted": len(results),
            "late_ms": [r["late_ms"] for r in results if r]}


def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, emit: bool = True) -> Dict:
    serving = Serving(cell, seed, require_tpu)
    try:
        arrivals = loadgen.schedule(seed, seconds, cell.traffic)
        rng = random.Random(int(seed) ^ 0x5EED)
        checked = set(rng.sample(
            range(len(arrivals)),
            min(int(cell.traffic["checked_requests"]), len(arrivals))))
        checked.add(max(range(len(arrivals)), key=lambda i: os.path.getsize(
            serving.file(arrivals[i]["body_index"]))))
        drove = serving.drive(arrivals, checked)
        trace_dir = None
        if trace:
            # the trace is taken over a short window of its own, the same
            # mix over bodies the timed window did not send: stopping a
            # trace stalls the host for seconds, which inside the timed
            # window would be read as the server's own tail
            trace_dir = os.path.join(cell.work, "trace")
            tail = loadgen.schedule(seed, TRACE_WINDOW_S, cell.traffic)
            for a in tail:
                a["body_index"] += len(arrivals)
            if len(arrivals) + len(tail) > int(cell.traffic["request_pool"]):
                raise common.NoResult("the pool of bodies is too small for "
                                      "the timed and the traced window")
            serving.drive(tail, trace_dir=trace_dir)
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
    tables = lookup_tables(cell.config, serving.data["head"])
    t_check = time.perf_counter()
    checks = check_answers(
        served_arrays(cell, serving.exe, tables, results, plan),
        serving.program.dims, seed, cell.limits())
    common.say(f"sampled answers parsed again and scored by the reference "
               f"in {time.perf_counter() - t_check:.1f}s")
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
        traced = readers.read_traced(
            cell, device["kind"], drove["registry"], drove["window_s"],
            trace_dir, late_ms=got["late_ms"],
            facts={"request_p95_ms": got["request_p95_ms"]})
        dev.update(traced["device"])
        values, breakdown = traced["values"], traced["breakdown"]
        names = cell.per_layer()
    else:
        names = cell.end_to_end()
    common.emit(correct, got["attempted"], got["failed"],
                common.metric_values(names, values), dev, breakdown,
                checks)
    return result


def _trace_slice(trace_dir: str) -> None:
    """Trace TRACE_FOR_S seconds of the window, TRACE_AFTER_S in."""
    import jax
    time.sleep(TRACE_AFTER_S + 0.25)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1       # the harness's annotations only
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    time.sleep(TRACE_FOR_S)
    jax.profiler.stop_trace()
