"""Retrieval-stack bench: embed throughput, index build, recall@10 vs
nprobe, and /neighbors serving latency — the end-to-end proof of the
corpus -> vector store -> ANN index -> served similarity query loop.

Pipeline (all on one generated-Java corpus, experiments/javagen.py, the
same generator the accuracy and serving benches use, extracted by the
real native extractor):

1. EMBED:     every extracted method through the batch embedding job
              (`embed` subcommand body, retrieval/embed_job.py) into a
              sharded vector store — rows/sec at the eval batch size.
2. INDEX:     IVF-flat build (`index-build` body, retrieval/index.py):
              jitted-Lloyd k-means + inverted lists; build wall time.
3. RECALL:    recall@10 of the IVF path vs the brute-force exact
              backend across an nprobe sweep, plus batched query
              latency per nprobe and the brute-force baseline — the
              recall/latency trade-off table of README "Retrieval".
4. SERVING:   `serve --retrieval_index` in process, real HTTP POST
              /neighbors under N concurrent clients re-submitting the
              corpus classes (cache OFF — every request pays
              extract + embed + search): p50/p99 and the
              near-duplicate-first rate (each method's top-1 neighbor
              should be its own corpus row — an identical vector).

Writes experiments/results/retrieval.json; summarized in
BENCH_RETRIEVAL.md. Wrapped by scripts/run_retrieval_bench.sh.
"""

from __future__ import annotations

import json
import os
import pickle
import random
import statistics
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

WORKDIR = "/tmp/retrieval_bench"
OUT_PATH = os.path.join(REPO, "experiments", "results", "retrieval.json")

N_CLASSES = 800           # generated-Java corpus size (~6 methods each)
VOCAB = 20_000
EMBED_BATCH = 256
NLIST = 32                # coarse-quantizer size for the bench corpus
NPROBE_SWEEP = (1, 2, 4, 8, 12, 16, 20, 24, 32)
RECALL_TARGET = 0.95      # the index ships the smallest nprobe >= this
RECALL_QUERIES = 256
SERVE_CLIENTS = 4
SERVE_REQUESTS_PER_CLIENT = 30


def log(msg: str) -> None:
    print(f"[retrieval_bench] {msg}", flush=True)


def build_model(corpus: str):
    """Untrained model whose VOCABULARIES come from the extracted
    corpus itself (the real preprocessing order — with the default
    shared OOV/PAD index, an out-of-vocab-only corpus would filter to
    zero rows). Weights stay untrained (the serving-bench convention:
    latency/throughput don't depend on their values; neighbor structure
    comes from shared contexts)."""
    from collections import Counter

    from code2vec_tpu.config import Config
    from code2vec_tpu.model_facade import Code2VecModel

    prefix = os.path.join(WORKDIR, "corpus")
    words, paths, targets = Counter(), Counter(), Counter()
    with open(corpus) as f:
        for line in f:
            fields = line.split()
            if not fields:
                continue
            targets[fields[0]] += 1
            for ctx in fields[1:]:
                pieces = ctx.split(",")
                if len(pieces) == 3:
                    words[pieces[0]] += 1
                    paths[pieces[1]] += 1
                    words[pieces[2]] += 1
    with open(prefix + ".train.c2v", "w") as f:
        f.write("stub tok0,p0,tok0" + " " * 199 + "\n")
    with open(prefix + ".dict.c2v", "wb") as f:
        pickle.dump(dict(words.most_common(VOCAB)), f)
        pickle.dump(dict(paths.most_common(VOCAB)), f)
        pickle.dump(dict(targets), f)
        pickle.dump(sum(targets.values()), f)
    config = Config(
        train_data_path_prefix=prefix,
        compute_dtype="float32",
        verbose_mode=0,
        test_batch_size=EMBED_BATCH,
        serve_batch_size=16,
        extractor_pool_size=2,
        serve_cache_entries=0,      # /neighbors latency = the full path
        embed_shard_rows=1024,
    )
    return Code2VecModel(config)


def make_sources():
    from experiments.javagen import NOUNS, generate_class
    rng = random.Random(7)
    return [generate_class(rng, NOUNS, f"Ret{i}", "com.bench",
                           rng.randint(4, 9))
            for i in range(N_CLASSES)]


def extract_corpus(sources) -> str:
    """Real-extractor pass over the generated classes -> a predict-line
    corpus file (method name as the target, contexts as extracted)."""
    from code2vec_tpu.config import Config
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    os.makedirs(WORKDIR, exist_ok=True)
    corpus = os.path.join(WORKDIR, "methods.test.c2v")
    t0 = time.perf_counter()
    rows = []
    config = Config(model_load_path=None, serve_artifact="unused",
                    verbose_mode=0)  # extractor knobs only, never verified
    with ExtractorPool(config, size=2, log=lambda m: None) as pool:
        for src in sources:
            lines, _ = pool.extract_source(src)
            rows.extend(line.rstrip("\n") for line in lines)
    with open(corpus, "w") as f:
        f.write("\n".join(rows) + "\n")
    for stale in (corpus + "b", corpus + "b.targets",
                  corpus + "b.meta.json"):
        if os.path.exists(stale):
            os.unlink(stale)
    log(f"extracted {len(rows)} methods from {len(sources)} classes "
        f"in {time.perf_counter() - t0:.1f}s")
    return corpus


def bench_embed(model, corpus: str) -> dict:
    import shutil

    from code2vec_tpu.retrieval.embed_job import run_embed_job
    store_dir = os.path.join(WORKDIR, "store")
    shutil.rmtree(store_dir, ignore_errors=True)
    summary = run_embed_job(model, corpus_path=corpus,
                            out_dir=store_dir, log=lambda m: None)
    log(f"embed: {summary['rows']} rows in {summary['seconds']:.1f}s "
        f"= {summary['rows_per_sec']:.0f} rows/s "
        f"({summary['shards']} shards)")
    return {**summary, "store_dir": store_dir,
            "batch_size": EMBED_BATCH}


def bench_index(store_dir: str, nprobe: int = 8) -> dict:
    import shutil

    from code2vec_tpu.retrieval.index import build_index
    idx_dir = os.path.join(WORKDIR, "index")
    shutil.rmtree(idx_dir, ignore_errors=True)
    meta = build_index(store_dir, idx_dir, nlist=NLIST, nprobe=nprobe,
                       kmeans_iters=10, seed=0, log=lambda m: None)
    log(f"index-build: backend {meta['backend']}, nlist {meta['nlist']},"
        f" default nprobe {meta['nprobe']}, {meta['build_seconds']}s")
    return {"index_dir": idx_dir, **{k: meta[k] for k in (
        "backend", "nlist", "nprobe", "rows", "build_seconds")}}


def bench_recall(store_dir: str, index: dict) -> dict:
    """Recall/latency sweep, then TUNE: rebuild the index with the
    smallest nprobe whose measured recall@10 clears RECALL_TARGET —
    the operating point a real deploy would pick from this exact
    curve, recorded as the artifact's default (what `serve
    --retrieval_index` then runs at)."""
    import numpy as np

    from code2vec_tpu.retrieval.index import load_index, measure_recall
    idx = load_index(index["index_dir"])
    rng = np.random.default_rng(11)
    pick = rng.permutation(idx.rows)[:RECALL_QUERIES]
    queries = np.asarray(idx._vectors)[pick]

    def timed_search(**kw):
        idx.search(queries, 10, **kw)              # compile outside
        t0 = time.perf_counter()
        for _ in range(3):
            idx.search(queries, 10, **kw)
        return (time.perf_counter() - t0) / 3 / len(queries) * 1e6

    brute_us = timed_search(exact=True)
    sweep = []
    for nprobe in NPROBE_SWEEP:
        if nprobe > idx.nlist:
            continue
        sweep.append({
            "nprobe": nprobe,
            "recall_at_10": round(
                measure_recall(idx, queries, 10, nprobe=nprobe), 4),
            "query_us": round(timed_search(nprobe=nprobe), 1),
        })
        log(f"recall@10 nprobe={nprobe}: {sweep[-1]['recall_at_10']} "
            f"({sweep[-1]['query_us']:.0f}us/query batched)")
    tuned = next((s for s in sweep
                  if s["recall_at_10"] >= RECALL_TARGET), sweep[-1])
    log(f"brute-force exact: {brute_us:.0f}us/query batched; tuned "
        f"operating point: nprobe {tuned['nprobe']} at recall@10 "
        f"{tuned['recall_at_10']}")
    if tuned["nprobe"] != idx.nprobe:
        index.update(bench_index(store_dir, nprobe=tuned["nprobe"]))
    return {"queries": RECALL_QUERIES, "k": 10,
            "brute_force_query_us": round(brute_us, 1),
            "recall_target": RECALL_TARGET,
            "default_nprobe": tuned["nprobe"],
            "default_nprobe_recall_at_10": tuned["recall_at_10"],
            "sweep": sweep}


def bench_serving(model, sources, index_dir: str) -> dict:
    import urllib.error

    from code2vec_tpu.serving.server import PredictionServer
    config = model.config
    config.retrieval_index = index_dir
    # the bench measures the full path, not an SLO: a generous deadline
    # keeps dev-CPU device steps from turning the tail into 504s
    config.serve_deadline_ms = 60_000.0
    server = PredictionServer(model, config, log=lambda m: None)
    port = server.start(port=0)
    try:
        bodies = sources[:SERVE_CLIENTS * SERVE_REQUESTS_PER_CLIENT]

        def post(body: str) -> dict:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/neighbors",
                data=body.encode(), method="POST",
                headers={"Content-Type": "text/plain"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        # Warmup outside the measurement: distinct classes land in
        # different context buckets — walk several so every serve
        # shape compiles before the clock starts.
        for body in bodies[:8]:
            post(body)
        latencies = []
        methods_total = [0]
        self_top1 = [0]
        shed = [0]
        lock = threading.Lock()

        def client(ci: int):
            rng = random.Random(ci)
            for _ in range(SERVE_REQUESTS_PER_CLIENT):
                body = rng.choice(bodies)
                t0 = time.perf_counter()
                try:
                    payload = post(body)
                except urllib.error.HTTPError as e:
                    if e.code in (503, 504):
                        with lock:
                            shed[0] += 1  # admission doing its job
                        continue
                    raise
                dt = time.perf_counter() - t0
                with lock:
                    latencies.append(dt)
                    for m in payload["methods"]:
                        methods_total[0] += 1
                        top = (m["neighbors"] or [None])[0]
                        # near-duplicate-first: the method's own corpus
                        # row (by id), or an exact clone of it (javagen
                        # corpora legitimately contain context-identical
                        # methods across classes — distance ~0 ties)
                        if top and (top["id"] == m["original_name"]
                                    or top["distance"] < 1e-3):
                            self_top1[0] += 1

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(SERVE_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        latencies.sort()

        def pct(p):
            return latencies[min(int(p * len(latencies)),
                                 len(latencies) - 1)]

        result = {
            "clients": SERVE_CLIENTS,
            "requests": len(latencies),
            "shed": shed[0],
            "methods_scored": methods_total[0],
            "near_duplicate_top1_rate": round(
                self_top1[0] / max(methods_total[0], 1), 4),
            "p50_ms": round(pct(0.50) * 1e3, 1),
            "p99_ms": round(pct(0.99) * 1e3, 1),
            "requests_per_sec": round(len(latencies) / wall, 1),
        }
        log(f"/neighbors: {result['requests']} requests ({shed[0]} "
            f"shed), p50 {result['p50_ms']}ms p99 "
            f"{result['p99_ms']}ms, near-duplicate-first rate "
            f"{result['near_duplicate_top1_rate']}")
        return result
    finally:
        server.drain(timeout=30)
        config.retrieval_index = None


def main() -> None:
    import jax

    t0 = time.perf_counter()
    sources = make_sources()
    corpus = extract_corpus(sources)
    model = build_model(corpus)
    embed = bench_embed(model, corpus)
    index = bench_index(embed["store_dir"])
    recall = bench_recall(embed["store_dir"], index)
    serving = bench_serving(model, sources, index["index_dir"])
    results = {
        "host": {"backend": jax.default_backend(),
                 "devices": jax.device_count(),
                 "jax": jax.__version__},
        "corpus": {"classes": N_CLASSES, "methods": embed["rows"],
                   "dim": model.config.code_vector_size},
        "embed": {k: embed[k] for k in
                  ("rows", "seconds", "rows_per_sec", "shards",
                   "batch_size")},
        "index_build": {k: index[k] for k in
                        ("backend", "nlist", "nprobe", "rows",
                         "build_seconds")},
        "recall": recall,
        "neighbors_serving": serving,
        "total_seconds": round(time.perf_counter() - t0, 1),
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    log(f"wrote {OUT_PATH} ({results['total_seconds']}s total)")

    diag = os.environ.get("C2V_CHAOS_DIAG_DIR")
    if diag:
        from code2vec_tpu import obs
        obs.exporters.write_prometheus(
            os.path.join(diag, "retrieval_bench_metrics.prom"))


if __name__ == "__main__":
    main()
