"""Shared by tests/benchmark/test_benchmark_*.py: the repo root on the
path, and a temporary copy of the benchmark to which toy configurations,
traffic mixes, cells and a per-layer metric are ADDED as new files and
new BENCHMARK.json entries, no existing file edited.

Why three files. The tier-1 command runs `-n 6 --dist loadfile`: whole
files are handed to six workers, files with most tests first, and a
worker gets its next file when it has two tests left. One test of the
standing tree, tests/test_edge.py::
test_shared_fleet_view_derives_candidates_and_view, fails whenever
test_fleet.py or test_pipeline.py ran earlier in its process (they leave
`fleet_swap_total{outcome="committed"}` in the process-wide metrics
registry, which that test then reads as 2 + theirs). In the standing
tree the worker that ran both is free again within a second of the
moment test_edge.py is handed out, and which worker takes it is a coin
toss: six new files of other sizes tipped it the wrong way (PR 22 and
the first PR 23 were refused over it). So the tests of the benchmark
are cut into exactly three files of 19 or 20 tests, long tests first:
they sort between test_extractor.py (21 tests) and test_cs_extractor.py
(18), the three workers that are free in the run's first twelve seconds
(the only ones that can have run test_pipeline.py or test_fleet.py by
then) take one each and stay busy for three quarters of a minute, and
test_edge.py (17 tests) goes to a worker that has run neither. Keep the
counts at 19 or 20 and add no fourth file here until test_edge.py or
the registry is repaired (PERF.md, Open questions)."""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


TOY_SIZES = dict(token_rows=3000, path_rows=2000, target_rows=1500,
                 token_dim=16, path_dim=16, code_dim=48, max_contexts=20,
                 batch_rows_per_chip=64, train_rows=4096)


def make_toy_root(dest: str) -> str:
    """BENCHMARK.json and the data files of benchmarks/ copied to `dest`,
    plus toy entries added by new files alone."""
    os.makedirs(dest)
    home = os.path.join(dest, "benchmarks")
    os.makedirs(home)
    for sub in ("configs", "traffic", "layer_metrics", "limits"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub),
                        os.path.join(home, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def load(*parts):
        with open(os.path.join(home, *parts)) as f:
            return json.load(f)

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)

    overrides = {"token_embeddings_size": 16, "path_embeddings_size": 16}
    toy = dict(load("configs", "java14m.json"), name="toy", **TOY_SIZES,
               program_overrides=overrides)
    dump(toy, "configs", "toy.json")
    dump(dict(toy, name="toy-nodrop", dropout_keep=1.0,
              program_overrides=dict(overrides, dropout_keep_rate=1.0)),
         "configs", "toy-nodrop.json")
    dump(dict(toy, name="toy32", max_contexts=32), "configs", "toy32.json")
    dump(dict(load("traffic", "train_hostfed.json"), name="toy_train",
              corpus_rows=4096, reference_block_rows=32,
              context_count={"dist": "lognormal", "mu": 2.0, "sigma": 0.7,
                             "min": 2}), "traffic", "toy_train.json")
    dump(dict(load("traffic", "serve_open_norepeat.json"), name="toy_serve",
              rate_per_s=20.0, request_pool=96, vocabulary_files=48,
              checked_requests=8, warm_requests=4, generator_threads=8,
              program_args=["--serve_batch_size", "8", "--serve_buckets",
                            "8,16", "--extractor_pool_size", "2"]),
         "traffic", "toy_serve.json")
    dump(dict(load("layer_metrics", "pack_ms.train.json"),
              name="device_put_ms.train",
              args={"name": "prefetch_device_put_seconds", "stat": "mean",
                    "scale": 1000.0}),
         "layer_metrics", "device_put_ms.train.json")
    for name in ("toy", "toy-nodrop", "toy32"):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "toy",
            "file": f"benchmarks/configs/{name}.json"})
    cells = [("toy.train", "toy", "toy_train"),
             ("toy-nodrop.train", "toy-nodrop", "toy_train"),
             ("toy32.serve", "toy32", "toy_serve")]
    for name, config, traffic in cells:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        listed = metric.get("workloads", [])
        if "java14m.train_hostfed" in listed:
            listed += ["toy.train", "toy-nodrop.train"]
        if "java14m.serve_open" in listed:
            listed.append("toy32.serve")
    bench["per_layer"].append({
        "name": "device_put_ms.train", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "feed",
        "moves": "examples_per_s", "workloads": ["toy.train"]})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest
