"""Shared by tests/benchmark/test_benchmark_*.py: the repo root on the
path, and a temporary copy of the benchmark to which toy configurations,
traffic mixes, cells and a per-layer metric are ADDED as new files and
new BENCHMARK.json entries, no existing file edited.

How a PR adds a per-layer metric: a file `layer_metrics/<metric>.json`
and an entry APPENDED to `per_layer` (`make_toy_root` does exactly that
with `device_put_ms.train`). The harness and every test here find an
entry by its NAME; none asserts a place, so a later entry breaks
nothing (until PR 36 three tests pinned the last place to PR 27's
metric and no other kind of PR could list one).

The files are cut by subject (benchmarks/README.md, "The tests"). They
were once held to three files of 19 or 20 tests, because the tier-1
command hands whole files to six workers and tests/test_edge.py::
test_shared_fleet_view_derives_candidates_and_view failed on a worker
that had run test_fleet.py or test_pipeline.py before it; since PR 24
that test takes the difference of the process-wide counter, and the
sizes of these files constrain nothing."""

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
