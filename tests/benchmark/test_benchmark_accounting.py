"""The seventeen per-layer metrics PR 37 added by files alone: the parts
of the served call's device stage, the request's phases and the server's
own total, collections, host stalls and compiles anywhere in the window.
Each reads a histogram the program observes (`registry_histogram`); each
is found by its name."""

import json
import os

import pytest

from bench_testlib import ROOT

from benchmarks import common, readers
from benchmarks.common import RegistryWindow

J, N, G, K = ("java14m.serve_open", "nemotron3-super-ep4.serve_score_open",
              "glm47-flash-pp8.serve_score_ctx_open",
              "keye-vl2-pp8.serve_score_longctx_open")
# the long-context cell reports none of them: the set of its metrics is
# pinned by an equality in tests/benchmark/test_benchmark_keye.py, a
# file this PR may not edit (PERF.md section 7)
SERVE = [J, N, G]
TRAIN = ["java14m.train_hostfed", "java14m-ctx500.train_hostfed",
         "java14m.train_dp4", "java14m.train_hostfed_realcounts"]
STEP, HOST, RUNTIME = "model step as served", "serving host", "host runtime"
PART, PHASE = "serving_predict_device_seconds", "serving_request_seconds"

# name -> (cells, layer, unit, source, moves, the series, its labels, stat)
ADDED = {
    "device_put_ms.serve": (SERVE, STEP, "ms", "program_span", PART,
                            {"part": "put"}, "mean"),
    "device_enqueue_ms.serve": (SERVE, STEP, "ms", "program_span", PART,
                                {"part": "enqueue"}, "mean"),
    "device_wait_ms.serve": (SERVE, STEP, "ms", "program_span", PART,
                             {"part": "wait"}, "mean"),
    "device_fetch_ms.serve": (SERVE, STEP, "ms", "program_span", PART,
                              {"part": "fetch"}, "mean"),
    "slot_lookup_ms.serve": ([G], STEP, "ms", "program_span", PART,
                             {"part": "lookup"}, "mean"),
    "request_server_total_ms.serve": (
        SERVE, HOST, "ms", "program_counter", PHASE,
        {"phase": "total", "status": "200"}, "mean"),
    "request_admit_ms.serve": (SERVE, HOST, "ms", "program_counter", PHASE,
                               {"phase": "admit"}, "mean"),
    "request_handoff_ms.serve": (SERVE, HOST, "ms", "program_counter",
                                 PHASE, {"phase": "handoff"}, "mean"),
    "request_respond_ms.serve": (SERVE, HOST, "ms", "program_counter",
                                 PHASE, {"phase": "respond"}, "mean"),
    "request_http_ms.serve": (SERVE, HOST, "ms", "program_counter", PHASE,
                              {"phase": "http"}, "mean"),
    "request_queue_wait_ms.serve": ([J], HOST, "ms", "program_counter",
                                    PHASE, {"phase": "queue_wait"}, "mean"),
    "gc_pause_ms.serve": (SERVE, RUNTIME, "ms", "program_counter",
                          "python_gc_pause_seconds", None, "sum"),
    "gc_pause_ms.train": (TRAIN, RUNTIME, "ms", "program_counter",
                          "python_gc_pause_seconds", None, "sum"),
    "host_stall_ms.serve": (SERVE, RUNTIME, "ms", "program_counter",
                            "host_stall_seconds", None, "sum"),
    "host_stall_ms.train": (TRAIN, RUNTIME, "ms", "program_counter",
                            "host_stall_seconds", None, "sum"),
    "compiles_anywhere_in_window.serve": (
        SERVE, RUNTIME, "compiles", "program_counter", "jax_compiles_during",
        {"span": "process"}, "sum"),
    "compiles_anywhere_in_window.train": (
        TRAIN, RUNTIME, "compiles", "program_counter", "jax_compiles_during",
        {"span": "process"}, "sum"),
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_there_are_seventeen_and_they_came_behind_the_forty_one():
    names = [m["name"] for m in bench()["per_layer"]]
    assert len(ADDED) == 17 and len(names) == len(set(names))
    places = sorted(names.index(n) for n in ADDED)
    assert places == list(range(places[0], places[0] + 17))
    assert places[0] > names.index("dense_blocks_run_pct.train")


@pytest.mark.parametrize("name", sorted(ADDED))
def test_an_added_metric_has_its_file_its_entry_and_its_cells(name):
    cells, layer, unit, source, series, labels, stat = ADDED[name]
    moves = "examples_per_s" if name.endswith(".train") else "request_p50_ms"
    b = bench()
    [entry] = [m for m in b["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "registry_histogram" in readers.KINDS
    for key in ("name", "layer", "unit", "moves", "source"):
        assert spec[key] == entry[key], key
    assert spec["what"]
    args = spec["args"]
    assert (args["name"], args.get("labels"), args["stat"]) == (
        series, labels, stat)
    assert args.get("scale") == (None if unit == "compiles" else 1000.0)
    for w in b["workloads"]:
        listed = name in [m["name"] for m in
                          common.Cell(ROOT, w["name"]).per_layer()]
        assert listed == (w["name"] in cells), w["name"]


def _registry():
    from code2vec_tpu.obs.metrics import MetricsRegistry
    return MetricsRegistry()


def test_the_parts_and_the_phases_read_their_own_series_in_ms():
    """Through the benchmark's own window over a registry of the
    program's kind: a part's mean, the total of the answered requests
    alone, a sum over every generation."""
    reg = _registry()
    window = RegistryWindow(reg)
    reg.histogram(PART, "", part="wait").observe(9.0)      # before it
    window.open()
    for part, seconds in (("put", 0.001), ("enqueue", 0.0004),
                          ("wait", 0.005), ("wait", 0.007),
                          ("fetch", 0.002), ("lookup", 0.0001)):
        reg.histogram(PART, "", part=part).observe(seconds)
    reg.histogram(PHASE, "", phase="total", status="200").observe(0.018)
    reg.histogram(PHASE, "", phase="total", status="504").observe(30.0)
    reg.histogram(PHASE, "", phase="respond").observe(0.0012)
    for generation, seconds in (("0", 0.001), ("0", 0.002), ("2", 0.25)):
        reg.histogram("python_gc_pause_seconds", "",
                      generation=generation).observe(seconds)
    window.close()
    got = readers.read_all(readers.Measured(
        common.Cell(ROOT, G), "TPU v5 lite", window, window_s=20.0))
    assert got["device_put_ms.serve"] == pytest.approx(1.0)
    assert got["device_enqueue_ms.serve"] == pytest.approx(0.4)
    assert got["device_wait_ms.serve"] == pytest.approx(6.0)
    assert got["device_fetch_ms.serve"] == pytest.approx(2.0)
    assert got["slot_lookup_ms.serve"] == pytest.approx(0.1)
    assert got["request_server_total_ms.serve"] == pytest.approx(18.0)
    assert got["request_respond_ms.serve"] == pytest.approx(1.2)
    assert got["gc_pause_ms.serve"] == pytest.approx(253.0)
    # nothing observed: left out of the line, nothing raises (so reads
    # the parent, whose program has none of the series)
    assert not {"request_admit_ms.serve", "request_http_ms.serve",
                "host_stall_ms.serve",
                "compiles_anywhere_in_window.serve"} & set(got)
    assert "slot_lookup_ms.serve" not in readers.read_all(readers.Measured(
        common.Cell(ROOT, J), "TPU v5 lite", window, window_s=20.0))


def test_a_sound_window_reads_zero_and_a_troubled_one_what_it_lost():
    """The program observes 0 a tick where nothing stalled, nothing
    compiled and no collection ended, so a sound window's sums ARE 0 and
    not missing; a stall of 3.5 s and two compiles read 3,500 and 2."""
    reg = _registry()
    window = RegistryWindow(reg)
    window.open()
    for _ in range(50):
        reg.histogram("host_stall_seconds", "", kind="none").observe(0.0)
        reg.histogram("python_gc_pause_seconds", "",
                      generation="none").observe(0.0)
        reg.histogram("jax_compiles_during", "", buckets=(0, 1, 2),
                      span="process").observe(0)
    # compiles inside a step do not make one anywhere else
    reg.histogram("jax_compiles_during", "", buckets=(0, 1, 2),
                  span="step_dispatch").observe(0)
    window.close()
    cell = common.Cell(ROOT, TRAIN[0])
    sound = readers.read_all(readers.Measured(cell, "TPU v5 lite", window,
                                              window_s=20.0))
    assert sound["host_stall_ms.train"] == 0.0
    assert sound["compiles_anywhere_in_window.train"] == 0.0
    assert sound["gc_pause_ms.train"] == 0.0    # a window without one
    window.open()
    reg.histogram("host_stall_seconds", "", kind="none").observe(0.0)
    reg.histogram("host_stall_seconds", "",
                  kind="descheduled").observe(3.5)
    reg.histogram("jax_compiles_during", "", buckets=(0, 1, 2),
                  span="process").observe(2)
    window.close()
    lost = readers.read_all(readers.Measured(cell, "TPU v5 lite", window,
                                             window_s=20.0))
    assert lost["host_stall_ms.train"] == pytest.approx(3500.0)
    assert lost["compiles_anywhere_in_window.train"] == 2.0
