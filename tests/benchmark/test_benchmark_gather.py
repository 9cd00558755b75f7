"""The two per-layer metrics PR 42 added by files alone:
`batches_gathered_pct.serve`, the share of a serve cell's batches before
whose cut the free dispatcher waited for requests en route (the
program's histogram `serving_batch_gathered_ratio`, serving/batcher.py),
and `gather_wait_mean_ms.serve`, the mean length of such a wait (the
dispatcher's state `delay`, span `serve.delay`)."""

import json
import os

import pytest

from bench_testlib import ROOT

from benchmarks import common, readers
from test_benchmark_cut_idle import Window

BURST = "brumby-14b-pp8.serve_score_rerank_burst"
PREDICT = "java14m.serve_open"
POISSON = "glm47-flash-pp8.serve_score_ctx_open"
# one cell the gather engages in, two that bypass it; the wait is listed
# where a window is sure to hold one (a window of the Poisson cell cuts
# ~240 batches of which a handful gather, some runs none: a listed cell
# whose line lacks the metric is refused)
CELLS = {"batches_gathered_pct.serve": (BURST, PREDICT, POISSON),
         "gather_wait_mean_ms.serve": (BURST, PREDICT)}
SERIES = {"batches_gathered_pct.serve":
          ("serving_batch_gathered_ratio", None, "program_counter"),
          "gather_wait_mean_ms.serve":
          ("serving_dispatcher_seconds", {"state": "delay"},
           "program_span")}


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_the_metric_is_appended_for_exactly_its_cells(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(metric) >= len(names) - 2    # appended, at the end
    entry = bench["per_layer"][names.index(metric)]
    assert tuple(entry["workloads"]) == CELLS[metric]
    assert entry["layer"] == "serving host"
    assert entry["moves"] == "request_p50_ms"
    assert entry["source"] == SERIES[metric][2]
    for cell in bench["workloads"]:
        listed = metric in [m["name"] for m in
                            common.Cell(ROOT, cell["name"]).per_layer()]
        assert listed == (cell["name"] in CELLS[metric]), cell["name"]


@pytest.mark.parametrize("name", CELLS["batches_gathered_pct.serve"])
def test_gathered_pct_reads_100_times_the_histograms_mean(name):
    metric = "batches_gathered_pct.serve"
    cell = common.Cell(ROOT, name)
    spec = cell.layer_metric_spec(metric)
    assert spec["reader"] in readers.KINDS
    assert (spec["args"]["name"], spec["args"].get("labels")) \
        == SERIES[metric][:2]
    # four batches, three of them gathered (1.0), one cut at once (0.0)
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite",
        Window({"serving_batch_gathered_ratio": (3.0, 4)}), window_s=20.0))
    assert got[metric] == pytest.approx(75.0)
    # the parent's registry has no such histogram: the metric is left
    # out of the line and nothing raises
    without = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", Window({}), window_s=20.0))
    assert metric not in without


class LabelledWindow(Window):
    """`Window` whose series are keyed by (name, labels)."""

    def histogram(self, name, labels=None):
        return self.series.get((name, tuple(sorted((labels or {}).items()))))


@pytest.mark.parametrize("name", CELLS["gather_wait_mean_ms.serve"])
def test_gather_wait_reads_the_delay_states_mean_in_ms(name):
    metric = "gather_wait_mean_ms.serve"
    cell = common.Cell(ROOT, name)
    spec = cell.layer_metric_spec(metric)
    assert spec["reader"] in readers.KINDS
    assert (spec["args"]["name"], spec["args"].get("labels")) \
        == SERIES[metric][:2]
    delay = ("serving_dispatcher_seconds", (("state", "delay"),))
    busy = ("serving_dispatcher_seconds", (("state", "dispatch"),))
    # 120 gathers of 2.5 ms; the dispatch state's time is another series
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite",
        LabelledWindow({delay: (0.3, 120), busy: (6.0, 240)}),
        window_s=20.0))
    assert got[metric] == pytest.approx(2.5)
    assert got["dispatcher_busy_pct.serve"] == pytest.approx(30.0)
    # the parent's dispatcher never enters the state, and neither does a
    # window without a gather: the metric is left out, nothing raises
    without = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", LabelledWindow({busy: (6.0, 240)}),
        window_s=20.0))
    assert metric not in without
