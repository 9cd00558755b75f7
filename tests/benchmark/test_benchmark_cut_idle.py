"""The per-layer metric PR 27 added by files alone:
`batches_cut_idle_pct.serve`, the share of a serve cell's batches whose
oldest request found the dispatcher free (the program's histogram
`serving_batch_cut_idle_ratio`, serving/batcher.py)."""

import json
import os

import pytest

from bench_testlib import ROOT

from benchmarks import common, readers

METRIC = "batches_cut_idle_pct.serve"
SERVE_CELLS = ("java14m.serve_open", "nemotron3-super-ep4.serve_score_open")


class Window:
    """A registry window holding the given histograms as (sum, count)."""

    def __init__(self, series):
        self.series = series

    def histogram(self, name, labels=None):
        return self.series.get(name)

    def gauge(self, name, labels=None):
        return None


def test_the_metric_is_listed_for_exactly_the_two_serve_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by its name: entries are appended, and none holds a place
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert tuple(entry["workloads"]) == SERVE_CELLS
    assert entry["layer"] == "serving host"
    assert entry["moves"] == "request_p50_ms"
    for cell in bench["workloads"]:
        listed = METRIC in [m["name"] for m in
                            common.Cell(ROOT, cell["name"]).per_layer()]
        assert listed == (cell["name"] in SERVE_CELLS), cell["name"]


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_the_metric_reads_100_times_the_histograms_mean(name):
    cell = common.Cell(ROOT, name)
    spec = cell.layer_metric_spec(METRIC)
    assert spec["reader"] in readers.KINDS
    assert spec["args"]["name"] == "serving_batch_cut_idle_ratio"
    # two batches, one cut idle (1.0) and one behind a call (0.0)
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite",
        Window({"serving_batch_cut_idle_ratio": (1.0, 2)}), window_s=20.0))
    assert got[METRIC] == pytest.approx(50.0)
    # the parent's registry has no such histogram: the metric is left
    # out of the line and nothing raises
    without = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", Window({}), window_s=20.0))
    assert METRIC not in without
