"""The cell and the per-layer metric PR 25 added by files alone:
`java14m.train_hostfed_realcounts` (the java14m configuration under
context counts as REALCODE.md measured them on real Java) and
`gather_live_pct.train`. Everything its name leads to has to resolve,
and the new mix has to draw what its file says."""

import numpy as np
import pytest

from bench_testlib import ROOT

from benchmarks import common, datagen, readers

CELL = "java14m.train_hostfed_realcounts"
TRAIN_CELLS = ("java14m.train_hostfed", "java14m-ctx500.train_hostfed",
               "java14m.train_dp4", CELL)


def test_the_cell_resolves_to_its_configuration_mix_and_limits():
    cell = common.Cell(ROOT, CELL)
    base = common.Cell(ROOT, "java14m.train_hostfed")
    assert cell.chips == 1 and cell.runner == "train"
    assert cell.config == base.config
    assert cell.limits() == base.limits()       # train.default.json
    changed = {k for k in set(cell.traffic) | set(base.traffic)
               if cell.traffic.get(k) != base.traffic.get(k)}
    assert changed == {"name", "what", "context_count"}
    assert cell.traffic["context_count"] == {
        "dist": "lognormal", "mu": 5.16, "sigma": 0.7, "min": 4}


def test_the_cell_reports_what_the_one_chip_train_cells_report():
    cell = common.Cell(ROOT, CELL)
    base = common.Cell(ROOT, "java14m.train_hostfed")
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(cell.end_to_end()) == names(base.end_to_end())
    assert names(cell.per_layer()) == names(base.per_layer())
    assert "train_step_roofline" in names(cell.per_layer())
    assert "collective_exposed_ms.train" not in names(cell.per_layer())


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_every_listed_metric_of_the_cell_has_a_reader(name):
    cell = common.Cell(ROOT, name)
    listed = [m["name"] for m in cell.per_layer()]
    assert "gather_live_pct.train" in listed
    for metric in listed:
        spec = cell.layer_metric_spec(metric)
        assert spec["reader"] in readers.KINDS, metric


def test_gather_live_pct_is_left_out_where_the_program_has_no_counter():
    """On the parent the histogram does not exist: the reader returns
    nothing, the line leaves the metric out, nothing raises."""
    cell = common.Cell(ROOT, CELL)
    assert not [m for m in common.Cell(ROOT, "java14m.serve_open").per_layer()
                if m["name"] == "gather_live_pct.train"]

    class Window:
        def __init__(self, series):
            self.series = series

        def histogram(self, name, labels=None):
            return self.series.get(name)

        def gauge(self, name, labels=None):
            return None
    without = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", Window({}), window_s=20.0))
    assert "gather_live_pct.train" not in without
    with_counter = readers.read_all(readers.Measured(
        cell, "TPU v5 lite",
        Window({"train_context_blocks_live_ratio": (387.0, 450)}),
        window_s=20.0))
    assert with_counter["gather_live_pct.train"] == pytest.approx(86.0)


@pytest.mark.parametrize("mix,cap,low,high", [
    ("train_hostfed_realcounts", 200, 150.0, 155.0),
    ("train_hostfed", 200, 85.0, 90.0),
    ("train_hostfed", 500, 91.0, 96.0),
])
def test_context_counts_of_the_mixes(mix, cap, low, high):
    traffic = common.load_json(
        f"{ROOT}/benchmarks/traffic/{mix}.json")
    counts = datagen.context_counts(
        np.random.default_rng(traffic["corpus_seed"]),
        traffic["corpus_rows"], traffic["context_count"], cap)
    assert counts.min() >= 4 and counts.max() == cap
    assert low <= counts.mean() <= high
    if mix == "train_hostfed_realcounts":
        assert 0.38 <= (counts == cap).mean() <= 0.46
