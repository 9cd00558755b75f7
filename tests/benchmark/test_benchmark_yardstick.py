"""The yardstick without a chip: the open-loop generator over a window
of full length at the serve cell's own rate, BENCHMARK.json and every
data file under benchmarks/ against the contract's limits (and that a
configuration, a mix, a cell and a per-layer metric of an existing
reader kind are added by files alone), the trace reduction on a
synthetic event list and on a small trace recorded on the chip (TPU v5
lite, java14m.train_hostfed, 100 ms), and the operations-and-bytes floor
against hand counts with the peaks.

This file is also ONE UNIT of the tier-1 run (`-n 6 --dist loadfile`
hands out whole files, most tests first): it holds 19 or 20 tests and
its long tests come first, so that the worker that takes it stays busy
for three quarters of a minute. See bench_testlib.py, "Why three files".
"""

import http.server
import json
import os
import re
import threading
import time

import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common, loadgen, readers, roofline
from benchmarks import trace_reduce as tr

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("_dim", "_rank", "hidden", "intermediate", "latent", "state",
               "head", "expansion", "experts_per")
MS = 1e6    # ns


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class QuickHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    answer = json.dumps({"methods": []}).encode()

    def log_message(self, *args):
        pass

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        self.send_response(200)
        self.send_header("Content-Length", str(len(self.answer)))
        self.end_headers()
        self.wfile.write(self.answer)


def test_the_generator_holds_its_schedule_over_a_window_of_full_length():
    """The serve cell's own mix (its rate, its threads) for three times
    `run_seconds` against a server that answers at once: every
    request is sent, none is late by a quarter of a second (the least a
    host-clock time may span), and the lateness does not grow through
    the window. `generator_late_p95_ms.serve` is read from this path."""
    traffic = common.Cell(ROOT, "java14m.serve_open").traffic
    seconds = 3.0 * bench()["run_seconds"]
    arrivals = loadgen.schedule(2 ** 31 + 77, seconds, traffic)
    class Stub(http.server.ThreadingHTTPServer):
        daemon_threads = True
        request_queue_size = 256    # a burst must not be refused

    httpd = Stub(("127.0.0.1", 0), QuickHandler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        plan = {"port": httpd.server_address[1], "deadline_ms": 2000.0,
                "threads": int(traffic["generator_threads"]),
                "requests": [{"due_s": a["due_s"], "file": ""}
                             for a in arrivals]}
        started = time.time()
        results = loadgen.run(plan, started + 0.2,
                              [b"class A {}"] * len(arrivals))
        took = time.time() - started
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert len(results) == round(traffic["rate_per_s"] * seconds)
    # every request was sent; the stub (not under test, on a loaded
    # machine) may drop a few
    assert all(r is not None for r in results)
    assert sum(r["status"] == 200 for r in results) >= 0.98 * len(results)
    assert seconds <= took < seconds + 5.0
    late = sorted(r["late_ms"] for r in results)
    assert late[0] > -1.0               # nothing is sent before it is due
    assert readers.percentile(late, 95) < 250.0
    third = len(results) // 3
    first = sum(r["late_ms"] for r in results[:third]) / third
    last = sum(r["late_ms"] for r in results[-third:]) / third
    assert last < first + 100.0


def data_files():
    out = []
    for sub in ("configs", "traffic", "layer_metrics", "limits", "."):
        d = os.path.join(ROOT, "benchmarks", sub)
        out += [os.path.join(d, f) for f in sorted(os.listdir(d))
                if f.endswith(".json")]
    return out


def test_data_files_load_and_are_named_from_permitted_characters():
    paths = data_files()
    assert len(paths) >= 17
    for path in paths:
        with open(path) as f:
            assert isinstance(json.load(f), dict), path
        rel = os.path.relpath(path, ROOT)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel) and len(rel) <= 200


def test_benchmark_json_has_exactly_the_contract_keys():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmarks/run.py"]
    assert b["paths"] == ["benchmarks", "tests/benchmark"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_name_and_unit_uses_the_permitted_characters():
    b = bench()
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in b[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_cells_and_metrics_refer_to_each_other():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not any(w in key for w in WIDTH_WORDS), key
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in b["workloads"]:
        assert w["config"] in configs
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "bound" not in m
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        for cell in m.get("workloads", []):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell]), (
                f"{m['name']} lists {cell}, which does not report "
                f"{m['moves']}")


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in bench()["workloads"]:
        cell = common.Cell(ROOT, w["name"])
        reported = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer(), w["name"]
        assert w["chips"] in cell.config["chips"]


def test_every_per_layer_metric_has_a_reader_file_that_agrees():
    b = bench()
    for m in b["per_layer"]:
        spec = common.Cell(ROOT, b["workloads"][0]["name"]
                           ).layer_metric_spec(m["name"])
        assert spec["reader"] in readers.KINDS, m["name"]
        for key in ("name", "layer", "unit", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"


def test_a_config_a_mix_a_cell_and_a_metric_are_added_by_files_alone(
        tmp_path):
    root = make_toy_root(str(tmp_path / "copy"))
    cell = common.Cell(root, "toy.train")
    assert cell.runner == "train" and cell.config["token_rows"] == 3000
    assert cell.traffic["corpus_rows"] == 4096
    assert "device_put_ms.train" in [m["name"] for m in cell.per_layer()]
    assert "collective_exposed_ms.train" not in [
        m["name"] for m in cell.per_layer()]

    class Window:           # a registry window with one histogram read
        def histogram(self, name, labels=None):
            return {"prefetch_device_put_seconds": (0.5, 100),
                    "prefetch_pack_seconds": (0.2, 100)}.get(name)

        def gauge(self, name, labels=None):
            return None
    got = readers.read_all(readers.Measured(cell, "TPU v5 lite", Window(),
                                            window_s=10.0))
    # the new metric reads through the existing kind; metrics with
    # nothing to read (no trace, no such histogram) are left out
    assert got == {"device_put_ms.train": pytest.approx(5.0),
                   "pack_ms.train": pytest.approx(2.0)}
    serve = common.Cell(root, "toy32.serve")
    assert serve.runner == "serve"
    assert {m["name"] for m in serve.end_to_end()} == {
        "request_p50_ms", "setup_s"}


def synthetic():
    """Two chips, two runs of jit_train_step each, 10 ms a run: compute
    0-6 ms, an all-reduce 5-9 ms (1 ms of it under compute), idle 9-10."""
    planes = []
    for chip in range(2):
        ops, mods, spans = [], [], []
        for run in range(2):
            t = run * 10 * MS
            mods.append(["jit_train_step(123)", t, 10 * MS])
            ops.append(["fusion.1 = f32[8] fusion(f32[8] p)", t, 6 * MS])
            ops.append(["all-reduce-start.1 = f32[8] all-reduce-start(x)",
                        t + 5 * MS, 0.1 * MS])
            ops.append(["all-reduce-done.1 = f32[8] all-reduce-done(x)",
                        t + 8.9 * MS, 0.1 * MS])
            spans.append(["all-reduce-start.1 = f32[8] all-reduce-start(x)",
                          t + 5 * MS, 4 * MS])
        mods.append(["jit_unpack(9)", 20 * MS, 1 * MS])
        ops.append(["copy.1 = s32[4] copy(s32[4] q)", 20 * MS, 1 * MS])
        planes.append({"name": f"/device:TPU:{chip}", "lines": {
            tr.OPS_LINE: ops, tr.MODULES_LINE: mods, tr.ASYNC_LINE: spans}})
    planes.append({"name": "/host:CPU", "lines": {"python3": [
        ["bench.next_batch", 9.2 * MS, 0.7 * MS],
        ["bench.next_batch", 19.1 * MS, 0.8 * MS]]}})
    return {"planes": planes}


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_union_and_idle_share_synthetic():
    got = tr.busy_and_window(synthetic())
    # busy per chip: each run's 6 ms of compute (the all-reduce-start
    # at 5.0-5.1 lies inside it) and its 0.1 ms all-reduce-done, then
    # the 1 ms copy: 6.1 + 6.1 + 1
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(21e-3)
    assert got["busy_s"] == pytest.approx(13.2e-3)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(1 - 13.2 / 21)


def test_program_time_counts_only_the_named_program():
    got = tr.program_time(synthetic(), "^jit_train_step")
    assert got["runs"] == 4
    assert got["seconds_per_run"] == pytest.approx(6.1e-3)
    assert tr.program_time(synthetic(), "^jit_unpack")[
        "seconds_per_run"] == pytest.approx(1e-3)
    assert tr.program_time(synthetic(), "^jit_nothing") is None


def test_exposed_collective_time():
    whole = tr.op_time(synthetic(), "^all-reduce", "^jit_train_step")
    bare = tr.op_time(synthetic(), "^all-reduce", "^jit_train_step",
                      exposed_only=True)
    assert whole["seconds_per_run"] == pytest.approx(4e-3)
    # 5-9 ms span, compute covers 5-6: 3 ms with nothing else running
    assert bare["seconds_per_run"] == pytest.approx(3e-3)
    assert tr.op_time(synthetic(), "^all-gather", "^jit_train_step")[
        "seconds_per_run"] == 0.0


def test_breakdown_names_ops_and_attributes_gaps():
    got = tr.breakdown(synthetic())
    assert got["device_ops"][0][0].startswith("fusion.1")
    assert got["device_ops"][0][1] == pytest.approx(4 * 6e-3)
    gaps = dict(got["idle_gaps"])
    # each chip idles 6.0-8.9 twice (host:other) and 9.0-10 / 19-20
    # while the host fetched the next batch
    assert gaps["bench.next_batch"] == pytest.approx(2 * 2 * 1e-3)
    assert gaps["host:other"] == pytest.approx(2 * 2 * 2.9e-3)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_op_label_keeps_the_name_first_and_drops_layouts():
    label = tr.op_label("%all-reduce.5 = f32[1301136,128]{1,0:T(8,128)} "
                        "all-reduce(f32[1301136,128]{1,0} %fusion.5)")
    assert label.startswith("all-reduce.5 = f32[1301136,128] all-reduce(")
    assert "{" not in label and "%" not in label


def recorded():
    path = os.path.join(ROOT, "tests", "benchmark", "data",
                        "trace_v5e_java14m_train_100ms.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces():
    trace = recorded()
    assert [p["name"] for p in tr.device_planes(trace)] == ["/device:TPU:0"]
    got = tr.busy_and_window(trace)
    assert got["window_s"] == pytest.approx(0.1, rel=1e-6)
    assert 0.99 < got["busy_s"] / got["window_s"] <= 1.0
    step = tr.program_time(trace, "^jit_train_step")
    assert step["runs"] == 3            # the third is cut by the 100 ms
    assert 0.030 < step["seconds_per_run"] < 0.045
    unpack = tr.program_time(trace, "^jit_unpack")
    assert unpack["seconds_per_run"] < 1e-4
    # one chip: no collective ran
    assert tr.op_time(trace, "^(all-reduce|reduce-scatter|all-gather)",
                      "^jit_train_step", True)["seconds_per_run"] == 0.0
    top = tr.breakdown(trace)["device_ops"]
    assert top[0][0].startswith("fusion.") and top[0][1] > 0


def java14m():
    with open(os.path.join(ROOT, "benchmarks/configs/java14m.json")) as f:
        return json.load(f)


def test_parameter_count_is_the_published_one():
    assert roofline.num_params(java14m()) == 383_672_704
    assert java14m()["parameters"] == 383_672_704
    with open(os.path.join(ROOT,
                           "benchmarks/configs/java14m-ctx500.json")) as f:
        ctx500 = json.load(f)
    assert roofline.num_params(ctx500) == ctx500["parameters"]


def test_floor_terms_against_hand_counts():
    terms = roofline.train_step_terms(java14m(), rows=1024,
                                      valid_contexts=90.0)
    flops = sum(t["flops"] for t in terms if t["in_floor"])
    transform = 6 * 1024 * 200 * 384 * 384
    attention = 12 * 1024 * 200 * 384
    logits = 6 * 1024 * 384 * 261245
    assert flops == transform + attention + logits
    nbytes = sum(t["bytes"] for t in terms if t["in_floor"])
    rows = 2 * 1024 * 90.0 * 3 * 128 * 4        # gathered + scattered
    assert nbytes == pytest.approx(rows + 20 * 383_672_704)
    left_out = [t for t in terms if not t["in_floor"]]
    assert len(left_out) == 1 and "logits chain" in left_out[0]["term"]
    assert left_out[0]["bytes"] == 2 * 1024 * 261245 * 4


def test_floor_is_the_larger_bound_and_names_it():
    f = roofline.train_step_floor(java14m(), 1024, 90.0, "TPU v5 lite")
    assert f["bound"] == "bytes"
    assert f["seconds"] == pytest.approx(f["bytes"] / 819e9)
    assert f["seconds_by_flops"] == pytest.approx(f["flops"] / 197e12)
    assert f["seconds"] >= f["seconds_by_flops"]
    # a step cannot beat it: at the floor the share reads exactly 100
    assert 100.0 * f["seconds"] / f["seconds"] == 100.0


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks_for("TPU v9 imaginary")
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ici_bits_per_s"] == 1600e9
