"""The yardstick without a chip: the open-loop generator over a window
of full length at the serve cell's own rate, BENCHMARK.json and every
data file under benchmarks/ against the contract's limits (every
per-layer entry found by its name, a case each; a configuration, a mix,
a cell and a per-layer metric of an existing reader kind added by files
alone), and the operations-and-bytes floor against hand counts with the
peaks. The trace reduction has a file of its own,
test_benchmark_trace.py.
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

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("_dim", "_rank", "hidden", "intermediate", "latent", "state",
               "head", "expansion", "experts_per")


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


@pytest.mark.parametrize("name", [m["name"] for m in bench()["per_layer"]])
def test_a_per_layer_entry_has_a_reader_file_that_agrees(name):
    """Every entry, found by its NAME: its file exists, names a reader
    kind there is, and states the entry's own fields; its cells are
    cells. An entry holds no place: PR 36's nine are cases like the
    rest, and a tenth appended behind them breaks no test."""
    b = bench()
    entries = [m for m in b["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    m = entries[0]
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".json"))
    spec = common.Cell(ROOT, b["workloads"][0]["name"]
                       ).layer_metric_spec(name)
    assert spec["reader"] in readers.KINDS, name
    for key in ("name", "layer", "unit", "moves", "source"):
        assert spec[key] == m[key], (name, key)
    assert m["better"] in ("lower", "higher") and spec["what"]
    assert set(m["workloads"]) <= {w["name"] for w in b["workloads"]}
    if "_roofline" in name:
        assert m["unit"] == "%" and m["better"] == "higher"


APPENDED_BY_PR_36 = {
    "ctx_score_step_device_ms.serve": ("lower", "GK"),
    "mla_attend_roofline.serve": ("higher", "G"),
    "moe_gated_experts_roofline.serve": ("higher", "GK"),
    "latent_cache_fill_pct.serve": ("higher", "GK"),
    "context_register_ms.setup": ("lower", "GK"),
    "index_select_roofline.serve": ("higher", "K"),
    "sparse_attend_roofline.serve": ("higher", "K"),
    "keys_selected_pct.serve": ("lower", "K"),
    "dense_blocks_run_pct.train": ("lower", "T")}


def test_the_nine_entries_of_pr_36_are_listed_for_their_cells():
    """Each found by its name. Of places only what the driver's check
    holds an appending PR to: the nine came after PR 27's entry, in
    ISSUE 36's order; where in the list that is, no test says."""
    b = bench()
    names = [m["name"] for m in b["per_layer"]]
    assert len(names) == len(set(names))
    places = [names.index(n) for n in
              ["batches_cut_idle_pct.serve", *APPENDED_BY_PR_36]]
    assert places == sorted(places)
    cells = {"G": ["glm47-flash-pp8.serve_score_ctx_open"],
             "K": ["keye-vl2-pp8.serve_score_longctx_open"]}
    cells["GK"] = cells["G"] + cells["K"]
    cells["T"] = [w["name"] for w in b["workloads"] if ".train_" in w["name"]]
    assert len(cells["T"]) == 4
    for m in b["per_layer"]:
        if m["name"] in APPENDED_BY_PR_36:
            better, which = APPENDED_BY_PR_36[m["name"]]
            assert m["better"] == better and m["workloads"] == cells[which]
    # the gauge stays an operator's (it would trace the step twice)
    assert "train_step_async_collectives" not in json.dumps(b)
    moves = {m["name"]: m["moves"] for m in b["per_layer"]}
    assert moves["context_register_ms.setup"] == "setup_s"
    assert moves["dense_blocks_run_pct.train"] == "examples_per_s"


def test_a_tenth_entry_appended_in_a_copy_is_read_like_the_rest(tmp_path):
    """What a later PR does: a file and an entry APPENDED. The copy's
    cells read it, and every older entry reads as before."""
    root = make_toy_root(str(tmp_path / "copy"))
    cell = common.Cell(root, "toy.train")
    real = {m["name"] for m in bench()["per_layer"]}
    assert {m["name"] for m in cell.bench["per_layer"]} - real == {
        "device_put_ms.train"}
    mine = [m["name"] for m in cell.per_layer()]
    assert {"dense_blocks_run_pct.train", "device_put_ms.train"} <= set(mine)

    class Window:
        def histogram(self, name, labels=None):
            return {"train_dense_blocks_run_ratio": (1.214, 2),
                    "train_context_blocks_live_ratio": (1.146, 2)}.get(name)

        def gauge(self, name, labels=None):
            return None
    got = readers.read_all(readers.Measured(cell, "TPU v5 lite", Window(),
                                            window_s=10.0))
    assert got["dense_blocks_run_pct.train"] == pytest.approx(60.7)
    assert got["gather_live_pct.train"] == pytest.approx(57.3)
    # nothing to read (no trace, no such histogram): left out
    assert not {"device_put_ms.train", "step_device_ms.train"} & set(got)


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
    assert got["device_put_ms.train"] == pytest.approx(5.0)
    assert got["pack_ms.train"] == pytest.approx(2.0)
    assert not {"read_ms.train", "step_device_ms.train"} & set(got)
    serve = common.Cell(root, "toy32.serve")
    assert serve.runner == "serve"
    assert {m["name"] for m in serve.end_to_end()} == {
        "request_p50_ms", "setup_s"}


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
