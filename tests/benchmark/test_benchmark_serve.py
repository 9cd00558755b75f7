"""The serve runner and the open-loop generator without a chip: a
rehearsal of the runner's control flow at toy widths on the CPU over
six seeds (through the program's own checkpoint restore; `correct`
true, no device metric), the same with every answer altered where it is
produced (`correct` false), the command's refusals (a CPU, an unknown
cell, a directory that holds only the benchmark), and the generator's
own check: latency from the due instant, lateness reported, a
deterministic schedule from the seed with the same work for every seed,
and a child that never imports jax.

This file is also ONE UNIT of the tier-1 run (`-n 6 --dist loadfile`
hands out whole files, most tests first): it holds 19 or 20 tests and
its long tests come first, so that the worker that takes it stays busy
for three quarters of a minute. See bench_testlib.py, "Why three files".
"""

import http.server
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import pytest

from bench_testlib import ROOT, has_result_line, make_toy_root

from benchmarks import common, loadgen
from benchmarks.runners import serve

SEEDS = [2 ** 31 + 23 + 104729 * i for i in range(6)]
TRAFFIC = {"rate_per_s": 50.0, "schedule_seed": 11, "request_pool": 4096}
ANSWER = json.dumps({"methods": [{"original_name": "get|x", "predictions": [
    {"name": ["get", "x"], "probability": 0.7}]}]}).encode()


@pytest.fixture(scope="module")
def toy_cell(tmp_path_factory):
    root = make_toy_root(str(tmp_path_factory.mktemp("bench") / "copy"))
    cell = common.Cell(root, "toy32.serve")
    cell.root = ROOT            # the extractor is built in the checkout
    return cell


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_rehearsal_reads_correct_and_prints_no_device_metric(
        toy_cell, capsys, seed):
    got = serve.run(toy_cell, seed, 2.0, False, require_tpu=False,
                    emit=False)
    assert got["attempted"] == 40 and got["failed"] == 0
    assert got["correct"], got["checks"]
    assert got["values"]["request_p95_ms"] >= got["values"]["request_p50_ms"]
    # the run went through the program's own restore of a checkpoint
    assert os.path.isdir(os.path.join(toy_cell.work, "checkpoint", "saved"))
    assert not has_result_line(capsys.readouterr().out)


def test_an_answer_altered_where_it_is_produced_reads_not_correct(
        toy_cell, monkeypatch):
    drive = serve.Serving.drive

    def swapped(self, arrivals, checked=(), trace_dir=None):
        """Every kept answer's first and last names swapped."""
        drove = drive(self, arrivals, checked, trace_dir)
        for r in drove["results"]:
            if r and "body" in r:
                body = json.loads(r["body"])
                for m in body["methods"]:
                    p = m["predictions"]
                    if len(p) > 1:
                        p[0], p[-1] = p[-1], p[0]
                r["body"] = json.dumps(body)
        return drove

    monkeypatch.setattr(serve.Serving, "drive", swapped)
    broken = serve.run(toy_cell, SEEDS[0], 2.0, False, require_tpu=False,
                       emit=False)
    assert not broken["correct"]
    assert "served_top_logit_gap" in [c["name"] for c in broken["checks"]
                                      if not c["ok"]]


def run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_runner_refuses_a_cpu_with_no_metric_line():
    got = run_cli(ROOT, "--workload", "java14m.train_hostfed", "--seed",
                  str(2 ** 31 + 3), "--seconds", "1", "--trace", "0")
    assert got.returncode != 0
    assert not has_result_line(got.stdout)
    assert "needs 1 TPU chip" in got.stderr


def test_the_runner_refuses_an_unknown_cell():
    got = run_cli(ROOT, "--workload", "no.such_cell", "--seed", "1",
                  "--seconds", "1")
    assert got.returncode != 0 and not has_result_line(got.stdout)


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bare / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    got = run_cli(str(bare), "--workload", "java14m.train_hostfed", "--seed",
                  "1", "--seconds", "1", "--trace", "0")
    assert got.returncode != 0 and not has_result_line(got.stdout)


@pytest.mark.parametrize("rate", [5.0, 20.0, 60.0, 160.0, 260.0])
def test_schedule_fills_the_window_at_any_rate(rate):
    """`round(rate * seconds)` arrivals, all inside the window, the gaps
    Poisson-like (their spread is near their mean), whatever the rate."""
    plan = loadgen.schedule(7, 20.0, dict(TRAFFIC, rate_per_s=rate,
                                          request_pool=8192))
    dues = [a["due_s"] for a in plan]
    assert len(plan) == round(rate * 20.0)
    assert 0.0 < dues[0] and dues[-1] < 20.0
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    mean = sum(gaps) / len(gaps)
    spread = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5
    assert mean == pytest.approx(1.0 / rate, rel=0.05)
    assert 0.7 < spread / mean < 1.3


def test_schedule_is_deterministic_and_the_same_work_for_every_seed():
    a = loadgen.schedule(2 ** 31 + 5, 10.0, TRAFFIC)
    b = loadgen.schedule(2 ** 31 + 5, 10.0, TRAFFIC)
    c = loadgen.schedule(2 ** 31 + 6, 10.0, TRAFFIC)
    assert a == b and a != c
    assert len(a) == len(c) == 500
    dues = [x["due_s"] for x in a]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 10.0

    # the same arrival instants for every seed, the bodies in another order
    assert [x["due_s"] for x in a] == [x["due_s"] for x in c]
    assert [x["body_index"] for x in a] != [x["body_index"] for x in c]
    assert sorted(x["body_index"] for x in a) == list(range(500))
    assert sorted(x["body_index"] for x in c) == list(range(500))
    with pytest.raises(ValueError):
        loadgen.schedule(1, 10.0, dict(TRAFFIC, request_pool=10))


def test_well_formed_answers():
    assert loadgen.well_formed(json.loads(ANSWER)) == 1
    assert loadgen.well_formed({"methods": []}) == 0
    assert loadgen.well_formed({"nothing": 1}) == -1
    assert loadgen.well_formed({"methods": [{"original_name": "a",
                                             "predictions": [{"name": "a",
                                                              "probability": 2}]}]}) == -1


class SlowHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(0.2)
        status = 503 if self.path == "/predict" and self.server.refuse else 200
        self.send_response(status)
        self.send_header("Content-Length", str(len(ANSWER)))
        self.end_headers()
        self.wfile.write(ANSWER)


@pytest.fixture
def slow_server():
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), SlowHandler)
    httpd.refuse = False
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield httpd
    httpd.shutdown()
    httpd.server_close()


def test_latency_counts_from_the_due_instant_and_lateness_is_reported(
        slow_server):
    """Three requests due together, one sender thread, a server that
    takes 0.2 s: the stall charges the second and third request."""
    plan = {"port": slow_server.server_address[1], "deadline_ms": 2000.0,
            "threads": 1,
            "requests": [{"due_s": 0.1, "file": "", "keep_body": i == 0}
                         for i in range(3)]}
    results = loadgen.run(plan, time.time() + 0.1, [b"class A {}"] * 3)
    lat = [r["latency_ms"] for r in results]
    late = [r["late_ms"] for r in results]
    assert all(r["ok"] and r["status"] == 200 and r["methods"] == 1
               for r in results)
    assert 190 < lat[0] < 350 and 390 < lat[1] < 600 and 590 < lat[2] < 850
    assert late[0] < 50 and 190 < late[1] < 350 and 390 < late[2] < 600
    assert "body" in results[0] and "body" not in results[1]


def test_a_refused_or_late_answer_is_not_ok(slow_server):
    slow_server.refuse = True
    plan = {"port": slow_server.server_address[1], "deadline_ms": 2000.0,
            "threads": 2, "requests": [{"due_s": 0.0, "file": ""}]}
    [refused] = loadgen.run(plan, time.time(), [b"x"])
    assert refused["status"] == 503 and not refused["ok"]
    slow_server.refuse = False
    plan["deadline_ms"] = 100.0             # the answer takes 200 ms
    [late] = loadgen.run(plan, time.time(), [b"x"])
    assert late["status"] == 200 and not late["ok"]


def test_the_child_is_jax_free_and_follows_the_protocol(slow_server, tmp_path):
    body = tmp_path / "A.java"
    body.write_text("class A {}")
    plan = tmp_path / "plan.json"
    plan.write_text(json.dumps({
        "port": slow_server.server_address[1], "deadline_ms": 2000.0,
        "threads": 2,
        "requests": [{"due_s": 0.05 * i, "file": str(body), "keep_body": True}
                     for i in range(4)]}))
    out = tmp_path / "results.json"
    probe = ("import sys, runpy; sys.argv = ['loadgen.py', '--plan', "
             f"{str(plan)!r}, '--out', {str(out)!r}]\n"
             "try:\n    runpy.run_path("
             f"{os.path.join(ROOT, 'benchmarks', 'loadgen.py')!r}, "
             "run_name='__main__')\nexcept SystemExit as e:\n"
             "    assert not e.code, e.code\n"
             "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    child = subprocess.Popen([sys.executable, "-c", probe],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             text=True)
    assert child.stdout.readline().strip() == "READY"
    child.stdin.write(f"GO {time.time() + 0.2!r}\n")
    child.stdin.flush()
    assert child.stdout.readline().strip() == "DONE"
    assert child.wait(timeout=30) == 0
    results = json.loads(out.read_text())
    assert len(results) == 4 and all(r["ok"] for r in results)
    assert json.loads(results[0]["body"])["methods"]
