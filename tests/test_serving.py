"""Serving subsystem tests: warm extractor pool, dynamic batcher,
prediction cache, HTTP server, REPL rewire.

A FAKE extractor binary (a small Python script speaking both the
one-shot `--file` CLI and the warm `--server` protocol, installed via
the C2V_NATIVE_EXTRACTOR env hook) stands in for the real parser, so
these tests pin the SERVING machinery — pooling, requeue-on-crash,
coalescing, bucketed compilation, cache byte-equality, SIGTERM drain —
independent of the cpp build. Behaviors are driven by markers in the
"Java" source: NCTX<n> (emit n contexts), SLOW_MARKER (sleep),
CRASH_ONCE (die with SIGKILL-ish 137 exactly once per stamp file),
BOOM_ALWAYS (deterministic parse rejection).
"""

import json
import os
import pickle
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from code2vec_tpu import obs
from code2vec_tpu.config import Config

pytestmark = pytest.mark.serving

FAKE_EXTRACTOR = r'''#!/usr/bin/env python3
"""Fake c2v extractor: deterministic output derived from the source."""
import os, re, sys, time


def extract(src):
    if "SLOW_MARKER" in src:
        time.sleep(float(os.environ.get("C2V_FAKE_SLEEP", "1.0")))
    if "CRASH_ALWAYS" in src:
        os._exit(137)
    if "CRASH_ONCE" in src:
        stamp = os.environ.get("C2V_FAKE_STAMP", "")
        if stamp and not os.path.exists(stamp):
            open(stamp, "w").close()
            os._exit(137)  # looks like an OOM SIGKILL exit
    if "BOOM_ALWAYS" in src:
        raise ValueError("fake deterministic parse error")
    m = re.search(r"NCTX(\d+)", src)
    nctx = int(m.group(1)) if m else 3
    names = re.findall(r"(\w+)\s*\(", src) or ["m"]
    lines = []
    for name in names:
        ctxs = " ".join("tok%d,(P%d)^(Q)_(R%d),tok%d" % (i, i, i, i)
                        for i in range(nctx))
        lines.append("%s %s" % (name, ctxs))
    return lines


def main():
    argv = sys.argv[1:]
    if os.environ.get("C2V_FAKE_NO_SERVER") and "--server" in argv:
        sys.stderr.write("unknown flag: --server\n")
        sys.exit(2)
    if "--server" not in argv:
        path = argv[argv.index("--file") + 1]
        try:
            with open(path) as f:
                lines = extract(f.read())
        except ValueError as e:
            sys.stderr.write(str(e) + "\n")
            sys.exit(1)
        sys.stdout.write("".join(l + "\n" for l in lines))
        return
    out = sys.stdout
    out.write("READY\n")
    out.flush()
    stdin = sys.stdin.buffer
    while True:
        header = stdin.readline()
        if not header:
            return
        header = header.decode().strip()
        try:
            if header.startswith("FILE "):
                with open(header[5:]) as f:
                    src = f.read()
            elif header.startswith("SRC "):
                n = int(header[4:])
                src = stdin.read(n).decode()
                stdin.readline()  # frame terminator
            elif not header:
                continue
            else:
                raise ValueError("bad request: " + header)
            lines = extract(src)
        except ValueError as e:
            out.write("ERR %s\n" % e)
            out.flush()
            continue
        out.write("OK %d\n" % len(lines))
        for l in lines:
            out.write(l + "\n")
        out.flush()


if __name__ == "__main__":
    main()
'''


@pytest.fixture()
def fake_extractor(tmp_path, monkeypatch):
    path = tmp_path / "fake-c2v-extract"
    path.write_text(FAKE_EXTRACTOR)
    path.chmod(0o755)
    monkeypatch.setenv("C2V_NATIVE_EXTRACTOR", str(path))
    monkeypatch.delenv("C2V_FAKE_NO_SERVER", raising=False)
    return str(path)


def _serving_config(tmp_path, **overrides) -> Config:
    kwargs = dict(
        train_data_path_prefix=str(tmp_path / "synthetic"),
        max_contexts=16,
        train_batch_size=8, test_batch_size=8,
        num_train_epochs=1,
        compute_dtype="float32",
        verbose_mode=0,
        serve_batch_size=4,
        serve_buckets="4,8",
        serve_cache_entries=16,
        extractor_pool_size=1,
        num_batches_to_log_progress=1000,
        shuffle_buffer_size=64,
        save_every_epochs=1000,
    )
    kwargs.update(overrides)
    return Config(**kwargs)


def _write_synthetic_dataset(tmp_path, n_rows=32, max_contexts=16):
    import random
    rng = random.Random(0)
    tokens = [f"tok{i}" for i in range(6)]
    paths = [f"p{i}" for i in range(4)]
    targets = ["name|alpha", "name|beta"]
    rows = []
    for _ in range(n_rows):
        t = rng.randrange(len(targets))
        ctxs = [f"{tokens[t]},{rng.choice(paths)},{tokens[t]}"
                for _ in range(rng.randint(2, 6))]
        rows.append(f"{targets[t]} " + " ".join(ctxs)
                    + " " * (max_contexts - len(ctxs)))
    prefix = str(tmp_path / "synthetic")
    with open(prefix + ".train.c2v", "w") as f:
        f.write("\n".join(rows) + "\n")
    with open(prefix + ".dict.c2v", "wb") as f:
        pickle.dump({w: 10 for w in tokens}, f)
        pickle.dump({p: 10 for p in paths}, f)
        pickle.dump({t: 10 for t in targets}, f)
        pickle.dump(n_rows, f)
    return prefix


@pytest.fixture(scope="module")
def served_model(tmp_path_factory):
    """One untrained tiny model shared by the module: serving tests pin
    machinery (batching, caching, drain), not model quality."""
    from code2vec_tpu.model_facade import Code2VecModel
    tmp_path = tmp_path_factory.mktemp("serving-model")
    _write_synthetic_dataset(tmp_path)
    return Code2VecModel(_serving_config(tmp_path))


def _counter_value(name, **labels):
    fams = obs.default_registry().collect()
    key = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
    child = fams.get(name, {}).get(key)
    return child.value if child is not None else 0.0


# ------------------------------------------------------------- pool


def test_pool_warm_extract_and_postprocess(fake_extractor, tmp_path):
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    config = _serving_config(tmp_path)
    with ExtractorPool(config, size=2) as pool:
        assert pool.warm, "fake extractor advertises --server"
        phases = {}
        lines, h2s = pool.extract_source(
            "class A { int f(int n) { return n; } } NCTX2", phases=phases)
        assert len(lines) == 1
        parts = lines[0].rstrip().split(" ")
        assert parts[0] == "f"
        # bridge semantics preserved: paths re-hashed, mapping inverts,
        # line padded to max_contexts
        w1, hashed, w2 = parts[1].split(",")
        assert h2s[hashed] == "(P0)^(Q)_(R0)"
        assert len(lines[0]) - len(lines[0].rstrip()) == 16 - 2
        assert phases["queue_wait"] >= 0 and phases["extract"] > 0
        # same worker serves a second request (no respawn)
        java_file = tmp_path / "Second.java"
        java_file.write_text("class B { int g() { return 2; } }")
        pid_before = {w.proc.pid for w in pool._idle}
        lines2, _ = pool.extract_file(str(java_file))
        assert lines2[0].split(" ")[0] == "g"
        assert {w.proc.pid for w in pool._idle} == pid_before


def test_pool_cold_fallback_when_no_server_mode(fake_extractor, tmp_path,
                                                monkeypatch):
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    monkeypatch.setenv("C2V_FAKE_NO_SERVER", "1")
    config = _serving_config(tmp_path)
    with ExtractorPool(config, size=1) as pool:
        assert not pool.warm
        lines, _ = pool.extract_source("class A { int g() { return 1; } }")
        assert lines[0].split(" ")[0] == "g"


def test_pool_requeues_crashed_worker_without_double_count(
        fake_extractor, tmp_path, monkeypatch):
    """A worker killed mid-request (exit 137 = OOM-style) requeues the
    request onto a fresh worker; extractor_failures_total counts the
    failed attempt EXACTLY once, labeled retried=yes."""
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    stamp = tmp_path / "crash-stamp"
    monkeypatch.setenv("C2V_FAKE_STAMP", str(stamp))
    config = _serving_config(tmp_path)
    before_yes = _counter_value("extractor_failures_total", retried="yes")
    before_no = _counter_value("extractor_failures_total", retried="no")
    before_rq = _counter_value("extractor_pool_requeues_total")
    with ExtractorPool(config, size=1) as pool:
        lines, _ = pool.extract_source(
            "class A { int h() { return 1; } } CRASH_ONCE")
        assert lines[0].split(" ")[0] == "h"
        assert stamp.exists()
        # the pool still has one LIVE worker after the replacement
        assert len(pool._idle) == 1 and pool._idle[0].alive
    assert _counter_value("extractor_failures_total",
                          retried="yes") == before_yes + 1
    assert _counter_value("extractor_failures_total",
                          retried="no") == before_no
    assert _counter_value("extractor_pool_requeues_total") == before_rq + 1


def test_pool_crash_exhausts_retries(fake_extractor, tmp_path,
                                     monkeypatch):
    from code2vec_tpu.serving.extractor_bridge import ExtractorCrash
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    config = _serving_config(tmp_path, extractor_retries=1)
    before_no = _counter_value("extractor_failures_total", retried="no")
    with ExtractorPool(config, size=1) as pool:
        with pytest.raises(ExtractorCrash):
            pool.extract_source("class A { int h() { return 1; } } "
                                "CRASH_ALWAYS")
    # final attempt counted retried=no (surfaced to the caller)
    assert _counter_value("extractor_failures_total",
                          retried="no") == before_no + 1


def test_pool_deterministic_rejection_not_retried(fake_extractor,
                                                  tmp_path):
    from code2vec_tpu.serving.extractor_pool import ExtractorPool
    config = _serving_config(tmp_path)
    before_rq = _counter_value("extractor_pool_requeues_total")
    with ExtractorPool(config, size=1) as pool:
        with pytest.raises(ValueError, match="deterministic parse error"):
            pool.extract_source("BOOM_ALWAYS")
        # rejection must not kill the warm worker
        assert pool._idle[0].alive
    assert _counter_value("extractor_pool_requeues_total") == before_rq


# ---------------------------------------------------------- batcher


class _HeldCall:
    """A predict_fn whose FIRST call blocks until `release()`. What is
    submitted meanwhile piles up behind it: the model call in flight is
    the only batching window the dispatch rule has, so this is how a
    test forces batch-mates."""

    def __init__(self, fn=lambda lines: [l.upper() for l in lines]):
        self.fn = fn
        self.calls = []
        self.entered = threading.Event()
        self._go = threading.Event()

    def __call__(self, lines):
        self.calls.append(list(lines))
        if len(self.calls) == 1:
            self.entered.set()
            assert self._go.wait(10), "the held call was never released"
        return self.fn(lines)

    def hold(self, batcher, rows=("hold",), **kwargs):
        """Submit the request whose call is held; returns its future
        once the dispatcher is inside `predict_fn`."""
        future = batcher.submit(list(rows), **kwargs)
        assert self.entered.wait(10)
        return future

    def release(self):
        self._go.set()


class _TokenRow(str):
    """A `/score` row as the batcher sees one: something with `.ids`
    (lm_facade.ScoreRequest). A string besides, so that one `predict_fn`
    answers both kinds of row."""

    @property
    def ids(self):
        return self.encode()


def _make_batcher(kind, predict_fn, **kwargs):
    """The batcher as the benchmark's cells build it (server.py): over
    extractor LINES (code2vec's facades: rows cap a batch), or over
    TOKEN rows with the `bucket_of` and `max_batch_tokens` that
    `ScoringModel.batcher_options()` gives and the model's buckets."""
    from code2vec_tpu.serving.batcher import DynamicBatcher
    if kind == "tokens":
        from types import SimpleNamespace
        from code2vec_tpu.lm_facade import ScoringModel
        model = SimpleNamespace(_buckets=(16, 32), token_budget=16 * 16)
        kwargs = dict(ScoringModel.batcher_options(model),
                      buckets=model._buckets, **kwargs)
    return DynamicBatcher(predict_fn, **kwargs)


def _rows(kind, *names):
    return [(_TokenRow if kind == "tokens" else str)(n) for n in names]


BATCHERS = ("lines", "tokens")


def test_batcher_coalesces_concurrent_requests():
    from code2vec_tpu.serving.batcher import DynamicBatcher
    predict_fn = _HeldCall(lambda lines: [f"r:{l}" for l in lines])
    calls = predict_fn.calls
    batcher = DynamicBatcher(predict_fn, max_batch_rows=4)
    held = predict_fn.hold(batcher)
    futures = []

    def submit(i):
        futures.append(batcher.submit([f"line{i}"]))

    threads = [threading.Thread(target=submit, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    predict_fn.release()
    assert held.result(timeout=10) == ["r:hold"]
    results = [f.result(timeout=10) for f in futures]
    assert sorted(r[0] for r in results) == [f"r:line{i}"
                                             for i in range(4)]
    # the 4 rows that piled up behind the held call -> ONE device
    # batch, not four
    assert batcher.batches_dispatched == 2
    assert [len(c) for c in calls] == [1, 4]
    batcher.drain()


def test_batcher_flushes_on_delay_and_preserves_order():
    from code2vec_tpu.serving.batcher import DynamicBatcher
    # alone on an idle batcher: flushed at once, no batch-mates needed
    batcher = DynamicBatcher(lambda lines: [l.upper() for l in lines],
                             max_batch_rows=100)
    f = batcher.submit(["a", "b", "c"])
    assert f.result(timeout=10) == ["A", "B", "C"]
    batcher.drain()
    # behind a held call: one batch, rows in submit order
    predict_fn = _HeldCall()
    batcher = DynamicBatcher(predict_fn, max_batch_rows=100)
    held = predict_fn.hold(batcher)
    futures = [batcher.submit(["a", "b", "c"]), batcher.submit(["d"]),
               batcher.submit(["e", "f"])]
    predict_fn.release()
    assert held.result(timeout=10) == ["HOLD"]
    assert [f.result(timeout=10) for f in futures] \
        == [["A", "B", "C"], ["D"], ["E", "F"]]
    assert predict_fn.calls == [["hold"], ["a", "b", "c", "d", "e", "f"]]
    batcher.drain()


@pytest.mark.parametrize("kind", BATCHERS)
def test_dispatcher_states_cover_the_threads_wall_time(monkeypatch, kind):
    """`serving_dispatcher_seconds{state}`: idle, delay and dispatch are
    observed on leaving each state and together account for the
    dispatcher thread's life; dispatch over the wall time is the busy
    share of the thread every request passes through. A free
    dispatcher dispatches: with nobody en route, nothing is ever spent
    in `delay`."""
    from code2vec_tpu.obs.metrics import Histogram
    from code2vec_tpu.serving import batcher as batcher_mod
    states = {s: Histogram() for s in ("idle", "delay", "dispatch")}
    monkeypatch.setattr(batcher_mod, "_H_STATE", states)

    def predict_fn(lines):
        time.sleep(0.02)
        return [l.upper() for l in lines]

    batcher = _make_batcher(kind, predict_fn, max_batch_rows=8)
    t0 = time.perf_counter()        # behind the model's import
    for i in range(6):
        assert batcher.submit(_rows(kind, f"a{i}", f"b{i}")).result(
            timeout=10) == [f"A{i}", f"B{i}"]
        time.sleep(0.01 * i)            # some idle time between requests
    batcher.drain(timeout=10)
    wall = time.perf_counter() - t0
    n = batcher.batches_dispatched
    assert n == 6 and states["dispatch"].count == n
    assert states["dispatch"].sum == pytest.approx(0.02 * n, rel=0.5)
    assert states["delay"].count == 0     # nobody waits out a window
    assert states["idle"].count >= 1
    covered = sum(h.sum for h in states.values())
    assert covered == pytest.approx(wall, rel=0.1, abs=0.05)


def test_batcher_error_propagates_and_drain_refuses():
    from code2vec_tpu.serving.batcher import DynamicBatcher

    def boom(lines):
        raise RuntimeError("device on fire")

    batcher = DynamicBatcher(boom, max_batch_rows=2)
    f = batcher.submit(["x"])
    with pytest.raises(RuntimeError, match="device on fire"):
        f.result(timeout=10)
    batcher.drain()
    f2 = batcher.submit(["y"])
    with pytest.raises(RuntimeError, match="draining"):
        f2.result(timeout=10)


def test_device_time_tracker_caches_sorted_view():
    """p95 runs per admission, samples land per batch: the sorted view
    must be cached between records (O(1) no-new-sample path) and
    invalidated by record()."""
    from code2vec_tpu.serving.batcher import _DeviceTimeTracker
    tr = _DeviceTimeTracker()
    for v in (0.4, 0.1, 0.3, 0.2):
        tr.record(7, v)
    assert tr.p95(7) == 0.4
    cached = tr._sorted[7]
    assert tr.p95(7) == 0.4
    assert tr._sorted[7] is cached, "no-new-sample path re-sorted"
    tr.record(7, 0.05)
    assert 7 not in tr._sorted, "record() must invalidate the view"
    assert tr.p95(7) == 0.4
    assert tr._sorted[7] is not cached


def test_batch_span_attrs_shared_and_thread_count_stable():
    """The dispatch thread builds ONE batch-span attrs dict per batch —
    every member trace holds the same object by reference, not a
    per-member dict construction; and the batcher runs exactly one
    dispatcher thread."""
    from code2vec_tpu.obs.reqtrace import RequestTrace
    from code2vec_tpu.serving.batcher import DynamicBatcher
    before = threading.active_count()
    predict_fn = _HeldCall(lambda lines: [l for l in lines])
    batcher = DynamicBatcher(predict_fn, max_batch_rows=3)
    assert threading.active_count() == before + 1
    held = predict_fn.hold(batcher)
    traces = [RequestTrace() for _ in range(3)]
    futures = []

    def submit(i):
        futures.append(batcher.submit([f"line{i}"], trace=traces[i]))

    threads = [threading.Thread(target=submit, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    predict_fn.release()
    for f in [held] + list(futures):
        f.result(timeout=10)
    assert batcher.batches_dispatched == 2      # the held call, the three
    batch_attrs = [attrs for tr in traces
                   for (name, _, _, _, _, attrs) in tr._spans
                   if name == "batch"]
    assert len(batch_attrs) == 3
    assert batch_attrs[0] is batch_attrs[1] is batch_attrs[2], \
        "batch-span attrs must be one shared dict per batch"
    assert batch_attrs[0]["requests"] == 3
    batcher.drain()


@pytest.mark.parametrize("kind", BATCHERS)
def test_drain_dispatches_what_is_pending_before_it_joins(kind):
    """`drain()` stops intake, cuts what piled up behind the call in
    flight, settles those futures and only then ends the thread."""
    predict_fn = _HeldCall(lambda lines: [l * 2 for l in lines])
    batcher = _make_batcher(kind, predict_fn, max_batch_rows=100)
    held = predict_fn.hold(batcher, _rows(kind, "hold"))
    f = batcher.submit(_rows(kind, "q"))    # pending behind the call
    drainer = threading.Thread(target=batcher.drain, args=(10,))
    drainer.start()                         # intake stops meanwhile
    deadline = time.monotonic() + 5
    while not batcher._draining and time.monotonic() < deadline:
        time.sleep(0.005)
    refused = batcher.submit(_rows(kind, "z"))
    predict_fn.release()
    drainer.join(timeout=15)
    assert not drainer.is_alive() and not batcher._thread.is_alive()
    # both were settled by the time the join returned
    assert held.done() and held.result() == ["holdhold"]
    assert f.done() and f.result() == ["qq"]
    assert predict_fn.calls == [["hold"], ["q"]]
    with pytest.raises(RuntimeError, match="draining"):
        refused.result(timeout=5)


# ------------- the dispatch rule, for a batcher of lines and of tokens


@pytest.mark.parametrize("kind", BATCHERS)
def test_lone_request_on_idle_batcher_dispatches_at_once(kind):
    """A free dispatcher dispatches: a lone request reaches `predict_fn`
    without waiting for batch-mates or a window, before any second
    request exists."""
    predict_fn = _HeldCall()
    batcher = _make_batcher(kind, predict_fn, max_batch_rows=64)
    try:
        phases = {}
        first = batcher.submit(_rows(kind, "solo"), phases=phases)
        assert predict_fn.entered.wait(10)      # inside the model call
        assert predict_fn.calls == [["solo"]]   # ... with nothing else
        predict_fn.release()
        assert first.result(timeout=10) == ["SOLO"]
        assert phases["batch_wait"] < 0.05
    finally:
        predict_fn.release()
        batcher.drain(timeout=10)


@pytest.mark.parametrize("budget", [None, 512])
def test_requests_behind_a_held_call_are_cut_as_one_batch(budget):
    """The call in flight is the only batching window: what was
    submitted behind it is cut the moment it returns, in submit order,
    inside the row cap and (a model that sets one) the token budget."""
    from code2vec_tpu.serving.batcher import DynamicBatcher
    from code2vec_tpu.serving.batcher import bucket_for
    predict_fn = _HeldCall(lambda lines: list(lines))
    kwargs = {}
    if budget is None:
        sent = [[f"r{i}"] for i in range(6)]
        want = [["r0", "r1", "r2", "r3"], ["r4", "r5"]]     # 4-row cap
    else:
        buckets = (128, 256)
        kwargs = dict(buckets=buckets, max_batch_tokens=budget,
                      bucket_of=lambda r: bucket_for(len(r), buckets))
        sent = [["x" * 100], ["y" * 100], ["z" * 200], ["w" * 100]]
        # 3 rows x the 256 bucket would pass 512 tokens; 2 x 256 fits
        want = [["x" * 100, "y" * 100], ["z" * 200, "w" * 100]]
    batcher = DynamicBatcher(predict_fn, max_batch_rows=4, **kwargs)
    try:
        held = predict_fn.hold(batcher)
        futures = [batcher.submit(lines) for lines in sent]
        assert predict_fn.calls == [["hold"]]   # nothing passes the call
        predict_fn.release()
        assert held.result(timeout=10) == ["hold"]
        assert [f.result(timeout=10) for f in futures] == sent
    finally:
        predict_fn.release()
        batcher.drain(timeout=10)
    assert predict_fn.calls[1:] == want
    assert batcher.batches_dispatched == 3


@pytest.mark.parametrize("kind", BATCHERS)
def test_request_expiring_behind_a_held_call_settles_504(kind):
    """Deadlines are not weakened: a request whose budget runs out
    while it waits behind the call in flight settles as
    DeadlineExceeded and never reaches `predict_fn`."""
    from code2vec_tpu.serving.admission import Deadline, DeadlineExceeded
    predict_fn = _HeldCall()
    batcher = _make_batcher(kind, predict_fn, max_batch_rows=8)
    try:
        held = predict_fn.hold(batcher, _rows(kind, "hold"))
        doomed = batcher.submit(_rows(kind, "late"),
                                deadline=Deadline(0.05))
        alive = batcher.submit(_rows(kind, "fine"),
                               deadline=Deadline(30.0))
        time.sleep(0.15)                        # the budget runs out
        predict_fn.release()
        assert held.result(timeout=10) == ["HOLD"]
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert alive.result(timeout=10) == ["FINE"]
    finally:
        predict_fn.release()
        batcher.drain(timeout=10)
    assert predict_fn.calls == [["hold"], ["fine"]]


@pytest.mark.parametrize("kind", BATCHERS)
def test_cut_idle_ratio_tells_straight_through_from_behind_a_call(
        monkeypatch, kind):
    """`serving_batch_cut_idle_ratio`, one observation a dispatched
    batch: 1.0 when the batch's oldest request found the dispatcher
    free, 0.0 when it was cut behind a model call."""
    from code2vec_tpu.obs.metrics import Histogram
    from code2vec_tpu.serving import batcher as batcher_mod
    cut_idle = Histogram(buckets=(0.0, 1.0))
    monkeypatch.setattr(batcher_mod, "_H_CUT_IDLE", cut_idle)
    predict_fn = _HeldCall()
    batcher = _make_batcher(kind, predict_fn, max_batch_rows=8)
    try:
        held = predict_fn.hold(batcher, _rows(kind, "hold"))
        assert (cut_idle.sum, cut_idle.count) == (1.0, 1)
        behind = [batcher.submit(_rows(kind, f"b{i}")) for i in range(3)]
        predict_fn.release()
        for f in [held] + behind:
            f.result(timeout=10)
        assert (cut_idle.sum, cut_idle.count) == (1.0, 2)
        # once the dispatcher is back from the call and free again,
        # the next one goes straight through
        time.sleep(0.05)
        assert batcher.submit(_rows(kind, "again")).result(timeout=10) \
            == ["AGAIN"]
        assert (cut_idle.sum, cut_idle.count) == (2.0, 3)
    finally:
        predict_fn.release()
        batcher.drain(timeout=10)


# ------------- the rule's one exception: gathering what is en route


class _Door:
    """The server's count of requests en route, kept by hand: what a
    `DynamicBatcher` is given as `en_route`. `arrive()` is a request
    that was en route being submitted: the submit first, then the count
    falls and the batcher is told, in the server's order."""

    def __init__(self, n=0):
        self.n = n
        self.asked = []         # the horizons the dispatcher asked with

    def __call__(self, within_s):
        self.asked.append(within_s)
        return self.n

    def arrive(self, batcher, lines, **kwargs):
        future = batcher.submit(lines, **kwargs)
        self.n -= 1
        batcher.en_route_changed()
        return future


def _gathering_batcher(monkeypatch, predict_fn, door, cap_s, step_s=None,
                       **kwargs):
    """A DynamicBatcher that is given the signal, under a cap of
    `cap_s`, its tracker having seen `step_s` steps in every bucket (a
    half of which is the ceiling where that is less than the cap);
    `step_s=None` leaves the tracker cold."""
    from code2vec_tpu.serving import batcher as batcher_mod
    monkeypatch.setattr(batcher_mod, "GATHER_CAP_S", cap_s)
    batcher = batcher_mod.DynamicBatcher(predict_fn, en_route=door,
                                         **kwargs)
    if step_s is not None:
        for bucket in batcher.buckets or (None,):
            for _ in range(batcher.device_times.MIN_SAMPLES):
                batcher.device_times.record(bucket, step_s)
    return batcher


def test_requests_en_route_join_the_pending_one_in_one_call(monkeypatch):
    """The server says three more are on their way: the free dispatcher
    holds the first, the three join it as they are submitted, and ONE
    model call takes the four rows in submit order."""
    predict_fn = _HeldCall(lambda lines: list(lines))
    predict_fn.release()
    door = _Door(3)
    batcher = _gathering_batcher(monkeypatch, predict_fn, door,
                                 cap_s=10.0, step_s=80.0,
                                 max_batch_rows=64)
    try:
        phases = {}
        futures = [batcher.submit(["r0"], phases=phases)]
        time.sleep(0.1)
        assert predict_fn.calls == []           # held, not dispatched
        for i in (1, 2, 3):
            futures.append(door.arrive(batcher, [f"r{i}"]))
        assert [f.result(timeout=10) for f in futures] \
            == [["r0"], ["r1"], ["r2"], ["r3"]]
        assert predict_fn.calls == [["r0", "r1", "r2", "r3"]]
        # cut when the last was submitted, far under the 10 s ceiling
        assert 0.1 <= phases["batch_wait"] < 5.0
        assert set(door.asked) == {10.0}
    finally:
        batcher.drain(timeout=10)
    assert batcher.batches_dispatched == 1


@pytest.mark.parametrize("case", [
    "nothing_en_route", "cold_tracker", "signal_stuck", "step_is_short",
    "full_by_rows", "full_by_tokens", "draining"])
def test_when_a_free_dispatcher_cuts_without_the_gather_running_out(
        monkeypatch, case):
    """The ends of a gather other than the last arrival: nothing at the
    door or a tracker still cold (no wait at all), a signal that never
    falls (the ceiling: the cap, or half the tracked step where that
    is less), a batch that is full by the row cap or by the token
    budget, and a drain (both at once, whatever the ceiling)."""
    from code2vec_tpu.serving.batcher import bucket_for
    predict_fn = _HeldCall(lambda lines: list(lines))
    predict_fn.release()
    kwargs, cap_s, step_s, n = dict(max_batch_rows=64), 10.0, 80.0, 1
    sent, low, high = [["solo"]], 0.0, 0.05
    if case == "nothing_en_route":
        n = 0
    elif case == "cold_tracker":
        step_s = None
    elif case == "signal_stuck":
        cap_s, low, high = 0.2, 0.15, 2.0
    elif case == "step_is_short":
        step_s, low, high = 0.4, 0.15, 2.0      # half of it: 0.2 s
    elif case == "full_by_rows":
        kwargs, sent, high = dict(max_batch_rows=2), [["a"], ["b"]], 2.0
    elif case == "full_by_tokens":
        buckets = (128, 256)
        kwargs = dict(max_batch_rows=64, buckets=buckets,
                      max_batch_tokens=512,
                      bucket_of=lambda r: bucket_for(len(r), buckets))
        # 2 rows x the 256 bucket: a third would pass 512 tokens
        sent, high = [["x" * 100], ["y" * 200]], 2.0
    else:
        high = 2.0
    door = _Door(n)
    batcher = _gathering_batcher(monkeypatch, predict_fn, door, cap_s,
                                 step_s, **kwargs)
    try:
        phases = {}
        t = time.perf_counter()
        futures = [batcher.submit(lines, phases=phases) for lines in sent]
        if case == "draining":
            time.sleep(0.05)
            assert predict_fn.calls == []       # gathering
            batcher.drain(timeout=10)
        assert [f.result(timeout=10) for f in futures] == sent
        assert time.perf_counter() - t < high + 1.0
        assert low <= phases["batch_wait"] < high
    finally:
        batcher.drain(timeout=10)
    assert predict_fn.calls == [[line for lines in sent for line in lines]]
    if case == "cold_tracker":
        assert door.asked == []                 # not even asked
    elif case in ("signal_stuck", "step_is_short"):
        assert set(door.asked) == {0.2}         # the horizon IS the ceiling


def test_request_expiring_during_a_gather_settles_504(monkeypatch):
    """Deadlines are not weakened by a gather either: a request whose
    budget runs out while the dispatcher waits for what is en route
    settles as DeadlineExceeded before the cut and never reaches
    `predict_fn`. (Its own bucket's steps are short, so its budget
    passes admission; the ceiling is half the DEEPEST pending bucket's
    step, here above the cap of 0.3 s.)"""
    from code2vec_tpu.serving.admission import Deadline, DeadlineExceeded
    from code2vec_tpu.serving.batcher import bucket_for
    predict_fn = _HeldCall(lambda lines: list(lines))
    predict_fn.release()
    buckets = (128, 256)
    batcher = _gathering_batcher(
        monkeypatch, predict_fn, _Door(1), cap_s=0.3, max_batch_rows=8,
        buckets=buckets, bucket_of=lambda r: bucket_for(len(r), buckets))
    for bucket, step_s in ((128, 0.01), (256, 80.0)):
        for _ in range(batcher.device_times.MIN_SAMPLES):
            batcher.device_times.record(bucket, step_s)
    try:
        alive = batcher.submit(["y" * 200])
        doomed = batcher.submit(["x" * 100], deadline=Deadline(0.05))
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert alive.result(timeout=10) == ["y" * 200]
    finally:
        batcher.drain(timeout=10)
    assert predict_fn.calls == [["y" * 200]]


def test_gathered_and_cut_idle_ratios_tell_the_three_cuts_apart(
        monkeypatch):
    """`serving_batch_gathered_ratio` beside
    `serving_batch_cut_idle_ratio`, one observation each a dispatched
    batch: (1, 1) gathered, (0, 1) straight through, (0, 0) behind a
    call."""
    from code2vec_tpu.obs.metrics import Histogram
    from code2vec_tpu.serving import batcher as batcher_mod
    cut_idle = Histogram(buckets=(0.0, 1.0))
    gathered = Histogram(buckets=(0.0, 1.0))
    monkeypatch.setattr(batcher_mod, "_H_CUT_IDLE", cut_idle)
    monkeypatch.setattr(batcher_mod, "_H_GATHERED", gathered)

    def seen():
        return (gathered.sum, cut_idle.sum, gathered.count)

    predict_fn = _HeldCall()
    door = _Door(1)
    batcher = _gathering_batcher(monkeypatch, predict_fn, door,
                                 cap_s=10.0, step_s=80.0,
                                 max_batch_rows=8)
    try:
        # gathered: held for the one en route, cut when it arrived; the
        # call it rides is the held one
        first = batcher.submit(["g0"])
        time.sleep(0.05)
        second = door.arrive(batcher, ["g1"])
        assert predict_fn.entered.wait(10)
        assert seen() == (1.0, 1.0, 1)
        # behind that call: not gathered, not cut idle
        behind = batcher.submit(["b0"])
        predict_fn.release()
        for f in (first, second, behind):
            f.result(timeout=10)
        assert predict_fn.calls == [["g0", "g1"], ["b0"]]
        assert seen() == (1.0, 1.0, 2)
        # straight through: free again, nothing at the door
        time.sleep(0.05)
        assert batcher.submit(["s0"]).result(timeout=10) == ["S0"]
        assert seen() == (1.0, 2.0, 3)
    finally:
        predict_fn.release()
        batcher.drain(timeout=10)


def test_parse_buckets_and_bucket_for():
    from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
    assert parse_buckets("32,64,128", 200) == (32, 64, 128, 200)
    # >= max_contexts dropped, max always appended, duplicates collapse
    assert parse_buckets("8,8,300", 200) == (8, 200)
    assert parse_buckets("", 200) == (200,)
    # cp filtering: buckets must stay divisible by the ctx-parallel degree
    assert parse_buckets("30,32,64", 200, cp=4) == (32, 64, 200)
    buckets = (32, 64, 200)
    assert bucket_for(1, buckets) == 32
    assert bucket_for(32, buckets) == 32
    assert bucket_for(33, buckets) == 64
    assert bucket_for(200, buckets) == 200


# ------------------------------------------- facade bucketed predict


def test_predict_bucket_bound_compilation_count(served_model):
    """Distinct request shapes map onto the configured bucket list: the
    compiled-step cache stays <= number of buckets no matter how many
    context counts traffic brings."""
    model = served_model
    buckets = model.context_buckets
    assert buckets == (4, 8, 16)
    start = model.predict_compile_count()

    def line(nctx):
        ctxs = " ".join(f"tok0,p0,tok0" for _ in range(nctx))
        return "somename " + ctxs + " " * (16 - nctx)

    for nctx in (1, 2, 3, 4, 5, 7, 9, 12, 16, 2, 6, 11):
        model.predict([line(nctx)], batch_size=4)
    assert model.predict_compile_count() - start <= len(buckets)
    # and the shapes actually bucketed (not one giant shape): a 2-context
    # request must NOT have compiled the 16-context shape alone
    assert (4, 4) in model._predict_steps


def test_predict_accepts_lazy_iterable(served_model):
    model = served_model
    lines = ["somename tok0,p0,tok0 tok1,p1,tok1" + " " * 14
             for _ in range(10)]
    consumed = []

    def gen():
        for l in lines:
            consumed.append(l)
            yield l

    out = model.predict(gen(), batch_size=4)
    assert len(out) == 10
    assert len(consumed) == 10
    # chunked (3 batches of <=4) results identical to one-shot list
    out2 = model.predict(lines, batch_size=16)
    for a, b in zip(out, out2):
        assert a.topk_predicted_words == b.topk_predicted_words
        np.testing.assert_allclose(a.topk_predicted_words_scores,
                                   b.topk_predicted_words_scores,
                                   rtol=1e-5)
        assert a.attention_per_context.keys() == \
            b.attention_per_context.keys()


def _served_against_masters(model, lines):
    """One predict batch as SERVED (the params `_call_predict_step`
    hands the step) beside a fresh step fed the float32 masters on the
    same device arrays -> ((indices, values), (indices, values))."""
    seen = []
    real = model._call_predict_step

    def spy(step, arrays):
        seen.append(arrays)
        out = real(step, arrays)
        seen.append(out)
        return out
    model._call_predict_step = spy
    try:
        model.predict(lines, batch_size=4)
    finally:
        del model._call_predict_step
    arrays, out = seen
    fed = model.builder.make_eval_step(model.state)(
        model.state.params, *arrays)
    return tuple((np.asarray(o.topk_indices), np.asarray(o.topk_values))
                 for o in (out, fed))


def test_the_served_tables_cast_copy_follows_the_weights(tmp_path):
    """The served step reads the target table in the compute dtype, cast
    ONCE per state (model_facade.py `_served_params`), and the copy
    follows the weights: after `--load`, after a restore into a live
    model and after a hot-swap the served top-k is bitwise that of a
    step fed the float32 table, and no stale copy answers."""
    import jax.numpy as jnp
    from code2vec_tpu.model_facade import Code2VecModel
    from code2vec_tpu.serving.server import PredictionServer
    from code2vec_tpu.serving.swap import SwapManager
    from code2vec_tpu.training import checkpoint as ckpt_mod
    _write_synthetic_dataset(tmp_path)

    def build(**kw):
        return Code2VecModel(_serving_config(
            tmp_path, compute_dtype="bfloat16", topk_block_size=2, **kw))
    lines = ["name|alpha " + " ".join(["tok0,p0,tok0", "tok1,p2,tok1"]),
             "name|beta " + " ".join(["tok3,p1,tok3"] * 3)]

    def same(got, want):
        return all(np.array_equal(g, w) for g, w in zip(got, want))

    a, c = build(seed=1), build(seed=2)
    assert a.builder._eval_topk_block() == 2          # the blockwise head
    served_a, masters_a = _served_against_masters(a, lines)
    assert same(served_a, masters_a)
    held = a._served_params()
    assert held is a._served_params()                 # cast once
    assert held["target_embedding"].dtype == jnp.bfloat16
    assert a.state.params["target_embedding"].dtype == jnp.float32
    assert held["transform"] is a.state.params["transform"]
    served_c, masters_c = _served_against_masters(c, lines)
    assert same(served_c, masters_c) and not same(served_c, served_a)

    # --load: a fresh model restored from a's checkpoint answers as a
    path_a, path_c = a.save(str(tmp_path / "a")), c.save(str(tmp_path / "c"))
    b = build(seed=3, model_load_path=path_a)
    served_b, masters_b = _served_against_masters(b, lines)
    assert same(served_b, masters_b) and same(served_b, served_a)

    # a restore into the LIVE model: the next call casts the new table
    stale = b._served_params()
    b.state = ckpt_mod.load_model(path_c, b.state, config=b.config,
                                  params_only=True)
    served_b, masters_b = _served_against_masters(b, lines)
    assert same(served_b, masters_b) and same(served_b, served_c)
    assert b._served_params() is not stale

    # hot-swap: the server's next batch reads the NEW model's copy, the
    # old model keeps its own
    srv = PredictionServer(a, a.config, log=lambda m: None)
    srv.swap = SwapManager(srv, build_model=lambda target: c)
    srv.swap.request_reload("weights-c")
    deadline = time.time() + 30
    while srv.swap.status()["state"] not in ("ready", "failed"):
        assert time.time() < deadline
        time.sleep(0.02)
    assert srv.swap.status()["state"] == "ready" and srv.model is c
    served_now, masters_now = _served_against_masters(srv.model, lines)
    assert same(served_now, masters_now) and same(served_now, served_c)
    assert same(_served_against_masters(a, lines)[0], served_a)
    # which merge the built step took, as the operator reads it
    assert obs.gauge("head_topk_sorted_columns", step="predict").value \
        == a.builder.eval_head_sorted_columns(4) == 2 + min(
            10, a.dims.real_target_vocab_size)
    assert "columns sorted a trip" in a.describe_head()


# ------------------------------------------------------------- http


def test_serve_main_warms_every_bucket_before_it_listens(served_model):
    """`serve` runs every (rows, bucket) predict shape once before the
    port opens and reports it as the start-up phase `serve_warm`: no
    request then pays a bucket's compile out of its deadline."""
    import dataclasses
    from code2vec_tpu.serving.server import serve_main
    logged = []
    config = dataclasses.replace(served_model.config, serve_port=0)
    config.log = logged.append
    gauge = obs.gauge("startup_phase_seconds", phase="serve_warm")
    gauge.set(0.0)
    stop = threading.Event()
    stop.set()                  # come up, warm, listen, drain
    assert serve_main(config, model=served_model, stop=stop,
                      install_signals=False) == 0
    assert gauge.value > 0.0
    warmed = [i for i, m in enumerate(logged) if "buckets warmed" in m]
    listening = [i for i, m in enumerate(logged) if "listening on" in m]
    assert warmed and listening and warmed[0] < listening[0]
    rows = config.serve_batch_size
    assert {(rows, m) for m in served_model.context_buckets} \
        <= set(served_model._predict_steps)
    compiled = served_model.predict_compile_count()
    line = "warm|probe " + " ".join(["tok0,path0,tok0"] * 3)
    served_model.predict([line], batch_size=rows, with_code_vectors=True)
    assert served_model.predict_compile_count() == compiled


@pytest.fixture()
def server(served_model, fake_extractor):
    from code2vec_tpu.serving.server import PredictionServer
    srv = PredictionServer(served_model, served_model.config,
                           log=lambda m: None)
    srv.start(port=0)
    yield srv
    srv.drain(timeout=10)


def _post(port, endpoint, body, ctype="text/plain"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{endpoint}", data=body.encode(),
        method="POST", headers={"Content-Type": ctype})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_http_end_to_end(server):
    code = "class A { int addOne(int n) { return n + 1; } }"
    status, body = _post(server.port, "predict", code)
    assert status == 200
    payload = json.loads(body)
    assert payload["model"] == "code2vec_tpu"
    [method] = payload["methods"]
    assert method["original_name"] == "addOne"
    assert method["predictions"], "top-k predictions missing"
    for p in method["predictions"]:
        assert 0.0 <= p["probability"] <= 1.0
    assert method["attention_paths"]
    for att in method["attention_paths"]:
        assert att["path"].startswith("(")  # hash inverted for display

    # JSON body form + /embed (vectors forced on)
    status, body = _post(server.port, "embed",
                         json.dumps({"code": code}), "application/json")
    assert status == 200
    embed_payload = json.loads(body)
    vectors = embed_payload["vectors"]
    assert len(vectors) == 1
    assert len(vectors[0]) == server.config.code_vector_size
    # the embedding-space identity rides every /embed response (the
    # same field /neighbors stamps) so clients can detect cross-model
    # vector mixing
    assert embed_payload["embedding_fingerprint"] == \
        server.model_fingerprint
    assert embed_payload["embedding_fingerprint"] == \
        embed_payload["model_fingerprint"]

    # healthz + metrics ride the same listener
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=30) as r:
        hz = json.loads(r.read())
    assert hz["status"] == "serving"
    assert hz["compiled_predict_steps"] <= len(hz["buckets"])
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
        metrics = r.read().decode()
    assert "serving_request_seconds_bucket" in metrics
    assert 'phase="total"' in metrics

    # error surface: empty body, parse rejection, unknown endpoint,
    # and crash-through-every-retry = infra 503 (NOT a client 422:
    # ExtractorCrash subclasses ValueError, the mapping must not lump
    # dead workers in with rejected sources)
    assert _post(server.port, "predict", "")[0] == 400
    assert _post(server.port, "predict", "BOOM_ALWAYS")[0] == 422
    assert _post(server.port, "nope", "x")[0] == 404
    assert _post(server.port, "predict", "CRASH_ALWAYS f(")[0] == 503


def test_healthz_batcher_block_names_the_one_batcher(server):
    """`/healthz`'s `batcher` block: the row cap and the batches
    dispatched so far, and nothing that told two batchers apart."""
    assert _post(server.port, "predict",
                 "class A { int f(int n) { return n; } }")[0] == 200
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=30) as r:
        block = json.loads(r.read())["batcher"]
    assert block == {"max_batch_rows": server.config.serve_batch_size,
                     "batches_dispatched": 1}


def test_http_coalesces_concurrent_requests(server, monkeypatch):
    before = server.batcher.batches_dispatched
    codes = [f"class A{i} {{ int f{i}(int n) {{ return n; }} }}"
             for i in range(4)]
    results = [None] * 4
    # the model call in flight is the batching window: hold one
    held = _HeldCall(server.batcher.predict_fn)
    monkeypatch.setattr(server.batcher, "predict_fn", held)
    holder = threading.Thread(target=_post, args=(
        server.port, "predict", "class H { int held() { return 0; } }"))
    holder.start()
    assert held.entered.wait(30)

    def post(i):
        results[i] = _post(server.port, "predict", codes[i])

    threads = [threading.Thread(target=post, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    deadline = time.perf_counter() + 30
    while len(server.batcher._pending) < 4:
        assert time.perf_counter() < deadline
        time.sleep(0.005)
    held.release()
    for t in threads + [holder]:
        t.join()
    assert all(r[0] == 200 for r in results)
    for i, (_, body) in enumerate(results):
        assert json.loads(body)["methods"][0]["original_name"] == f"f{i}"
    # 4 single-method requests behind the held call, serve_batch_size=4:
    # strictly fewer device batches than requests proves coalescing
    assert server.batcher.batches_dispatched - before < 4
    assert [len(c) for c in held.calls] == [1, 4]


def test_cache_hit_is_byte_equal_and_normalized(server):
    code = "class B { int mul(int a, int b) { return a * b; } }"
    hits0 = _counter_value("serving_cache_hits_total")
    status, body1 = _post(server.port, "predict", code)
    assert status == 200
    # same method, different formatting -> same cache entry, byte-equal
    reformatted = code.replace("{ ", "{\n    ").replace("; ", ";\n")
    status, body2 = _post(server.port, "predict", reformatted)
    assert status == 200
    assert body2 == body1
    assert _counter_value("serving_cache_hits_total") == hits0 + 1
    # a real edit (here: one that changes the extracted contexts) misses
    # the cache and re-predicts
    misses0 = _counter_value("serving_cache_misses_total")
    status, body3 = _post(server.port, "predict",
                          code.replace("a * b", "a + b") + " NCTX5")
    assert body3 != body1
    assert _counter_value("serving_cache_misses_total") == misses0 + 1


def test_cache_lru_eviction():
    from code2vec_tpu.serving.cache import PredictionCache, cache_key
    ev0 = _counter_value("serving_cache_evictions_total")
    cache = PredictionCache(capacity=2)
    k = [cache_key(f"code{i}") for i in range(3)]
    cache.put(k[0], b"0")
    cache.put(k[1], b"1")
    assert cache.get(k[0]) == b"0"  # touch: k[1] is now LRU
    cache.put(k[2], b"2")
    assert cache.get(k[1]) is None
    assert cache.get(k[0]) == b"0" and cache.get(k[2]) == b"2"
    assert _counter_value("serving_cache_evictions_total") == ev0 + 1
    # capacity 0 disables cleanly
    off = PredictionCache(capacity=0)
    off.put(k[0], b"x")
    assert off.get(k[0]) is None


def test_sigterm_drain_finishes_inflight(served_model, fake_extractor,
                                         monkeypatch):
    """The preemption-grace pattern: a drain racing an in-flight request
    lets it finish (200), refuses everything after, and tears the
    listener down."""
    from code2vec_tpu.serving.server import PredictionServer
    monkeypatch.setenv("C2V_FAKE_SLEEP", "1.0")
    srv = PredictionServer(served_model, served_model.config,
                           log=lambda m: None)
    srv.start(port=0)
    slow_result = {}

    def slow_post():
        slow_result["r"] = _post(
            srv.port, "predict",
            "class S { int slow() { return 1; } } SLOW_MARKER")

    t = threading.Thread(target=slow_post)
    t.start()
    # let the request enter the extractor before draining
    deadline = time.time() + 5
    while srv._inflight == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert srv._inflight == 1
    assert srv.drain(timeout=30) is True
    t.join(timeout=30)
    status, body = slow_result["r"]
    assert status == 200
    assert json.loads(body)["methods"][0]["original_name"] == "slow"
    # the listener is gone: a new request cannot even connect
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/healthz",
                               timeout=5)


# ---------------------------------------------------- request tracing


# ---------------------------- the server's count of requests en route


class _FakeScorer:
    """A `--model_config` model in the manner of lm_facade.ScoringModel,
    without arrays: `POST /score` rows reach the batcher straight from
    the handler thread (no extractor), a step sleeps `step_s`."""

    served_endpoints = ("score", "contexts")
    uses_extractor = False
    model_name = "fake-scorer"
    top_k = 10
    context_buckets = (16, 32)
    _predict_steps = {}

    def __init__(self, config, step_s=0.01):
        self.config, self.step_s, self.calls = config, step_s, []

    def model_fingerprint(self):
        return "fake-fingerprint"

    def predict_compile_count(self):
        return 0

    def batcher_options(self):
        from code2vec_tpu.serving.batcher import bucket_for
        return {"bucket_of": lambda r: bucket_for(len(r.ids),
                                                  self.context_buckets),
                "max_batch_tokens": 16 * 16}

    def validate(self, ids, top_k, context=None):
        from types import SimpleNamespace
        if not ids:
            raise ValueError("ids must hold a token")
        if context == "gone":
            raise LookupError("context 'gone' is unknown")
        return SimpleNamespace(ids=list(ids), top_k=int(top_k))

    def register_context(self, ids):
        time.sleep(0.3)
        return {"context": "c0", "tokens": len(ids)}

    def score_batch(self, rows):
        from types import SimpleNamespace
        self.calls.append([r.ids[0] for r in rows])
        time.sleep(self.step_s)
        return [SimpleNamespace(
            unknown_context=None, tokens=len(r.ids), context_tokens=0,
            token_ids=[r.ids[0]], logits=[1.0], probabilities=[1.0])
            for r in rows]


@pytest.fixture()
def scoring_server(tmp_path, monkeypatch):
    """A server over the fake scorer whose batcher gathers under a
    ceiling of 2 s (the cap lifted to it, the 16 bucket's tracker
    holding steps of 8 s): wide enough for sixteen client threads of
    this machine."""
    from code2vec_tpu.serving import batcher as batcher_mod
    from code2vec_tpu.serving.server import PredictionServer
    monkeypatch.setattr(batcher_mod, "GATHER_CAP_S", 2.0)
    config = _serving_config(tmp_path, serve_batch_size=16,
                             serve_deadline_ms=30000.0)
    srv = PredictionServer(_FakeScorer(config), config,
                           log=lambda m: None)
    for _ in range(srv.batcher.device_times.MIN_SAMPLES):
        srv.batcher.device_times.record(16, 8.0)
    srv.start(port=0)
    yield srv
    srv.drain(timeout=10)


def _score_body(first_id, **more):
    return json.dumps(dict({"ids": [first_id, 7, 7], "top_k": 1}, **more))


def _nobody_en_route(srv, within_s=5.0):
    """The count comes back to nothing (a handler thread leaves it a
    moment after its client has the answer)."""
    t_end = time.monotonic() + within_s
    while srv._en_route and time.monotonic() < t_end:
        time.sleep(0.005)
    return not srv._en_route and srv.requests_en_route(60.0) == 0


def test_sixteen_posts_at_one_instant_ride_one_batch(scoring_server):
    """A burst as the rerank cell sends it, one connection a request:
    all sixteen are connected before any sends, so from the first
    submit to the last the server sees the others en route (accepted,
    or held by the kernel for the listener) and the free dispatcher
    cuts ONE batch of sixteen."""
    import http.client
    srv, model = scoring_server, scoring_server.model
    connected = threading.Barrier(16)
    statuses = [None] * 16

    def client(i):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=30)
        try:
            conn.connect()
            connected.wait(10)
            conn.request("POST", "/score", body=_score_body(100 + i),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            statuses[i] = resp.status
            resp.read()
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert statuses == [200] * 16
    assert len(model.calls) == 1
    assert sorted(model.calls[0]) == list(range(100, 116))
    assert _nobody_en_route(srv)
    # ... and a lone request afterwards finds nothing at the door
    t = time.perf_counter()
    assert _post(srv.port, "score", _score_body(200))[0] == 200
    assert time.perf_counter() - t < 1.0
    assert model.calls[1:] == [[200]]


def test_a_silent_connection_holds_a_request_to_the_ceiling_at_most(
        tmp_path, monkeypatch):
    """A connection that sends nothing (a pooled or probing client)
    counts as en route only while it is younger than the ceiling: a
    request right behind it is cut at the ceiling, a later one at
    once."""
    import socket
    from code2vec_tpu.obs.metrics import Histogram
    from code2vec_tpu.serving import batcher as batcher_mod
    from code2vec_tpu.serving.server import PredictionServer
    gathered = Histogram(buckets=(0.0, 1.0))
    monkeypatch.setattr(batcher_mod, "_H_GATHERED", gathered)
    monkeypatch.setattr(batcher_mod, "GATHER_CAP_S", 0.3)
    config = _serving_config(tmp_path, serve_batch_size=16,
                             serve_deadline_ms=30000.0)
    srv = PredictionServer(_FakeScorer(config), config,
                           log=lambda m: None)
    for _ in range(srv.batcher.device_times.MIN_SAMPLES):
        srv.batcher.device_times.record(16, 8.0)
    srv.start(port=0)
    silent = socket.create_connection(("127.0.0.1", srv.port))
    try:
        t = time.perf_counter()
        assert _post(srv.port, "score", _score_body(1))[0] == 200
        held = time.perf_counter() - t
        assert (gathered.sum, gathered.count) == (1.0, 1)
        assert 0.2 < held < 2.0         # the ceiling, 0.3 s, and no more
        time.sleep(0.35)                # the silent one is stale now
        t = time.perf_counter()
        assert _post(srv.port, "score", _score_body(2))[0] == 200
        assert time.perf_counter() - t < 0.25
        assert (gathered.sum, gathered.count) == (1.0, 2)
    finally:
        silent.close()
        assert _nobody_en_route(srv)    # a closed socket leaves the count
        srv.drain(timeout=10)


def test_en_route_count_is_nothing_after_every_way_a_request_ends(
        scoring_server):
    """The count cannot leak: answered, cache hit, shed, 400, 404, an
    endpoint that never reaches the batcher, a client that went away."""
    import socket
    srv = scoring_server
    port = srv.port
    body = _score_body(5)
    assert _post(port, "score", body)[0] == 200
    assert _post(port, "score", body)[0] == 200             # cache hit
    assert srv.model.calls == [[5]]
    assert _post(port, "score", "{not json")[0] == 400
    assert _post(port, "score", json.dumps({"top_k": 1}))[0] == 400
    assert _post(port, "score", _score_body(6, context="gone"))[0] == 404
    assert _post(port, "predict", "class A {}")[0] == 404   # not served
    assert _post(port, "nowhere", "x")[0] == 404
    # shed: a budget under the bucket's tracked step (DeadlineInfeasible)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/score", data=_score_body(8).encode(),
        method="POST", headers={"X-Deadline-Ms": "4000"})
    with pytest.raises(urllib.error.HTTPError) as shed:
        urllib.request.urlopen(req, timeout=30)
    assert shed.value.code == 503
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                timeout=30) as r:
        assert r.status == 200
    assert _nobody_en_route(srv)
    # a registration never reaches the batcher: while the model works
    # on it (0.3 s) it holds no dispatcher
    done = []
    t = threading.Thread(target=lambda: done.append(
        _post(port, "contexts", json.dumps({"ids": [1, 2, 3]}))))
    t.start()
    time.sleep(0.15)
    assert t.is_alive() and srv.requests_en_route(60.0) == 0
    t.join(30)
    assert done[0][0] == 200
    # a client that sends half a request and goes away
    gone = socket.create_connection(("127.0.0.1", port))
    gone.sendall(b"POST /score HTTP/1.1\r\nContent-Length: 400\r\n\r\n{")
    time.sleep(0.1)
    assert srv.requests_en_route(60.0) == 1     # seen, not submitted
    gone.close()
    assert _nobody_en_route(srv)
    # refused at the door of a draining server
    with srv._inflight_cond:
        srv._draining = True
    try:
        assert _post(port, "score", _score_body(9))[0] == 503
    finally:
        with srv._inflight_cond:
            srv._draining = False
    assert _nobody_en_route(srv)
    assert srv.model.calls == [[5]]     # none of them reached the model


def test_a_predict_request_in_extraction_is_not_en_route(
        served_model, fake_extractor, monkeypatch):
    """2 ms of extraction is not "about to arrive": a `/predict`
    request leaves the count where it enters the extractor pool, so
    `/predict` traffic all but bypasses the gather."""
    from code2vec_tpu.serving.server import PredictionServer
    monkeypatch.setenv("C2V_FAKE_SLEEP", "0.6")
    srv = PredictionServer(served_model, served_model.config,
                           log=lambda m: None)
    srv.start(port=0)
    try:
        done = []
        t = threading.Thread(target=lambda: done.append(_post(
            srv.port, "predict",
            "class A { int slow() { return 1; } } // SLOW_MARKER")))
        t.start()
        seen_inside = False
        t_end = time.monotonic() + 10
        while t.is_alive() and time.monotonic() < t_end:
            if srv.admission.depth >= 1:        # past the gate: extracting
                seen_inside = True
                assert srv.requests_en_route(60.0) == 0
            time.sleep(0.02)
        t.join(30)
        assert seen_inside and done[0][0] == 200
        assert _nobody_en_route(srv)
    finally:
        srv.drain(timeout=10)


def _post_full(port, endpoint, body, ctype="text/plain", headers=None,
               query=""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{endpoint}{query}", data=body.encode(),
        method="POST", headers=dict({"Content-Type": ctype},
                                    **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


@pytest.fixture()
def traced_server(served_model, fake_extractor):
    """A server with --serve_debug_trace on (the ?debug=trace gate)."""
    import dataclasses
    from code2vec_tpu.serving.server import PredictionServer
    config = dataclasses.replace(served_model.config,
                                 serve_debug_trace=True)
    srv = PredictionServer(served_model, config, log=lambda m: None)
    srv.start(port=0)
    yield srv
    srv.drain(timeout=10)


def test_trace_id_minted_and_debug_tree_names_every_phase(traced_server):
    """Acceptance pin: a request through the real HTTP server returns an
    X-Trace-Id whose span tree (via the debug-trace knob) names every
    pipeline phase it crossed, including the batch it rode."""
    status, body, headers = _post_full(
        traced_server.port, "predict",
        "class T { int traced(int n) { return n; } }",
        query="?debug=trace")
    assert status == 200
    trace_id = headers["X-Trace-Id"]
    assert len(trace_id) == 32 and int(trace_id, 16)
    payload = json.loads(body)
    trace = payload["trace"]
    assert trace["trace_id"] == trace_id
    by_name = {}
    for s in trace["spans"]:
        by_name.setdefault(s["name"], s)
    # every pipeline phase the request crossed, as a tree
    assert {"request", "cache_lookup", "admission", "extract_wait",
            "extract", "batch_wait", "batch", "device",
            "render"} <= set(by_name)
    root = by_name["request"]
    assert root["span_id"] == trace["root_span_id"]
    assert root["attrs"] == {"endpoint": "predict", "status": 200}
    for child in ("cache_lookup", "admission", "extract_wait",
                  "extract", "batch_wait", "batch", "render"):
        assert by_name[child]["parent_id"] == root["span_id"], child
    # the device span hangs under the SHARED batch span
    batch = by_name["batch"]
    assert by_name["device"]["parent_id"] == batch["span_id"]
    assert trace_id in batch["attrs"]["members"]
    assert batch["attrs"]["rows"] == 1
    assert by_name["cache_lookup"]["attrs"]["hit"] is False
    assert by_name["extract"]["attrs"]["mode"] == "warm"
    assert by_name["extract"]["attrs"]["worker_pid"] > 0
    # the traceparent response header names the root span
    version, tid, sid, flags = headers["traceparent"].split("-")
    assert (version, flags) == ("00", "01")
    assert tid == trace_id and sid == trace["root_span_id"]
    # the normal (non-debug) response stays trace-free
    status, body2, headers2 = _post_full(
        traced_server.port, "predict",
        "class T { int traced(int n) { return n; } }")
    assert "trace" not in json.loads(body2)
    assert headers2["X-Trace-Id"] != trace_id  # fresh id per request


def test_predict_stages_hang_under_the_device_span_and_fill_it(
        traced_server):
    """The coalesced model call's stages (the facade's obs.spans, which
    also feed `serving_predict_stage_seconds{stage}`) are children of
    the batch's `device` span in the request tree and account for it."""
    stages = ("parse", "assemble", "device", "render")
    hists = {s: obs.histogram("serving_predict_stage_seconds", stage=s)
             for s in stages}
    fill = {d: obs.histogram("serving_batch_fill_ratio", dim=d)
            for d in ("rows", "contexts")}
    before = {s: h.count for s, h in hists.items()}
    fill_before = {d: (h.count, h.sum) for d, h in fill.items()}
    status, body, _ = _post_full(
        traced_server.port, "predict",
        "class T { int staged(int n) { return n; } }",
        query="?debug=trace")
    assert status == 200
    spans = json.loads(body)["trace"]["spans"]
    [device] = [s for s in spans if s["name"] == "device"]
    children = [s for s in spans if s["parent_id"] == device["span_id"]]
    assert [c["name"] for c in children] == [
        "predict." + s for s in stages]
    inside = sum(c["duration_ms"] for c in children)
    assert inside <= device["duration_ms"] + 0.01
    assert inside >= device["duration_ms"] - 2.0    # breaker, fan-out
    for c in children:
        assert c["start_ms"] >= device["start_ms"] - 0.01
    assert {s: h.count - before[s] for s, h in hists.items()} == dict.fromkeys(
        stages, 1)
    rows = traced_server.config.serve_batch_size
    count, total = fill_before["rows"]
    assert fill["rows"].count == count + 1
    assert fill["rows"].sum - total == pytest.approx(1.0 / rows)
    assert 0.0 < fill["contexts"].sum - fill_before["contexts"][1] \
        <= 1.0 / rows


def test_inbound_traceparent_honored_and_echoed(traced_server):
    """A caller-supplied W3C traceparent joins ITS trace: same trace id
    end to end, the server's root span parented under the caller's
    span, and the echoed traceparent naming the server's root span."""
    inbound_trace, inbound_span = "ab" * 16, "cd" * 8
    status, body, headers = _post_full(
        traced_server.port, "predict",
        "class I { int inbound() { return 1; } }",
        headers={"traceparent":
                 f"00-{inbound_trace}-{inbound_span}-01"},
        query="?debug=trace")
    assert status == 200
    assert headers["X-Trace-Id"] == inbound_trace
    trace = json.loads(body)["trace"]
    assert trace["trace_id"] == inbound_trace
    assert trace["remote_parent"] == inbound_span
    [root] = [s for s in trace["spans"] if s["name"] == "request"]
    assert root["parent_id"] == inbound_span
    assert headers["traceparent"] == \
        f"00-{inbound_trace}-{root['span_id']}-01"
    # malformed traceparent: minted id, not a 400
    status, _, headers = _post_full(
        traced_server.port, "predict",
        "class I { int inbound2() { return 1; } }",
        headers={"traceparent": "zz-garbage"})
    assert status == 200
    assert headers["X-Trace-Id"] != inbound_trace


def test_minted_ids_unique_across_coalesced_batch(traced_server):
    """Concurrent requests coalesced into one device batch each keep
    their OWN trace id; the shared batch span id ties the trees
    together and its `members` attr lists exactly the requests that
    rode it."""
    codes = [f"class B{i} {{ int rode{i}(int n) {{ return n; }} }}"
             for i in range(4)]
    results = [None] * 4

    def post(i):
        results[i] = _post_full(traced_server.port, "predict", codes[i],
                                query="?debug=trace")

    threads = [threading.Thread(target=post, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r[0] == 200 for r in results)
    trace_ids = [r[2]["X-Trace-Id"] for r in results]
    assert len(set(trace_ids)) == 4, "minted ids must be unique"
    batches = {}  # batch span id -> (members attr, rider trace ids)
    for (_, body, headers) in results:
        trace = json.loads(body)["trace"]
        assert trace["trace_id"] == headers["X-Trace-Id"]
        [batch] = [s for s in trace["spans"] if s["name"] == "batch"]
        [device] = [s for s in trace["spans"] if s["name"] == "device"]
        assert device["parent_id"] == batch["span_id"]
        members, riders = batches.setdefault(
            batch["span_id"], (batch["attrs"]["members"], set()))
        assert batch["attrs"]["members"] == members
        riders.add(trace["trace_id"])
    # each batch span's members list is EXACTLY the requests that rode
    # it — no request missing, none from another batch
    for members, riders in batches.values():
        assert set(members) == riders
    assert {t for _, r in batches.values() for t in r} == set(trace_ids)


def test_cache_hit_fast_path_carries_trace_id(traced_server):
    code = "class H { int hits(int n) { return n * 2; } }"
    status, _, h1 = _post_full(traced_server.port, "predict", code)
    assert status == 200
    hits0 = _counter_value("serving_cache_hits_total")
    status, body, h2 = _post_full(traced_server.port, "predict", code,
                                  query="?debug=trace")
    assert status == 200
    assert _counter_value("serving_cache_hits_total") == hits0 + 1
    # the hit got its own fresh id...
    assert h2["X-Trace-Id"] != h1["X-Trace-Id"]
    trace = json.loads(body)["trace"]
    assert trace["trace_id"] == h2["X-Trace-Id"]
    by_name = {s["name"]: s for s in trace["spans"]}
    # ...and an honest short tree: cache hit, no pipeline phases
    assert by_name["cache_lookup"]["attrs"]["hit"] is True
    assert "extract" not in by_name and "device" not in by_name
    # error paths carry the id too (here: 400 empty body)
    status, _, h3 = _post_full(traced_server.port, "predict", "   ")
    assert status == 400 and len(h3["X-Trace-Id"]) == 32


def test_debug_trace_gated_off_by_default(server):
    """Security gate: without --serve_debug_trace the ?debug=trace query
    is ignored — the span tree exposes internals (worker pids, batch
    composition) that must not leak from a production endpoint."""
    assert not server.config.serve_debug_trace
    status, body, headers = _post_full(
        server.port, "predict",
        "class G { int gated() { return 1; } }", query="?debug=trace")
    assert status == 200
    assert "trace" not in json.loads(body)
    assert "X-Trace-Id" in headers  # the id itself still rides


def test_telemetry_cli_flags_parse():
    from code2vec_tpu.cli import config_from_args
    config = config_from_args([
        "serve", "--load", "/tmp/nonexistent-model",
        "--serve_debug_trace", "--serve_flight_dir", "/tmp/fl",
        "--serve_flight_records", "64", "--serve_telemetry_port", "0"])
    assert config.serve_debug_trace is True
    assert config.serve_flight_dir == "/tmp/fl"
    assert config.serve_flight_records == 64
    assert config.serve_telemetry_port == 0
    # defaults: debug trace OFF, flight dir/telemetry port unset
    config2 = config_from_args(["--serve", "--load", "/tmp/x"])
    assert config2.serve_debug_trace is False
    assert config2.serve_flight_dir is None
    assert config2.serve_telemetry_port is None


# -------------------------------------------------------------- REPL


def test_repl_golden_output_format(served_model, fake_extractor,
                                   tmp_path, monkeypatch, capsys):
    """The rewired REPL (warm pool underneath) keeps the reference's
    exact display format (interactive_predict.py:39-72): Original name /
    tab-indented (prob) predicted rows / Attention: score<TAB>context
    triples."""
    from code2vec_tpu.serving.interactive import InteractivePredictor
    input_file = tmp_path / "Input.java"
    input_file.write_text(
        "class A { int addOne(int n) { return n + 1; } }")
    answers = iter(["", "q"])
    monkeypatch.setattr("builtins.input", lambda *a: next(answers))
    predictor = InteractivePredictor(served_model.config, served_model)
    assert predictor.extractor_pool.size == 1
    predictor.predict(str(input_file))
    out = capsys.readouterr().out
    assert "Starting interactive prediction..." in out
    assert "Exiting..." in out
    lines = out.splitlines()
    assert "Original name:\taddOne" in lines
    pred_re = re.compile(r"^\t\(\d\.\d{6}\) predicted: (\[.*\]|.+)$")
    att_re = re.compile(r"^\d\.\d{6}\tcontext: .+,\(.+\),.+$")
    assert any(pred_re.match(l) for l in lines), lines
    assert "Attention:" in lines
    assert any(att_re.match(l) for l in lines), lines
    # the pool is torn down when the REPL exits
    assert predictor.extractor_pool._closed


def test_serve_cli_flags_parse():
    from code2vec_tpu.cli import config_from_args
    config = config_from_args([
        "serve", "--load", "/tmp/nonexistent-model", "--serve_port", "0",
        "--serve_batch_size", "32", "--serve_buckets", "16,32",
        "--serve_cache_entries", "128", "--extractor_pool_size", "3"])
    assert config.serve is True
    assert config.serve_port == 0
    assert config.serve_batch_size == 32
    assert config.serve_buckets == "16,32"
    assert config.serve_cache_entries == 128
    assert config.extractor_pool_size == 3
    # --serve flag form equals the subcommand form
    config2 = config_from_args(["--serve", "--load", "/tmp/x"])
    assert config2.serve is True
    # the coalescing delay is gone (a free dispatcher dispatches): the
    # flag is rejected, not ignored, and Config has no such field
    with pytest.raises(SystemExit):
        config_from_args(["serve", "--load", "/tmp/x",
                          "--serve_max_delay_ms", "2.5"])
    assert not hasattr(config2, "serve_max_delay_ms")


@pytest.mark.parametrize("flag", [
    ["--serve_continuous"], ["--serve_inflight_steps", "2"],
    ["--serve_mips_nprobe", "8"], ["--serve_mips_nlist", "64"],
    ["--serve_mips_crossover", "4"]])
def test_flags_of_the_second_batcher_and_head_are_refused(flag):
    """One dispatcher and one head serve: the options that chose another
    are rejected by the parser, not ignored, and `Config` holds no such
    field."""
    from code2vec_tpu.cli import config_from_args
    with pytest.raises(SystemExit):
        config_from_args(["serve", "--load", "/tmp/x"] + flag)
    assert not hasattr(Config(), flag[0].lstrip("-"))
