"""Serving resilience chaos suite: admission control + deadlines,
circuit breakers, health-gated hot-swap, and supervised multi-replica
serving under injected faults and real SIGKILLs.

The contract under test, end to end: a serving stack under overload or
partial failure must degrade GRACEFULLY and HONESTLY — excess load is
shed as 503 + Retry-After (never queued unboundedly), an expired
request is a 504 that never occupies a device slot, a dead dependency
fails fast behind a breaker while cache hits keep serving, a bad model
swap leaves the old model serving with the failure visible, a
SIGKILLed replica yields zero malformed responses and the supervisor
converges back to N live replicas. Fast in-process tests run in tier-1;
the multi-process supervisor drills are marked `slow` and run via
scripts/run_chaos.sh with their own timeout budget.

Builds on the PR-7 scripted fake extractor (test_serving.FAKE_EXTRACTOR)
plus a FakeModel so failures are injectable at every pipeline stage,
and on the `admission_enqueue` / `swap_validate` / `replica_heartbeat`
fault points (utils/faults.py).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from code2vec_tpu import obs
from code2vec_tpu.utils import faults

from test_serving import (
    FAKE_EXTRACTOR, _HeldCall, _counter_value, _serving_config,
)

pytestmark = [pytest.mark.serving, pytest.mark.serving_chaos]

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "chaos_serving_child.py")


@pytest.fixture()
def fake_extractor(tmp_path, monkeypatch):
    path = tmp_path / "fake-c2v-extract"
    path.write_text(FAKE_EXTRACTOR)
    path.chmod(0o755)
    monkeypatch.setenv("C2V_NATIVE_EXTRACTOR", str(path))
    monkeypatch.delenv("C2V_FAKE_NO_SERVER", raising=False)
    return str(path)


# --------------------------------------------------------- fake model


class _FakeResult:
    def __init__(self, name, contexts, topk, vec_size, finite):
        self.original_name = name
        self.topk_predicted_words = [f"predicted|w{i}"
                                     for i in range(topk)]
        self.topk_predicted_words_scores = [
            (0.5 / (i + 1)) if finite else float("nan")
            for i in range(topk)]
        self.attention_per_context = {}
        for i, ctx in enumerate(contexts):
            bits = ctx.split(",")
            if len(bits) == 3:
                self.attention_per_context[tuple(bits)] = 1.0 / (i + 1)
        self.code_vector = [0.25] * vec_size


class FakeModel:
    """The surface PredictionServer + SwapManager need, with every
    failure mode injectable: `fail_with` poisons the device step,
    `predict_delay_s` wedges it, `scores_finite=False` and a mismatched
    `topk`/`vec_size` make a swap candidate fail validation."""

    def __init__(self, config, fingerprint="fpA", topk=3, vec_size=8,
                 predict_delay_s=0.0, scores_finite=True):
        self.config = config
        self._fp = fingerprint
        self.topk = topk
        self.vec_size = vec_size
        self.predict_delay_s = predict_delay_s
        self.scores_finite = scores_finite
        self.fail_with = None
        self.context_buckets = (4, 8, config.max_contexts)
        self._predict_steps = {}

        class _SpecialWords:
            oov = "<OOV>"

        class _TargetVocab:
            special_words = _SpecialWords()

        class _Vocabs:
            target_vocab = _TargetVocab()

        self.vocabs = _Vocabs()

    def model_fingerprint(self):
        return self._fp

    def predict_compile_count(self):
        return 0

    def predict(self, lines, batch_size=None, with_code_vectors=False):
        if self.fail_with is not None:
            raise self.fail_with
        if self.predict_delay_s:
            time.sleep(self.predict_delay_s)
        out = []
        for line in lines:
            parts = line.split()
            out.append(_FakeResult(parts[0], parts[1:], self.topk,
                                   self.vec_size, self.scores_finite))
        return out

    def smoke_schema(self):
        import math
        [r] = self.predict(["swapsmoke a,b,c"], batch_size=1,
                           with_code_vectors=True)
        return {"topk": len(r.topk_predicted_words),
                "code_vector_size": len(r.code_vector),
                "scores_finite": all(
                    math.isfinite(s)
                    for s in r.topk_predicted_words_scores)}


def _chaos_config(tmp_path, **overrides):
    kwargs = dict(
        serve_breaker_min_requests=2,
        serve_breaker_cooldown_s=0.4,
        serve_breaker_window_s=30.0,
        extractor_retries=0,
        serve_deadline_ms=0.0,  # tests opt into deadlines explicitly
    )
    kwargs.update(overrides)
    return _serving_config(tmp_path, **kwargs)


@pytest.fixture()
def chaos_server(tmp_path, fake_extractor):
    """Factory: PredictionServer on a FakeModel + real warm fake-extractor
    pool, drained at teardown."""
    from code2vec_tpu.serving.server import PredictionServer

    made = []

    def make(**overrides):
        config = _chaos_config(tmp_path, **overrides)
        model = FakeModel(config)
        srv = PredictionServer(model, config, log=lambda m: None)
        srv.start(port=0)
        made.append(srv)
        return srv, model

    yield make
    for srv in made:
        srv.drain(timeout=10)


def _post(port, endpoint, body, headers=None):
    hdrs = {"Content-Type": "text/plain"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{endpoint}", data=body.encode(),
        method="POST", headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _hist_count(name, **labels):
    key = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
    child = obs.default_registry().collect().get(name, {}).get(key)
    return child.count if child is not None else 0


# ----------------------------------------------- admission + deadlines


def test_overload_sheds_queue_full_503_with_retry_after(
        chaos_server, monkeypatch):
    """serve_queue_depth=1 + one slow in-flight request: the next
    cache-miss request is SHED — an honest 503 + Retry-After + counted
    shed reason, not an unbounded queue entry."""
    monkeypatch.setenv("C2V_FAKE_SLEEP", "1.5")
    srv, _ = chaos_server(serve_queue_depth=1)
    shed0 = _counter_value("serving_requests_shed_total",
                           reason="queue_full")
    slow_result = {}

    def slow_post():
        slow_result["r"] = _post(
            srv.port, "predict",
            "class S { int slowOne() { return 1; } } SLOW_MARKER")

    t = threading.Thread(target=slow_post)
    t.start()
    deadline = time.time() + 5
    while srv.admission.depth == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert srv.admission.depth == 1
    t0 = time.perf_counter()
    status, body, headers = _post(
        srv.port, "predict", "class Q { int quick() { return 2; } }")
    shed_latency = time.perf_counter() - t0
    assert status == 503
    payload = json.loads(body)
    assert payload["shed"] == "queue_full"
    assert int(headers["Retry-After"]) >= 1
    # shed BEFORE any pipeline work: immediate, not behind the slow one
    assert shed_latency < 0.5
    assert _counter_value("serving_requests_shed_total",
                          reason="queue_full") == shed0 + 1
    # the slow request itself still finishes fine
    t.join(timeout=30)
    assert slow_result["r"][0] == 200
    # satellite: the 503 is IN the total-latency histogram (status label)
    assert _hist_count("serving_request_seconds",
                       phase="total", status="503") >= 1


def test_deadline_expiry_is_504_and_never_blocks_past_budget(
        chaos_server, monkeypatch):
    """X-Deadline-Ms propagates into the extractor as the per-request
    timeout: a 200ms-deadline request against a 2s-hang extractor gets
    its 504 in well under the hang time."""
    monkeypatch.setenv("C2V_FAKE_SLEEP", "2.0")
    srv, _ = chaos_server()
    exp0 = _counter_value("serving_requests_expired_total",
                          stage="extract")
    t0 = time.perf_counter()
    status, body, _ = _post(
        srv.port, "predict",
        "class D { int deadlined() { return 3; } } SLOW_MARKER",
        headers={"X-Deadline-Ms": "200"})
    elapsed = time.perf_counter() - t0
    assert status == 504
    assert "deadline" in json.loads(body)["error"]
    assert elapsed < 1.5, f"blocked {elapsed:.2f}s past a 200ms deadline"
    assert _counter_value("serving_requests_expired_total",
                          stage="extract") == exp0 + 1
    assert _hist_count("serving_request_seconds",
                       phase="total", status="504") >= 1


def test_admission_estimated_wait_sheds_doomed_requests():
    """Once the EWMA knows a request costs ~0.5s, a request with a
    100ms budget behind a queued pipeline is refused up front."""
    from code2vec_tpu.serving.admission import (
        AdmissionController, Deadline, Shed,
    )
    gate = AdmissionController(max_depth=8, concurrency=1)
    gate.admit()
    gate.finish(0.5)  # seed the EWMA
    gate.admit()      # one request in flight
    with pytest.raises(Shed) as exc:
        gate.admit(Deadline(0.1))
    assert exc.value.reason == "deadline"
    # an unbounded-deadline request is still admitted
    gate.admit(Deadline(0.0))
    gate.finish(0.5)
    gate.finish(0.5)


def test_batcher_refuses_infeasible_deadline_and_expires_waiters():
    """The batcher's two deadline duties: refuse a request whose budget
    cannot cover its bucket's observed p95 device time (503 shed, no
    device slot), and settle a request that expires behind the model
    call in flight as 504 before dispatch."""
    from code2vec_tpu.serving.admission import (
        Deadline, DeadlineExceeded, DeadlineInfeasible,
    )
    from code2vec_tpu.serving.batcher import DynamicBatcher

    batcher = DynamicBatcher(lambda lines: [l for l in lines],
                             max_batch_rows=64)
    try:
        # seed the p95 estimate: 0.5s device calls
        for _ in range(4):
            batcher.device_times.record(None, 0.5)
        f = batcher.submit(["line a,b,c"], deadline=Deadline(0.1))
        with pytest.raises(DeadlineInfeasible):
            f.result(timeout=5)
        # a feasible budget on a free dispatcher: dispatched at once,
        # nothing waits for batch-mates, so no 504
        t0 = time.perf_counter()
        f2 = batcher.submit(["line a,b,c"], deadline=Deadline(1.0))
        assert f2.result(timeout=5) == ["line a,b,c"]
        assert time.perf_counter() - t0 < 2.0
    finally:
        batcher.drain()
    # expiry while waiting behind a held model call -> 504 without
    # dispatch
    held_call = _HeldCall(lambda lines: [l for l in lines])
    batcher2 = DynamicBatcher(held_call, max_batch_rows=64)
    try:
        first = held_call.hold(batcher2)
        t0 = time.perf_counter()
        f3 = batcher2.submit(["line a,b,c"], deadline=Deadline(0.05))
        time.sleep(0.1)
        held_call.release()
        with pytest.raises(DeadlineExceeded):
            f3.result(timeout=5)
        assert time.perf_counter() - t0 < 2.0
        assert first.result(timeout=5) == ["hold"]
        assert batcher2.batches_dispatched == 1      # the held call only
        assert held_call.calls == [["hold"]]
    finally:
        held_call.release()
        batcher2.drain()


def test_admission_fault_point_surfaces_as_honest_error(chaos_server):
    """An armed fault in the admission layer itself must surface as a
    well-formed JSON error response — never a hang or a torn body."""
    srv, _ = chaos_server()
    faults.reset("admission_enqueue=raise")
    try:
        status, body, _ = _post(
            srv.port, "predict",
            "class F { int faulty() { return 4; } }")
    finally:
        faults.reset(None)
    assert status == 500
    assert "FaultInjected" in json.loads(body)["error"]


# ------------------------------------------------- flight recorder


def test_breaker_open_under_load_dumps_shed_trace_ids(chaos_server,
                                                      tmp_path):
    """Acceptance pin: kicking a breaker open under load produces a
    flight-recorder dump containing the shed requests' trace ids — the
    incident dump is DELAYED so the black box captures both the
    failures that opened the breaker and the shed storm it caused."""
    flight_dir = tmp_path / "flight"
    srv, _ = chaos_server(serve_flight_dir=str(flight_dir),
                          serve_cache_entries=0,
                          serve_breaker_cooldown_s=10.0)
    srv.flight.configure(dump_delay_s=0.6)
    # extractor crash storm (retries=0, min_requests=2) opens the breaker
    for i in range(2):
        status, _, _ = _post(
            srv.port, "predict",
            f"class C{i} {{ int crash{i}() {{ return 1; }} }} "
            f"CRASH_ALWAYS")
        assert status == 503
    assert srv.extractor_breaker.state == "open"
    # load against the open breaker: fail-fast sheds, each with its id
    shed_ids = []
    for i in range(3):
        status, body, headers = _post(
            srv.port, "predict",
            f"class S{i} {{ int shed{i}() {{ return 1; }} }}")
        assert status == 503
        payload = json.loads(body)
        assert payload["shed"] == "breaker"
        assert payload["trace_id"] == headers["X-Trace-Id"]
        shed_ids.append(headers["X-Trace-Id"])
    deadline = time.time() + 10
    files = []
    while time.time() < deadline:
        files = sorted(flight_dir.glob("flight-*.json"))
        if files:
            break
        time.sleep(0.05)
    assert files, "a breaker open must produce a flight dump"
    doc = json.loads(files[0].read_text())
    assert doc["reason"] == "breaker_open"
    recorded = {r["trace_id"]: r for r in doc["requests"]}
    for tid in shed_ids:
        assert tid in recorded, "shed request missing from the dump"
        assert recorded[tid]["status"] == 503
        assert recorded[tid]["reason"] == "breaker"
        assert recorded[tid]["endpoint"] == "predict"
    assert any(e["kind"] == "breaker_open" and e.get("incident")
               and e["breaker"] == "extractor" for e in doc["events"])


def test_admin_dump_endpoint_writes_flight_file(chaos_server, tmp_path):
    flight_dir = tmp_path / "dumps"
    srv, _ = chaos_server(serve_flight_dir=str(flight_dir))
    status, _, headers = _post(
        srv.port, "predict", "class D { int dumped() { return 1; } }")
    assert status == 200
    wanted = headers["X-Trace-Id"]
    status, body, _ = _post(srv.port, "admin/dump", "")
    assert status == 200
    payload = json.loads(body)
    assert os.path.dirname(payload["path"]) == str(flight_dir)
    doc = json.loads(open(payload["path"]).read())
    assert doc["reason"] == "admin"
    assert payload["requests"] == len(doc["requests"]) >= 1
    assert wanted in {r["trace_id"] for r in doc["requests"]}


def test_drain_timeout_incident_dumps_synchronously(
        chaos_server, tmp_path, monkeypatch):
    """A drain timeout is an exit-path incident: the dump must land
    BEFORE the process would exit (no delayed timer), with the
    abandoned request still in the ring."""
    monkeypatch.setenv("C2V_FAKE_SLEEP", "2.0")
    flight_dir = tmp_path / "drainflight"
    srv, _ = chaos_server(serve_flight_dir=str(flight_dir))
    result = {}

    def slow_post():
        result["r"] = _post(
            srv.port, "predict",
            "class A { int abandoned() { return 1; } } SLOW_MARKER")

    t = threading.Thread(target=slow_post)
    t.start()
    deadline = time.time() + 5
    while srv._inflight == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert srv.drain(timeout=0.2) is False
    files = list(flight_dir.glob("flight-*drain_timeout.json"))
    assert len(files) == 1, "exit-path incidents dump synchronously"
    doc = json.loads(files[0].read_text())
    assert any(e["kind"] == "drain_timeout" and e["abandoned"] == 1
               for e in doc["events"])
    t.join(timeout=30)


# ------------------------------------------------------------ breakers


def test_extractor_crash_storm_opens_breaker_cache_still_serves(
        chaos_server, tmp_path):
    """The acceptance scenario: an extractor crash storm opens the
    breaker (fail-fast 503s, no extractor work), cache hits still serve
    (graceful degradation), and the half-open probe closes it again."""
    srv, _ = chaos_server()
    good = "class G { int golden() { return 1; } }"
    status, cached_body, _ = _post(srv.port, "predict", good)
    assert status == 200

    for i in range(2):
        status, _, _ = _post(
            srv.port, "predict",
            f"class C{i} {{ int crash{i}() {{ return 1; }} }} "
            f"CRASH_ALWAYS")
        assert status == 503
    assert srv.extractor_breaker.state == "open"

    # open breaker: a NEW request fails fast without touching the pool
    reqs0 = _counter_value("extractor_pool_requests_total")
    shed0 = _counter_value("serving_requests_shed_total",
                           reason="breaker")
    status, body, headers = _post(
        srv.port, "predict", "class N { int nope() { return 2; } }")
    assert status == 503
    assert json.loads(body)["shed"] == "breaker"
    assert "Retry-After" in headers
    assert _counter_value("extractor_pool_requests_total") == reqs0
    assert _counter_value("serving_requests_shed_total",
                          reason="breaker") == shed0 + 1

    # ... but the cache hit path is untouched: byte-equal 200
    status, body, _ = _post(srv.port, "predict", good)
    assert status == 200
    assert body == cached_body

    # half-open after the cooldown: one good probe closes the breaker
    time.sleep(srv.config.serve_breaker_cooldown_s + 0.1)
    assert srv.extractor_breaker.state == "half_open"
    status, _, _ = _post(srv.port, "predict",
                         "class R { int recovered() { return 3; } }")
    assert status == 200
    assert srv.extractor_breaker.state == "closed"
    assert _counter_value("serving_breaker_transitions_total",
                          breaker="extractor", to="open") >= 1
    assert _counter_value("serving_breaker_transitions_total",
                          breaker="extractor", to="closed") >= 1


def test_device_failure_storm_opens_device_breaker(chaos_server):
    srv, model = chaos_server()
    model.fail_with = RuntimeError("device wedged")
    for i in range(2):
        status, _, _ = _post(
            srv.port, "predict",
            f"class D{i} {{ int dev{i}() {{ return 1; }} }}")
        assert status == 500
    assert srv.device_breaker.state == "open"
    status, body, _ = _post(
        srv.port, "predict", "class D9 { int dev9() { return 1; } }")
    assert status == 503
    assert json.loads(body)["shed"] == "breaker"
    # recovery: dependency healthy again, half-open probe closes it
    model.fail_with = None
    time.sleep(srv.config.serve_breaker_cooldown_s + 0.1)
    status, _, _ = _post(
        srv.port, "predict", "class D8 { int dev8() { return 1; } }")
    assert status == 200
    assert srv.device_breaker.state == "closed"


def test_aborted_half_open_probe_rearms_instead_of_wedging():
    """Regression: a half-open probe that ends without a dependency
    verdict (the REQUEST's deadline expired mid-call) must re-arm the
    probe slot — not leave _probe_inflight stuck so the breaker sheds
    forever after the dependency recovered."""
    from code2vec_tpu.serving.breaker import CircuitBreaker

    t = [0.0]
    b = CircuitBreaker("x", window_s=10, failure_ratio=0.5,
                       min_requests=2, cooldown_s=5,
                       clock=lambda: t[0])
    for _ in range(2):
        assert b.allow()
        b.record(ok=False)
    assert b.state == "open"
    t[0] = 5.1
    assert b.allow()        # the half-open probe slot
    b.abort()               # probe ended with no verdict
    assert b.allow()        # slot re-armed: next request probes again
    b.record(ok=True)
    assert b.state == "closed"
    b.abort()               # no-op outside half-open
    assert b.state == "closed" and b.allow()


def test_client_parse_errors_do_not_open_the_breaker(chaos_server):
    """A storm of bad client input (deterministic 422 rejections) is a
    HEALTHY extractor answering; it must never open the breaker and
    shed good clients."""
    srv, _ = chaos_server()
    for _ in range(4):
        status, _, _ = _post(srv.port, "predict", "BOOM_ALWAYS")
        assert status == 422
    assert srv.extractor_breaker.state == "closed"
    status, _, _ = _post(srv.port, "predict",
                         "class K { int keeps() { return 1; } }")
    assert status == 200


# ------------------------------------------------------------ hot swap


def _wait_swap_state(srv, states, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        state = srv.swap.status()["state"]
        if state in states:
            return state
        time.sleep(0.02)
    raise AssertionError(
        f"swap never reached {states}; status={srv.swap.status()}")


def test_hot_swap_under_live_traffic_single_fingerprint_responses(
        chaos_server):
    """Every response during a live swap is attributable to exactly ONE
    model fingerprint (old or new, never a mix), and traffic after the
    swap serves the new weights."""
    from code2vec_tpu.serving.swap import SwapManager

    srv, model_a = chaos_server(serve_cache_entries=0)

    def build_b(artifact_dir):
        assert artifact_dir == "artifact-b"
        time.sleep(0.3)  # overlap the load: old model keeps serving
        return FakeModel(srv.config, fingerprint="fpB")

    srv.swap = SwapManager(srv, build_model=build_b)
    seen = []
    stop_load = threading.Event()

    def load(ci):
        i = 0
        while not stop_load.is_set():
            status, body, _ = _post(
                srv.port, "predict",
                f"class L{ci}x{i} {{ int m{ci}x{i}() {{ return 1; }} }}")
            assert status == 200
            seen.append(json.loads(body)["model_fingerprint"])
            i += 1

    threads = [threading.Thread(target=load, args=(ci,))
               for ci in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)
        status, body, _ = _post(srv.port, "admin/reload",
                                json.dumps({"artifact": "artifact-b"}),
                                headers={"Content-Type":
                                         "application/json"})
        assert status == 202
        assert _wait_swap_state(srv, {"ready"}) == "ready"
        time.sleep(0.2)  # post-swap traffic
    finally:
        stop_load.set()
        for t in threads:
            t.join(timeout=30)
    assert set(seen) <= {"fpA", "fpB"}, f"mixed fingerprints: {set(seen)}"
    assert seen[-1] == "fpB" and "fpB" in seen
    status, body, _ = _post(srv.port, "predict",
                            "class Z { int after() { return 9; } }")
    assert json.loads(body)["model_fingerprint"] == "fpB"
    hz = json.loads(_get(srv.port, "/healthz")[1])
    assert hz["model"]["fingerprint"] == "fpB"
    assert hz["model"]["swap_status"]["state"] == "ready"
    assert hz["model"]["swap_status"]["swapped_fingerprint"] == "fpB"


def test_swap_validation_failure_leaves_old_model_serving(chaos_server):
    """A candidate with a mismatched output schema (narrower top-k) is
    REJECTED: swap status failed + visible in /healthz, old fingerprint
    keeps serving, failure counted."""
    from code2vec_tpu.serving.swap import SwapManager

    srv, _ = chaos_server()
    failed0 = _counter_value("serving_swap_total", outcome="failed")
    srv.swap = SwapManager(
        srv, build_model=lambda d: FakeModel(srv.config,
                                             fingerprint="fpBad",
                                             topk=5))
    status, _, _ = _post(srv.port, "admin/reload",
                         json.dumps({"artifact": "bad"}),
                         headers={"Content-Type": "application/json"})
    assert status == 202
    assert _wait_swap_state(srv, {"failed"}) == "failed"
    swap_status = srv.swap.status()
    assert "topk" in swap_status["error"]
    assert srv.model_fingerprint == "fpA"
    status, body, _ = _post(srv.port, "predict",
                            "class V { int still() { return 1; } }")
    assert status == 200
    assert json.loads(body)["model_fingerprint"] == "fpA"
    hz = json.loads(_get(srv.port, "/healthz")[1])
    assert hz["model"]["swap_status"]["state"] == "failed"
    assert _counter_value("serving_swap_total",
                          outcome="failed") == failed0 + 1


def test_swap_rejects_nonfinite_scores(chaos_server):
    from code2vec_tpu.serving.swap import SwapManager

    srv, _ = chaos_server()
    srv.swap = SwapManager(
        srv, build_model=lambda d: FakeModel(srv.config,
                                             fingerprint="fpNaN",
                                             scores_finite=False))
    srv.swap.request_reload("nan-artifact")
    assert _wait_swap_state(srv, {"failed"}) == "failed"
    assert "non-finite" in srv.swap.status()["error"]
    assert srv.model_fingerprint == "fpA"


def test_swap_fault_injection_leaves_old_model(chaos_server):
    """The `swap_validate` chaos drill: a fault at the top of the
    load+validate worker fails the swap visibly; never a torn
    half-swapped server."""
    from code2vec_tpu.serving.swap import SwapManager

    srv, _ = chaos_server()
    srv.swap = SwapManager(
        srv, build_model=lambda d: FakeModel(srv.config,
                                             fingerprint="fpC"))
    faults.reset("swap_validate=raise")
    try:
        srv.swap.request_reload("fault-artifact")
        assert _wait_swap_state(srv, {"failed"}) == "failed"
    finally:
        faults.reset(None)
    assert "FaultInjected" in srv.swap.status()["error"]
    assert srv.model_fingerprint == "fpA"
    status, _, _ = _post(srv.port, "predict",
                         "class W { int works() { return 1; } }")
    assert status == 200


def test_swap_adopts_new_model_bucket_grid(chaos_server):
    """Regression: after a hot swap the batcher's deadline-feasibility
    math must run against the NEW model's context-bucket grid, with the
    old grid's device-time samples dropped."""
    srv, _ = chaos_server()
    old_tracker = srv.batcher.device_times
    new = FakeModel(srv.config, fingerprint="fpGrid")
    new.context_buckets = (2, srv.config.max_contexts)
    srv.swap_model(new)
    assert srv.batcher.buckets == (2, srv.config.max_contexts)
    assert srv.batcher.device_times is not old_tracker
    status, body, _ = _post(srv.port, "predict",
                            "class G { int grid() { return 1; } }")
    assert status == 200
    assert json.loads(body)["model_fingerprint"] == "fpGrid"


def test_swap_concurrent_reload_conflicts_409_and_bad_body_400(
        chaos_server):
    from code2vec_tpu.serving.swap import SwapManager

    srv, _ = chaos_server()

    def slow_build(d):
        time.sleep(0.5)
        return FakeModel(srv.config, fingerprint="fpS")

    srv.swap = SwapManager(srv, build_model=slow_build)
    jhdr = {"Content-Type": "application/json"}
    assert _post(srv.port, "admin/reload",
                 json.dumps({"artifact": "s"}), headers=jhdr)[0] == 202
    status, body, _ = _post(srv.port, "admin/reload",
                            json.dumps({"artifact": "t"}), headers=jhdr)
    assert status == 409
    assert "in flight" in json.loads(body)["error"]
    # no target / malformed JSON are 400s, not 500s
    assert _post(srv.port, "admin/reload", "{}", headers=jhdr)[0] == 400
    assert _post(srv.port, "admin/reload", "{nope", headers=jhdr)[0] == 400
    _wait_swap_state(srv, {"ready"})


# --------------------------------------------- drain + SLO accounting


def test_healthz_flips_503_draining_the_moment_sigterm_lands(
        chaos_server, monkeypatch):
    """The load-balancer eviction contract: while a drain waits on
    in-flight work the listener must answer /healthz with 503 +
    status=draining, and new predicts are refused as draining sheds."""
    monkeypatch.setenv("C2V_FAKE_SLEEP", "1.2")
    srv, _ = chaos_server()
    slow_result = {}

    def slow_post():
        slow_result["r"] = _post(
            srv.port, "predict",
            "class S { int slowDrain() { return 1; } } SLOW_MARKER")

    t = threading.Thread(target=slow_post)
    t.start()
    deadline = time.time() + 5
    while srv._inflight == 0 and time.time() < deadline:
        time.sleep(0.01)
    drain_thread = threading.Thread(target=srv.drain,
                                    kwargs={"timeout": 30})
    drain_thread.start()
    deadline = time.time() + 5
    while not srv._draining and time.time() < deadline:
        time.sleep(0.005)
    status, body = _get(srv.port, "/healthz")
    assert status == 503
    hz = json.loads(body)
    assert hz["status"] == "draining"
    assert hz["inflight"] >= 1
    # intake refused with the draining shed reason while the in-flight
    # request is allowed to finish
    shed0 = _counter_value("serving_requests_shed_total",
                           reason="draining")
    status, _, _ = _post(srv.port, "predict",
                         "class N { int newReq() { return 2; } }")
    assert status == 503
    assert _counter_value("serving_requests_shed_total",
                          reason="draining") == shed0 + 1
    drain_thread.join(timeout=30)
    t.join(timeout=30)
    assert slow_result["r"][0] == 200


def test_drain_timeout_exits_nonzero_with_abandoned_count(
        tmp_path, fake_extractor, monkeypatch):
    """A drain that exceeds serve_drain_timeout_s exits nonzero with the
    abandoned-request count in the final heartbeat."""
    from code2vec_tpu.serving.server import serve_main

    monkeypatch.setenv("C2V_FAKE_SLEEP", "5.0")
    hb_path = tmp_path / "serve.heartbeat.json"
    config = _chaos_config(tmp_path, serve_port=0,
                           serve_drain_timeout_s=0.3,
                           serve_heartbeat_interval_s=0.1,
                           heartbeat_file=str(hb_path))
    model = FakeModel(config)
    stop = threading.Event()
    rc_holder = {}

    def run():
        rc_holder["rc"] = serve_main(config, model=model, stop=stop,
                                     install_signals=False)

    serve_thread = threading.Thread(target=run)
    serve_thread.start()
    try:
        deadline = time.time() + 10
        port = None
        while port is None and time.time() < deadline:
            try:
                port = json.loads(hb_path.read_text()).get("port")
            except (OSError, ValueError):
                time.sleep(0.02)
        assert port, "server heartbeat never reported a port"
        slow = threading.Thread(target=_post, args=(
            port, "predict",
            "class S { int abandoned() { return 1; } } SLOW_MARKER"))
        slow.start()
        deadline = time.time() + 5
        while time.time() < deadline:
            try:
                if json.loads(hb_path.read_text()).get("inflight", 0):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.02)
    finally:
        stop.set()
    serve_thread.join(timeout=30)
    slow.join(timeout=30)
    assert rc_holder["rc"] == 1
    deadline = time.time() + 2
    hb = json.loads(hb_path.read_text())
    while hb.get("status") != "error" and time.time() < deadline:
        time.sleep(0.05)
        hb = json.loads(hb_path.read_text())
    assert hb["status"] == "error"
    assert hb["abandoned_requests"] >= 1


def test_total_phase_histogram_records_every_terminal_status(
        chaos_server):
    """Satellite bugfix pin: errored and shed requests land in
    serving_request_seconds{phase=total,status=...} — the tail is
    measured, not invisible."""
    srv, _ = chaos_server()
    cases = {
        "200": ("class H { int histOk() { return 1; } }", 200),
        "400": ("", 400),
        "422": ("BOOM_ALWAYS", 422),
        "503": ("class H2 { int histCrash() { return 1; } } "
                "CRASH_ALWAYS", 503),
    }
    before = {s: _hist_count("serving_request_seconds",
                             phase="total", status=s) for s in cases}
    for s, (code, want) in cases.items():
        status, _, _ = _post(srv.port, "predict", code)
        assert status == want
    for s in cases:
        assert _hist_count("serving_request_seconds", phase="total",
                           status=s) == before[s] + 1, f"status {s}"


def test_watchdog_timer_cancelled_thread_count_stable(
        fake_extractor, tmp_path):
    """Satellite bugfix pin: the pool's per-request watchdog Timer is
    cancelled on the fast path — sustained traffic must not accumulate
    idle Timer threads waiting out the 30s extractor timeout."""
    from code2vec_tpu.serving.extractor_pool import ExtractorPool

    config = _serving_config(tmp_path, extractor_timeout_s=30.0)
    with ExtractorPool(config, size=1) as pool:
        assert pool.warm
        pool.extract_source("class W { int warm() { return 1; } }")
        time.sleep(0.2)
        baseline = threading.active_count()
        for i in range(25):
            pool.extract_source(
                f"class T{i} {{ int t{i}() {{ return 1; }} }}")
        time.sleep(0.3)  # cancelled timers wind down
        after = threading.active_count()
    assert after <= baseline + 1, (
        f"{after - baseline} threads accumulated over 25 requests "
        f"(uncancelled watchdog timers)")


# ------------------------------------------------- supervisor (slow)


def _write_child_overrides(tmp_path, fake_extractor, **extra):
    overrides = dict(
        serve_host="127.0.0.1",
        max_contexts=16,
        serve_batch_size=4,
        serve_buckets="4,8",
        serve_cache_entries=0,
        extractor_pool_size=1,
        serve_drain_timeout_s=5.0,
        serve_heartbeat_interval_s=0.2,
    )
    overrides.update(extra)
    path = tmp_path / "child-config.json"
    path.write_text(json.dumps(overrides))
    return str(path)


def _supervisor_config(tmp_path, **overrides):
    kwargs = dict(
        serve=True,
        serve_host="127.0.0.1",
        serve_port=0,
        serve_replicas=2,
        serve_max_restarts=5,
        serve_heartbeat_interval_s=0.2,
        serve_drain_timeout_s=5.0,
        heartbeat_file=str(tmp_path / "supervisor.heartbeat.json"),
        verbose_mode=0,
    )
    kwargs.update(overrides)
    from code2vec_tpu.config import Config
    return Config(**kwargs)


def _wait_live_replicas(sup, n, timeout=30.0):
    """Poll the supervisor heartbeat until n replicas are alive with
    known ports; returns the heartbeat dict."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            hb = json.loads(open(sup.heartbeat_path).read())
        except (OSError, ValueError):
            hb = None
        if hb:
            live = [r for r in hb["replicas"]
                    if r["alive"] and r["port"]]
            if len(live) >= n:
                return hb
        time.sleep(0.05)
    raise AssertionError(f"never reached {n} live replicas; last={hb}")


@pytest.fixture()
def run_supervisor(tmp_path, fake_extractor, monkeypatch):
    """Factory: a Supervisor on lightweight fake-model replica children
    (tests/chaos_serving_child.py), run on a daemon thread, torn down at
    test end."""
    from code2vec_tpu.serving.supervisor import Supervisor

    running = []

    def start(config, child_args=(), force_proxy=True):
        if force_proxy:
            monkeypatch.setenv("C2V_SERVE_FORCE_PROXY", "1")
        else:
            monkeypatch.delenv("C2V_SERVE_FORCE_PROXY", raising=False)
        child_command = [sys.executable, CHILD] + list(child_args)
        sup = Supervisor(config, child_command=child_command)
        rc_holder = {}
        thread = threading.Thread(
            target=lambda: rc_holder.update(rc=sup.run()), daemon=True)
        thread.start()
        running.append((sup, thread))
        return sup, thread, rc_holder

    yield start
    for sup, thread in running:
        sup._stop.set()
        thread.join(timeout=40)


@pytest.mark.slow
@pytest.mark.chaos
def test_supervisor_sigkill_under_load_no_corrupt_responses_converges(
        tmp_path, fake_extractor, run_supervisor):
    """THE serving chaos proof: SIGKILL one of two replicas under
    concurrent load. Zero malformed responses (every body is valid JSON
    with either a result or an honest error), the supervisor restores
    2 live replicas, and a coordinated SIGTERM drain exits 0."""
    overrides = _write_child_overrides(tmp_path, fake_extractor)
    config = _supervisor_config(tmp_path)
    sup, thread, rc_holder = run_supervisor(config, (overrides,))
    hb = _wait_live_replicas(sup, 2)
    port = sup.port

    responses = []
    resp_lock = threading.Lock()
    stop_load = threading.Event()
    malformed = []

    def load(ci):
        i = 0
        while not stop_load.is_set():
            try:
                status, body, _ = _post(
                    port, "predict",
                    f"class K{ci}x{i} {{ int m{ci}x{i}() "
                    f"{{ return 1; }} }}")
            except Exception as e:  # noqa: BLE001 — proxied kill window
                # a torn TCP connection counts as a failure to retry,
                # not a corrupt response; record it separately
                with resp_lock:
                    responses.append(("conn_error", str(e)))
                i += 1
                continue
            try:
                payload = json.loads(body)
                ok = (("methods" in payload)
                      if status == 200 else ("error" in payload))
                if not ok:
                    raise ValueError(f"incomplete payload: {payload}")
            except ValueError as e:
                malformed.append((status, body[:200], str(e)))
            with resp_lock:
                responses.append((status, None))
            i += 1

    threads = [threading.Thread(target=load, args=(ci,))
               for ci in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.5)
        victim = next(r for r in hb["replicas"] if r["alive"])
        os.kill(victim["pid"], signal.SIGKILL)
        # convergence: the supervisor restarts the victim with backoff
        deadline = time.time() + 30
        while time.time() < deadline:
            hb2 = json.loads(open(sup.heartbeat_path).read())
            entry = next(r for r in hb2["replicas"]
                         if r["index"] == victim["index"])
            if (entry["alive"] and entry["port"]
                    and entry["pid"] != victim["pid"]
                    and entry["restarts"] >= 1):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"victim never restarted: {hb2}")
        _wait_live_replicas(sup, 2)
        time.sleep(0.5)  # post-recovery traffic
    finally:
        stop_load.set()
        for t in threads:
            t.join(timeout=30)
    assert not malformed, f"corrupt responses: {malformed[:3]}"
    statuses = [s for s, _ in responses]
    assert statuses.count(200) > 0
    # post-recovery the service is fully back: a fresh request succeeds
    status, body, _ = _post(port, "predict",
                            "class A { int after() { return 1; } }")
    assert status == 200
    assert json.loads(body)["methods"][0]["original_name"] == "after"
    # coordinated drain: SIGTERM fan-out, every replica exits 0
    sup._stop.set()
    thread.join(timeout=40)
    assert rc_holder["rc"] == 0
    final = json.loads(open(sup.heartbeat_path).read())
    assert final["status"] == "done"


@pytest.mark.slow
@pytest.mark.chaos
def test_supervisor_reuseport_replicas_share_one_port(
        tmp_path, fake_extractor, run_supervisor):
    """SO_REUSEPORT mode: both replicas bind the SAME port and traffic
    is served through it (kernel load-balancing)."""
    import socket as socket_mod
    if not hasattr(socket_mod, "SO_REUSEPORT"):
        pytest.skip("platform lacks SO_REUSEPORT")
    overrides = _write_child_overrides(tmp_path, fake_extractor)
    config = _supervisor_config(tmp_path)
    sup, thread, rc_holder = run_supervisor(config, (overrides,),
                                            force_proxy=False)
    assert sup.reuseport
    hb = _wait_live_replicas(sup, 2)
    ports = {r["port"] for r in hb["replicas"]}
    assert ports == {sup.port}
    # in reuseport mode replica.port is assigned at spawn, before the
    # child has bound the socket: wait for actual readiness
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if _get(sup.port, "/healthz")[0] == 200:
                break
        except (urllib.error.URLError, OSError):
            time.sleep(0.1)
    for i in range(4):
        status, body, _ = _post(
            sup.port, "predict",
            f"class R{i} {{ int rp{i}() {{ return 1; }} }}")
        assert status == 200
    sup._stop.set()
    thread.join(timeout=40)
    assert rc_holder["rc"] == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_supervisor_escalates_when_restart_budget_exhausted(
        tmp_path, monkeypatch):
    """A replica that cannot stay up is a deploy problem: after
    serve_max_restarts the supervisor kills everything and exits
    nonzero with the escalation in its heartbeat."""
    from code2vec_tpu.serving.supervisor import Supervisor

    monkeypatch.setenv("C2V_SERVE_FORCE_PROXY", "1")
    config = _supervisor_config(tmp_path, serve_replicas=1,
                                serve_max_restarts=1)
    sup = Supervisor(config, child_command=[
        sys.executable, "-c", "import sys; sys.exit(7)"])
    rc = sup.run()
    assert rc == 1
    hb = json.loads(open(sup.heartbeat_path).read())
    assert hb["status"] == "error"
    assert hb["escalated"] is True
    assert hb["replicas"][0]["restarts"] == 1


@pytest.mark.slow
@pytest.mark.chaos
def test_supervisor_restarts_replica_with_stale_heartbeat(
        tmp_path, fake_extractor, run_supervisor, monkeypatch):
    """The hung-replica drill (`replica_heartbeat` fault point): a
    replica whose heartbeat ticker dies keeps its process alive but
    goes stale; the supervisor kills and restarts it."""
    faults.reset(None)  # keep the fault env out of THIS process
    monkeypatch.setenv("C2V_FAULTS", "replica_heartbeat@2=raise")
    overrides = _write_child_overrides(tmp_path, fake_extractor)
    config = _supervisor_config(tmp_path, serve_replicas=1)
    sup, thread, rc_holder = run_supervisor(config, (overrides,))
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                hb = json.loads(open(sup.heartbeat_path).read())
            except (OSError, ValueError):
                hb = {"replicas": [{"restarts": 0}]}
            if hb["replicas"][0]["restarts"] >= 1:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"stale replica never restarted: {hb}")
        assert _counter_value("serving_replica_restarts_total") >= 1
    finally:
        faults.reset("")  # back to lazy env re-read for other tests


# ----------------------------------------------------------- CLI seam


def test_serve_resilience_cli_flags_parse():
    from code2vec_tpu.cli import config_from_args

    config = config_from_args([
        "serve", "--load", "/tmp/nonexistent-model",
        "--serve_deadline_ms", "1500", "--serve_deadline_max_ms", "9000",
        "--serve_queue_depth", "32", "--serve_breaker_window", "20",
        "--serve_breaker_failure_ratio", "0.25",
        "--serve_breaker_min_requests", "8",
        "--serve_breaker_cooldown", "2.5",
        "--replicas", "3", "--serve_max_restarts", "7",
        "--serve_heartbeat_interval", "1.5"])
    assert config.serve_deadline_ms == 1500
    assert config.serve_deadline_max_ms == 9000
    assert config.serve_queue_depth == 32
    assert config.serve_breaker_window_s == 20
    assert config.serve_breaker_failure_ratio == 0.25
    assert config.serve_breaker_min_requests == 8
    assert config.serve_breaker_cooldown_s == 2.5
    assert config.serve_replicas == 3
    assert config.serve_max_restarts == 7
    assert config.serve_heartbeat_interval_s == 1.5
    config.verify()


def test_replicas_rejected_outside_serve():
    from code2vec_tpu.cli import config_from_args

    config = config_from_args(["--data", "/tmp/x", "--replicas", "2"])
    with pytest.raises(ValueError, match="serve subcommand"):
        config.verify()


def test_deadline_default_must_not_exceed_max():
    from code2vec_tpu.cli import config_from_args

    config = config_from_args([
        "serve", "--load", "/tmp/nonexistent-model",
        "--serve_deadline_ms", "5000", "--serve_deadline_max_ms", "1000"])
    with pytest.raises(ValueError, match="serve_deadline_max_ms"):
        config.verify()


def test_scale_down_prefers_coldest_cache_replica(tmp_path):
    """Cache-warmth-aware scale-down (PR-13 follow-on, roofline PR):
    the victim is the replica with the fewest serving_cache_hits_total
    over the CURRENT warmth window (hits since the last baseline
    sample — lifetime counters measure uptime, not warmth); missing/
    unreadable snapshots count 0; counter resets clamp to 0; all-equal
    windows fall back to newest-first."""
    from code2vec_tpu.serving.supervisor import Supervisor

    config = _supervisor_config(tmp_path, serve_replicas=3)
    sup = Supervisor(config, child_command=["true"])

    def write_metrics(replica, hits):
        with open(replica.metrics_path, "w") as f:
            f.write("# TYPE serving_cache_hits_total counter\n"
                    f"serving_cache_hits_total {hits}\n")

    r0, r1, r2 = sup.replicas
    write_metrics(r0, 50)
    write_metrics(r1, 3)
    write_metrics(r2, 90)
    assert sup._scale_down_victims(sup.replicas, 1) == [r1]
    # two victims: the two coldest caches, coldest first
    assert sup._scale_down_victims(sup.replicas, 2) == [r1, r0]
    # WINDOWED, not lifetime: baseline the counters, then give the
    # lifetime-richest replica (r2) the QUIETEST window — it must be
    # the victim despite its big historical count
    sup._sample_warmth_baselines()
    write_metrics(r0, 80)    # +30 this window
    write_metrics(r1, 60)    # +57
    write_metrics(r2, 91)    # +1  <- coldest window, biggest lifetime
    assert sup._scale_down_victims(sup.replicas, 1) == [r2]
    # a restarted replica's counter reset clamps to 0 (fresh cache IS
    # cold), never a negative that would wrap the ordering
    write_metrics(r2, 2)
    assert sup._scale_down_victims(sup.replicas, 1) == [r2]
    sup._sample_warmth_baselines()
    # replica without a snapshot (still starting) = coldest of all
    os.remove(r2.metrics_path)
    assert sup._scale_down_victims(sup.replicas, 1) == [r2]
    # unreadable garbage parses to 0 samples -> counts 0 hits
    with open(r2.metrics_path, "wb") as f:
        f.write(b"\x00\xff garbage")
    assert sup._scale_down_victims(sup.replicas, 1) == [r2]
    # all-equal warmth: newest-first (the pre-roofline policy)
    for r in sup.replicas:
        r.warmth_prev = 0.0
        write_metrics(r, 7)
    assert sup._scale_down_victims(sup.replicas, 1) == [r2]
    assert sup._scale_down_victims(sup.replicas, 2) == [r2, r1]


def test_supervisor_gives_each_replica_a_chip_or_refuses(tmp_path,
                                                          monkeypatch):
    """One process per chip. With the host's chip count injected (a TPU
    host; the CPU has no such limit): more replicas than chips is ONE
    start-up line and exit 1 with nothing spawned — not a restart storm
    — and within the count every replica's environment binds it to a
    chip of its own."""
    from code2vec_tpu.serving import supervisor as sup_mod

    monkeypatch.setattr(sup_mod, "exclusive_chips", lambda: 1)
    sup = sup_mod.Supervisor(_supervisor_config(tmp_path),
                             child_command=[sys.executable, "-c", "pass"])
    lines = []
    sup.log = lines.append
    assert sup.run() == 1
    assert all(r.proc is None for r in sup.replicas)
    assert len(lines) == 1 and "needs 2 TPU chips" in lines[0] \
        and "has 1" in lines[0]

    monkeypatch.setattr(sup_mod, "exclusive_chips", lambda: 2)
    script = ("import os, time\n"
              f"out = os.path.join({str(tmp_path)!r}, 'env' + "
              "os.environ['C2V_SERVE_REPLICA'])\n"
              "open(out + '.tmp', 'w').write(' '.join(os.environ.get(k, '-') "
              "for k in ('TPU_VISIBLE_CHIPS', 'TPU_MESH_CONTROLLER_PORT', "
              "'TPU_PROCESS_BOUNDS')))\n"
              "os.rename(out + '.tmp', out)\n"
              "time.sleep(60)\n")
    sup = sup_mod.Supervisor(_supervisor_config(tmp_path),
                             child_command=[sys.executable, "-c", script])
    sup.log = lines.append
    try:
        for replica in sup.replicas:
            sup._spawn(replica)
        seen = []
        for i in range(2):
            path = tmp_path / f"env{i}"
            deadline = time.time() + 30
            while not path.exists() and time.time() < deadline:
                time.sleep(0.05)
            seen.append(path.read_text().split())
        assert [r.chip for r in sup.replicas] == [0, 1]
        assert seen == [["0", "8476", "1,1,1"], ["1", "8477", "1,1,1"]]
        with pytest.raises(ValueError, match="chips on this host"):
            sup.request_scale(3)
    finally:
        for replica in sup.replicas:
            sup._kill(replica)
            if replica.proc is not None:
                replica.proc.wait(timeout=10)
