"""Cross-host serving fleet suite (code2vec_tpu/serving/fleet/):
health-gated router (weighted routing, deadline-bounded retry, trace
propagation, multi-model isolation), control-plane scaling policy
(hysteresis, bounds, cooldown), canary-first coordinated hot-swap
(commit / halt / rollback), plus the satellite pins — jittered
Retry-After, flight-dump retention, telemetry admin verbs.

Fast tests run in tier-1 on stubs; the multi-host chaos drills (real
ControlPlane + router over real Supervisor subprocesses running
fake-model replicas) are marked `slow` and run via scripts/run_chaos.sh
with their own budget.
"""

import http.server
import json
import os
import signal
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from code2vec_tpu import obs
from code2vec_tpu.config import Config

from test_serving import FAKE_EXTRACTOR, _counter_value

pytestmark = pytest.mark.fleet

HERE = os.path.dirname(os.path.abspath(__file__))
FLEET_HOST = os.path.join(HERE, "chaos_fleet_host.py")


def _post(port, path, body, headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body.encode(),
        method="POST", headers=dict({"Content-Type": "text/plain"},
                                    **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _get(port, path, timeout=30):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ------------------------------------------------- satellite: jitter


def test_retry_after_jitter_bounds_and_varies():
    """503 Retry-After carries jitter so a fleet-wide shed does not
    teach every client the same retry instant (satellite pin)."""
    from code2vec_tpu.serving.admission import retry_after_seconds

    values = {retry_after_seconds(4.0) for _ in range(200)}
    assert all(4 <= v <= 6 for v in values), values  # ceil(4..6)
    assert len(values) >= 2, "no jitter: every client retries at once"
    # floor: never below 1 second, even for tiny bases
    assert all(retry_after_seconds(0.0) >= 1 for _ in range(20))
    # jitter disabled -> exact ceil of the base
    assert retry_after_seconds(2.5, jitter_frac=0.0) == 3


# --------------------------------------- satellite: flight retention


def test_flight_dump_retention_deletes_oldest_past_cap(tmp_path):
    from code2vec_tpu.obs.flight import FlightRecorder

    rec = FlightRecorder(capacity=8)
    rec.configure(dump_dir=str(tmp_path), max_dumps=3)
    rec.event("x")
    paths = []
    for i in range(5):
        p = rec.dump(reason=f"r{i}",
                     path=str(tmp_path / f"flight-0000{i}-r{i}.json"))
        os.utime(p, (i, i))  # deterministic mtime order
        paths.append(p)
    left = sorted(f.name for f in tmp_path.glob("flight-*.json"))
    assert len(left) == 3
    # newest kept, oldest deleted
    assert os.path.basename(paths[-1]) in left
    assert os.path.basename(paths[0]) not in left
    # cap 0 = unbounded (the pre-knob behavior)
    rec.configure(max_dumps=0)
    for i in range(5, 8):
        rec.dump(reason=f"r{i}",
                 path=str(tmp_path / f"flight-0000{i}-r{i}.json"))
    assert len(list(tmp_path.glob("flight-*.json"))) == 6


# ------------------------------------------------- quantile helpers


def test_quantile_from_buckets_window_and_edges():
    from code2vec_tpu.serving.telemetry import quantile_from_buckets

    cur = {"0.1": 10.0, "0.5": 90.0, "1": 100.0, "+Inf": 100.0}
    # p95 rank 95 lands in the (0.5, 1] bucket: 0.5 + 0.5 * 5/10
    assert quantile_from_buckets(cur, None, 0.95) == pytest.approx(0.75)
    # windowed: identical prev snapshot -> empty window -> None
    assert quantile_from_buckets(cur, cur, 0.95) is None
    # window with only fast samples since prev
    nxt = {"0.1": 30.0, "0.5": 110.0, "1": 120.0, "+Inf": 120.0}
    assert quantile_from_buckets(nxt, cur, 0.5) <= 0.5
    # quantile in +Inf -> largest finite bound (conservative floor)
    assert quantile_from_buckets(
        {"0.1": 0.0, "+Inf": 10.0}, None, 0.5) == 0.1
    assert quantile_from_buckets({}, None, 0.5) is None


# ------------------------------------------------------ router units


def test_weighted_order_prefers_heavy_drops_zero():
    from code2vec_tpu.serving.fleet.router import weighted_order

    firsts = [weighted_order([(1.0, "a"), (0.05, "b"), (0.0, "c")])[0]
              for _ in range(500)]
    assert firsts.count("a") > 400
    assert "c" not in {x for order in (
        weighted_order([(1.0, "a"), (0.0, "c")]) for _ in range(50))
        for x in order}
    assert weighted_order([]) == []
    assert weighted_order([(0.0, "c")]) == []


class _StubBackendHandler(http.server.BaseHTTPRequestHandler):
    fingerprint = "fp-stub"
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_POST(self):  # noqa: N802
        length = int(self.headers.get("Content-Length", 0))
        self.rfile.read(length)
        body = json.dumps({
            "model_fingerprint": self.fingerprint,
            "seen_model": self.headers.get("X-Model"),
            "seen_deadline": self.headers.get("X-Deadline-Ms"),
            "methods": []}).encode() + b"\n"
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _stub_backend(fingerprint):
    handler = type("H", (_StubBackendHandler,),
                   {"fingerprint": fingerprint})
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


class _StubControl:
    """The duck-typed surface FleetRouter consumes."""

    def __init__(self, candidates):
        self.candidates = candidates  # model -> list OR None

    def hosts_for(self, model):
        return self.candidates.get(model)

    def fleet_view(self):
        return {"hosts": [], "models": {m: {} for m in self.candidates}}

    def merged_fleet_metrics(self):
        return "# empty\n"

    def request_swap(self, payload):
        return 202, {"accepted": True, "payload": payload}

    def request_scale(self, host, n):
        return 200, {"host": host, "desired_replicas": n}

    def drain_host(self, host):
        return 202, {"host": host, "draining": True}


@pytest.fixture()
def router_config():
    return Config(serve=True, serve_host="127.0.0.1",
                  serve_deadline_ms=2000.0, verbose_mode=0)


def _make_router(config, control):
    from code2vec_tpu.serving.fleet.router import FleetRouter
    return FleetRouter(config, control, host="127.0.0.1", port=0,
                       log=lambda m: None)


def test_router_forwards_and_retries_past_dead_host(router_config):
    """A connection-refused candidate is retried on the next host; the
    client sees one healthy answer, trace headers included."""
    backend = _stub_backend("fp-live")
    dead_port = _free_port()
    control = _StubControl({"default": [
        (1.0, "dead", ("127.0.0.1", dead_port)),
        (1.0, "live", ("127.0.0.1", backend.server_address[1]))]})
    router = _make_router(router_config, control)
    try:
        for _ in range(6):  # weighted order is random: hit both orders
            status, body, headers = _post(router.port, "/predict",
                                          "class A { int a(){} }")
            assert status == 200
            payload = json.loads(body)
            assert payload["model_fingerprint"] == "fp-live"
            assert headers["X-Trace-Id"]
            assert headers["traceparent"].split("-")[1] \
                == headers["X-Trace-Id"]
    finally:
        router.close()
        backend.shutdown()


def test_router_retry_honors_remaining_deadline_budget(router_config):
    """Satellite pin: after a black-hole host consumes the budget, the
    retry is NOT dispatched — an honest, prompt 504 with a trace id
    (a retry past the budget can only produce a late 504)."""
    # accepts the TCP handshake, never answers: the first attempt
    # burns the whole X-Deadline-Ms budget
    hole = socket.socket()
    hole.bind(("127.0.0.1", 0))
    hole.listen(1)
    backend = _stub_backend("fp-after-hole")
    control = _StubControl({"default": [
        (1000.0, "hole", ("127.0.0.1", hole.getsockname()[1])),
        (0.001, "live", ("127.0.0.1", backend.server_address[1]))]})
    router = _make_router(router_config, control)
    try:
        t0 = time.perf_counter()
        status, body, headers = _post(
            router.port, "/predict", "class B { int b(){} }",
            headers={"X-Deadline-Ms": "300"})
        elapsed = time.perf_counter() - t0
        # the hole is weight-1000: first virtually always. Either the
        # budget died there (504, no retry) or the rare live-first
        # order answered 200 — never a LATE success and never a hang.
        assert status in (200, 504)
        assert elapsed < 2.0, f"blocked {elapsed:.2f}s on a 300ms budget"
        if status == 504:
            payload = json.loads(body)
            assert "deadline" in payload["error"]
            assert payload["trace_id"] == headers["X-Trace-Id"]
    finally:
        router.close()
        backend.shutdown()
        hole.close()


def test_router_unknown_model_404_no_host_503_with_trace(router_config):
    backend = _stub_backend("fp-m1")
    control = _StubControl({
        "m1": [(1.0, "h", ("127.0.0.1", backend.server_address[1]))],
        "empty": []})
    router = _make_router(router_config, control)
    try:
        status, body, headers = _post(router.port, "/predict", "x",
                                      headers={"X-Model": "nope"})
        assert status == 404
        assert json.loads(body)["trace_id"] == headers["X-Trace-Id"]
        status, body, headers = _post(router.port, "/predict", "x",
                                      headers={"X-Model": "empty"})
        assert status == 503
        assert json.loads(body)["trace_id"] == headers["X-Trace-Id"]
        assert int(headers["Retry-After"]) >= 1
        # default model group absent in this control -> 404 too
        status, _, _ = _post(router.port, "/predict", "x")
        assert status == 404
    finally:
        router.close()
        backend.shutdown()


def test_router_multi_model_isolation_and_inbound_trace(router_config):
    """X-Model keys the host group; a request can only reach a host
    mounting its model (structural cross-model isolation), and an
    inbound traceparent survives the hop."""
    b1, b2 = _stub_backend("fp-m1"), _stub_backend("fp-m2")
    control = _StubControl({
        "m1": [(1.0, "h1", ("127.0.0.1", b1.server_address[1]))],
        "m2": [(1.0, "h2", ("127.0.0.1", b2.server_address[1]))]})
    router = _make_router(router_config, control)
    try:
        for model, fp in (("m1", "fp-m1"), ("m2", "fp-m2")):
            inbound = "ab" * 16
            status, body, headers = _post(
                router.port, "/predict", "class C { int c(){} }",
                headers={"X-Model": model,
                         "traceparent": f"00-{inbound}-{'cd' * 8}-01"})
            assert status == 200
            payload = json.loads(body)
            assert payload["model_fingerprint"] == fp
            assert payload["seen_model"] == model
            assert headers["X-Trace-Id"] == inbound
        # admin verbs dispatch to the control plane, not a host
        status, body, _ = _post(
            router.port, "/admin/scale",
            json.dumps({"host": "h1", "replicas": 3}),
            headers={"Content-Type": "application/json"})
        assert status == 200
        assert json.loads(body)["desired_replicas"] == 3
        status, body, _ = _post(
            router.port, "/admin/drain", json.dumps({"host": "h2"}),
            headers={"Content-Type": "application/json"})
        assert status == 202
        status, _, _ = _post(router.port, "/admin/reload",
                             json.dumps({"artifact": "/a"}),
                             headers={"Content-Type":
                                      "application/json"})
        assert status == 202
        # /fleet + /healthz answered locally
        assert _get(router.port, "/fleet")[0] == 200
        hz = json.loads(_get(router.port, "/healthz")[1])
        assert hz["status"] == "routing"
    finally:
        router.close()
        b1.shutdown()
        b2.shutdown()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------- scaling policy


def _scale_config(**overrides):
    kwargs = dict(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_poll_interval_s=0.2, fleet_scale_min=1, fleet_scale_max=4,
        fleet_scale_up_shed_rate=0.05, fleet_scale_up_ticks=2,
        fleet_scale_down_ticks=3, fleet_scale_cooldown_s=0.0,
        fleet_models="default=/tmp/none")
    kwargs.update(overrides)
    return Config(**kwargs)


def _policy_control(tmp_path, config):
    from code2vec_tpu.serving.fleet.control import (
        ControlPlane, HostSpec,
    )
    config.heartbeat_file = str(tmp_path / "fleet.heartbeat.json")
    control = ControlPlane(
        config, [HostSpec("h0", ["true"])], log=lambda m: None)
    host = control.hosts[0]
    host.state, host.weight = "healthy", 1.0
    posts = []
    control._post = lambda h, path, payload, timeout=10.0: (
        posts.append((h.id, path, payload)) or (True, "{}"))
    return control, host, posts


def _view(requests, sheds, desired=2):
    return {"desired_replicas": desired,
            "replicas": [{"requests_total": requests,
                          "requests_shed_total": sheds}]}


def _scale_metrics(requests, sheds):
    """The host /metrics slice the autoscaler's tsdb window reads."""
    return (
        "# TYPE serving_requests_total counter\n"
        f'serving_requests_total{{endpoint="/predict",status="200"}}'
        f" {requests}\n"
        "# TYPE serving_requests_shed_total counter\n"
        f"serving_requests_shed_total {sheds}\n")


def _scale_ticker(control, host, now):
    def tick(requests, sheds):
        host.view = _view(requests, sheds)
        control.tsdb.append(
            {f"host:{host.id}": _scale_metrics(requests, sheds)},
            now=now[0])
        control._scale_tick(host, now[0])
        now[0] += 1.0
    return tick


def test_scale_up_needs_consecutive_ticks_and_respects_max(tmp_path):
    config = _scale_config()
    control, host, posts = _policy_control(tmp_path, config)
    tick = _scale_ticker(control, host, [100.0])

    tick(100, 0)        # seed the window
    tick(200, 50)       # shed_rate 0.5 -> up_tick 1: hysteresis holds
    assert posts == []
    tick(300, 100)      # up_tick 2 -> scale up 2 -> 3
    assert posts == [("h0", "/admin/scale", {"replicas": 3})]
    tick(400, 150)
    tick(500, 200)      # two more bad ticks -> 3 -> 4 (the max)
    assert posts[-1] == ("h0", "/admin/scale", {"replicas": 4})
    tick(600, 250)
    tick(700, 300)      # at fleet_scale_max: no further action
    assert len(posts) == 2


def test_scale_up_blocked_by_cooldown_then_idle_scales_down(tmp_path):
    config = _scale_config(fleet_scale_cooldown_s=3600.0)
    control, host, posts = _policy_control(tmp_path, config)
    tick = _scale_ticker(control, host, [100.0])

    tick(100, 0)
    tick(200, 50)
    tick(300, 100)      # action + cooldown armed
    assert len(posts) == 1
    tick(400, 150)
    tick(500, 200)      # over threshold again, but inside cooldown
    assert len(posts) == 1
    host.cooldown_until = 0.0
    # sustained idle (zero new requests) for fleet_scale_down_ticks
    tick(500, 200)
    tick(500, 200)
    assert len(posts) == 1  # hysteresis: 2 idle ticks < 3
    tick(500, 200)
    assert posts[-1] == ("h0", "/admin/scale", {"replicas": 2})
    # floor: drive down to min=1, then idle forever stays at 1
    host.cooldown_until = 0.0
    host.desired_replicas = 1
    for _ in range(5):
        tick(500, 200)
    assert posts[-1][2] == {"replicas": 2}  # no action below the floor


def test_scale_window_survives_replica_restart(tmp_path):
    """A replica restart zeroes its counters mid-window. The tsdb's
    reset-aware increase (telemetry.counter_delta) reads the
    post-restart values as growth IN FULL — never a negative delta,
    never a phantom idle tick, and no lost decision tick."""
    config = _scale_config()
    control, host, posts = _policy_control(tmp_path, config)
    tick = _scale_ticker(control, host, [100.0])
    tick(1000, 0)
    tick(50, 10)   # counters went BACKWARD (restart)
    # 50 post-restart requests, 10 shed -> a real over-threshold tick
    assert host.idle_ticks == 0 and host.up_ticks == 1
    assert posts == []  # hysteresis still holds at 1 tick
    # and the boot tick itself never reads as idle
    control2, host2, posts2 = _policy_control(tmp_path / "b", config)
    tick2 = _scale_ticker(control2, host2, [100.0])
    tick2(100, 0)
    assert host2.idle_ticks == 0 and posts2 == []


# ------------------------------------------------ swap driver (stub)


class _SwapHost:
    def __init__(self, host_id, fail_targets=()):
        self.id = host_id
        self.fail_targets = set(fail_targets)
        self.fingerprint = "fp-v1"
        self.swap_state = "idle"
        self.swap_target = None
        self.reloads = []

    def apply_reload(self, artifact):
        self.reloads.append(artifact)
        self.swap_target = artifact
        name = os.path.basename(artifact)
        if name in self.fail_targets:
            self.swap_state = "failed"
        else:
            self.fingerprint = f"fp-{name}"
            self.swap_state = "ready"


class _SwapControl:
    def __init__(self, hosts, rollback="v1"):
        class _Cfg:
            fleet_swap_timeout_s = 3.0
        self.config = _Cfg()
        self.hosts = hosts
        self._rollback = rollback
        self.committed_artifact = None
        self.flight = obs.default_flight_recorder()
        self.log = lambda m: None

    def swap_hosts(self, model):
        return list(self.hosts) if model == "default" else None

    def host_reload(self, host, artifact, retrieval_index=None,
                    traceparent=None):
        host.apply_reload(artifact)
        host.retrieval_index = retrieval_index
        host.reload_traceparent = traceparent
        return True, ""

    def host_fleet(self, host):
        return {"replicas": [
            {"model_fingerprint": host.fingerprint,
             "swap_state": host.swap_state,
             "swap_target": host.swap_target, "draining": False}
            for _ in range(2)]}

    def rollback_target(self, model):
        return self._rollback

    def set_artifact(self, model, artifact, retrieval_index=None):
        self.committed_artifact = artifact
        self.committed_retrieval_index = retrieval_index


def _run_swap(driver, artifact, **kw):
    driver.request(artifact, **kw)
    deadline = time.time() + 15
    while driver.status()["state"] in ("canary", "rolling",
                                       "rolling_back"):
        if time.time() > deadline:
            raise AssertionError(f"swap wedged: {driver.status()}")
        time.sleep(0.02)
    return driver.status()


def test_fleet_swap_canary_first_commit(tmp_path):
    from code2vec_tpu.serving.fleet.swap import (
        FleetSwapBusy, FleetSwapDriver,
    )

    h0, h1 = _SwapHost("h0"), _SwapHost("h1")
    control = _SwapControl([h0, h1])
    driver = FleetSwapDriver(control, poll_interval_s=0.01)
    status = _run_swap(driver, "/artifacts/v2")
    assert status["state"] == "committed"
    assert status["target_fingerprint"] == "fp-v2"
    assert [h["outcome"] for h in status["hosts"]] == ["committed"] * 2
    # canary-first: h0 swapped strictly before h1
    assert h0.reloads == ["/artifacts/v2"] and h1.reloads == \
        ["/artifacts/v2"]
    assert control.committed_artifact == "/artifacts/v2"
    assert h0.fingerprint == h1.fingerprint == "fp-v2"
    # busy conflict is a 409-shaped error
    driver._worker = threading.Thread(target=time.sleep, args=(0.3,))
    driver._worker.start()
    with pytest.raises(FleetSwapBusy, match="in flight"):
        driver.request("/artifacts/v3")


def test_fleet_swap_canary_failure_halts_untouched(tmp_path):
    from code2vec_tpu.serving.fleet.swap import FleetSwapDriver

    h0, h1 = _SwapHost("h0", fail_targets={"bad"}), _SwapHost("h1")
    control = _SwapControl([h0, h1])
    driver = FleetSwapDriver(control, poll_interval_s=0.01)
    status = _run_swap(driver, "/artifacts/bad")
    assert status["state"] == "failed"
    assert "canary" in status["error"]
    # halt-and-report: the non-canary host was NEVER touched
    assert h1.reloads == []
    assert h1.fingerprint == "fp-v1"
    assert control.committed_artifact is None


def test_fleet_swap_post_canary_failure_rolls_back_fleet(tmp_path):
    from code2vec_tpu.serving.fleet.swap import FleetSwapDriver

    h0, h1 = _SwapHost("h0"), _SwapHost("h1", fail_targets={"v2"})
    control = _SwapControl([h0, h1], rollback="/artifacts/v1")
    driver = FleetSwapDriver(control, poll_interval_s=0.01)
    status = _run_swap(driver, "/artifacts/v2")
    assert status["state"] == "rolled_back"
    # the canary committed v2, then was rolled back to v1 — the fleet
    # converges on ONE fingerprint instead of staying mixed
    assert h0.reloads == ["/artifacts/v2", "/artifacts/v1"]
    assert h1.reloads == ["/artifacts/v2", "/artifacts/v1"]
    assert h0.fingerprint == h1.fingerprint == "fp-v1"
    outcomes = {h["host"]: h["outcome"] for h in status["hosts"]
                if "rolled_back" in h["outcome"]}
    assert set(outcomes) == {"h0", "h1"}
    # no rollback target -> halt-and-report instead
    h0b, h1b = _SwapHost("h0"), _SwapHost("h1", fail_targets={"v2"})
    control2 = _SwapControl([h0b, h1b], rollback=None)
    driver2 = FleetSwapDriver(control2, poll_interval_s=0.01)
    status2 = _run_swap(driver2, "/artifacts/v2")
    assert status2["state"] == "failed"
    assert "rollback" in status2["error"]


# ------------------------------------------------- telemetry verbs


def test_telemetry_server_post_handlers_dispatch_and_400():
    from code2vec_tpu.serving.telemetry import TelemetryServer

    seen = []

    def scale(payload):
        if "replicas" not in payload:
            raise ValueError("missing replicas")
        seen.append(payload)
        return 200, {"ok": True}

    srv = TelemetryServer(lambda: "# m\n", lambda: {},
                          post_handlers={"/admin/scale": scale})
    try:
        status, body, _ = _post(srv.port, "/admin/scale",
                                json.dumps({"replicas": 3}),
                                headers={"Content-Type":
                                         "application/json"})
        assert status == 200 and json.loads(body)["ok"]
        assert seen == [{"replicas": 3}]
        assert _post(srv.port, "/admin/scale", "{}")[0] == 400
        assert _post(srv.port, "/admin/scale", "{nope")[0] == 400
        assert _post(srv.port, "/admin/nope", "{}")[0] == 404
        # GETs still serve
        assert _get(srv.port, "/metrics")[0] == 200
    finally:
        srv.close()


# --------------------------------------------------------- CLI seam


def test_fleet_cli_flags_parse_and_verify():
    from code2vec_tpu.cli import config_from_args

    config = config_from_args([
        "fleet", "--fleet_models", "stable=/a,canary=/b",
        "--fleet_hosts", "3", "--fleet_port", "0",
        "--fleet_poll_interval", "0.5",
        "--fleet_scale_min", "1", "--fleet_scale_max", "6",
        "--fleet_scale_up_shed_rate", "0.1",
        "--fleet_scale_up_p95_ms", "250",
        "--fleet_scale_up_ticks", "3", "--fleet_scale_down_ticks", "8",
        "--fleet_scale_cooldown", "30", "--fleet_swap_timeout", "90",
        "--fleet_max_host_restarts", "2",
        "--serve_flight_max_dumps", "16"])
    assert config.fleet and config.serve
    assert config.fleet_hosts == 3
    assert config.fleet_models == "stable=/a,canary=/b"
    assert config.fleet_scale_max == 6
    assert config.fleet_scale_up_p95_ms == 250
    assert config.fleet_swap_timeout_s == 90
    assert config.serve_flight_max_dumps == 16
    config.verify()  # fleet_models carries the models: no --load needed

    bad = config_from_args(["fleet", "--fleet_models", "oops"])
    with pytest.raises(ValueError, match="fleet_models"):
        bad.verify()
    inverted = config_from_args([
        "fleet", "--artifact", "/a", "--fleet_scale_min", "3",
        "--fleet_scale_max", "2"])
    with pytest.raises(ValueError, match="fleet_scale_max"):
        inverted.verify()


def test_host_base_command_strips_fleet_flags():
    from code2vec_tpu.serving.fleet.control import _host_base_command

    cmd = _host_base_command(
        ["fleet", "--artifact", "/a", "--fleet_hosts", "2",
         "--fleet_models", "m=/x", "--replicas", "2",
         "--serve_port", "9000", "--heartbeat_file", "/tmp/hb"],
        strip_artifact=True)
    tail = cmd[3:]
    assert tail[0] == "serve"
    assert "--fleet_hosts" not in tail and "--fleet_models" not in tail
    assert "--serve_port" not in tail and "--heartbeat_file" not in tail
    assert "--artifact" not in tail
    assert tail[tail.index("--replicas") + 1] == "2"


# ---------------------------------------------- chaos drills (slow)


@pytest.fixture()
def fake_extractor(tmp_path, monkeypatch):
    path = tmp_path / "fake-c2v-extract"
    path.write_text(FAKE_EXTRACTOR)
    path.chmod(0o755)
    monkeypatch.setenv("C2V_NATIVE_EXTRACTOR", str(path))
    monkeypatch.delenv("C2V_FAKE_NO_SERVER", raising=False)
    return str(path)


def _write_json(tmp_path, name, payload):
    path = tmp_path / name
    path.write_text(json.dumps(payload))
    return str(path)


def _replica_overrides(**extra):
    overrides = dict(
        serve_host="127.0.0.1", max_contexts=16, serve_batch_size=4,
        serve_buckets="4,8",
        serve_cache_entries=0, extractor_pool_size=1,
        serve_drain_timeout_s=5.0, serve_heartbeat_interval_s=0.2,
        serve_deadline_ms=3000.0)
    overrides.update(extra)
    return overrides


def _host_overrides(**extra):
    overrides = dict(
        serve_host="127.0.0.1", serve_port=0, serve_telemetry_port=0,
        serve_replicas=2, serve_max_restarts=5,
        serve_heartbeat_interval_s=0.2, serve_drain_timeout_s=5.0)
    overrides.update(extra)
    return overrides


def _fleet_config(tmp_path, **overrides):
    kwargs = dict(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_hosts=2, fleet_poll_interval_s=0.25,
        fleet_max_host_restarts=5, fleet_swap_timeout_s=30.0,
        serve_drain_timeout_s=6.0,
        # the drills assert on deterministic replica sets: keep the
        # autoscaler from draining idle replicas mid-drill (the policy
        # has its own unit tests above)
        fleet_scale_down_ticks=1000000, fleet_scale_up_shed_rate=1.0,
        heartbeat_file=str(tmp_path / "fleet.heartbeat.json"))
    kwargs.update(overrides)
    return Config(**kwargs)


@pytest.fixture()
def run_fleet(tmp_path, fake_extractor):
    """Factory: ControlPlane + FleetRouter over real Supervisor host
    subprocesses running fake-model replicas; torn down at test end."""
    from code2vec_tpu.serving.fleet.control import (
        ControlPlane, HostSpec,
    )
    from code2vec_tpu.serving.fleet.router import FleetRouter

    running = []

    def start(config, host_specs, artifacts=None):
        control = ControlPlane(config, host_specs, log=lambda m: None)
        for model, artifact in (artifacts or {}).items():
            control.set_initial_artifact(model, artifact)
        control.router = FleetRouter(config, control, host="127.0.0.1",
                                     port=0, log=lambda m: None)
        rc_holder = {}
        thread = threading.Thread(
            target=lambda: rc_holder.update(rc=control.run()),
            daemon=True)
        thread.start()
        running.append((control, thread))
        return control, thread, rc_holder

    yield start
    for control, thread in running:
        control.stop()
        thread.join(timeout=60)


def _wait_fleet(control, predicate, timeout=45.0, what="condition"):
    deadline = time.time() + timeout
    view = None
    while time.time() < deadline:
        view = control.fleet_view()
        if predicate(view):
            return view
        time.sleep(0.1)
    raise AssertionError(f"fleet never reached {what}; last={view}")


def _all_routable(n):
    # readiness = every host routable AND at least one replica per
    # host has written a "serving" heartbeat (under SO_REUSEPORT a
    # replica's port is assigned at spawn, BEFORE the child binds)
    def ready(view):
        hosts = [h for h in view["hosts"] if h["weight"] > 0]
        if len(hosts) < n:
            return False
        for h in hosts:
            replicas = (h.get("replicas_serving") or 0)
            if replicas < 1:
                return False
        return True
    return ready


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_host_kill_under_load_converges_and_readmits(
        tmp_path, fake_extractor, run_fleet):
    """THE fleet chaos drill (ROADMAP acceptance): SIGKILL one entire
    host (supervisor + its replicas) under concurrent overload across
    2 hosts x 2 replicas. Every client failure is an honest shed
    (503/504, valid JSON, trace id in body and header), zero malformed
    or cross-fingerprint responses, the router converges onto the
    survivor, and the killed host's capacity is re-admitted after the
    control plane restarts it."""
    replica_cfg = _write_json(
        tmp_path, "replica.json",
        _replica_overrides(fingerprint="fp-drill",
                           serve_queue_depth=2))
    host_cmd = [sys.executable, FLEET_HOST,
                _write_json(tmp_path, "host.json", _host_overrides()),
                replica_cfg]
    from code2vec_tpu.serving.fleet.control import HostSpec
    config = _fleet_config(tmp_path)
    control, thread, rc_holder = run_fleet(
        config, [HostSpec("default-0", host_cmd),
                 HostSpec("default-1", host_cmd)])
    _wait_fleet(control, _all_routable(2), what="2 routable hosts")
    port = control.router.port

    malformed, responses = [], []
    lock = threading.Lock()
    stop_load = threading.Event()

    def load(ci):
        i = 0
        while not stop_load.is_set():
            try:
                status, body, headers = _post(
                    port, "/predict",
                    f"class K{ci}x{i} {{ int m{ci}x{i}() "
                    f"{{ return 1; }} }}", timeout=30)
            except Exception as e:  # noqa: BLE001 — a torn TCP conn is
                # a client-side retry, not a corrupt response
                with lock:
                    responses.append(("conn_error", str(e)))
                i += 1
                continue
            try:
                payload = json.loads(body)
                if status == 200:
                    ok = (payload.get("model_fingerprint") == "fp-drill"
                          and "methods" in payload)
                else:
                    ok = (status in (503, 504)
                          and payload.get("trace_id")
                          and payload["trace_id"]
                          == headers.get("X-Trace-Id"))
                if not ok:
                    raise ValueError(f"dishonest: {status} {payload}")
            except ValueError as e:
                with lock:
                    malformed.append((status, body[:200], str(e)))
            with lock:
                responses.append((status, None))
            i += 1

    threads = [threading.Thread(target=load, args=(ci,))
               for ci in range(6)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.0)
        # kill the WHOLE host: supervisor first, then its replicas
        victim = control.hosts[0]
        victim_pid = victim.proc.pid
        hb = victim.heartbeat()
        replica_pids = [r["pid"] for r in hb["replicas"] if r["pid"]]
        os.kill(victim_pid, signal.SIGKILL)
        for pid in replica_pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        # convergence: the control plane restarts the host (new pid)
        # and its capacity is re-admitted into routing
        _wait_fleet(
            control,
            lambda v: (v["hosts"][0]["pid"] not in (None, victim_pid)
                       and v["hosts"][0]["weight"] > 0
                       and v["hosts"][0]["restarts"] >= 1
                       and (v["hosts"][0]["replica_count"] or 0) >= 2),
            timeout=60, what="killed host restarted + re-admitted")
        time.sleep(1.0)  # post-recovery traffic through both hosts
    finally:
        stop_load.set()
        for t in threads:
            t.join(timeout=30)
    assert not malformed, f"dishonest responses: {malformed[:3]}"
    statuses = [s for s, _ in responses]
    assert statuses.count(200) > 0, "no successes at all"
    # a fresh request through the recovered fleet succeeds
    status, body, _ = _post(port, "/predict",
                            "class Z { int after() { return 1; } }")
    assert status == 200
    assert json.loads(body)["model_fingerprint"] == "fp-drill"
    assert _counter_value("fleet_host_restarts_total") >= 1
    # coordinated shutdown: router drains, hosts drain, rc 0
    control.stop()
    thread.join(timeout=60)
    assert rc_holder["rc"] == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_canary_swap_commits_then_rolls_back_on_host_failure(
        tmp_path, fake_extractor, run_fleet):
    """Fleet-wide coordinated hot-swap drill (ROADMAP acceptance):
    (1) canary-first rollout lands ONE new fingerprint on every
    replica of every host; (2) a rollout where a non-canary host's
    replicas reject the candidate rolls the WHOLE fleet back to the
    previous artifact — never a permanently mixed fleet."""
    from code2vec_tpu.serving.fleet.control import HostSpec

    # host 1's replicas fail validation for artifact basename "v3"
    ok_replicas = _write_json(
        tmp_path, "replica-ok.json",
        _replica_overrides(fingerprint="fp-v1", fake_swap=True))
    failing_replicas = _write_json(
        tmp_path, "replica-fail-v3.json",
        _replica_overrides(fingerprint="fp-v1", fake_swap=True,
                           swap_fail_targets=["v3"]))
    host_json = _write_json(tmp_path, "host.json", _host_overrides())
    config = _fleet_config(tmp_path)
    control, thread, rc_holder = run_fleet(
        config,
        [HostSpec("default-0",
                  [sys.executable, FLEET_HOST, host_json, ok_replicas]),
         HostSpec("default-1",
                  [sys.executable, FLEET_HOST, host_json,
                   failing_replicas])],
        artifacts={"default": "/artifacts/v1"})
    _wait_fleet(control, _all_routable(2), what="2 routable hosts")
    port = control.router.port

    def fleet_fingerprints(view):
        return view["models"]["default"]["fingerprints"]

    # ---- rollout 1: clean canary-first commit to v2
    status, body, _ = _post(port, "/admin/reload",
                            json.dumps({"artifact": "/artifacts/v2"}),
                            headers={"Content-Type":
                                     "application/json"})
    assert status == 202
    view = _wait_fleet(
        control, lambda v: v["swap"]["state"] == "committed",
        what="swap committed")
    assert view["swap"]["target_fingerprint"] == "fp-v2"
    # canary strictly first in the outcome order
    assert [h["host"] for h in view["swap"]["hosts"]] == \
        ["default-0", "default-1"]
    view = _wait_fleet(
        control,
        lambda v: fleet_fingerprints(v) == ["fp-v2"]
        and not v["models"]["default"]["mixed_fingerprints"],
        what="every replica on fp-v2")
    # every replica of every host landed the new fingerprint
    for host in view["hosts"]:
        assert host["fingerprints"] == ["fp-v2"], host
    assert view["models"]["default"]["artifact"] == "/artifacts/v2"
    # a 409 while nothing is in flight would be a bug: re-assert idle
    # behavior via a second no-op check of status below

    # ---- rollout 2: host 1 rejects v3 -> fleet-wide rollback to v2
    status, _, _ = _post(port, "/admin/reload",
                         json.dumps({"artifact": "/artifacts/v3"}),
                         headers={"Content-Type": "application/json"})
    assert status == 202
    view = _wait_fleet(
        control, lambda v: v["swap"]["state"] == "rolled_back",
        timeout=90, what="swap rolled back")
    assert "default-1" in view["swap"]["error"]
    view = _wait_fleet(
        control, lambda v: fleet_fingerprints(v) == ["fp-v2"],
        what="fleet back on fp-v2 after rollback")
    assert not view["models"]["default"]["mixed_fingerprints"]
    assert view["models"]["default"]["artifact"] == "/artifacts/v2"
    # live traffic still serves the rolled-back weights, honestly
    status, body, _ = _post(port, "/predict",
                            "class R { int rb() { return 1; } }")
    assert status == 200
    assert json.loads(body)["model_fingerprint"] == "fp-v2"
    control.stop()
    thread.join(timeout=60)
    assert rc_holder["rc"] == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_multi_model_groups_and_host_scale_e2e(
        tmp_path, fake_extractor, run_fleet):
    """Multi-model fleet: X-Model routes to the right group's weights
    (zero cross-model responses by construction, asserted on the
    fingerprint), unknown models 404, and a manual /admin/scale
    resizes one host's replica set live (up, then drained back
    down)."""
    from code2vec_tpu.serving.fleet.control import HostSpec

    host_json = _write_json(tmp_path, "host.json",
                            _host_overrides(serve_replicas=1))
    specs, artifacts = [], {}
    for model in ("stable", "exp"):
        replicas = _write_json(
            tmp_path, f"replica-{model}.json",
            _replica_overrides(fingerprint=f"fp-{model}"))
        specs.append(HostSpec(
            f"{model}-0",
            [sys.executable, FLEET_HOST, host_json, replicas],
            model=model))
        artifacts[model] = f"/artifacts/{model}"
    config = _fleet_config(tmp_path, fleet_hosts=1,
                           fleet_models="stable=/a,exp=/b")
    control, thread, rc_holder = run_fleet(config, specs,
                                           artifacts=artifacts)
    _wait_fleet(control, _all_routable(2), what="both model hosts up")
    port = control.router.port
    for model in ("stable", "exp"):
        for i in range(3):
            status, body, _ = _post(
                port, "/predict",
                f"class M{i} {{ int m{i}() {{ return 1; }} }}",
                headers={"X-Model": model})
            assert status == 200
            assert json.loads(body)["model_fingerprint"] == \
                f"fp-{model}", f"cross-model response for {model}"
    assert _post(port, "/predict", "x",
                 headers={"X-Model": "nope"})[0] == 404
    # manual scale override: 1 -> 2 replicas on the stable host
    status, _, _ = _post(port, "/admin/scale",
                         json.dumps({"host": "stable-0",
                                     "replicas": 2}),
                         headers={"Content-Type": "application/json"})
    assert status == 200
    _wait_fleet(
        control,
        lambda v: next(h for h in v["hosts"]
                       if h["host"] == "stable-0")["replica_count"]
        == 2,
        what="stable-0 scaled to 2 replicas")
    # and back down: the retired replica drains, count returns to 1
    status, _, _ = _post(port, "/admin/scale",
                         json.dumps({"host": "stable-0",
                                     "replicas": 1}),
                         headers={"Content-Type": "application/json"})
    assert status == 200
    _wait_fleet(
        control,
        lambda v: next(h for h in v["hosts"]
                       if h["host"] == "stable-0")["replica_count"]
        == 1,
        what="stable-0 drained back to 1 replica")
    # fleet-wide merged metrics include both hosts' counters
    status, body = _get(port, "/metrics")
    assert status == 200
    from code2vec_tpu.serving import telemetry
    assert telemetry.sum_family(body.decode(),
                                "serving_requests_total") >= 6
    control.stop()
    thread.join(timeout=60)
    assert rc_holder["rc"] == 0


# --------------------- shared forwarding core (serving/forwarding.py)


class _FakeDeadline:
    def __init__(self, remaining_values, bounded=True):
        self._vals = list(remaining_values)
        self.bounded = bounded

    def remaining(self):
        return self._vals.pop(0) if self._vals else 0.0


class _FakeSpan:
    def __init__(self, attrs):
        self.attrs = attrs

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _FakeTrace:
    trace_id = "f" * 32

    def traceparent(self):
        return f"00-{self.trace_id}-{'b' * 16}-01"

    def span(self, name, **attrs):
        return _FakeSpan(attrs)


def _run_forward(targets, deadline=None, **kw):
    from code2vec_tpu.serving.forwarding import forward_with_retry
    replies = []
    outcomes = []
    forward_with_retry(
        method="POST", path="/predict", body=b"x",
        fwd_headers={}, targets=targets,
        deadline=deadline or _FakeDeadline([10.0] * 8),
        trace=_FakeTrace(),
        reply=lambda *a: replies.append(a),
        what="replicas", unreachable_error="all replicas unreachable",
        on_outcome=outcomes.append, **kw)
    assert len(replies) == 1, "reply must be called exactly once"
    return replies[0], outcomes


def test_forwarding_relays_backend_and_stamps_trace():
    srv = _stub_backend("fp-fwd")
    port = srv.server_address[1]
    try:
        (code, payload, headers, ctype), outcomes = _run_forward(
            [("b", "127.0.0.1", port)])
        assert code == 200 and outcomes == ["forwarded"]
        assert headers["X-Trace-Id"]  # stamped even when backend lacks it
        assert json.loads(payload)["model_fingerprint"] == "fp-fwd"
    finally:
        srv.shutdown()


def test_forwarding_retries_dead_then_succeeds_and_counts():
    srv = _stub_backend("fp-retry")
    port = srv.server_address[1]
    dead = _free_port()

    class _Ctr:
        n = 0

        def inc(self):
            self.n += 1

    ctr = _Ctr()
    try:
        (code, _, _, _), outcomes = _run_forward(
            [("dead", "127.0.0.1", dead), ("live", "127.0.0.1", port)],
            retry_counter=ctr)
        assert code == 200 and outcomes == ["forwarded"]
        assert ctr.n == 1
    finally:
        srv.shutdown()


def test_forwarding_expired_budget_is_honest_504():
    dead = _free_port()
    (code, payload, headers, _), outcomes = _run_forward(
        [("d1", "127.0.0.1", dead), ("d2", "127.0.0.1", dead)],
        deadline=_FakeDeadline([0.5, 0.0]))
    assert code == 504 and outcomes == ["expired"]
    body = json.loads(payload)
    assert "deadline exhausted retrying replicas" in body["error"]
    assert body["trace_id"] == _FakeTrace.trace_id
    assert headers["X-Trace-Id"] == _FakeTrace.trace_id


def test_forwarding_all_unreachable_503_with_retry_after():
    dead = _free_port()
    (code, payload, headers, _), outcomes = _run_forward(
        [("d1", "127.0.0.1", dead)], retry_after="1.2")
    assert code == 503 and outcomes == ["unreachable"]
    assert "all replicas unreachable" in json.loads(payload)["error"]
    assert headers["Retry-After"] == "1.2"
    assert headers["traceparent"].startswith("00-" + _FakeTrace.trace_id)


def test_handle_admin_post_error_mapping():
    from code2vec_tpu.serving.forwarding import handle_admin_post

    class _Handler:
        headers = {"Content-Length": "2"}

        class rfile:
            @staticmethod
            def read(n):
                return b"{}"

    out = []

    def run(dispatch, **kw):
        out.clear()
        handle_admin_post(_Handler(), dispatch,
                          lambda code, body: out.append((code, body)),
                          **kw)
        return out[0]

    assert run(lambda p: (200, {"ok": True})) == (200, {"ok": True})
    code, body = run(lambda p: (_ for _ in ()).throw(
        ValueError("bad knob")))
    assert code == 400 and "bad knob" in body["error"]
    # "in flight" ValueError -> 409 only when the caller opts in
    code, _ = run(lambda p: (_ for _ in ()).throw(
        ValueError("a swap is already in flight")), conflict_409=True)
    assert code == 409
    code, _ = run(lambda p: (_ for _ in ()).throw(
        ValueError("a swap is already in flight")))
    assert code == 400
    # KeyError -> 404 naming the host only when the caller opts in
    code, body = run(lambda p: (_ for _ in ()).throw(KeyError("h7")),
                     keyerror_is_missing_host=True)
    assert code == 404 and "h7" in body["error"]
    code, _ = run(lambda p: (_ for _ in ()).throw(KeyError("h7")))
    assert code == 500
    # anything else -> 500 as an HTTP error, never a torn connection
    code, body = run(lambda p: (_ for _ in ()).throw(
        RuntimeError("boom")))
    assert code == 500 and "RuntimeError" in body["error"]
