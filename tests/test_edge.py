"""Edge-tier suite (code2vec_tpu/serving/fleet/edge.py + the router's
consistent-hash cache affinity + the remote HostLauncher seam):

- affinity ring laws (determinism, balance, minimal disruption) and
  the cache INVARIANTS affinity must preserve — byte-equality of
  responses whichever host answers, and fingerprint-keying across a
  hot-swap (a stale-fingerprint cache entry can never serve) — pinned
  against scripted 2-host backends running the real cache_key;
- SharedFleetView: candidate derivation from a polled /fleet snapshot,
  honest no-view/unknown-model semantics, admin relay with status
  pass-through (including 409);
- RemoteHostLauncher: {address} substitution, env filtering + shell
  quoting, and launch failure mapping onto the EXISTING host_down ->
  backoff -> host_escalation incident path;
- the (artifact, retrieval_index) PAIR a (re)spawned host reconciles
  onto (PR-15 residue);
- slow chaos drills: SIGKILL one of 2 router processes under 4-client
  load (zero failed requests — survivors absorb, control plane
  respawns), and a fleet-wide coordinated swap with N routers live
  whose killed host converges back onto the committed pair.

Fast tests run in tier-1; the drills are `slow` + `chaos` and run via
scripts/run_chaos.sh under EDGE_BUDGET.
"""

import http.server
import json
import os
import signal
import sys
import threading
import time

import pytest

from code2vec_tpu.config import Config

from test_serving import _counter_value
from test_fleet import (  # noqa: F401 — fake_extractor is a fixture
    FLEET_HOST, _all_routable, _fleet_config, _free_port, _get,
    _host_overrides, _post, _replica_overrides, _wait_fleet,
    _write_json, fake_extractor,
)

pytestmark = pytest.mark.edge

HERE = os.path.dirname(os.path.abspath(__file__))


def _router_test_config(**overrides):
    kwargs = dict(serve=True, serve_host="127.0.0.1",
                  serve_deadline_ms=2000.0, verbose_mode=0)
    kwargs.update(overrides)
    return Config(**kwargs)


# ------------------------------------------------- affinity ring laws


def test_affinity_ring_deterministic_and_balanced():
    from code2vec_tpu.serving.fleet.router import (
        AFFINITY_VNODES, affinity_host, affinity_ring,
    )

    hosts = ["default-0", "default-1", "default-2"]
    ring = affinity_ring(hosts)
    # order-independent and deterministic (no per-process salt: every
    # router in the tier must agree on the preferred host)
    assert ring == affinity_ring(list(reversed(hosts)))
    assert len(ring) == len(hosts) * AFFINITY_VNODES
    counts = {h: 0 for h in hosts}
    for i in range(3000):
        counts[affinity_host(f"key-{i}".encode(), ring)] += 1
    # vnodes keep the split rough-thirds, not exact — assert no host
    # owns a pathological share
    assert min(counts.values()) > 3000 / len(hosts) * 0.5, counts
    assert max(counts.values()) < 3000 / len(hosts) * 1.5, counts
    # stable per key
    assert (affinity_host(b"class A {}", ring)
            == affinity_host(b"class A {}", ring))
    assert affinity_host(b"anything", []) is None


def test_affinity_ring_removal_remaps_only_the_lost_hosts_keys():
    from code2vec_tpu.serving.fleet.router import (
        affinity_host, affinity_ring,
    )

    full = affinity_ring(["h0", "h1", "h2", "h3"])
    reduced = affinity_ring(["h0", "h1", "h3"])
    moved = 0
    for i in range(2000):
        key = f"key-{i}".encode()
        before = affinity_host(key, full)
        after = affinity_host(key, reduced)
        if before == "h2":
            moved += 1
            assert after != "h2"
        else:
            # consistent hashing's whole point: survivors keep their
            # keys (and their warm cache entries)
            assert after == before, key
    assert moved > 0


def test_apply_affinity_prefers_healthy_ring_host():
    from code2vec_tpu.serving.cache import normalize_source
    from code2vec_tpu.serving.fleet.router import (
        FleetRouter, affinity_host, affinity_ring, weighted_order,
    )
    from test_fleet import _StubControl

    config = _router_test_config()
    router = FleetRouter(config, _StubControl({}), host="127.0.0.1",
                         port=0, log=lambda m: None)
    try:
        body = b"class A { int f() { return 1; } }"
        candidates = [(1.0, "h0", ("127.0.0.1", 1)),
                      (1.0, "h1", ("127.0.0.1", 2)),
                      (0.1, "h2", ("127.0.0.1", 3))]
        # the ring holds FULLY-healthy hosts only: h2 (degraded, 0.1)
        # must never be preferred
        expected = affinity_host(
            normalize_source(body.decode()), affinity_ring(("h0", "h1")))
        for _ in range(25):
            ordered = weighted_order([(w, (hid, addr))
                                      for w, hid, addr in candidates])
            router._apply_affinity(body, candidates, ordered)
            assert ordered[0][0] == expected
            # affinity reorders, never drops: every candidate still
            # reachable by the retry walk
            assert sorted(h for h, _ in ordered) == ["h0", "h1", "h2"]
        # the affinity key is the NORMALIZED source: a reformatted
        # variant lands on the same host (where its cache entry is)
        variant = b"class A {\n    int f() {\n        return 1; } }"
        ordered = weighted_order([(w, (hid, addr))
                                  for w, hid, addr in candidates])
        router._apply_affinity(variant, candidates, ordered)
        assert ordered[0][0] == expected
        # no fully-healthy host at all -> pure weighted fallback,
        # order untouched
        degraded = [(0.1, "h0", ("127.0.0.1", 1)),
                    (0.1, "h1", ("127.0.0.1", 2))]
        ordered = weighted_order([(w, (hid, addr))
                                  for w, hid, addr in degraded])
        before = list(ordered)
        router._apply_affinity(body, degraded, ordered)
        assert ordered == before
        assert _counter_value("fleet_router_affinity_total",
                              outcome="fallback") >= 1
        assert _counter_value("fleet_router_affinity_total",
                              outcome="preferred") >= 25
    finally:
        router.close()


# --------------------------- cache invariants vs scripted 2-host fleet


class _CachingBackend(http.server.ThreadingHTTPServer):
    """Scripted host backend running the REAL cache keying
    (serving/cache.py cache_key, fingerprint-as-knob): response bytes
    are a deterministic function of (normalized source, fingerprint),
    cached exactly as a replica caches them."""

    daemon_threads = True

    def __init__(self):
        import hashlib

        from code2vec_tpu.serving.cache import (
            cache_key, normalize_source,
        )

        backend = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def do_POST(self):  # noqa: N802 (stdlib API name)
                length = int(self.headers.get("Content-Length", 0))
                code = self.rfile.read(length).decode()
                with backend.lock:
                    fp = backend.fingerprint
                    key = cache_key(code, endpoint="predict", topk=3,
                                    model=fp)
                    cached = backend.cache.get(key)
                    if cached is not None:
                        backend.hits += 1
                        body = cached
                    else:
                        backend.misses += 1
                        digest = hashlib.blake2b(
                            normalize_source(code),
                            digest_size=8).hexdigest()
                        body = json.dumps(
                            {"model_fingerprint": fp,
                             "methods": [{"digest": digest}]},
                            sort_keys=True).encode() + b"\n"
                        backend.cache[key] = body
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        super().__init__(("127.0.0.1", 0), Handler)
        self.lock = threading.Lock()
        self.fingerprint = "fp-v1"
        self.cache = {}
        self.hits = self.misses = 0
        threading.Thread(target=self.serve_forever, daemon=True).start()

    @property
    def port(self):
        return self.server_address[1]

    def swap_to(self, fingerprint):
        with self.lock:
            self.fingerprint = fingerprint


@pytest.fixture()
def two_host_backends():
    from test_fleet import _StubControl

    backends = {"h0": _CachingBackend(), "h1": _CachingBackend()}
    control = _StubControl({"default": [
        (1.0, hid, ("127.0.0.1", b.port))
        for hid, b in sorted(backends.items())]})
    yield backends, control
    for b in backends.values():
        b.shutdown()


def test_affinity_never_changes_response_bytes(two_host_backends):
    """The byte-equality invariant: affinity picks WHICH host answers;
    the response is a host-local function of (normalized source,
    knobs, fingerprint), so affinity-on and affinity-off responses are
    byte-identical — and repeats concentrate on ONE host's cache."""
    from code2vec_tpu.serving.fleet.router import FleetRouter

    backends, control = two_host_backends
    on = FleetRouter(_router_test_config(), control,
                     host="127.0.0.1", port=0, log=lambda m: None)
    off = FleetRouter(_router_test_config(fleet_cache_affinity=False),
                      control, host="127.0.0.1", port=0,
                      log=lambda m: None)
    try:
        assert on.affinity and not off.affinity
        sources = [f"class C{i} {{ int m{i}() {{ return {i}; }} }}"
                   for i in range(12)]
        for src in sources:
            first = _post(on.port, "/predict", src)[1]
            for _ in range(3):
                assert _post(on.port, "/predict", src)[1] == first
                assert _post(off.port, "/predict", src)[1] == first
            # a whitespace variant shares the cache entry AND the bytes
            variant = src.replace(" { ", " {\n    ")
            assert _post(on.port, "/predict", variant)[1] == first
        # with affinity on, each source warmed exactly ONE host: every
        # affinity-routed request either missed once or hit — no
        # double-warming across the fleet for affinity-routed traffic
        # (the off-router's sampled requests also hit: both routers
        # share the backends, and bytes are identical either way)
        hits = sum(b.hits for b in backends.values())
        misses = sum(b.misses for b in backends.values())
        assert misses >= len(sources)
        assert hits > misses  # repeats + variants overwhelmingly hit
        # both hosts took a share of the keyspace
        assert all(b.misses > 0 for b in backends.values()), \
            {h: b.misses for h, b in backends.items()}
    finally:
        on.close()
        off.close()


def test_hot_swap_mid_affinity_window_never_serves_stale_fingerprint(
        two_host_backends):
    """The fingerprint-keying invariant: affinity keeps routing a
    source to the same host across a hot-swap, and that host's cache
    still HOLDS the old-fingerprint entry — but the key includes the
    live fingerprint, so the stale bytes can never serve."""
    from code2vec_tpu.serving.fleet.router import FleetRouter

    backends, control = two_host_backends
    router = FleetRouter(_router_test_config(), control,
                         host="127.0.0.1", port=0, log=lambda m: None)
    try:
        src = "class Swap { int mid() { return 7; } }"
        before = json.loads(_post(router.port, "/predict", src)[1])
        assert before["model_fingerprint"] == "fp-v1"
        assert _post(router.port, "/predict", src)[1]  # warm the entry
        stale_entries = sum(len(b.cache) for b in backends.values())
        assert stale_entries >= 1
        for b in backends.values():
            b.swap_to("fp-v2")
        after = json.loads(_post(router.port, "/predict", src)[1])
        # same source, same preferred host, old entry still cached —
        # the response MUST carry the new fingerprint
        assert after["model_fingerprint"] == "fp-v2"
        assert after["methods"] == before["methods"]  # same content
        # the stale entry was never evicted, only out-keyed
        assert sum(len(b.cache) for b in backends.values()) \
            > stale_entries
    finally:
        router.close()


# ------------------------------------------------- shared fleet view


class _ControlListener(http.server.ThreadingHTTPServer):
    """Canned control-plane listener: /fleet JSON, /metrics text, and
    scripted admin status codes (409 pass-through is the interesting
    one)."""

    daemon_threads = True

    def __init__(self, view):
        listener = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _reply(self, code, body, ctype="application/json"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 (stdlib API name)
                if self.path == "/fleet":
                    self._reply(200, json.dumps(listener.view).encode())
                elif self.path == "/metrics":
                    self._reply(
                        200,
                        b"# TYPE fleet_swap_total counter\n"
                        b'fleet_swap_total{outcome="committed"} 2\n',
                        ctype="text/plain")
                else:
                    self._reply(404, b"{}")

            def do_POST(self):  # noqa: N802 (stdlib API name)
                length = int(self.headers.get("Content-Length", 0))
                listener.admin_bodies.append(
                    (self.path, json.loads(self.rfile.read(length))))
                code, payload = listener.admin_replies.get(
                    self.path, (404, {"error": "no such endpoint"}))
                self._reply(code, json.dumps(payload).encode())

        super().__init__(("127.0.0.1", 0), Handler)
        self.view = view
        self.admin_bodies = []
        self.admin_replies = {}
        threading.Thread(target=self.serve_forever, daemon=True).start()

    @property
    def port(self):
        return self.server_address[1]


_CANNED_VIEW = {
    "role": "fleet-control",
    "models": {"default": {"routable": 2}},
    "hosts": [
        {"host": "default-0", "model": "default", "weight": 1.0,
         "address": "10.0.0.5", "port": 8101},
        {"host": "default-1", "model": "default", "weight": 0.1,
         "port": 8102},                       # no address -> loopback
        {"host": "default-2", "model": "default", "weight": 1.0,
         "address": "10.0.0.7", "port": None},  # no port -> dropped
    ],
}


def test_shared_fleet_view_derives_candidates_and_view():
    from code2vec_tpu.serving.fleet.edge import SharedFleetView

    listener = _ControlListener(_CANNED_VIEW)
    try:
        view = SharedFleetView(_router_test_config(),
                               f"127.0.0.1:{listener.port}",
                               "router-7", log=lambda m: None)
        # before the first successful poll: an EMPTY candidate list
        # (retryable 503), never a None (that would 404 a real model)
        assert view.hosts_for("default") == []
        assert view.view_age_s() is None
        assert view.refresh()
        assert view.hosts_for("default") == [
            (1.0, "default-0", ("10.0.0.5", 8101)),
            (0.1, "default-1", ("127.0.0.1", 8102)),
        ]
        assert view.hosts_for("nope") is None  # known models, not this
        fleet = view.fleet_view()
        assert fleet["role"] == "fleet-router"
        assert fleet["router"] == "router-7"
        assert fleet["view_age_s"] is not None
        # metrics re-merge: the listener's counter (2) survives
        # alongside this process's own registry, whose own count of the
        # same series (left by whichever tests ran here before) adds
        from code2vec_tpu import obs
        own = obs.default_registry().collect().get(
            "fleet_swap_total", {}).get((("outcome", "committed"),))
        merged = view.merged_fleet_metrics()
        assert ('fleet_swap_total{outcome="committed"} '
                f'{2 + int(own.value if own else 0)}') in merged
        with pytest.raises(ValueError):
            SharedFleetView(_router_test_config(), "no-port", "r",
                            log=lambda m: None)
    finally:
        listener.shutdown()


def test_router_forwards_x_tenant_to_backend():
    """The tenant identity pin (serving/tenancy.py): X-Tenant rides
    the shared forwarding contract router -> host, alongside X-Model
    and X-Deadline-Ms — a header in REQUEST_FORWARD_HEADERS can never
    silently stop at one hop."""
    from code2vec_tpu.serving.fleet.router import FleetRouter
    from code2vec_tpu.serving.forwarding import REQUEST_FORWARD_HEADERS
    from test_fleet import _StubControl

    assert "X-Tenant" in REQUEST_FORWARD_HEADERS

    captured = []

    class _Capture(http.server.ThreadingHTTPServer):
        daemon_threads = True

        def __init__(self):
            class Handler(http.server.BaseHTTPRequestHandler):
                protocol_version = "HTTP/1.1"

                def log_message(self, *args):
                    pass

                def do_POST(self):  # noqa: N802 (stdlib API name)
                    length = int(self.headers.get("Content-Length", 0))
                    self.rfile.read(length)
                    captured.append(dict(self.headers))
                    body = b'{"ok": true}\n'
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

            super().__init__(("127.0.0.1", 0), Handler)
            threading.Thread(target=self.serve_forever,
                             daemon=True).start()

    backend = _Capture()
    control = _StubControl({"default": [
        (1.0, "h0", ("127.0.0.1", backend.server_address[1]))]})
    router = FleetRouter(_router_test_config(), control,
                         host="127.0.0.1", port=0, log=lambda m: None)
    try:
        status, _, _ = _post(router.port, "/predict",
                             "class A { int f() { return 1; } }",
                             headers={"X-Tenant": "acme",
                                      "X-Deadline-Ms": "1500"})
        assert status == 200
        [headers] = captured
        assert headers.get("X-Tenant") == "acme"
        assert headers.get("X-Deadline-Ms") == "1500"
        # absent header stays absent: the backend sees exactly what
        # the client sent, never an injected default
        captured.clear()
        status, _, _ = _post(router.port, "/predict",
                             "class A { int g() { return 2; } }")
        assert status == 200
        [headers] = captured
        assert "X-Tenant" not in headers
    finally:
        router.close()
        backend.shutdown()


def test_shared_fleet_view_admin_relay_passes_status_through():
    from code2vec_tpu.serving.fleet.edge import SharedFleetView

    listener = _ControlListener(_CANNED_VIEW)
    listener.admin_replies = {
        "/admin/reload": (409, {"error": "a fleet swap is already in "
                                         "flight"}),
        "/admin/scale": (200, {"host": "default-0",
                               "desired_replicas": 3}),
        "/admin/drain": (202, {"host": "default-1", "draining": True}),
    }
    try:
        view = SharedFleetView(_router_test_config(),
                               f"127.0.0.1:{listener.port}",
                               "router-0", log=lambda m: None)
        assert view.refresh()
        code, body = view.request_swap({"artifact": "/a/v2"})
        assert (code, body["error"].startswith("a fleet swap")) \
            == (409, True)
        assert view.request_scale("default-0", 3) \
            == (200, {"host": "default-0", "desired_replicas": 3})
        assert view.drain_host("default-1")[0] == 202
        # the payload reached the listener verbatim
        assert ("/admin/reload", {"artifact": "/a/v2"}) \
            in listener.admin_bodies
    finally:
        listener.shutdown()
    # control plane gone: refresh fails but keeps the cached view;
    # admin relays answer an honest 503
    assert not view.refresh()
    assert view.hosts_for("default") != []
    code, body = view.request_swap({"artifact": "/a/v3"})
    assert code == 503 and "unreachable" in body["error"]


# --------------------------------------------- remote host launcher


def test_remote_launcher_substitutes_address_filters_env_and_quotes(
        tmp_path):
    from code2vec_tpu.serving.fleet.control import (
        FLEET_HOST_ADDRESS_ENV, RemoteHostLauncher,
    )

    recorder = tmp_path / "fakessh"
    args_out = tmp_path / "args.txt"
    recorder.write_text("#!/bin/sh\n"
                        f"printf '%s\\n' \"$@\" > {args_out}\n")
    recorder.chmod(0o755)
    launcher = RemoteHostLauncher(f"{recorder} {{address}}")
    env = dict(os.environ,
               **{FLEET_HOST_ADDRESS_ENV: "10.1.2.3",
                  "C2V_FLEET_HOST": "default-0",
                  "PYTHONPATH": "/repo path",        # space survives
                  "SECRET_TOKEN": "must-not-travel"})
    proc = launcher.launch(
        [sys.executable, "-m", "code2vec_tpu.cli", "serve",
         "--fleet_models", "default=/a b/v1"],
        env, str(tmp_path / "host.log"))
    assert proc.wait(timeout=30) == 0
    lines = args_out.read_text().splitlines()
    assert lines[0] == "10.1.2.3"  # {address} became the wrapper arg
    remote = lines[1]
    assert remote.startswith("env ")
    assert "C2V_FLEET_HOST=default-0" in remote
    assert f"{FLEET_HOST_ADDRESS_ENV}=10.1.2.3" in remote
    assert "'/repo path'" in remote          # quoted for the far shell
    assert "SECRET_TOKEN" not in remote      # filtered, not exported
    assert "'default=/a b/v1'" in remote     # command args quoted too
    with pytest.raises(ValueError):
        RemoteHostLauncher("   ")


def test_remote_launcher_command_survives_a_real_shell(tmp_path):
    # "sh -c" is the degenerate remote substrate: the flattened
    # `env K=V ... cmd` word must execute verbatim under a real shell
    from code2vec_tpu.serving.fleet.control import (
        FLEET_HOST_ADDRESS_ENV, RemoteHostLauncher,
    )

    launcher = RemoteHostLauncher("sh -c")
    log_path = str(tmp_path / "host.log")
    env = dict(os.environ, **{FLEET_HOST_ADDRESS_ENV: "10.9.9.9",
                              "C2V_MARKER": "it's \"quoted\""})
    proc = launcher.launch(
        [sys.executable, "-c",
         "import os; print(os.environ['C2V_MARKER'], "
         "os.environ['" + FLEET_HOST_ADDRESS_ENV + "'])"],
        env, log_path)
    assert proc.wait(timeout=30) == 0
    assert open(log_path).read().strip() \
        == "it's \"quoted\" 10.9.9.9"


def test_remote_launch_failure_rides_host_down_then_escalates(
        tmp_path):
    from code2vec_tpu.serving.fleet.control import (
        ControlPlane, HostSpec, RemoteHostLauncher,
    )

    config = Config(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_models="default=/a/v1", fleet_max_host_restarts=1,
        fleet_addresses="10.0.0.1",
        fleet_launcher="/nonexistent-wrapper-xyz {address}",
        heartbeat_file=str(tmp_path / "fleet.heartbeat.json"))
    config.verify()
    restarts_before = _counter_value("fleet_host_restarts_total")
    control = ControlPlane(
        config, [HostSpec("default-0", ["true"], address="10.0.0.1")],
        launcher=RemoteHostLauncher(config.fleet_launcher),
        log=lambda m: None)
    host = control.hosts[0]
    control._spawn(host)
    # the missing wrapper binary joined the ORDINARY death path:
    # host_down incident, backoff gate armed, restart budget ticking
    assert host.proc is None
    assert host.restarts == 1
    assert host.restart_at is not None
    assert not control._escalated
    assert _counter_value("fleet_host_restarts_total") \
        == restarts_before + 1
    # the retry fails the same way and exhausts the budget ->
    # host_escalation, fleet stop
    host.restart_at = 0.0
    control._check_host(host, time.monotonic())
    assert control._escalated
    assert control._stop.is_set()


# ------------------------- (artifact, retrieval_index) reconciliation


class _FakeProc:
    pid = 4242

    def poll(self):
        return None

    def wait(self, timeout=None):
        return 0

    def send_signal(self, sig):
        pass


class _RecordingLauncher:
    def __init__(self):
        self.launches = []

    def launch(self, command, env, log_path):
        self.launches.append((list(command), dict(env), log_path))
        return _FakeProc()


def test_respawned_host_reconciles_onto_artifact_index_pair(tmp_path):
    """PR-15 residue: a host (re)spawned after a retrieval_refresh must
    get the (artifact, retrieval_index) PAIR in its reload-target file
    — the artifact alone would revive the model with no/stale index."""
    from code2vec_tpu.serving.fleet.control import (
        FLEET_HOST_ADDRESS_ENV, ControlPlane, HostSpec,
    )
    from code2vec_tpu.serving.server import RELOAD_TARGET_FILENAME

    config = Config(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_models="default=/a/v1",
        heartbeat_file=str(tmp_path / "fleet.heartbeat.json"))
    launcher = _RecordingLauncher()
    control = ControlPlane(
        config,
        [HostSpec("default-0", ["host-cmd"], boot_artifact="/a/v1")],
        launcher=launcher, log=lambda m: None)
    control.set_initial_artifact("default", "/a/v1")
    host = control.hosts[0]
    target = os.path.join(host.host_dir, RELOAD_TARGET_FILENAME)

    control._spawn(host)                 # boot == current, no index
    assert not os.path.exists(target)
    assert launcher.launches[-1][1][FLEET_HOST_ADDRESS_ENV] \
        == "127.0.0.1"

    # a swap that rode an index: the pair, not the artifact alone
    control.set_artifact("default", "/a/v2", retrieval_index="/idx/r7")
    control._spawn(host)
    payload = json.load(open(target))
    assert (payload["artifact"], payload["retrieval_index"]) \
        == ("/a/v2", "/idx/r7")

    # an index refresh re-targeting the BOOT artifact still writes the
    # pair (the artifact matches the boot one, the index must ride)
    control.set_artifact("default", "/a/v1", retrieval_index="/idx/r8")
    control._spawn(host)
    payload = json.load(open(target))
    assert (payload["artifact"], payload["retrieval_index"]) \
        == ("/a/v1", "/idx/r8")

    # a plain promote clears the index: reviving the old one would
    # serve stale vectors against the new weights
    control.set_artifact("default", "/a/v3")
    control._spawn(host)
    payload = json.load(open(target))
    assert payload["artifact"] == "/a/v3"
    assert "retrieval_index" not in payload


def test_first_heartbeat_reconcile_reaches_remote_hosts(tmp_path):
    """The respawn reconcile must ride the host's own telemetry
    surface, not the control plane's local filesystem: a remote host
    (or a supervisor that restarted by itself) never reads the
    reload-target file, so the control plane compares the host's
    REPORTED reload state against the committed (artifact, index) pair
    at the first view after every spawn and re-issues /admin/reload on
    disagreement."""
    from code2vec_tpu.serving.fleet.control import ControlPlane, HostSpec

    config = Config(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_models="default=/a/v1",
        heartbeat_file=str(tmp_path / "fleet.heartbeat.json"))
    control = ControlPlane(
        config,
        [HostSpec("default-0", ["host-cmd"], boot_artifact="/a/v1")],
        launcher=_RecordingLauncher(), log=lambda m: None)
    control.set_initial_artifact("default", "/a/v1")
    host = control.hosts[0]
    posts = []
    control._post = lambda h, path, payload, timeout=10.0: (
        posts.append((h.id, path, dict(payload))) or (True, "{}"))

    control._spawn(host)
    assert host.needs_reconcile
    # boot pair == committed pair: no reload, flag cleared
    host.view = {"replicas": []}
    control._reconcile_host(host)
    assert not host.needs_reconcile and posts == []

    # the fleet commits a refreshed pair, then the host dies and comes
    # back reporting only its boot artifact (remote host: the
    # reload-target file never reached its filesystem)
    control.set_artifact("default", "/a/v2", retrieval_index="/idx/r9")
    control._spawn(host)
    host.view = {"replicas": []}
    control._reconcile_host(host)
    assert posts == [("default-0", "/admin/reload",
                      {"artifact": "/a/v2",
                       "retrieval_index": "/idx/r9"})]
    assert not host.needs_reconcile

    # a host that already processed the fan-out (its view reports the
    # committed pair) is left alone
    control._spawn(host)
    host.view = {"last_reload": {"artifact": "/a/v2",
                                 "retrieval_index": "/idx/r9"}}
    posts.clear()
    control._reconcile_host(host)
    assert posts == [] and not host.needs_reconcile

    # artifact matches but the index is missing from the report (the
    # residue this PR closes: supervisor status omitted it) -> the
    # FULL pair is re-issued
    control._spawn(host)
    host.view = {"last_reload": {"artifact": "/a/v2"}}
    control._reconcile_host(host)
    assert posts and posts[-1][2] == {"artifact": "/a/v2",
                                      "retrieval_index": "/idx/r9"}

    # an in-flight coordinated swap defers to the swap driver: no
    # competing reload, the flag stays set for the next tick
    control._spawn(host)
    host.view = {"last_reload": {"artifact": "/a/v1"}}
    control.swap._set(state="rolling")
    posts.clear()
    control._reconcile_host(host)
    assert posts == [] and host.needs_reconcile


def test_supervisor_last_reload_reports_index_pair(tmp_path):
    """fleet_view's last_reload must carry the retrieval_index it
    fanned out — the control plane's reconcile compares pairs, and an
    artifact-only report would read as 'index missing' forever."""
    from code2vec_tpu import obs
    from code2vec_tpu.serving.supervisor import Supervisor

    config = Config(serve=True, serve_host="127.0.0.1", verbose_mode=0,
                    heartbeat_file=str(tmp_path / "sup.heartbeat.json"))
    sup = Supervisor.__new__(Supervisor)
    sup.config = config
    sup.replicas = []
    sup.run_dir = str(tmp_path)
    sup.reuseport = False
    sup.log = lambda m: None
    sup.flight = obs.default_flight_recorder()
    status = sup.reload_all("/a/v2", retrieval_index="/idx/r9")
    sup._last_reload = status
    assert status["artifact"] == "/a/v2"
    assert status["retrieval_index"] == "/idx/r9"
    # and a plain reload omits the key (pair semantics: absent index
    # means none mounted, not unknown)
    assert "retrieval_index" not in sup.reload_all("/a/v3")


def test_fleet_view_carries_pair_and_router_tier(tmp_path):
    from code2vec_tpu.serving.fleet.control import (
        ControlPlane, HostSpec, RouterSpec,
    )

    config = Config(
        serve=True, fleet=True, serve_host="127.0.0.1", verbose_mode=0,
        fleet_models="default=/a/v1", fleet_routers=2,
        heartbeat_file=str(tmp_path / "fleet.heartbeat.json"))
    config.verify()
    control = ControlPlane(config, [HostSpec("default-0", ["cmd"])],
                           launcher=_RecordingLauncher(),
                           log=lambda m: None)
    control.set_initial_artifact("default", "/a/v1")
    control.set_artifact("default", "/a/v2", retrieval_index="/idx/r2")
    control.add_router(RouterSpec("router-0", ["cmd"]))
    view = control.fleet_view()
    assert view["models"]["default"]["artifact"] == "/a/v2"
    assert view["models"]["default"]["retrieval_index"] == "/idx/r2"
    assert [r["router"] for r in view["routers"]] == ["router-0"]
    assert view["hosts"][0]["address"] == "127.0.0.1"


# --------------------------------------------------- CLI / re-exec


def test_router_base_command_keeps_knobs_strips_topology():
    from code2vec_tpu.serving.fleet.control import _router_base_command

    argv = ["fleet", "--fleet_routers", "3",
            "--fleet_control", "127.0.0.1:9", "--fleet_port", "9100",
            "--serve_port", "9000", "--serve_telemetry_port", "9001",
            "--heartbeat_file", "/x/hb.json", "--fleet_no_affinity",
            "--serve_deadline_ms", "1500",
            "--fleet_poll_interval", "0.5",
            "--fleet_models", "default=/a"]
    cmd = _router_base_command(argv)
    assert cmd[:3] == [sys.executable, "-m", "code2vec_tpu.cli"]
    rest = cmd[3:]
    # keeps the `fleet` subcommand: dispatch keys on C2V_FLEET_ROUTER
    assert rest[0] == "fleet"
    for flag in ("--fleet_routers", "--fleet_control", "--fleet_port",
                 "--serve_port", "--serve_telemetry_port",
                 "--heartbeat_file"):
        assert flag not in rest, flag
    # operator knobs (including the affinity toggle) are inherited
    for flag in ("--fleet_no_affinity", "--serve_deadline_ms",
                 "--fleet_poll_interval", "--fleet_models"):
        assert flag in rest, flag


def test_cli_edge_flags_parse_and_config_verifies():
    from code2vec_tpu.cli import config_from_args

    cfg = config_from_args(
        ["fleet", "--fleet_models", "default=/a",
         "--fleet_routers", "2", "--fleet_control", "127.0.0.1:9901",
         "--fleet_no_affinity", "--fleet_launcher", "ssh {address}",
         "--fleet_addresses", "10.0.0.1,10.0.0.2"])
    assert cfg.fleet_routers == 2
    assert cfg.fleet_control == "127.0.0.1:9901"
    assert cfg.fleet_cache_affinity is False
    assert cfg.fleet_launcher == "ssh {address}"
    assert cfg.fleet_addresses == "10.0.0.1,10.0.0.2"
    cfg.verify()
    # defaults: one embedded router, affinity ON
    base = config_from_args(["fleet", "--fleet_models", "default=/a"])
    assert base.fleet_routers == 1
    assert base.fleet_cache_affinity is True

    def bad(**kw):
        cfg = Config(serve=True, fleet=True, serve_host="127.0.0.1",
                     fleet_models="default=/a", **kw)
        with pytest.raises(ValueError):
            cfg.verify()

    bad(fleet_routers=0)
    bad(fleet_control="no-port")
    bad(fleet_launcher="ssh {address}")   # {address}, no addresses


# ------------------------------------------------ chaos drills (slow)


def _run_edge_fleet(tmp_path, config, host_specs, artifacts=None,
                    router_ports=()):
    """ControlPlane + PRIVATE control listener + N router-agent
    subprocesses (the fleet_main n_routers>=2 topology, built by hand
    so the drill owns the ports and the teardown)."""
    from code2vec_tpu.serving.fleet.control import (
        ControlPlane, RouterSpec,
    )
    from code2vec_tpu.serving.fleet.router import FleetRouter

    control = ControlPlane(config, host_specs, log=lambda m: None)
    for model, artifact in (artifacts or {}).items():
        control.set_initial_artifact(model, artifact)
    control.router = FleetRouter(config, control, host="127.0.0.1",
                                 port=0, log=lambda m: None)
    for i, port in enumerate(router_ports):
        control.add_router(RouterSpec(
            f"router-{i}",
            [sys.executable, "-m", "code2vec_tpu.cli", "fleet",
             "--fleet_models", "default=/tmp/unused",
             "--serve_host", "127.0.0.1", "--serve_port", str(port),
             "--fleet_control", f"127.0.0.1:{control.router.port}",
             "--fleet_poll_interval", "0.25", "--verbose", "0"]))
    rc_holder = {}
    thread = threading.Thread(
        target=lambda: rc_holder.update(rc=control.run()), daemon=True)
    thread.start()
    return control, thread, rc_holder


@pytest.fixture()
def run_edge(tmp_path, fake_extractor):  # noqa: F811 — pytest fixture
    running = []

    def start(config, host_specs, artifacts=None, router_ports=()):
        out = _run_edge_fleet(tmp_path, config, host_specs,
                              artifacts=artifacts,
                              router_ports=router_ports)
        running.append(out)
        return out

    yield start
    for control, thread, _rc in running:
        control.stop()
        thread.join(timeout=60)


def _routers_routing(n):
    def ready(view):
        routing = [r for r in view.get("routers", [])
                   if r["state"] == "routing" and r["port"]]
        return len(routing) >= n
    return ready


@pytest.mark.slow
@pytest.mark.chaos
def test_edge_router_sigkill_under_load_zero_failed_requests(
        tmp_path, fake_extractor, run_edge):
    """THE edge chaos drill (ISSUE acceptance): SIGKILL one of 2
    router processes under 4-client load. Clients follow the VIP
    convention — fixed member ports, retry the next member on a
    refused/torn connection — and ZERO requests fail or come back
    malformed; the control plane respawns the router (same
    backoff/escalation policy as hosts) and the fleet exits rc 0."""
    replica_cfg = _write_json(
        tmp_path, "replica.json",
        _replica_overrides(fingerprint="fp-edge"))
    host_cmd = [sys.executable, FLEET_HOST,
                _write_json(tmp_path, "host.json", _host_overrides()),
                replica_cfg]
    from code2vec_tpu.serving.fleet.control import HostSpec
    ports = [_free_port(), _free_port()]
    config = _fleet_config(tmp_path)
    control, thread, rc_holder = run_edge(
        config, [HostSpec("default-0", host_cmd),
                 HostSpec("default-1", host_cmd)],
        router_ports=ports)
    _wait_fleet(control,
                lambda v: _all_routable(2)(v) and _routers_routing(2)(v),
                timeout=60, what="2 routable hosts + 2 routing routers")
    restarts_before = _counter_value("edge_router_restarts_total")

    failures, malformed = [], []
    lock = threading.Lock()
    stop_load = threading.Event()

    def load(ci):
        i = 0
        while not stop_load.is_set():
            src = (f"class K{ci}x{i} {{ int m{ci}x{i}() "
                   f"{{ return 1; }} }}")
            served = False
            deadline = time.time() + 30
            attempt = ci  # pin each client to a different start member
            last = None
            while time.time() < deadline:
                port = ports[attempt % len(ports)]
                attempt += 1
                try:
                    status, body, headers = _post(port, "/predict",
                                                  src, timeout=15)
                except Exception as e:  # noqa: BLE001 — refused/torn
                    # connection: the VIP retries the next member
                    last = ("conn_error", str(e))
                    time.sleep(0.05)
                    continue
                try:
                    payload = json.loads(body)
                except ValueError:
                    with lock:
                        malformed.append((status, body[:200]))
                    break
                if status == 200:
                    if (payload.get("model_fingerprint") != "fp-edge"
                            or "methods" not in payload):
                        with lock:
                            malformed.append((status, body[:200]))
                    served = True
                    break
                # an honest shed retries; anything else is malformed
                if status not in (503, 504) \
                        or not payload.get("trace_id"):
                    with lock:
                        malformed.append((status, body[:200]))
                    break
                last = (status, None)
                time.sleep(0.1)
            if not served and not stop_load.is_set():
                with lock:
                    failures.append((ci, i, last))
            i += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=load, args=(ci,))
               for ci in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.0)
        view = control.fleet_view()
        victim = view["routers"][0]
        assert victim["pid"]
        os.kill(victim["pid"], signal.SIGKILL)
        _wait_fleet(
            control,
            lambda v: (v["routers"][0]["pid"] not in (None,
                                                      victim["pid"])
                       and v["routers"][0]["restarts"] >= 1
                       and v["routers"][0]["state"] == "routing"),
            timeout=60, what="killed router respawned + routing")
        time.sleep(1.0)  # post-recovery traffic through both members
    finally:
        stop_load.set()
        for t in threads:
            t.join(timeout=60)
    assert not failures, f"failed client requests: {failures[:3]}"
    assert not malformed, f"malformed responses: {malformed[:3]}"
    assert _counter_value("edge_router_restarts_total") \
        >= restarts_before + 1
    # both members (including the respawned one, on its ORIGINAL port
    # — the VIP never re-learns addresses) serve a fresh request
    for port in ports:
        status, body, _ = _post(port, "/predict",
                                "class Z { int after() { return 1; } }")
        assert status == 200, (port, body[:200])
        assert json.loads(body)["model_fingerprint"] == "fp-edge"
    control.stop()
    thread.join(timeout=60)
    assert rc_holder["rc"] == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_edge_swap_commits_with_routers_live_and_respawn_gets_pair(
        tmp_path, fake_extractor, run_edge):
    """Coordinated hot-swap with N routers live: a reload POSTed to a
    PUBLIC router relays to the control plane, commits fleet-wide
    (every router's own /fleet converges on it), and a host SIGKILLed
    after the commit respawns onto the committed (artifact,
    retrieval_index) PAIR at its first heartbeat (PR-15 residue)."""
    replicas = _write_json(
        tmp_path, "replica.json",
        _replica_overrides(fingerprint="fp-v1", fake_swap=True,
                           fake_retrieval=True))
    host_json = _write_json(tmp_path, "host.json", _host_overrides())
    host_cmd = [sys.executable, FLEET_HOST, host_json, replicas]
    from code2vec_tpu.serving.fleet.control import HostSpec
    ports = [_free_port(), _free_port()]
    config = _fleet_config(tmp_path)
    control, thread, rc_holder = run_edge(
        config, [HostSpec("default-0", host_cmd,
                          boot_artifact="/artifacts/v1"),
                 HostSpec("default-1", host_cmd,
                          boot_artifact="/artifacts/v1")],
        artifacts={"default": "/artifacts/v1"}, router_ports=ports)
    _wait_fleet(control,
                lambda v: _all_routable(2)(v) and _routers_routing(2)(v),
                timeout=60, what="2 routable hosts + 2 routing routers")

    # the swap rides a retrieval index; POSTed to a PUBLIC router
    status, body, _ = _post(
        ports[1], "/admin/reload",
        json.dumps({"artifact": "/artifacts/v2",
                    "retrieval_index": "/indexes/r2"}),
        headers={"Content-Type": "application/json"})
    assert status == 202, body[:300]
    view = _wait_fleet(control,
                       lambda v: v["swap"]["state"] == "committed",
                       timeout=60, what="swap committed")
    assert view["swap"]["target_fingerprint"] == "fp-v2"
    assert view["models"]["default"]["artifact"] == "/artifacts/v2"
    assert view["models"]["default"]["retrieval_index"] == "/indexes/r2"

    # EVERY router's own /fleet (its polled shared view) converges
    for port in ports:
        deadline = time.time() + 15
        while True:
            rv = json.loads(_get(port, "/fleet")[1])
            if (rv.get("role") == "fleet-router"
                    and (rv.get("swap") or {}).get("state")
                    == "committed"
                    and rv["models"]["default"]["artifact"]
                    == "/artifacts/v2"):
                break
            assert time.time() < deadline, (port, rv.get("swap"))
            time.sleep(0.25)

    # SIGKILL one whole host (supervisor + replicas) AFTER the commit
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
    _wait_fleet(
        control,
        lambda v: (v["hosts"][0]["pid"] not in (None, victim_pid)
                   and v["hosts"][0]["weight"] > 0
                   and v["hosts"][0]["restarts"] >= 1
                   and v["hosts"][0]["fingerprints"] == ["fp-v2"]),
        timeout=90, what="killed host respawned onto fp-v2")
    # the PAIR pin: every replica of the respawned host converged onto
    # (artifact, retrieval_index) — the first-heartbeat SIGHUP
    # delivered BOTH, not the artifact alone
    deadline = time.time() + 30
    while True:
        hv = control.host_fleet(control.hosts[0]) or {}
        live = [r for r in hv.get("replicas", [])
                if not r.get("draining")]
        if live and all(
                r.get("swap_target") == "/artifacts/v2"
                and r.get("swap_retrieval_index") == "/indexes/r2"
                and r.get("swap_state") == "ready"
                and r.get("model_fingerprint") == "fp-v2"
                for r in live):
            break
        assert time.time() < deadline, \
            [(r.get("swap_target"), r.get("swap_retrieval_index"),
              r.get("swap_state")) for r in live]
        time.sleep(0.25)

    # live traffic through a router serves the committed weights
    status, body, _ = _post(ports[0], "/predict",
                            "class P { int pair() { return 2; } }")
    assert status == 200
    assert json.loads(body)["model_fingerprint"] == "fp-v2"
    control.stop()
    thread.join(timeout=60)
    assert rc_holder["rc"] == 0
