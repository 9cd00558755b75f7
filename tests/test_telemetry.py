"""Fleet telemetry suite (serving/telemetry.py + supervisor wiring):
exposition-text parse/merge semantics (counters + histograms summed,
gauges labeled per replica), the /fleet view math, and the 2-replica
supervisor e2e acceptance pin — merged /metrics request counters equal
the sum of the per-replica counters under concurrent load, fixing the
PR-9 reuseport one-replica-scrape gap."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from code2vec_tpu.obs.metrics import MetricsRegistry
from code2vec_tpu.serving import telemetry

from test_serving import FAKE_EXTRACTOR

pytestmark = pytest.mark.telemetry

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "chaos_serving_child.py")


@pytest.fixture()
def fake_extractor(tmp_path, monkeypatch):
    path = tmp_path / "fake-c2v-extract"
    path.write_text(FAKE_EXTRACTOR)
    path.chmod(0o755)
    monkeypatch.setenv("C2V_NATIVE_EXTRACTOR", str(path))
    return str(path)


# ------------------------------------------------------ parse + merge


def _registry_text(requests=0, shed=0, depth=0.0, lat=()):
    reg = MetricsRegistry()
    if requests:
        reg.counter("serving_requests_total", "reqs",
                    endpoint="predict", status="200").inc(requests)
    if shed:
        reg.counter("serving_requests_shed_total", "sheds",
                    reason="breaker").inc(shed)
    reg.gauge("serving_admission_depth", "depth").set(depth)
    h = reg.histogram("serving_device_seconds", "lat", buckets=(0.1, 1.0))
    for v in lat:
        h.observe(v)
    return reg.render_prometheus()


def test_parse_prometheus_text_roundtrips_obs_render():
    text = _registry_text(requests=3, shed=1, depth=2.0,
                          lat=(0.05, 0.5, 5.0))
    fams = telemetry.parse_prometheus_text(text)
    assert fams["serving_requests_total"].kind == "counter"
    assert fams["serving_requests_total"].samples[
        "serving_requests_total"][
        (("endpoint", "predict"), ("status", "200"))] == 3.0
    assert fams["serving_admission_depth"].kind == "gauge"
    hist = fams["serving_device_seconds"]
    assert hist.kind == "histogram"
    # bucket samples attach to the DECLARING family, le labels parsed
    buckets = hist.samples["serving_device_seconds_bucket"]
    assert buckets[(("le", "0.1"),)] == 1.0
    assert buckets[(("le", "1"),)] == 2.0
    assert buckets[(("le", "+Inf"),)] == 3.0
    assert hist.samples["serving_device_seconds_count"][()] == 3.0
    assert hist.samples["serving_device_seconds_sum"][()] \
        == pytest.approx(5.55)
    # garbage lines are skipped, not fatal
    assert telemetry.parse_prometheus_text(
        "!!!\nnot a line\n# weird\n") == {}


def test_merge_sums_counters_and_histograms_labels_gauges():
    merged = telemetry.merge_prometheus_snapshots({
        "0": _registry_text(requests=3, shed=1, depth=2.0,
                            lat=(0.05, 0.5)),
        "1": _registry_text(requests=4, depth=5.0, lat=(5.0,)),
    })
    # counters summed across replicas by (name, labels)
    assert ('serving_requests_total{endpoint="predict",status="200"} 7'
            in merged)
    assert 'serving_requests_shed_total{reason="breaker"} 1' in merged
    # histogram buckets/sum/count summed
    assert 'serving_device_seconds_bucket{le="0.1"} 1' in merged
    assert 'serving_device_seconds_bucket{le="1"} 2' in merged
    assert 'serving_device_seconds_bucket{le="+Inf"} 3' in merged
    assert 'serving_device_seconds_count 3' in merged
    # gauges NOT summed: one sample per replica, replica label added
    assert 'serving_admission_depth{replica="0"} 2' in merged
    assert 'serving_admission_depth{replica="1"} 5' in merged
    # and the merged text re-parses (it is valid exposition format)
    fams = telemetry.parse_prometheus_text(merged)
    assert telemetry.sum_family(fams, "serving_requests_total") == 7.0
    assert fams["serving_device_seconds"].kind == "histogram"


def test_sum_family_with_label_filter():
    text = _registry_text(requests=5, shed=2)
    assert telemetry.sum_family(text, "serving_requests_total") == 5.0
    assert telemetry.sum_family(text, "serving_requests_total",
                                status="200") == 5.0
    assert telemetry.sum_family(text, "serving_requests_total",
                                status="503") == 0.0
    assert telemetry.sum_family(text, "nope_total") == 0.0


def test_fleet_replica_view_staleness_and_shed_rate():
    now = time.time()
    hb = {"wall_time": now - 1.5, "status": "serving",
          "model_fingerprint": "fp-a",
          "breakers": {"extractor": "closed", "device": "open"},
          "requests_total": 50, "requests_shed_total": 10,
          "requests_expired_total": 2, "swap_state": "idle",
          "inflight": 1}
    view = telemetry.fleet_replica_view(hb, now)
    assert view["heartbeat_age_s"] == pytest.approx(1.5, abs=0.05)
    assert view["shed_rate"] == pytest.approx(0.2)
    assert view["breakers"]["device"] == "open"
    assert view["model_fingerprint"] == "fp-a"
    # zero traffic: rate 0.0, not a division error
    assert telemetry.fleet_replica_view(
        {"wall_time": now, "requests_total": 0}, now)["shed_rate"] == 0.0
    # no heartbeat yet: nulls, never a crash
    empty = telemetry.fleet_replica_view(None, now)
    assert empty["status"] is None and empty["shed_rate"] is None


def test_merge_survives_torn_and_garbage_snapshots():
    """A torn or mid-rewrite snapshot must never crash the merge: bad
    lines are skipped per line, full-garbage text merges to nothing."""
    good = _registry_text(requests=3)
    torn = good[: len(good) // 2]  # truncated mid-line
    merged = telemetry.merge_prometheus_snapshots(
        {"0": good, "1": torn, "2": "\x00\xff not prometheus {{{",
         "3": ""})
    fams = telemetry.parse_prometheus_text(merged)
    # the intact replica's counters survive; the torn one contributes
    # only its complete lines; garbage contributes nothing
    assert telemetry.sum_family(fams, "serving_requests_total") >= 3.0


def test_supervisor_scrape_skips_and_counts_bad_replica_snapshot(
        tmp_path):
    """Satellite pin: a replica metrics file caught torn/garbled must
    be SKIPPED AND COUNTED — the supervisor /metrics scrape stays 200
    on the surviving replicas' truth, never a 500."""
    from code2vec_tpu import obs
    from code2vec_tpu.config import Config
    from code2vec_tpu.serving.supervisor import Supervisor
    from code2vec_tpu.serving.telemetry import TelemetryServer

    config = Config(
        serve=True, serve_host="127.0.0.1", serve_port=0,
        serve_replicas=2, verbose_mode=0,
        heartbeat_file=str(tmp_path / "supervisor.heartbeat.json"))
    sup = Supervisor(config, child_command=["true"])  # never spawned
    # replica 0: binary garbage (a torn rewrite / disk corruption);
    # replica 1: a valid snapshot
    with open(sup.replicas[0].metrics_path, "wb") as f:
        f.write(b"\x00\xffgarbage{{{ 7\n===")
    with open(sup.replicas[1].metrics_path, "w") as f:
        f.write(_registry_text(requests=5))

    def skipped():
        return sum(
            child.value for labels, child in obs.default_registry()
            .collect().get("serving_telemetry_snapshots_skipped_total",
                           {}).items())

    before = skipped()
    merged = sup.merged_metrics()
    assert telemetry.sum_family(
        merged, "serving_requests_total") >= 5.0
    assert skipped() == before + 1
    # and over HTTP: 200, never a 500, repeat scrapes keep counting
    telem = TelemetryServer(sup.merged_metrics, sup.fleet_view,
                            host="127.0.0.1", port=0)
    try:
        status, body = _get("127.0.0.1", telem.port, "/metrics")
        assert status == 200
        assert telemetry.sum_family(
            body.decode(), "serving_requests_total") >= 5.0
        assert skipped() == before + 2
    finally:
        telem.close()


# --------------------------------------------------- supervisor e2e


def _get(host, port, path):
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=30) as r:
        return r.status, r.read()


def _post(port, endpoint, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{endpoint}", data=body.encode(),
        method="POST", headers=dict({"Content-Type": "text/plain"},
                                    **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _wait_live_replicas(sup, n, timeout=30.0):
    deadline = time.time() + timeout
    hb = None
    while time.time() < deadline:
        try:
            hb = json.loads(open(sup.heartbeat_path).read())
        except (OSError, ValueError):
            hb = None
        if hb:
            live = [r for r in hb["replicas"] if r["alive"] and r["port"]]
            if len(live) >= n:
                return hb
        time.sleep(0.05)
    raise AssertionError(f"never reached {n} live replicas; last={hb}")


def test_supervisor_merged_metrics_equal_replica_sum_and_fleet(
        tmp_path, fake_extractor, monkeypatch):
    """Acceptance pin: a 2-replica supervisor serves merged /metrics
    whose request counters equal the sum of the per-replica counters
    under concurrent load — plus the /fleet JSON view (breaker state,
    shed rate, staleness, fingerprints) and the proxy-port /metrics
    interception (never round-robined to one replica)."""
    from code2vec_tpu.config import Config
    from code2vec_tpu.serving.supervisor import Supervisor
    monkeypatch.setenv("C2V_SERVE_FORCE_PROXY", "1")
    overrides = dict(
        serve_host="127.0.0.1", max_contexts=16, serve_batch_size=4,
        serve_buckets="4,8",
        serve_cache_entries=0, extractor_pool_size=1,
        serve_drain_timeout_s=5.0, serve_heartbeat_interval_s=0.2)
    overrides_path = tmp_path / "child-config.json"
    overrides_path.write_text(json.dumps(overrides))
    config = Config(
        serve=True, serve_host="127.0.0.1", serve_port=0,
        serve_replicas=2, serve_max_restarts=5,
        serve_heartbeat_interval_s=0.2, serve_drain_timeout_s=5.0,
        serve_telemetry_port=0,
        heartbeat_file=str(tmp_path / "supervisor.heartbeat.json"),
        verbose_mode=0)
    sup = Supervisor(config, child_command=[
        sys.executable, CHILD, str(overrides_path)])
    rc_holder = {}
    thread = threading.Thread(
        target=lambda: rc_holder.update(rc=sup.run()), daemon=True)
    thread.start()
    try:
        hb = _wait_live_replicas(sup, 2)
        assert hb["telemetry_port"] == sup._telemetry.port
        tport = hb["telemetry_port"]

        # concurrent load through the public (proxy) port
        n_requests, n_threads = 12, 4
        statuses = []
        lock = threading.Lock()

        def load(ci):
            for i in range(n_requests // n_threads):
                status, _, _ = _post(
                    sup.port, "predict",
                    f"class L{ci}x{i} {{ int m{ci}x{i}() "
                    f"{{ return 1; }} }}")
                with lock:
                    statuses.append(status)

        threads = [threading.Thread(target=load, args=(ci,))
                   for ci in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert statuses == [200] * n_requests

        # the proxy must carry trace headers BOTH ways: an inbound
        # traceparent reaches the replica (same trace id end to end)
        # and the replica's X-Trace-Id/traceparent reach the client
        inbound = "ab" * 16
        status, _, hdrs = _post(
            sup.port, "predict",
            "class P { int proxied() { return 1; } }",
            headers={"traceparent":
                     f"00-{inbound}-{'cd' * 8}-01"})
        assert status == 200
        assert hdrs["X-Trace-Id"] == inbound
        assert hdrs["traceparent"].split("-")[1] == inbound
        expected_total = n_requests + 1  # the traced request counts too

        # The supervisor folds its OWN process registry into the merge
        # (as replica="supervisor") — in this test the supervisor runs
        # IN the pytest process, whose registry carries counts from
        # earlier serving tests, so the acceptance equality is on the
        # merge MINUS the supervisor-process contribution (constant
        # here: nothing serves in-process during this test).
        from code2vec_tpu import obs
        sup_own = telemetry.sum_family(
            obs.default_registry().render_prometheus(),
            "serving_requests_total")
        # replica snapshots are rewritten every 0.2s: poll the MERGED
        # endpoint until every request is visible
        deadline = time.time() + 20
        merged_total = per_replica = None
        while time.time() < deadline:
            _, merged_body = _get("127.0.0.1", tport, "/metrics")
            merged_total = telemetry.sum_family(
                merged_body.decode(),
                "serving_requests_total") - sup_own
            per_replica = []
            for r in sup.replicas:
                try:
                    text = open(r.metrics_path).read()
                except OSError:
                    text = ""
                per_replica.append(telemetry.sum_family(
                    text, "serving_requests_total"))
            if merged_total >= expected_total:
                break
            time.sleep(0.1)
        # THE acceptance equality: merged == sum over replicas == load
        assert merged_total == expected_total
        assert sum(per_replica) == expected_total
        # the proxy spread load over BOTH replicas (round-robin), so a
        # one-replica scrape would undercount — the gap being fixed
        assert all(v > 0 for v in per_replica)
        # gauges export per replica, not summed
        merged_text = merged_body.decode()
        assert 'extractor_pool_size{replica="0"}' in merged_text
        assert 'extractor_pool_size{replica="1"}' in merged_text
        # public (proxy) port serves the SAME merged view
        _, pub_body = _get("127.0.0.1", sup.port, "/metrics")
        assert telemetry.sum_family(
            pub_body.decode(), "serving_requests_total") >= n_requests

        # /fleet: the ROADMAP fleet item's signal set
        _, fleet_body = _get("127.0.0.1", tport, "/fleet")
        fleet = json.loads(fleet_body)
        assert fleet["mode"] == "proxy"
        assert fleet["replica_count"] == 2 and not fleet["escalated"]
        assert len(fleet["replicas"]) == 2
        fingerprints = set()
        for r in fleet["replicas"]:
            assert r["alive"] and r["restarts"] == 0
            assert r["status"] == "serving"
            assert r["heartbeat_age_s"] < fleet["stale_after_s"]
            assert r["breakers"] == {"extractor": "closed",
                                     "device": "closed"}
            assert r["shed_rate"] == 0.0
            assert r["requests_total"] > 0
            fingerprints.add(r["model_fingerprint"])
        assert len(fingerprints) == 2  # per-pid fake fingerprints
        assert sum(r["requests_total"]
                   for r in fleet["replicas"]) == expected_total
        # /fleet on the public proxy port too
        _, pub_fleet = _get("127.0.0.1", sup.port, "/fleet")
        assert json.loads(pub_fleet)["replica_count"] == 2
    finally:
        sup._stop.set()
        thread.join(timeout=40)
    assert rc_holder.get("rc") == 0
