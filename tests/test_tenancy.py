"""Tenant-fair serving suite (code2vec_tpu/serving/tenancy.py + the
tenant threading through admission, batchers, server and fleet):

- weight/qps spec parsing laws and their Config-validation surfacing;
- deterministic token-bucket refill against an injected clock, and the
  BUGFIX pin: a tenant_quota shed's Retry-After derives from THAT
  tenant's bucket refill time, never the fleet-wide EWMA estimate;
- admission share laws: a lone tenant owns the whole queue (work
  conservation ⇒ tenancy on for one tenant == tenancy off), contending
  tenants converge to weighted shares (1:2:4 ⇒ accepted ratios within
  10% under saturation), per-tenant depth bounds sum to <= max_depth,
  an idle tenant keeps its share inside the active window and releases
  it after;
- `other`-bucket label collapse + the bounded-cardinality registration
  guard (the registry can never grow unbounded tenant label values);
- dwrr_take interleave laws (single tenant ⇒ None: the byte-identical
  FIFO path);
- end-to-end byte-equality: a single tenant's responses with tenancy
  ON equal the tenancy-OFF bytes;
- satellite pins: the pipeline manifest records its promote model
  group, FleetSwapDriver refuses an unmapped group naming the fleet's
  known groups;
- the slow tenant-overload chaos drill: a hot tenant floods a real
  HTTP server while an in-share tenant keeps serving (run via
  scripts/run_chaos.sh under TENANCY_BUDGET).
"""

import dataclasses
import json
import threading
import urllib.error
import urllib.request

import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.serving.tenancy import (
    DEFAULT_TENANT, OTHER_LABEL, TENANT_HEADER, TenantPolicy,
    TokenBucket, dwrr_take, parse_tenant_qps, parse_tenant_weights,
    tenant_metric,
)

from test_serving import (  # noqa: F401 — fixtures
    _serving_config, fake_extractor, served_model,
)

pytestmark = pytest.mark.tenancy


class _Clock:
    """Injectable monotonic clock: tests advance it explicitly so
    bucket refill and active-window behavior are exact, not timing."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------ spec parsing


def test_parse_tenant_weights_laws():
    assert parse_tenant_weights("") == {}
    assert parse_tenant_weights(None) == {}
    assert parse_tenant_weights("acme") == {"acme": 1.0}
    assert parse_tenant_weights(" acme=4, dev=1.5 ,ci ") == {
        "acme": 4.0, "dev": 1.5, "ci": 1.0}
    for bad in ("=2", "acme=0", "acme=-1", "acme=x", "a=1,a=2"):
        with pytest.raises(ValueError):
            parse_tenant_weights(bad)


def test_parse_tenant_qps_laws():
    assert parse_tenant_qps("") == {}
    assert parse_tenant_qps("5") == {"*": 5.0}
    assert parse_tenant_qps("acme=50,dev=0") == {"acme": 50.0,
                                                 "dev": 0.0}
    for bad in ("acme=-1", "acme=x", "a=1,a=2", "=3"):
        with pytest.raises(ValueError):
            parse_tenant_qps(bad)


def test_config_validates_tenancy_knobs():
    # a typo'd share spec fails at startup, not silently in production
    with pytest.raises(ValueError, match="serve_tenants"):
        Config(train_data_path_prefix="x",
               serve_tenants="acme=0").verify()
    with pytest.raises(ValueError, match="serve_tenant_qps"):
        Config(train_data_path_prefix="x",
               serve_tenant_qps="acme=-2").verify()
    with pytest.raises(ValueError, match="serve_tenant_default_weight"):
        Config(train_data_path_prefix="x", serve_tenants="acme=1",
               serve_tenant_default_weight=0.0).verify()
    Config(train_data_path_prefix="x", serve_tenants="acme=4,dev=1",
           serve_tenant_qps="acme=50").verify()


def test_policy_from_config_off_means_none():
    assert TenantPolicy.from_config(Config()) is None
    pol = TenantPolicy.from_config(Config(serve_tenants="a=2"))
    assert pol is not None and pol.weight("a") == 2.0


# -------------------------------------------------- identity collapse


def test_resolve_and_label_collapse():
    pol = TenantPolicy({"acme": 4.0, "dev": 1.0})
    assert TenantPolicy.resolve(None) == DEFAULT_TENANT
    assert TenantPolicy.resolve("  ") == DEFAULT_TENANT
    assert TenantPolicy.resolve(" acme ") == "acme"
    assert pol.label("acme") == "acme"
    assert pol.label(None) == DEFAULT_TENANT
    # every unconfigured tenant collapses into ONE bucket: the label
    # set is closed no matter what clients put in X-Tenant
    assert pol.label("fuzz-1") == OTHER_LABEL
    assert pol.label("fuzz-2") == OTHER_LABEL
    assert pol.labels == ("acme", "dev", DEFAULT_TENANT, OTHER_LABEL)


def test_tenant_metric_cardinality_guard():
    pol = TenantPolicy({"acme": 1.0})
    # the registry refuses unbounded tenant label values ...
    with pytest.raises(ValueError, match="outside the configured"):
        tenant_metric("counter", "serving_requests_total", "h",
                      "fuzz-1", pol.labels)
    # ... and any metric name outside the closed tenant-family set
    with pytest.raises(ValueError, match="not a tenant-labeled"):
        tenant_metric("counter", "bogus_total", "h", "acme",
                      pol.labels)
    c = tenant_metric("counter", "serving_requests_shed_total",
                      "requests shed before the model ran, by reason",
                      "acme", pol.labels, reason="test_guard")
    before = c.value
    c.inc()
    assert c.value == before + 1


def test_dynamic_registration_allowlist_mirrors_tenant_metrics():
    """scripts/check_metrics_doc.py's closed allowlist and tenancy.py's
    guard set must stay the same tuple — the doc gate is only as
    honest as this mirror."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_metrics_doc",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "check_metrics_doc.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from code2vec_tpu.serving import tenancy
    declared = mod._DYNAMIC_REGISTRATIONS[
        os.path.join("serving", "tenancy.py")]
    assert tuple(declared) == tenancy._TENANT_METRICS


# ------------------------------------------------------- token bucket


def test_token_bucket_refill_is_deterministic():
    clock = _Clock()
    b = TokenBucket(2.0, clock=clock)  # burst = max(1, 2) = 2
    assert b.try_take() and b.try_take()
    assert not b.try_take()
    assert b.retry_after_s() == pytest.approx(0.5)  # (1-0)/2 qps
    clock.advance(0.5)
    assert b.try_take()
    assert not b.try_take()
    clock.advance(0.25)
    assert b.retry_after_s() == pytest.approx(0.25)
    # refill caps at burst: a long idle gap is not a storm credit
    clock.advance(100.0)
    assert b.try_take() and b.try_take() and not b.try_take()


def test_zero_rate_bucket_blocks_hard():
    pol = TenantPolicy({"a": 1.0}, qps={"a": 0.0})
    assert pol.bucket("a") is None  # 0 = uncapped, not blocked
    b = TokenBucket(0.0, burst=0.0, clock=_Clock())
    assert not b.try_take()
    assert b.retry_after_s() == 60.0


def test_shared_star_qps_and_per_label_buckets():
    pol = TenantPolicy({"a": 1.0, "b": 1.0}, qps={"*": 5.0, "b": 1.0})
    assert pol.bucket("a").rate == 5.0
    assert pol.bucket("b").rate == 1.0
    assert pol.bucket("a") is pol.bucket("a")  # one bucket per label


# ------------------------------------------------- admission fairness


def _policy_controller(weights, max_depth, clock=None, qps=None,
                       concurrency=1):
    from code2vec_tpu.serving.admission import AdmissionController
    pol = TenantPolicy(weights, qps=qps, clock=clock or _Clock())
    return AdmissionController(max_depth=max_depth,
                               concurrency=concurrency,
                               tenancy=pol), pol


def test_lone_tenant_owns_the_whole_queue():
    """Work conservation: with no contention the share bound IS the
    global bound — tenancy on with one tenant == tenancy off."""
    ac, _ = _policy_controller({"a": 1.0, "b": 2.0}, max_depth=8)
    for _ in range(8):
        ac.admit(tenant="a")
    from code2vec_tpu.serving.admission import Shed
    with pytest.raises(Shed) as e:
        ac.admit(tenant="a")
    # the 9th refusal is the GLOBAL queue, not a share cap
    assert e.value.reason == "queue_full"


def test_contending_tenants_get_weighted_bounds():
    clock = _Clock()
    ac, _ = _policy_controller({"a": 1.0, "b": 2.0, "c": 5.0},
                               max_depth=16, clock=clock)
    from code2vec_tpu.serving.admission import Shed
    # all three probe: each lands in the active set
    for t in ("a", "b", "c"):
        ac.admit(tenant=t)
    # bounds are floor(depth * w / total): 2, 4, 10 — summing <= 16,
    # so an in-share tenant can never be refused by the global gate
    assert ac.tenant_bound("a") == 2
    assert ac.tenant_bound("b") == 4
    assert ac.tenant_bound("c") == 10
    # c floods to its bound, then sheds tenant_quota — while a still
    # admits (the most-over-share tenant is always the first refused)
    for _ in range(9):
        ac.admit(tenant="c")
    with pytest.raises(Shed) as e:
        ac.admit(tenant="c")
    assert e.value.reason == "tenant_quota"
    assert "fair share" in str(e.value)
    ac.admit(tenant="a")  # in-share tenant keeps admitting


def test_idle_tenant_releases_share_after_active_window():
    clock = _Clock()
    ac, pol = _policy_controller({"a": 1.0, "b": 1.0}, max_depth=8,
                                 clock=clock)
    ac.admit(tenant="b")
    ac.finish(0.01, tenant="b")
    # inside the window b still reserves half the queue ...
    assert ac.tenant_bound("a") == 4
    # ... and after it (with zero in flight) the queue is a's again
    clock.advance(pol.active_window_s + 1.0)
    assert ac.tenant_bound("a") == 8


def test_saturated_shares_converge_to_weights():
    """The fairness law the drill measures: under saturation with
    equal service times, accepted throughput converges to the 1:2:4
    weights within 10%."""
    from code2vec_tpu.serving.admission import Shed
    clock = _Clock()
    ac, _ = _policy_controller({"a": 1.0, "b": 2.0, "c": 4.0},
                               max_depth=14, clock=clock)
    tenants = ("a", "b", "c")
    accepted = {t: 0 for t in tenants}
    inflight = []
    for i in range(4000):
        clock.advance(0.001)
        for t in tenants:  # every tenant has infinite backlog
            try:
                ac.admit(tenant=t)
                inflight.append(t)
                accepted[t] += 1
            except Shed:
                pass
        if inflight:  # equal service time: complete the oldest
            done = inflight.pop(0)
            ac.finish(0.01, tenant=done)
    total = sum(accepted.values())
    shares = {t: accepted[t] / total for t in tenants}
    assert shares["a"] == pytest.approx(1 / 7, rel=0.10), shares
    assert shares["b"] == pytest.approx(2 / 7, rel=0.10), shares
    assert shares["c"] == pytest.approx(4 / 7, rel=0.10), shares


def test_rate_quota_retry_after_is_the_buckets_not_the_ewma():
    """THE BUGFIX PIN: an over-quota tenant's Retry-After derives from
    its own token-bucket refill time. A fleet under heavy load has a
    huge queue-wait EWMA; leaking that into a quota shed would tell a
    blocked tenant to back off for the whole fleet's drain time."""
    from code2vec_tpu.serving.admission import Shed
    clock = _Clock()
    ac, _ = _policy_controller({"a": 1.0}, max_depth=64, clock=clock,
                               qps={"a": 0.25})
    # poison the fleet-wide estimate: 50s EWMA, deep queue
    ac._ewma_s = 50.0
    ac.admit(tenant="a")  # burst token
    with pytest.raises(Shed) as e:
        ac.admit(tenant="a")
    assert e.value.reason == "tenant_quota"
    assert "rate quota" in str(e.value)
    # bucket: rate 0.25 ⇒ a whole token in 4s — NOT 50s * depth
    assert e.value.retry_after_s == pytest.approx(4.0, abs=0.1)


def test_share_shed_retry_after_is_tenant_scoped():
    """A share shed waits for the TENANT's in-flight work to drain,
    not the whole queue's."""
    from code2vec_tpu.serving.admission import Shed
    clock = _Clock()
    ac, _ = _policy_controller({"a": 1.0, "b": 1.0}, max_depth=8,
                               clock=clock, concurrency=1)
    ac._ewma_s = 2.0
    ac.admit(tenant="b")  # contention: a's bound becomes 4
    for _ in range(4):
        ac.admit(tenant="a")
    with pytest.raises(Shed) as e:
        ac.admit(tenant="a")
    assert e.value.reason == "tenant_quota"
    # 2s EWMA * 4 held / 1 concurrency = 8s; the GLOBAL estimate would
    # be 2 * 8 = 16s
    assert e.value.retry_after_s == pytest.approx(8.0)


def test_admission_without_tenant_is_unchanged():
    """tenancy=None (or tenant=None) keeps the PR-9 gate bit-for-bit:
    same reasons, same bookkeeping."""
    from code2vec_tpu.serving.admission import (
        AdmissionController, Shed,
    )
    ac = AdmissionController(max_depth=2)
    ac.admit()
    ac.admit()
    with pytest.raises(Shed) as e:
        ac.admit()
    assert e.value.reason == "queue_full"
    ac.finish(0.01)
    ac.admit()


# ---------------------------------------------------------- DWRR laws


class _Row:
    def __init__(self, tenant, n=1):
        self.tenant = tenant
        self.lines = ["x"] * n


def test_dwrr_single_tenant_returns_none():
    # one tenant pending ⇒ the caller keeps its FIFO path (the
    # byte-equality mechanism for the tenancy-on single-tenant case)
    assert dwrr_take([_Row("a"), _Row("a")], 4, lambda t: 1.0, {}) \
        is None
    assert dwrr_take([], 4, lambda t: 1.0, {}) is None


def test_dwrr_interleaves_by_weight():
    pol = TenantPolicy({"a": 1.0, "b": 3.0})
    pending = [_Row("a") for _ in range(8)] + \
              [_Row("b") for _ in range(8)]
    state = {}
    picked = dwrr_take(pending, 4, pol.weight, state)
    assert picked is not None and len(picked) == 4
    by_tenant = [pending[i].tenant for i in picked]
    # weight 1:3 over a 4-row batch ⇒ 1 a-row, 3 b-rows
    assert by_tenant.count("a") == 1 and by_tenant.count("b") == 3
    # FIFO within a tenant
    a_rows = [i for i in picked if pending[i].tenant == "a"]
    assert a_rows == sorted(a_rows)


def test_dwrr_oversized_head_dispatches_alone():
    pol = TenantPolicy({"a": 1.0, "b": 1.0})
    pending = [_Row("a", n=10), _Row("b", n=1)]
    picked = dwrr_take(pending, 4, pol.weight, {})
    # the first take is always allowed (an oversized request must not
    # deadlock), and nothing else fits after it
    assert picked == [0]


def test_dwrr_carries_deficit_across_batches():
    pol = TenantPolicy({"a": 1.0, "b": 1.0})
    state = {}
    pending = [_Row("a") for _ in range(6)] + \
              [_Row("b") for _ in range(6)]
    first = dwrr_take(pending, 4, pol.weight, state)
    remaining = [p for i, p in enumerate(pending) if i not in first]
    second = dwrr_take(remaining, 4, pol.weight, state)
    counts = {"a": 0, "b": 0}
    for idx_set, pool in ((first, pending), (second, remaining)):
        for i in idx_set:
            counts[pool[i].tenant] += 1
    # equal weights ⇒ equal service over two batches
    assert counts["a"] == counts["b"] == 4


def test_classic_batcher_dwrr_under_two_tenants():
    """With two tenants backed up, a filled batch carries both in
    weighted proportion instead of one tenant's FIFO run."""
    import time as _time

    from code2vec_tpu.serving.batcher import DynamicBatcher
    pol = TenantPolicy({"a": 1.0, "b": 1.0})
    seen = []
    gate = threading.Event()

    def predict(lines):
        if list(lines) == ["warm"]:
            gate.wait(timeout=5)  # hold the dispatcher: backlogs build
        seen.append(list(lines))
        return [f"r:{ln}" for ln in lines]

    b = DynamicBatcher(max_batch_rows=4, predict_fn=predict,
                       tenancy=pol)
    try:
        warm = b.submit(["warm"], tenant="a")
        _time.sleep(0.2)  # dispatcher is now blocked inside predict
        futs = [b.submit([f"a{i}"], tenant="a") for i in range(4)]
        futs += [b.submit([f"b{i}"], tenant="b") for i in range(4)]
        gate.set()
        assert warm.result(timeout=5)
        for f in futs:
            assert f.result(timeout=5)
    finally:
        gate.set()
        b.drain(timeout=5)
    first_full = next(batch for batch in seen
                      if len(batch) == 4 and "warm" not in batch)
    tenants = ["a" if ln.startswith("a") else "b" for ln in first_full]
    assert tenants.count("a") == 2 and tenants.count("b") == 2, seen


# ----------------------------------------- satellite pins: fleet/pipe


def test_manifest_records_promote_model_group(tmp_path):
    from code2vec_tpu.pipeline.manifest import PipelineManifest
    m = PipelineManifest.load_or_create(str(tmp_path), "fp1",
                                        ["ingest"], model="prod")
    assert m.data["model"] == "prod"
    # survives reload (a postmortem reads it off the file)
    m2 = PipelineManifest.load_or_create(str(tmp_path), "fp1",
                                         ["ingest"])
    assert m2.data["model"] == "prod"


def test_fleet_swap_refuses_unmapped_model_group_naming_known():
    """A promote for a model group the router's --fleet_models map
    does not know fails EARLY with the known groups in the message,
    not ambiguously at canary convergence."""
    from code2vec_tpu.serving.fleet.swap import FleetSwapDriver

    class _Control:
        models = ["default", "prod"]

        def swap_hosts(self, model):
            return None if model not in self.models else []

    driver = FleetSwapDriver(_Control())
    with pytest.raises(ValueError) as e:
        driver.request("artifact-dir", model="staging")
    msg = str(e.value)
    assert "staging" in msg
    assert "default" in msg and "prod" in msg
    assert "--fleet_models" in msg


def test_x_tenant_rides_the_forwarding_contract():
    from code2vec_tpu.serving.forwarding import REQUEST_FORWARD_HEADERS
    assert TENANT_HEADER in REQUEST_FORWARD_HEADERS
    assert "X-Model" in REQUEST_FORWARD_HEADERS
    assert "X-Deadline-Ms" in REQUEST_FORWARD_HEADERS


# ------------------------------------------- end-to-end byte equality


def test_single_tenant_bytes_equal_tenancy_off(served_model,
                                               fake_extractor):
    """The zero-behavior-change contract, end to end: one tenant's
    responses with tenancy ON are byte-identical to tenancy OFF, for
    the named tenant, the default tenant and an unconfigured one."""
    from code2vec_tpu.serving.server import PredictionServer
    codes = [
        "class A { int f(int n) { return n; } } NCTX2",
        "class B { int g() { return 2; } } NCTX1",
    ]
    off = PredictionServer(served_model, served_model.config,
                           log=lambda m: None)
    on = PredictionServer(
        served_model,
        dataclasses.replace(served_model.config,
                            serve_tenants="acme=4,dev=1",
                            serve_tenant_qps="acme=1000"),
        log=lambda m: None)
    try:
        assert off.tenancy is None and on.tenancy is not None
        for tenant in (None, "acme", "unconfigured-tenant"):
            for endpoint in ("predict", "embed"):
                for code in codes:
                    s1, b1, _ = off.handle_request(endpoint, code,
                                                   tenant=tenant)
                    s2, b2, _ = on.handle_request(endpoint, code,
                                                  tenant=tenant)
                    assert (s1, s2) == (200, 200)
                    assert b1 == b2, (tenant, endpoint, code)
        # healthz: the tenancy block appears ONLY when the policy is on
        assert "tenancy" not in off.healthz()
        hz = on.healthz()["tenancy"]
        assert hz["tenants"]["acme"]["weight"] == 4.0
        assert hz["tenants"]["acme"]["qps"] == 1000.0
    finally:
        off.drain(timeout=10)
        on.drain(timeout=10)


# --------------------------------------------- chaos: overload drill


def _http_post(port, endpoint, body, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{endpoint}", data=body.encode(),
        method="POST", headers=dict({"Content-Type": "text/plain"},
                                    **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


@pytest.mark.slow
@pytest.mark.chaos
def test_tenant_overload_drill(served_model, fake_extractor):
    """A hot tenant hammering a rate quota sheds tenant_quota with a
    per-tenant Retry-After while an in-share tenant keeps serving with
    ZERO sheds — the in-process version of the fleet drill."""
    from code2vec_tpu.serving.server import PredictionServer
    srv = PredictionServer(
        served_model,
        dataclasses.replace(served_model.config,
                            serve_tenants="hot=1,cold=1",
                            serve_tenant_qps="hot=2",
                            serve_queue_depth=32),
        log=lambda m: None)
    srv.start(port=0)
    hot_results = []

    def flood():
        for i in range(20):
            status, body, headers = _http_post(
                srv.port, "predict",
                f"class H {{ int f{i}() {{ return {i}; }} }}",
                headers={TENANT_HEADER: "hot"})
            hot_results.append((status, body, headers))

    try:
        threads = [threading.Thread(target=flood) for _ in range(3)]
        for t in threads:
            t.start()
        cold = []
        for i in range(10):
            cold.append(_http_post(
                srv.port, "predict",
                f"class C {{ int g{i}() {{ return {i}; }} }}",
                headers={TENANT_HEADER: "cold"}))
        for t in threads:
            t.join(timeout=60)
        # the in-share tenant never shed
        assert all(s == 200 for s, _, _ in cold), \
            [(s, b[:80]) for s, b, _ in cold]
        sheds = [(s, b, h) for s, b, h in hot_results if s == 503]
        oks = [s for s, _, _ in hot_results if s == 200]
        assert oks, "the hot tenant must still get its quota through"
        assert sheds, "60 rapid-fire requests at 2 qps must shed"
        for s, body, headers in sheds:
            payload = json.loads(body)
            assert payload["shed"] == "tenant_quota", payload
            # honest, per-tenant retry hint (jittered int >= 1)
            assert int(headers["Retry-After"]) >= 1
        # no malformed responses: every answer parsed as JSON with a
        # terminal status
        for s, body, _ in hot_results + cold:
            assert s in (200, 503), (s, body[:120])
            json.loads(body)
    finally:
        srv.drain(timeout=15)
