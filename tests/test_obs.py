"""Observability subsystem (code2vec_tpu/obs): registry semantics,
Prometheus text rendering, span tracer + Chrome trace export, the
atomic file exporters and the /metrics HTTP endpoint — plus a tier-1
smoke test that runs a tiny train loop and asserts the heartbeat file,
Prometheus snapshot, TB event file and Chrome trace all appear with sane
contents, and regression tests for the per-batch non-finite-loss guard
(windows that the old average-only sentinel discarded unchecked)."""

import json
import os
import struct
import threading
import urllib.request

import numpy as np
import pytest

from code2vec_tpu import obs
from code2vec_tpu.data.reader import EpochEnd, RowBatch
from code2vec_tpu.obs import exporters
from code2vec_tpu.obs.metrics import MetricsRegistry
from code2vec_tpu.obs.tracer import SpanTracer, span
from code2vec_tpu.training.loop import NonFiniteLossError, Trainer


# ------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(4.0)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.1, 0.5, 2.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(2.65)
    # le is INCLUSIVE (Prometheus semantics): the 0.1 observation counts
    # in the 0.1 bucket
    assert h.cumulative_counts() == [2, 3]


def test_registration_is_idempotent_and_type_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total", point="save")
    b = reg.counter("x_total", point="save")
    assert a is b                       # same (name, labels) -> same child
    other = reg.counter("x_total", point="load")
    assert other is not a               # different labels -> sibling
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_reset_zeroes_the_series_of_a_prefix_and_keeps_the_handles():
    reg = MetricsRegistry()
    c, other = reg.counter("moe_x_total"), reg.counter("serving_x_total")
    g = reg.gauge("moe_g", stage="a")
    h = reg.histogram("moe_h", buckets=(1.0,))
    for m in (c, other, g):
        m.inc(3)
    h.observe(0.5)
    h.observe(2.0)
    reg.reset("moe_")
    assert (c.value, g.value, h.count, h.sum) == (0.0, 0.0, 0, 0.0)
    assert h.cumulative_counts() == [0] and other.value == 3
    c.inc()                 # the handle taken before still records
    h.observe(0.5)
    assert reg.counter("moe_x_total").value == 1
    assert h.cumulative_counts() == [1] and h.count == 1
    reg.reset()
    assert other.value == 0 and c.value == 0


def test_prometheus_render_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", method="get").inc(3)
    reg.gauge("temp").set(1.5)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render_prometheus()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{method="get"} 3' in text
    assert "temp 1.5" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_sum 5.05" in text
    assert "lat_seconds_count 2" in text
    assert text.endswith("\n")


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("c_total", path='we"ird\\name\n').inc()
    text = reg.render_prometheus()
    assert 'path="we\\"ird\\\\name\\n"' in text


def test_tb_scalars_flatten_histograms_and_labels():
    reg = MetricsRegistry()
    reg.counter("c_total", kind="a").inc(2)
    h = reg.histogram("h_seconds", buckets=(1.0,))
    h.observe(0.5)
    h.observe(1.5)
    tags = dict(reg.tb_scalars())
    assert tags["c_total.kind.a"] == 2.0
    assert tags["h_seconds/count"] == 2.0
    assert tags["h_seconds/sum"] == pytest.approx(2.0)
    assert tags["h_seconds/mean"] == pytest.approx(1.0)


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("h_seconds", buckets=(0.5,))

    def work():
        for _ in range(5000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40000
    assert h.count == 40000
    assert h.cumulative_counts() == [40000]


def test_default_registry_module_helpers():
    c = obs.counter("obs_selftest_total", "test counter")
    before = c.value
    obs.counter("obs_selftest_total").inc()
    assert obs.counter("obs_selftest_total").value == before + 1
    assert "obs_selftest_total" in obs.default_registry().render_prometheus()


# --------------------------------------------------------------- tracer

def test_span_times_and_feeds_histogram_even_when_tracer_disabled():
    reg = MetricsRegistry()
    tracer = SpanTracer()
    assert not tracer.enabled
    h = reg.histogram("s_seconds", buckets=(10.0,))
    with span("work", hist=h, tracer=tracer) as s:
        pass
    assert h.count == 1
    assert s.seconds >= 0
    assert len(tracer) == 0            # disabled: nothing buffered


def test_tracer_ring_buffer_bounded_and_exports_chrome_trace(tmp_path):
    tracer = SpanTracer(capacity=8)
    tracer.enable()
    for i in range(20):
        with span(f"s{i}", tracer=tracer):
            pass
    assert len(tracer) == 8            # ring buffer: newest 8 kept
    out = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(out))
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == [f"s{i}" for i in range(12, 20)]
    for e in doc["traceEvents"]:
        if e["ph"] == "X":             # Perfetto-required complete-event keys
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in doc["traceEvents"])
    assert doc["otherData"]["trace_epoch_unix_s"] > 0


def test_span_has_three_sinks_and_each_only_when_it_is_on(tmp_path):
    """One `obs.span`: the histogram always, the ring only when armed,
    and (jax is imported in this process) the annotation `c2v.<name>`
    in whatever profiler session runs, on the session's own clock."""
    import glob
    import jax
    reg = MetricsRegistry()
    tracer = SpanTracer()
    h = reg.histogram("three_seconds", buckets=(10.0,))
    with span("three_sinks", hist=h, tracer=tracer):
        pass
    assert h.count == 1 and len(tracer) == 0
    tracer.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1       # what the benchmark's serve trace uses
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    tracer.mark_profiler_start()
    try:
        with span("three_sinks", hist=h, tracer=tracer) as inside:
            pass
    finally:
        jax.profiler.stop_trace()
    with span("three_sinks", hist=h, tracer=tracer):
        pass                            # after the session: not in its file
    assert h.count == 3 and len(tracer) == 2
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = [e for plane in data.planes for line in plane.lines
             for e in line.events if e.name == "c2v.three_sinks"]
    assert len(found) == 1
    assert found[0].duration_ns == pytest.approx(inside.seconds * 1e9,
                                                 rel=0.5, abs=2e5)
    # the two files of one run can be laid over each other: the export
    # says where on its axis the profiler session began
    other = tracer.chrome_trace()["otherData"]
    assert other["trace_epoch_perf_counter_s"] > 0
    [session] = other["profiler_sessions"]
    ring = [e for e in tracer.chrome_trace()["traceEvents"]
            if e.get("name") == "three_sinks"]
    assert 0 <= session["ts"] <= ring[0]["ts"]
    assert abs(ring[0]["ts"] - session["ts"]
               - found[0].start_ns / 1e3) < 5e4     # 50 ms, in us


def test_host_worker_opens_a_span_and_imports_no_jax():
    """Router agents and host workers run with C2V_HOST_WORKER=1 and
    must stay jax-free: `obs` looks jax up in sys.modules only."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from code2vec_tpu import obs\n"
        "from code2vec_tpu.obs import tracer\n"
        "h = obs.histogram('worker_seconds')\n"
        "obs.default_tracer().enable()\n"
        "with tracer.collect() as got:\n"
        "    with obs.span('in_worker', hist=h):\n"
        "        pass\n"
        "assert h.count == 1 and len(obs.default_tracer()) == 1\n"
        "assert [g[0] for g in got] == ['in_worker']\n"
        "assert tracer._ANNOTATION is None\n"
        "print('jax' in sys.modules)\n")
    env = dict(os.environ, C2V_HOST_WORKER="1",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_compile_listener_counts_a_fresh_jit_once():
    """`jax_compile_seconds{stage}`: one observation a compile event, so
    the count inside a window is the number of compiles there; a second
    call of the same shape adds nothing, another shape adds one."""
    import jax
    import jax.numpy as jnp
    with span("registers_the_listener"):
        pass
    hist = {stage: obs.histogram("jax_compile_seconds", stage=stage)
            for stage in ("trace", "lower", "backend")}
    total = obs.counter("jax_compile_seconds_total")
    lines = []
    obs.log_compiles_from_now(lines.append)
    try:
        x = jnp.ones((3, 5))            # made before the counts are read
        before = {k: h.count for k, h in hist.items()}
        spent = total.value

        @jax.jit
        def fresh_for_the_listener(a):
            return jnp.tanh(a) @ a.T    # jnp.tanh: a jit traced inside

        fresh_for_the_listener(x).block_until_ready()
        once = {k: h.count - before[k] for k, h in hist.items()}
        assert once == {"trace": 1, "lower": 1, "backend": 1}
        assert total.value > spent
        fresh_for_the_listener(x + 1.0).block_until_ready()
        assert hist["backend"].count - before["backend"] <= 2   # the add
        seen = hist["backend"].count
        fresh_for_the_listener(x).block_until_ready()
        assert hist["backend"].count == seen
        assert any("fresh_for_the_listener" in ln for ln in lines)
        n_lines = len(lines)
    finally:
        obs.log_compiles_from_now(None)
    fresh_for_the_listener(jnp.ones((2, 2))).block_until_ready()
    assert len(lines) == n_lines        # start-up compiles are not logged


def test_span_counts_the_compiles_inside_it():
    """`jax_compiles_during{span}`: one observation a span, 1 around a
    fresh jit and 0 around its second call, so a window's sum is its
    compiles and a sound window still has something to read (0)."""
    import jax
    import jax.numpy as jnp
    hist = obs.compiles_during("a_step_for_the_test")
    x = jnp.ones((4, 3))

    @jax.jit
    def fresh_inside_a_span(a):
        return a * 2.0 - 1.0

    with span("a_step_for_the_test", compiles=hist):
        fresh_inside_a_span(x).block_until_ready()
    assert (hist.count, hist.sum) == (1, 1.0)
    with span("a_step_for_the_test", compiles=hist):
        fresh_inside_a_span(x).block_until_ready()
    assert (hist.count, hist.sum) == (2, 1.0)


def test_collect_hands_over_this_threads_spans_only():
    from code2vec_tpu.obs import tracer as tracer_mod
    done = threading.Event()

    def elsewhere():
        with span("other_thread"):
            pass
        done.set()
    with tracer_mod.collect() as got:
        with span("outer"):
            with span("inner"):
                pass
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    with span("after"):
        pass
    assert done.is_set()
    assert [name for name, _, _ in got] == ["inner", "outer"]
    assert all(seconds >= 0 and start > 0 for _, start, seconds in got)


def test_span_records_on_exception():
    tracer = SpanTracer()
    tracer.enable()
    with pytest.raises(RuntimeError):
        with span("failing", tracer=tracer):
            raise RuntimeError("boom")
    assert len(tracer) == 1            # the span still closed + recorded


def test_tracer_counts_dropped_spans_and_high_water():
    """The ring drops oldest spans silently from the FILE's point of
    view — the drops must be first-class metrics so a truncated Chrome
    trace is detectable from /metrics alone (and from the trace file's
    otherData.spans_dropped)."""
    dropped_before = obs.counter("obs_spans_dropped_total").value
    tracer = SpanTracer(capacity=4)
    tracer.enable()
    for i in range(10):
        tracer.record(f"s{i}", 0.0, 0.001)
    assert len(tracer) == 4
    assert tracer.dropped == 6
    assert tracer.high_water == 4
    assert obs.counter("obs_spans_dropped_total").value \
        == dropped_before + 6
    assert obs.gauge("obs_span_ring_high_water").value >= 4
    doc = tracer.chrome_trace()
    assert doc["otherData"]["spans_dropped"] == 6
    # under capacity: nothing dropped, high-water tracks the fill level
    small = SpanTracer(capacity=16)
    small.enable()
    small.record("only", 0.0, 0.001)
    assert small.dropped == 0 and small.high_water == 1


def test_tracer_id_tagged_spans_export_args():
    tracer = SpanTracer()
    tracer.enable()
    tracer.record("tagged", 0.0, 0.002, trace_id="a" * 32,
                  span_id="b" * 16, parent_id="c" * 16,
                  attrs={"endpoint": "predict"})
    tracer.record("plain", 0.0, 0.001)
    doc = tracer.chrome_trace()
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e["ph"] == "X"}
    args = by_name["tagged"]["args"]
    assert args["trace_id"] == "a" * 32
    assert args["span_id"] == "b" * 16
    assert args["parent_id"] == "c" * 16
    assert args["endpoint"] == "predict"
    assert "args" not in by_name["plain"]


# ------------------------------------------------------------- reqtrace

def test_traceparent_parse_and_format():
    from code2vec_tpu.obs import reqtrace
    parsed = reqtrace.parse_traceparent(
        "00-" + "a1" * 16 + "-" + "b2" * 8 + "-01")
    assert parsed == {"trace_id": "a1" * 16,
                      "parent_span_id": "b2" * 8}
    # malformed / absent / all-zero headers are ignored, never fatal
    for bad in (None, "", "garbage", "00-xyz-abc-01",
                "00-" + "0" * 32 + "-" + "b2" * 8 + "-01",
                "00-" + "a1" * 16 + "-" + "0" * 16 + "-01"):
        assert reqtrace.parse_traceparent(bad) is None
    out = reqtrace.format_traceparent("a1" * 16, "b2" * 8)
    assert reqtrace.parse_traceparent(out) == parsed
    tid, sid = reqtrace.mint_trace_id(), reqtrace.mint_span_id()
    assert len(tid) == 32 and len(sid) == 16
    assert tid != reqtrace.mint_trace_id()  # 128-bit: never collides


def test_request_trace_span_tree_and_ring_forwarding():
    from code2vec_tpu.obs import reqtrace
    from code2vec_tpu.obs.reqtrace import RequestTrace
    ring = SpanTracer()
    ring.enable()
    rt = RequestTrace(tracer=ring)
    assert rt.minted and len(rt.trace_id) == 32
    with rt.span("request", endpoint="predict") as root:
        with rt.span("cache_lookup") as sp:
            sp.attrs["hit"] = False
        # a shareable id is minted by the CALLER (the batcher's idiom
        # for the shared batch span) — add_span itself defers minting
        # to export time
        shared = reqtrace.mint_span_id()
        rt.add_span("batch", 0.0, 0.005, span_id=shared,
                    attrs={"batch_id": 7}, forward=False)
        rt.add_span("device", 0.0, 0.005, parent_id=shared)
        root.attrs["status"] = 200
    doc = rt.to_dict()
    assert doc["trace_id"] == rt.trace_id
    by_name = {s["name"]: s for s in doc["spans"]}
    assert set(by_name) == {"request", "cache_lookup", "batch", "device"}
    root_id = doc["root_span_id"]
    assert by_name["request"]["span_id"] == root_id
    assert by_name["request"]["parent_id"] is None
    assert by_name["cache_lookup"]["parent_id"] == root_id
    assert by_name["batch"]["parent_id"] == root_id
    assert by_name["device"]["parent_id"] == by_name["batch"]["span_id"]
    assert by_name["request"]["attrs"]["status"] == 200
    # the ring got every span EXCEPT the forward=False batch copy,
    # tagged with the trace id
    ring_events = [e for e in ring.chrome_trace()["traceEvents"]
                   if e["ph"] == "X"]
    ring_names = {e["name"] for e in ring_events}
    assert ring_names == {"request", "cache_lookup", "device"}
    for e in ring_events:
        assert e["args"]["trace_id"] == rt.trace_id


def test_request_trace_honors_inbound_parent():
    from code2vec_tpu.obs.reqtrace import RequestTrace
    header = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    rt = RequestTrace.from_headers(header)
    assert rt.trace_id == "ab" * 16
    assert not rt.minted
    with rt.span("request"):
        pass
    doc = rt.to_dict()
    # the root hangs under the CALLER's span: distributed tracing
    assert doc["spans"][0]["parent_id"] == "cd" * 8
    assert doc["remote_parent"] == "cd" * 8
    echoed = rt.traceparent()
    assert echoed.split("-")[1] == "ab" * 16
    assert echoed.split("-")[2] == doc["root_span_id"]
    # malformed header -> minted id, not an error
    rt2 = RequestTrace.from_headers("not-a-traceparent")
    assert rt2.minted and rt2.trace_id != rt.trace_id


# ------------------------------------------------------- flight recorder

def test_flight_recorder_rings_bounded_and_dump_schema(tmp_path):
    from code2vec_tpu.obs.flight import FlightRecorder
    rec = FlightRecorder(capacity=4, events_capacity=8)
    rec.configure(dump_dir=str(tmp_path))
    for i in range(10):
        rec.record_request(trace_id=f"t{i}", endpoint="predict",
                           status=200, duration_s=0.01,
                           phases={"extract": 0.002},
                           fingerprint="fp1")
    rec.event("swap_start", target="/x")
    path = rec.dump(reason="manual")
    doc = json.load(open(path))
    assert doc["schema_version"] == 1
    assert doc["reason"] == "manual"
    assert doc["requests_recorded"] == 10
    # ring: only the newest 4 survive
    assert [r["trace_id"] for r in doc["requests"]] \
        == ["t6", "t7", "t8", "t9"]
    req = doc["requests"][-1]
    assert req["status"] == 200
    assert req["phases_ms"]["extract"] == pytest.approx(2.0)
    assert req["fingerprint"] == "fp1"
    assert doc["events"] == [{"t": doc["events"][0]["t"],
                              "kind": "swap_start", "target": "/x"}]


def test_flight_incident_schedules_one_coalesced_dump(tmp_path):
    import time as _time
    from code2vec_tpu.obs.flight import FlightRecorder
    dumps_before = obs.counter("flight_dumps_total").value
    rec = FlightRecorder(capacity=8)
    rec.configure(dump_dir=str(tmp_path), dump_delay_s=0.15)
    rec.incident("breaker_open", breaker="extractor")
    # the delay window captures the FALLOUT: sheds recorded after the
    # incident still make the dump
    rec.record_request(trace_id="shed1", endpoint="predict", status=503,
                       duration_s=0.0, reason="breaker")
    rec.incident("breaker_open", breaker="device")  # coalesces
    deadline = _time.time() + 5
    files = []
    while _time.time() < deadline:
        files = list(tmp_path.glob("flight-*.json"))
        if files:
            break
        _time.sleep(0.02)
    assert len(files) == 1, "exactly one coalesced dump"
    doc = json.load(open(files[0]))
    assert doc["reason"] == "breaker_open"
    assert [r["trace_id"] for r in doc["requests"]] == ["shed1"]
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds.count("breaker_open") == 2
    assert all(e["incident"] for e in doc["events"])
    assert doc["incidents_coalesced"] == 1
    assert obs.counter("flight_dumps_total").value == dumps_before + 1
    assert obs.counter("flight_incidents_total",
                       kind="breaker_open").value >= 2


def test_flight_incident_immediate_dumps_synchronously(tmp_path):
    from code2vec_tpu.obs.flight import FlightRecorder
    rec = FlightRecorder()
    rec.configure(dump_dir=str(tmp_path), dump_delay_s=30.0)
    rec.record_request(trace_id="a1", endpoint="predict", status=504,
                       duration_s=2.0, reason="deadline_expired")
    rec.incident("drain_timeout", immediate=True, abandoned=1)
    files = list(tmp_path.glob("flight-*drain_timeout.json"))
    assert len(files) == 1  # no timer wait: exit paths dump NOW
    doc = json.load(open(files[0]))
    assert doc["requests"][0]["trace_id"] == "a1"


def test_flight_no_dump_dir_records_but_never_dumps(tmp_path):
    from code2vec_tpu.obs.flight import FlightRecorder
    rec = FlightRecorder()
    rec.incident("breaker_open", breaker="x")
    snap = rec.snapshot()
    assert snap["events"][0]["kind"] == "breaker_open"
    assert not list(tmp_path.iterdir())


# ------------------------------------------------------------ exporters

def test_write_prometheus_is_atomic_and_complete(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc(7)
    path = str(tmp_path / "sub" / "metrics.prom")
    exporters.write_prometheus(path, registry=reg)
    assert open(path).read() == reg.render_prometheus()
    # no tmp litter left behind
    assert os.listdir(tmp_path / "sub") == ["metrics.prom"]


def test_heartbeat_schema(tmp_path):
    path = str(tmp_path / "hb.json")
    exporters.write_heartbeat(path, status="running", step=12, epoch=3,
                              last_loss=1.25)
    hb = json.load(open(path))
    assert hb["schema_version"] == exporters.HEARTBEAT_SCHEMA_VERSION
    assert hb["step"] == 12 and hb["epoch"] == 3
    assert hb["last_loss"] == 1.25
    assert hb["status"] == "running"
    assert hb["pid"] == os.getpid()
    assert hb["wall_time"] > 1.7e9     # a real unix timestamp
    # rewrite replaces, never appends
    exporters.write_heartbeat(path, status="done", step=13)
    hb2 = json.load(open(path))
    assert hb2["step"] == 13 and hb2["status"] == "done"


def test_metrics_http_server_serves_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("served_total").inc(5)
    server = exporters.start_metrics_server(0, registry=reg)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert "served_total 5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=10)
    finally:
        exporters.stop_metrics_server(server)


# ------------------------------------------------ checkpoint-layer metrics

def test_verify_failure_counts_into_registry(tmp_path):
    from code2vec_tpu.training import checkpoint as ckpt_mod
    c = obs.counter("checkpoint_verify_failures_total")
    before = c.value
    with pytest.raises(ckpt_mod.CheckpointIntegrityError):
        ckpt_mod.verify_checkpoint(str(tmp_path / "nonexistent"))
    assert c.value == before + 1
    text = obs.default_registry().render_prometheus()
    assert "checkpoint_verify_seconds_bucket" in text


def test_fault_fire_counts_into_registry():
    from code2vec_tpu.utils import faults
    faults.reset("obs_probe=raise")
    try:
        with pytest.raises(faults.FaultInjected):
            faults.fault_point("obs_probe")
    finally:
        faults.reset(None)
    c = obs.counter("fault_injected_total", point="obs_probe",
                    action="raise")
    assert c.value == 1


# ------------------------------------------------------ train-loop smoke

def _fake_batch(n=2, m=4):
    return RowBatch(
        source_token_indices=np.ones((n, m), np.int32),
        path_indices=np.ones((n, m), np.int32),
        target_token_indices=np.ones((n, m), np.int32),
        context_valid_mask=np.ones((n, m), np.float32),
        target_index=np.ones((n,), np.int32),
        example_valid=np.ones((n,), bool))


def _marker_stream(batches_per_epoch, epochs):
    for e in range(epochs):
        for _ in range(batches_per_epoch):
            yield _fake_batch()
        yield EpochEnd(e + 1)


class _State:
    step = np.zeros((), np.int32)


def test_train_loop_emits_heartbeat_snapshot_tb_and_trace(tiny_config,
                                                          tmp_path):
    """Tier-1 smoke for the whole export surface: one tiny train run with
    every sink configured produces (a) a JSON heartbeat with step/epoch/
    loss, (b) a Prometheus snapshot with the step-breakdown histograms,
    (c) a TB event file carrying the obs/ tags, (d) a Perfetto-loadable
    Chrome trace with the per-batch host spans."""
    tiny_config.num_train_epochs = 1
    tiny_config.num_batches_to_log_progress = 2
    tiny_config.verbose_mode = 0
    tiny_config.use_tensorboard = True
    tiny_config.model_save_path = str(tmp_path / "model")
    tiny_config.metrics_file = str(tmp_path / "metrics.prom")
    tiny_config.heartbeat_file = str(tmp_path / "heartbeat.json")
    tiny_config.trace_export = str(tmp_path / "trace.json")

    def train_step(state, *args):
        return state, np.float32(2.0)

    saves = []
    trainer = Trainer(tiny_config, train_step,
                      save_fn=lambda s, e, suffix="": saves.append(e))
    try:
        trainer.train(_State(), _marker_stream(6, 1),
                      rng=np.zeros((2,), np.uint32))
    finally:
        obs.default_tracer().disable()

    # (a) heartbeat: final state says the run finished cleanly
    hb = json.load(open(tiny_config.heartbeat_file))
    assert hb["status"] == "done"
    assert hb["step"] == 6
    assert hb["epoch"] == 1
    assert hb["last_loss"] == pytest.approx(2.0)
    assert hb["rss_bytes"] > 0

    # (b) Prometheus snapshot: step-time breakdown + loop counters
    prom = open(tiny_config.metrics_file).read()
    assert "train_data_wait_seconds_bucket" in prom
    assert "train_step_dispatch_seconds_bucket" in prom
    assert "train_loss_sync_seconds_bucket" in prom
    assert "train_last_avg_loss 2" in prom
    assert "train_epochs_total" in prom

    # (c) TB event file exists and carries both the classic train/ tags
    # and the registry dump under obs/
    tb_dir = tiny_config.tensorboard_dir
    events = [f for f in os.listdir(tb_dir) if "tfevents" in f]
    assert len(events) == 1
    blob = open(os.path.join(tb_dir, events[0]), "rb").read()
    assert b"train/loss" in blob
    assert b"obs/train_batches_total" in blob

    # (d) Chrome trace: per-batch host spans, Perfetto-loadable JSON
    doc = json.load(open(tiny_config.trace_export))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "step_dispatch" in names
    assert "data_wait" in names
    assert "loss_sync" in names

    assert saves == [1]                # the loop itself behaved normally


def test_prefetch_worker_times_its_read_and_its_busy_share():
    """The feed's headroom: the worker observes `prefetch_read_seconds`
    (its next() on the reader) and `prefetch_busy_seconds` (read + pack)
    once a batch, not for an epoch marker and not while it waits on a
    full queue."""
    import time
    from code2vec_tpu.utils.prefetch import DevicePrefetcher
    hist = {n: obs.histogram(f"prefetch_{n}_seconds")
            for n in ("read", "pack", "busy")}
    before = {n: (h.count, h.sum) for n, h in hist.items()}

    def slow_reader():
        for _ in range(5):
            time.sleep(0.01)
            yield _fake_batch()
        time.sleep(0.05)                # the marker's read is no batch's
        yield EpochEnd(1)

    got = []
    for item in DevicePrefetcher(slow_reader(), mesh=None, depth=1):
        time.sleep(0.02)                # a slow consumer: the queue fills
        got.append(item)
    assert len(got) == 6 and isinstance(got[-1], EpochEnd)
    count = {n: hist[n].count - before[n][0] for n in hist}
    spent = {n: hist[n].sum - before[n][1] for n in hist}
    assert count == {"read": 5, "pack": 5, "busy": 5}
    assert 0.05 * 0.9 <= spent["read"] < 0.05 + 0.04
    assert spent["busy"] == pytest.approx(spent["read"] + spent["pack"],
                                          abs=1e-6)


def test_train_loop_with_obs_disabled_writes_nothing(tiny_config, tmp_path):
    """Default config: no heartbeat/snapshot/trace files appear and the
    loop runs exactly as before (the instrumentation is passive)."""
    tiny_config.num_train_epochs = 1
    tiny_config.verbose_mode = 0

    def train_step(state, *args):
        return state, np.float32(1.0)

    trainer = Trainer(tiny_config, train_step)
    trainer.train(_State(), _marker_stream(3, 1),
                  rng=np.zeros((2,), np.uint32))
    assert not any(p.name.endswith((".prom", ".json"))
                   for p in tmp_path.iterdir())


# ----------------------------------- per-batch non-finite guard (ROADMAP)

def test_nan_batch_caught_when_eval_reset_would_discard_it(tiny_config):
    """Regression for the average-only sentinel's blind spot: a poisoned
    batch in a window that a mid-epoch eval drains used to be DISCARDED
    unchecked (the eval reset cleared pending_losses). The per-batch
    guard must trip the halt policy there."""
    tiny_config.num_train_epochs = 1
    tiny_config.num_batches_to_log_progress = 100   # no log boundary
    tiny_config.num_train_batches_to_evaluate = 2   # eval at batch 2
    tiny_config.verbose_mode = 0
    tiny_config.on_nonfinite_loss = "halt"
    steps, saves, evals = [], [], []

    def train_step(state, *args):
        steps.append(1)
        return state, (np.float32("nan") if len(steps) == 1
                       else np.float32(1.0))

    trainer = Trainer(tiny_config, train_step,
                      evaluate_fn=lambda s: evals.append(1),
                      save_fn=lambda s, e, suffix="": saves.append(suffix))
    with pytest.raises(NonFiniteLossError, match="nan"):
        trainer.train(_State(), _marker_stream(8, 1),
                      rng=np.zeros((2,), np.uint32))
    assert len(steps) == 2             # tripped at the eval-boundary drain
    assert evals == []                 # BEFORE the eval ran
    assert saves == ["_nanhalt"]
    assert trainer.preempted


def test_nan_batch_caught_at_epoch_boundary_before_clean_save(tiny_config):
    """Same blind spot at the epoch boundary: the poisoned tail window
    must halt BEFORE the end-of-epoch clean save (which would otherwise
    become the newest resume candidate with poisoned params)."""
    tiny_config.num_train_epochs = 1
    tiny_config.num_batches_to_log_progress = 100
    tiny_config.verbose_mode = 0
    tiny_config.on_nonfinite_loss = "halt"
    saves = []

    def train_step(state, *args):
        return state, np.float32("inf")

    trainer = Trainer(tiny_config, train_step,
                      save_fn=lambda s, e, suffix="": saves.append(suffix))
    with pytest.raises(NonFiniteLossError):
        trainer.train(_State(), _marker_stream(3, 1),
                      rng=np.zeros((2,), np.uint32))
    assert saves == ["_nanhalt"]       # no clean epoch save happened


def test_nan_window_halts_instead_of_preempt_checkpointing(tiny_config):
    """A preemption landing inside a NaN-poisoned window must NOT save
    the poisoned params as a resume-ELIGIBLE `_preempt` artifact: the
    drain runs first, the halt policy wins, and the state goes under
    `_nanhalt` (invisible to resume) — otherwise an auto-restarting
    scheduler would crash-loop on the NaN checkpoint."""
    import os as _os
    import signal as _signal
    tiny_config.num_train_epochs = 1
    tiny_config.num_batches_to_log_progress = 100   # no log boundary
    tiny_config.verbose_mode = 0
    tiny_config.on_nonfinite_loss = "halt"
    saves, steps = [], []

    def train_step(state, *args):
        steps.append(1)
        if len(steps) == 2:
            _os.kill(_os.getpid(), _signal.SIGTERM)
        return state, np.float32("nan")

    trainer = Trainer(tiny_config, train_step,
                      save_fn=lambda s, e, suffix="": saves.append(suffix))
    with pytest.raises(NonFiniteLossError):
        trainer.train(_State(), _marker_stream(8, 1),
                      rng=np.zeros((2,), np.uint32))
    assert saves == ["_nanhalt"]       # never a plain "_preempt"
    assert trainer.preempted


def test_nonfinite_batches_counted(tiny_config):
    tiny_config.num_train_epochs = 1
    tiny_config.num_batches_to_log_progress = 4
    tiny_config.verbose_mode = 0
    tiny_config.on_nonfinite_loss = "warn"
    c = obs.counter("train_nonfinite_loss_batches_total")
    before = c.value
    steps = []

    def train_step(state, *args):
        steps.append(1)
        return state, (np.float32("nan") if len(steps) in (2, 3)
                       else np.float32(1.0))

    trainer = Trainer(tiny_config, train_step)
    trainer.train(_State(), _marker_stream(4, 1),
                  rng=np.zeros((2,), np.uint32))
    assert c.value == before + 2       # each poisoned batch counted
