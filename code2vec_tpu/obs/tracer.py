"""Host-side span tracer: wall-time spans in a ring buffer, exportable as
Chrome trace-event JSON (loads in Perfetto / chrome://tracing / the
TensorBoard trace viewer).

This complements the device-side `jax.profiler` trace (`--profile_dir`):
the profiler shows where XLA spends device time, this shows where the
HOST spends wall time — data wait vs. dispatch vs. loss sync vs.
checkpoint saves vs. eval — which is exactly the split the device trace
cannot see.

Spans may carry request-scoped identity (obs/reqtrace.py): a trace id,
a span id and a parent span id, plus free-form attrs. Identified spans
export with an `args` payload so one serving request is reconstructable
as a TREE from the bulk Chrome trace (filter by `trace_id` in
Perfetto), not just a flat phase list.

Cost model: recording is OFF by default; a disabled tracer's
`maybe_record` is one attribute check. When enabled, each span is one
tuple append into a bounded deque (the ring buffer caps memory on long
runs — a multi-day run keeps the most recent `capacity` spans). Span
TIMING (perf_counter pairs) is done by the caller / the `span` context
manager regardless, because the same measurement usually feeds a
histogram that is always on.

One clock with the device trace: where jax is ALREADY imported, a
`span` also enters `jax.profiler.TraceAnnotation("c2v." + name)`, so
the same block lands on the host plane of whatever profiler session is
running (`--profile_dir`, a harness's own `start_trace`) beside the
device's operations. With no session open that is one atomic check
inside the annotation. jax is never imported from here: router agents
and host workers (`C2V_HOST_WORKER=1`) stay jax-free, and their spans
go to the histogram and the ring alone.

The ring DROPS the oldest span when full — silently from the file's
point of view, so the drops are first-class metrics:
`obs_spans_dropped_total` counts every overwritten span and
`obs_span_ring_high_water` records the fullest the ring has been; a
truncated Chrome trace is detectable from a /metrics scrape alone (and
from the trace file itself: `otherData.spans_dropped`).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Callable, List, Optional, Tuple

from code2vec_tpu.obs import metrics as _metrics


# Cached handles: once the ring is full, the drop counter increments on
# EVERY record() — a registry get-or-create per span (key build + the
# registry lock) inside the tracer lock would be a permanent tax for
# the rest of the process lifetime. Lazy so importing this module
# registers nothing.
_C_DROPPED = None
_G_HIGH_WATER = None


def _c_dropped():
    global _C_DROPPED
    if _C_DROPPED is None:
        _C_DROPPED = _metrics.default_registry().counter(
            "obs_spans_dropped_total",
            "spans overwritten in the tracer ring buffer (the Chrome "
            "trace export is missing at least this many oldest spans)")
    return _C_DROPPED


def _g_high_water():
    global _G_HIGH_WATER
    if _G_HIGH_WATER is None:
        _G_HIGH_WATER = _metrics.default_registry().gauge(
            "obs_span_ring_high_water",
            "max spans ever resident in the tracer ring buffer; at "
            "capacity together with obs_spans_dropped_total > 0 the "
            "exported trace is truncated")
    return _G_HIGH_WATER


class SpanTracer:
    """Bounded ring buffer of (name, start, duration, thread[, ids])
    spans."""

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        # perf_counter epoch: Chrome trace wants microsecond timestamps on
        # one monotonic axis; absolute wall time is recorded separately in
        # the metadata so runs can still be aligned to the clock.
        self._epoch = time.perf_counter()
        self._epoch_wall = time.time()
        # (perf_counter, unix) at each profiler session this process
        # started itself (--profile_dir). The profiler counts its
        # events from the session's start, so this is the offset that
        # lays its file over this one
        self._profiler_starts: List[Tuple[float, float]] = []
        self._dropped = 0
        self._high_water = 0
        self.enabled = False

    def enable(self) -> None:
        # eager metric registration: a replica that never fills its ring
        # still exports obs_spans_dropped_total=0 / high_water, so the
        # merged scrape (and the SLO/tsdb layer above it) sees the
        # series exist instead of inferring health from absence
        _c_dropped()
        _g_high_water()
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def mark_profiler_start(self) -> None:
        """Call beside `jax.profiler.start_trace`: the export's
        `otherData.profiler_sessions` then says where on this file's
        axis (`ts`, microseconds) and on the unix clock the session,
        from which the profiler counts its events, began."""
        self._profiler_starts.append((time.perf_counter(), time.time()))

    def __len__(self) -> int:
        return len(self._buf)

    @property
    def dropped(self) -> int:
        """Spans overwritten by the ring since construction."""
        return self._dropped

    @property
    def high_water(self) -> int:
        return self._high_water

    def maybe_record(self, name: str, start_s: float, dur_s: float,
                     **ids) -> None:
        """Record a completed span (perf_counter start + duration). No-op
        when disabled — the one-attr check keeps instrumented call sites
        free to call this unconditionally."""
        if not self.enabled:
            return
        self.record(name, start_s, dur_s, **ids)

    def record(self, name: str, start_s: float, dur_s: float,
               trace_id: Optional[str] = None,
               span_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               attrs: Optional[dict] = None) -> None:
        # a list, not a tuple: the last slot memoizes this span's
        # serialized Chrome-trace event. Spans are immutable once
        # recorded (attrs are captured "at close" by every call site),
        # so periodic exporters — the serve heartbeat, the control
        # poll tick — pay json encoding only for spans NEW since the
        # previous export instead of re-encoding the whole ring
        item = [name, start_s, dur_s, threading.get_ident(),
                threading.current_thread().name,
                trace_id, span_id, parent_id, attrs, None]
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1
                _c_dropped().inc()
            self._buf.append(item)
            n = len(self._buf)
            if n > self._high_water:
                self._high_water = n
                g = _g_high_water()
                # several tracer instances share the process gauge; it
                # tracks the fullest ring anywhere in the process
                if n > g.value:
                    g.set(n)

    # ------------------------------------------------------------ export

    def _serialize_chrome_trace(self) -> str:
        """Chrome trace-event JSON (`traceEvents` of `ph:"X"` complete
        events + thread/process-name metadata so Perfetto labels the
        host threads readably). Serialized by hand instead of json.dump:
        the export runs in the trainer's `finally` — including the
        preemption path, where a scheduler grace window is ticking — and
        the stdlib encoder costs seconds on a full 65536-span buffer
        (hundreds of thousands of tiny dict encodes). Span names are
        produced by our own call sites; the fields that could need
        escaping go through json.dumps."""
        with self._lock:
            spans = list(self._buf)
            dropped = self._dropped
        pid = os.getpid()
        parts = []
        seen_tids = {}
        for item in spans:
            (name, start_s, dur_s, tid, tname,
             trace_id, span_id, parent_id, attrs, cached) = item
            if tid not in seen_tids:
                seen_tids[tid] = tname
            if cached is None:
                args = ""
                if trace_id or span_id or parent_id or attrs:
                    payload = dict(attrs or {})
                    if trace_id:
                        payload["trace_id"] = trace_id
                    if span_id:
                        payload["span_id"] = span_id
                    if parent_id:
                        payload["parent_id"] = parent_id
                    args = ',"args":%s' % json.dumps(payload,
                                                     sort_keys=True)
                cached = (
                    '{"name":%s,"ph":"X","cat":"host","ts":%.3f,'
                    '"dur":%.3f,"pid":%d,"tid":%d%s}'
                    % (json.dumps(name), (start_s - self._epoch) * 1e6,
                       dur_s * 1e6, pid, tid, args))
                # idempotent fill outside any lock: every racer
                # computes the identical string for an immutable span
                item[9] = cached
            parts.append(cached)
        for tid, tname in seen_tids.items():
            parts.append(
                '{"name":"thread_name","ph":"M","pid":%d,"tid":%d,'
                '"args":{"name":%s}}' % (pid, tid, json.dumps(tname)))
        parts.append(
            '{"name":"process_name","ph":"M","pid":%d,'
            '"args":{"name":"code2vec_tpu host"}}' % pid)
        sessions = ",".join(
            '{"ts":%.3f,"unix_s":%r}' % ((perf - self._epoch) * 1e6, wall)
            for perf, wall in self._profiler_starts)
        return ('{"traceEvents":[%s],"displayTimeUnit":"ms",'
                '"otherData":{"trace_epoch_unix_s":%r,'
                '"trace_epoch_perf_counter_s":%r,'
                '"profiler_sessions":[%s],'
                '"spans_dropped":%d,'
                '"producer":"code2vec_tpu.obs.tracer"}}'
                % (",".join(parts), self._epoch_wall, self._epoch,
                   sessions, dropped))

    def chrome_trace(self) -> dict:
        """The trace as a parsed object (in-process inspection, tests);
        one serializer, so this can never drift from the exported file."""
        return json.loads(self._serialize_chrome_trace())

    def export_chrome_trace(self, path: str) -> str:
        """Atomically write the Chrome trace JSON to `path`."""
        tmp = f"{path}.tmp-{os.getpid()}"
        dirpart = os.path.dirname(os.path.abspath(path))
        os.makedirs(dirpart, exist_ok=True)
        with open(tmp, "w") as f:
            f.write(self._serialize_chrome_trace())
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path


_DEFAULT = SpanTracer()


def default_tracer() -> SpanTracer:
    return _DEFAULT


# ------------------------------------------------------ the jax side
# Looked up lazily and only in `sys.modules`: this module never imports
# jax. The first span opened in a process that has jax also registers
# the compile listener below, so nothing else has to remember to.
_ANNOTATION = None      # jax.profiler.TraceAnnotation once jax is seen


def _jax_annotation():
    global _ANNOTATION
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:        # no jax here (or still mid-import)
        return None
    _ANNOTATION = profiler.TraceAnnotation
    _register_compile_listener(jax)
    return _ANNOTATION


_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_COMPILE_HELP = (
    "jax compile events by stage, one observation an event: trace "
    "(outermost only: a jitted function traced inside another's trace "
    "is inside its time), lower, backend (XLA compile or "
    "compile-cache load)")
_module_lock = threading.Lock()    # the listener flag, the collect count
_compile_listening = False
_backend_compiles: Optional[_metrics.Histogram] = None  # once listening
_compile_local = threading.local()
_steady_log: Optional[Callable[[str], None]] = None


def _register_compile_listener(jax) -> None:
    global _compile_listening, _backend_compiles
    reg = _metrics.default_registry()
    with _module_lock:
        if _compile_listening:
            return
        hists = {stage: reg.histogram("jax_compile_seconds", _COMPILE_HELP,
                                      stage=stage)
                 for stage in _COMPILE_STAGES.values()}
        _backend_compiles = hists["backend"]
        _compile_listening = True
    total = reg.counter(
        "jax_compile_seconds_total",
        "seconds this process spent tracing, lowering and compiling "
        "(or loading from the compile cache): the sum of "
        "jax_compile_seconds")

    def on_start(event: str, value, **kwargs) -> None:
        # the context manager that times an event announces its start
        # with a scalar: traces nest, and only the outermost counts
        if _COMPILE_STAGES.get(event) == "trace":
            _compile_local.traces = getattr(_compile_local, "traces", 0) + 1

    def on_duration(event: str, duration: float, **kwargs) -> None:
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
            return
        if stage == "trace":
            depth = getattr(_compile_local, "traces", 1) - 1
            _compile_local.traces = max(depth, 0)
            if depth > 0:
                return
        hists[stage].observe(duration)
        total.inc(duration)
        log = _steady_log
        if stage == "backend" and log is not None:
            log(f"Compiled {kwargs.get('fun_name', '?')} in "
                f"{duration:.2f}s after start-up (XLA compile or "
                f"compile-cache load inside the steady state)")

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def log_compiles_from_now(log: Optional[Callable[[str], None]]) -> None:
    """From here on every backend compile is one log line naming the
    function: called after the first train step and when the server
    reports ready, where a compile means a shape nobody warmed."""
    global _steady_log
    _steady_log = log


def backend_compiles() -> int:
    """Backend compiles (XLA compile or compile-cache load) this process
    has finished since the listener was registered; 0 before."""
    seen = _backend_compiles
    return seen.count if seen is not None else 0


def compiles_during(span_name: str) -> _metrics.Histogram:
    """The histogram for `span(span_name, compiles=...)`: backend
    compiles that finished while one such span was open. The host
    watch (obs/stalls.py) observes `span="process"` once a tick, the
    compiles finished since its tick before: ANYWHERE in the process,
    also outside a step or a dispatched batch."""
    return _metrics.default_registry().histogram(
        "jax_compiles_during",
        "backend compiles (XLA compile or compile-cache load, on any "
        "thread) that finished while one span of this name was open, "
        "one observation a span (span=process: one a 20 ms tick of the "
        "host watch, the whole process): the sum over a window is the "
        "compiles inside the steps or batches there (span=process: "
        "anywhere), and 0 is the sound reading once every shape is warm",
        buckets=(0, 1, 2, 4, 8, 16), span=span_name)


_STARTUP_HELP = (
    "wall seconds of one start-up phase, set once: vocab_load, "
    "state_init, restore (--load), first_step (call until the result "
    "is ready), serve_warm (every predict bucket run once)")


def startup_phase(phase: str) -> "span":
    """The span around one start-up phase; its seconds become
    `startup_phase_seconds{phase}`."""
    return span("startup." + phase,
                gauge=_metrics.default_registry().gauge(
                    "startup_phase_seconds", _STARTUP_HELP, phase=phase))


# Spans closed on a thread while it `collect`s are also handed to the
# collector: how the batcher learns the stages of the model call it
# made, to hang them under the batch's `device` span. A process-wide
# count keeps the check in `span.__exit__` to one global read.
_collecting = 0
_collect_local = threading.local()


@contextlib.contextmanager
def collect():
    """Yields a list that receives (name, start_s, seconds) of every
    span closed on THIS thread inside the block."""
    global _collecting
    outer = getattr(_collect_local, "spans", None)
    got: List[Tuple[str, float, float]] = []
    _collect_local.spans = got
    with _module_lock:
        _collecting += 1
    try:
        yield got
    finally:
        with _module_lock:
            _collecting -= 1
        _collect_local.spans = outer


class span:
    """Context manager timing one named host-side section.

    Always measures (two perf_counter calls); feeds the measurement to
    an optional always-on histogram (or sets a gauge), to the tracer's
    ring buffer when tracing is enabled, and, where jax is imported, to
    the profiler as the annotation `c2v.<name>` (see the module
    docstring). With `compiles` (a `compiles_during` histogram) it also
    observes how many backend compiles finished inside the block.
    Reentrant-per-instance is NOT supported — create one
    per `with` (the usual idiom `with obs.span("x"):` does)."""

    __slots__ = ("name", "hist", "gauge", "compiles", "tracer", "_t0",
                 "_annotation", "_compiles0", "seconds")

    def __init__(self, name: str, hist: Optional[_metrics.Histogram] = None,
                 tracer: Optional[SpanTracer] = None,
                 gauge: Optional[_metrics.Gauge] = None,
                 compiles: Optional[_metrics.Histogram] = None):
        self.name = name
        self.hist = hist
        self.gauge = gauge
        self.compiles = compiles
        self.tracer = tracer if tracer is not None else _DEFAULT
        self.seconds = 0.0

    def __enter__(self) -> "span":
        annotation = _ANNOTATION or _jax_annotation()
        if annotation is not None:
            annotation = annotation("c2v." + self.name)
            annotation.__enter__()
        self._annotation = annotation
        if self.compiles is not None:
            self._compiles0 = backend_compiles()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self.hist is not None:
            self.hist.observe(self.seconds)
        if self.gauge is not None:
            self.gauge.set(self.seconds)
        if self.compiles is not None and _backend_compiles is not None:
            self.compiles.observe(_backend_compiles.count - self._compiles0)
        self.tracer.maybe_record(self.name, self._t0, self.seconds)
        if _collecting:
            got = getattr(_collect_local, "spans", None)
            if got is not None:
                got.append((self.name, self._t0, self.seconds))
        return False
