"""Batched prediction HTTP server: the paper's model behind real traffic.

Request path:

    POST /predict  --cache miss-->  admission gate (bounded queue +
    deadline budget check) --> extractor pool (warm --server workers,
    circuit-broken, deadline as timeout) --> dynamic batcher (coalesce +
    context-bucketed padded shapes, deadline-aware) --> jitted predict
    step (circuit-broken) --> JSON response --> LRU cache

Endpoints (JSON unless noted; schema in README "Serving"):

- `POST /predict`  body = raw Java source (or `{"code": "..."}`);
  per-method top-k name predictions + attention paths (+ code vectors
  when the model was created with --export_code_vectors).
- `POST /embed`    same input; code vectors only (forces them on
  regardless of --export_code_vectors — the embedding IS the product).
  Carries `embedding_fingerprint` so clients can detect cross-model
  vector mixing (the same field `/neighbors` stamps).
- `POST /neighbors`  same input; nearest stored methods per input
  method via the mounted retrieval index (`serve --retrieval_index
  DIR`): snippet -> extractor pool -> embed batch -> ANN search ->
  method ids + scores + distances. JSON bodies may add `"k"` /
  `"nprobe"` knobs. Requires the index fingerprint to match the
  weights that embedded the batch — never answers across embedding
  spaces (503 instead).
- `POST /admin/reload`  `{"artifact": DIR}` — health-gated live model
  hot-swap (serving/swap.py): loads + validates off the request path,
  then swaps the model reference between batches. 202 accepted; poll
  `/healthz` `model.swap_status`. SIGHUP re-reads `--artifact`.
- `GET  /healthz`  liveness + pool/batcher/cache/breaker/admission
  gauges; `"status": "serving"` flips to `"draining"` — and the HTTP
  status to 503, the load-balancer eviction contract — during SIGTERM
  grace.
- `GET  /metrics`  Prometheus text format — the same registry/plumbing
  as the trainer's --metrics_port (obs/exporters.py). Under
  `--replicas N` scrape the SUPERVISOR's merged endpoint instead
  (serving/telemetry.py; this per-replica one samples a single
  kernel-chosen replica).
- `POST /admin/dump`  write the incident flight recorder's rings
  (obs/flight.py: last-N terminal request records + anomaly events) to
  a timestamped JSON file now; body `{"path": ...}`.

Request-scoped tracing (obs/reqtrace.py, README "Telemetry"): every
request carries a trace id — inbound W3C `traceparent` honored,
otherwise minted — echoed in the `X-Trace-Id` + `traceparent` response
headers on EVERY terminal status; the request's span tree (admission,
cache lookup, extractor pool, batcher, the shared device-batch span,
render) lands in the ring tracer for the bulk Chrome export and, with
`--serve_debug_trace` + `?debug=trace`, in the response itself.

Resilience semantics (serving/admission.py, serving/breaker.py; README
"Operating the server"):

- every request carries a DEADLINE (`--serve_deadline_ms`, client
  `X-Deadline-Ms` header, clamped by `--serve_deadline_max_ms`),
  propagated through the whole pipeline; expiry mid-pipeline is an
  honest 504 that never occupies a device slot;
- overload SHEDS with 503 + Retry-After instead of queueing unboundedly
  (`serving_requests_shed_total{reason=queue_full|deadline|breaker|
  draining}`);
- circuit breakers around the extractor pool and the device step fail
  fast when a dependency is down — cache hits still serve while the
  extractor breaker is open (graceful degradation);
- every response carries the `model_fingerprint` of the exact weights
  that produced it (hot-swap attribution).

Every request is timed into per-phase SLO histograms
(`serving_request_seconds{phase=queue_wait|extract|batch_wait|device}`),
and the `total` phase carries a `status` label and is recorded for
EVERY terminal status — errored and shed requests are part of the tail,
not invisible.

Shutdown mirrors the trainer's preemption-grace pattern
(training/loop.py PreemptionWatcher): SIGTERM stops intake, in-flight
requests finish (bounded by config.serve_drain_timeout_s), the batcher
flushes, the extractor pool is torn down, and the process exits 0 — or
1 with the abandoned-request count in the final heartbeat when the
drain timed out.
"""

from __future__ import annotations

import http.server
import json
import os
import select
import signal
import socket
import socketserver
import threading
import time
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.obs.flight import default_flight_recorder
from code2vec_tpu.obs.reqtrace import RequestTrace
from code2vec_tpu.serving.admission import (
    _SHED_HELP, AdmissionController, Deadline, DeadlineExceeded, Shed,
    deadline_from_request, expired_counter, retry_after_seconds,
)
from code2vec_tpu.serving.batcher import DynamicBatcher
from code2vec_tpu.serving.breaker import CircuitBreaker
from code2vec_tpu.serving.cache import (
    PredictionCache, cache_key_normalized, normalize_source,
)
from code2vec_tpu.serving.extractor_bridge import (
    ExtractionTimeout, ExtractorCrash,
)
from code2vec_tpu.serving.extractor_pool import ExtractorPool
from code2vec_tpu.serving.interactive import parse_prediction_results
from code2vec_tpu.serving.swap import SwapError, SwapManager
from code2vec_tpu.serving.tenancy import (
    TENANT_HEADER, TenantPolicy, tenant_metric,
)
from code2vec_tpu.utils.faults import FaultInjected

# A request's time inside `handle_request`, in order: admit + queue_wait
# + extract + batch_wait + device + handoff + respond is its `total`
# but for the few statements between them; `http` is the handler's own
# time around `handle_request`.
_PIPELINE_PHASES = ("admit", "queue_wait", "extract", "batch_wait",
                    "device", "handoff", "respond", "http")

# Env hook (set by the serving supervisor): bind the listen socket with
# SO_REUSEPORT so N replica processes share one port and the kernel
# load-balances accepts across them.
REUSEPORT_ENV = "C2V_SERVE_REUSEPORT"

_PHASE_HELP = (
    "per-request serving latency by phase: admit (entry to the end of "
    "admission: normalise, key, cache probe, admission gate; a cache "
    "hit ends here), queue_wait (extractor slot: the extractor call "
    "less the extraction, so the wait for a worker and the pool's "
    "bookkeeping around it), extract (path extraction), batch_wait "
    "(coalescing), device (model call), handoff (end of the model "
    "call to the handler thread running again: the dispatcher's "
    "fan-out and the wake-up), respond (from there to the end of "
    "handle_request: render, json.dumps, cache.put), total (entry to "
    "exit of handle_request, what the phases before it add up to; "
    "carries a "
    "`status` label and is recorded for EVERY terminal status, "
    "shed/errored included), http (the handler's own time around "
    "that: read and decode of the body before, the write of the "
    "answer after)")


def _phase_hist(phase: str):
    return obs.histogram("serving_request_seconds", _PHASE_HELP,
                         phase=phase)


_H_PHASE = {p: _phase_hist(p) for p in _PIPELINE_PHASES}


def _total_hist(status: str):
    return obs.histogram("serving_request_seconds", _PHASE_HELP,
                         phase="total", status=status)


_REQUESTS_HELP = "HTTP requests by endpoint and outcome"


def _requests_counter(endpoint: str, status: str):
    return obs.counter("serving_requests_total", _REQUESTS_HELP,
                       endpoint=endpoint, status=status)


def _nothing() -> None:
    """`handle_request`'s `arrived` for callers that keep no count."""


class _HTTPError(Exception):
    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code


class PredictionServer:
    """Owns the pool + batcher + cache + admission gate + breakers +
    swap manager around one (swappable) model.

    Separable from HTTP: `handle_request(endpoint, code, ...)` returns
    `(status, body, headers)`, so tests and the bench can drive the
    full path — including shedding and deadline accounting — in
    process, and the HTTP layer stays a thin framing shim.
    """

    def __init__(self, model, config=None, log=None,
                 swap_build_model=None, swap_mount_index=None):
        self.config = config or model.config
        self.log = log or self.config.log
        # The model reference is (model, fingerprint), swapped
        # atomically by swap_model(): the batcher reads it ONCE per
        # dispatched batch, so a response can never mix weights.
        self._model_lock = threading.Lock()
        self._model_ref: Tuple[object, str] = (model,
                                               model.model_fingerprint())
        # The model says which endpoints it answers and whether its
        # requests pass the extractor: code2vec's facades answer
        # /predict, /embed and /neighbors from source code; a
        # --model_config model (lm_facade.py) answers /score from token
        # ids and starts no extractor.
        self.endpoints = tuple(getattr(
            model, "served_endpoints", ("predict", "embed", "neighbors")))
        # ... and those whose requests end in the batcher (/contexts is
        # the model's own: it interleaves its chunks itself)
        self.batched_endpoints = tuple(
            e for e in self.endpoints if e != "contexts")
        self.pool = None
        if getattr(model, "uses_extractor", True):
            self.pool = ExtractorPool(
                self.config, size=self.config.extractor_pool_size,
                log=self.log)
        # with_code_vectors=True: /predict and /embed rows coalesce into
        # the SAME batches (a per-endpoint batcher would halve fill);
        # the step computes vectors anyway, the flag only materializes
        # them host-side, and _render decides per endpoint what ships.
        # Tenancy policy (serving/tenancy.py): None when
        # --serve_tenants is unset — the whole tenant layer is then
        # inert and the serve path is bit-identical to a build without
        # it (pinned in tests/test_tenancy.py).
        self.tenancy = TenantPolicy.from_config(self.config)
        batcher_kw = dict(
            max_batch_rows=self.config.serve_batch_size,
            buckets=model.context_buckets,
            tenancy=self.tenancy)
        # how a row buckets and what a batch may hold, where the model's
        # rows are not extractor lines (batcher.DynamicBatcher)
        batcher_kw.update(getattr(model, "batcher_options", dict)())
        # Requests EN ROUTE to the batcher (its dispatch rule gathers
        # them before a free dispatcher cuts): a connection from the
        # instant the listener hands it to a handler thread, or from its
        # next request line where it is kept alive, until the request is
        # submitted, enters the extractor pool (extraction takes
        # milliseconds: not "about to arrive") or ends any other way.
        # Keyed by the connection, valued by when the request was seen.
        self._en_route: Dict[object, float] = {}
        self._en_route_lock = threading.Lock()
        self._en_route_within_s = 0.0   # the horizon the batcher asks with
        self.batcher = DynamicBatcher(
            self._batched_predict,
            en_route=self.requests_en_route, **batcher_kw)
        # whom the last request to leave the count tells
        self._nobody_en_route = self.batcher.en_route_changed
        self.cache = PredictionCache(self.config.serve_cache_entries)
        self.topk = self.config.top_k_words_considered_during_prediction
        # Live-traffic sample for the continuous-training pipeline's
        # shadow eval (serving/traffic.py): every Nth cache-miss
        # request's EXTRACTED lines into a bounded ring file that the
        # pipeline replays through incumbent and candidate
        # (--serve_traffic_sample; None = off).
        from code2vec_tpu.serving.traffic import sampler_for
        self.traffic = sampler_for(self.config, log=self.log)
        # Retrieval mount (serve --retrieval_index DIR): /neighbors
        # serves ANN code search from this index. Mounting validates the
        # index artifact AND that its recorded embedding fingerprint is
        # the live model's — a stale index is a startup error, loud.
        self.retrieval = None
        if getattr(self.config, "retrieval_index", None):
            from code2vec_tpu.retrieval.api import RetrievalHandle
            self.retrieval = RetrievalHandle.mount(
                self.config.retrieval_index, self._model_ref[1],
                default_topk=getattr(self.config, "retrieval_topk", 10),
                log=self.log)
        self.admission = AdmissionController(
            max_depth=self.config.serve_queue_depth,
            concurrency=self.config.extractor_pool_size,
            tenancy=self.tenancy)
        # Flight recorder (obs/flight.py): terminal request records +
        # anomaly events, dumped on incident (README "Telemetry"). Dump
        # dir defaults next to the heartbeat file so the supervisor's
        # run dir collects every replica's black boxes.
        self.flight = default_flight_recorder()
        flight_dir = getattr(self.config, "serve_flight_dir", None)
        if not flight_dir and self.config.heartbeat_file:
            flight_dir = os.path.dirname(
                os.path.abspath(self.config.heartbeat_file))
        self.flight.configure(
            dump_dir=flight_dir,
            capacity=getattr(self.config, "serve_flight_records", 512),
            max_dumps=getattr(self.config, "serve_flight_max_dumps",
                              64),
            log=self.log)
        breaker_kw = dict(
            window_s=self.config.serve_breaker_window_s,
            failure_ratio=self.config.serve_breaker_failure_ratio,
            min_requests=self.config.serve_breaker_min_requests,
            cooldown_s=self.config.serve_breaker_cooldown_s,
            on_transition=self._on_breaker_transition)
        self.extractor_breaker = CircuitBreaker("extractor", **breaker_kw)
        self.device_breaker = CircuitBreaker("device", **breaker_kw)
        # swap_build_model/swap_mount_index: injection seams mirroring
        # SwapManager's — the fleet chaos children swap between
        # in-process fake models (and mount scripted index handles for
        # the retrieval-refresh restart drills)
        self.swap = SwapManager(self, build_model=swap_build_model,
                                mount_index=swap_mount_index)
        self._httpd: Optional[socketserver.BaseServer] = None
        self._host_watch = None     # set by start()
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        self._draining = False
        self._drained = threading.Event()
        self.abandoned_requests = 0
        self.started_at = time.time()
        self.port: Optional[int] = None

    # ------------------------------------------------------------ model

    @property
    def model(self):
        return self._model_ref[0]

    @property
    def model_fingerprint(self) -> str:
        """Fingerprint of the weights currently serving — mixed into
        every cache key and stamped on every response. Swappable."""
        return self._model_ref[1]

    def swap_model(self, new_model, retrieval_handle=None) -> str:
        """Atomically replace the serving model (called by the
        SwapManager AFTER validation). In-flight batches finish on the
        model reference they already read; the next dispatched batch —
        and the next cache key — uses the new one. `retrieval_handle`
        (an already-mounted, fingerprint-checked RetrievalHandle)
        remounts /neighbors atomically WITH the flip — the pipeline's
        retrieval-refresh stage delivers a rebuilt index this way."""
        fp = new_model.model_fingerprint()
        with self._model_lock:
            self._model_ref = (new_model, fp)
            # the deadline-feasibility math must run against the NEW
            # model's bucket grid (and fresh device-time samples — p95s
            # keyed to the old grid would misprice every refusal)
            self.batcher.rebucket(new_model.context_buckets)
            if retrieval_handle is not None:
                self.retrieval = retrieval_handle
                self.log(f"Retrieval index remounted atomically with "
                         f"the model swap (fingerprint "
                         f"{retrieval_handle.fingerprint})")
            # Embedding-space backstop, atomic with the flip: a mounted
            # index whose vectors came from different weights must never
            # answer /neighbors again (the SwapManager's `refuse` policy
            # normally rejects such a swap before it gets here; under
            # `detach` — or any future caller bypassing validation —
            # this is what keeps the invariant).
            if (self.retrieval is not None and self.retrieval.attached
                    and self.retrieval.fingerprint != fp):
                self.retrieval.detach(
                    f"model hot-swapped to fingerprint {fp}, index "
                    f"holds vectors from "
                    f"{self.retrieval.fingerprint}; rebuild the index "
                    f"(embed + index-build) against the new model")
                self.log("Retrieval index DETACHED on hot-swap: "
                         "embedding fingerprints diverged; /neighbors "
                         "now answers 503 (see /healthz retrieval)")
        return fp

    def _on_breaker_transition(self, name: str, to: str) -> None:
        """Breaker flips are flight-recorder anomalies; an OPEN is an
        incident (auto-dump when a dump dir is configured) — the black
        box captures both the failures that opened it and the shed storm
        that follows."""
        if to == "open":
            self.flight.incident("breaker_open", breaker=name)
        else:
            self.flight.event("breaker_transition", breaker=name, to=to)

    def _batched_predict(self, lines):
        """The batcher's predict_fn: ONE model-reference read per batch
        (swap atomicity), device circuit breaker around the call, and
        the computing model's fingerprint attached to every result so
        responses are attributable to exactly one set of weights."""
        self.device_breaker.check()
        model, fp = self._model_ref
        try:
            score = getattr(model, "score_batch", None)
            if score is not None:
                results = score(lines)
            else:
                results = model.predict(
                    lines, batch_size=self.config.serve_batch_size,
                    with_code_vectors=True)
        except BaseException:
            self.device_breaker.record(ok=False)
            raise
        self.device_breaker.record(ok=True)
        return [(r, fp) for r in results]

    # --------------------------------------------------------- en route

    def _en_route_enter(self, conn) -> None:
        with self._en_route_lock:
            self._en_route[conn] = time.perf_counter()

    def _en_route_leave(self, conn) -> None:
        """Idempotent. When the last one leaves, a gathering dispatcher
        is told so and looks again at once (by the horizon it last
        asked with)."""
        with self._en_route_lock:
            if self._en_route.pop(conn, None) is None:
                return
            left = self._en_route_fresh_locked(self._en_route_within_s)
        if not left:
            self._nobody_en_route()

    def _en_route_fresh_locked(self, within_s: float) -> int:
        horizon = time.perf_counter() - within_s
        return sum(seen >= horizon for seen in self._en_route.values())

    def requests_en_route(self, within_s: float) -> int:
        """Requests seen in the last `within_s` seconds and not yet
        submitted to the batcher: its `en_route` signal. An older entry
        counts for nothing (a connection that sends nothing, a probing
        or pooled client, holds no dispatcher)."""
        with self._en_route_lock:
            self._en_route_within_s = within_s
            n = self._en_route_fresh_locked(within_s)
        if n or self._httpd is None:
            return n
        # A connection the kernel holds for the listener is en route
        # too: a handler thread runs its request through to the submit
        # before `serve_forever` gets the interpreter back to accept the
        # next one, so a burst's later requests are not in the count yet
        # when its first is pending (PERF.md section 6, PR 42).
        try:
            return len(select.select([self._httpd.socket], [], [], 0)[0])
        except (OSError, ValueError):   # the listener is closed
            return 0

    # ---------------------------------------------------------- predict

    def handle_request(self, endpoint: str, code: str,
                       deadline: Optional[Deadline] = None,
                       params: Optional[Dict] = None,
                       trace: Optional[RequestTrace] = None,
                       tenant: Optional[str] = None,
                       arrived: Callable[[], None] = _nothing
                       ) -> Tuple[int, bytes, Dict[str, str]]:
        """Full serve path for one request -> (http_status, body,
        extra_headers). `arrived()` (the HTTP handler's: the request
        leaves the count of requests en route) is called once the
        request is submitted to the batcher or enters the extractor
        pool (a request that ends before either leaves with its
        handler); in-process callers pass none. EVERY terminal status
        lands in
        serving_request_seconds{phase=total,status=...} and
        serving_requests_total — overload and errors are measured, not
        invisible. Every request carries a trace (inbound `traceparent`
        or minted here): the id rides the X-Trace-Id response header,
        the span tree lands in the ring tracer, and the terminal record
        goes into the flight recorder.

        `tenant` is the raw X-Tenant header value; with a tenancy
        policy it is collapsed onto the closed label set for
        scheduling and metrics, recorded verbatim in the trace and
        flight record. Without a policy it is ignored entirely."""
        t0 = time.perf_counter()
        if trace is None:
            trace = RequestTrace()
        tlabel: Optional[str] = None
        if self.tenancy is not None:
            tenant = self.tenancy.resolve(tenant)
            tlabel = self.tenancy.label(tenant)
        root = trace.span("request", endpoint=endpoint)
        root.__enter__()
        if tlabel is not None:
            root.attrs["tenant"] = tenant
        phases: Dict[str, float] = {}
        status, body, headers = 500, b"", {}
        reason: Optional[str] = None
        try:
            body = self._handle(endpoint, code, deadline, phases,
                                params=params, trace=trace,
                                tenant=tlabel, t0=t0, arrived=arrived)
            status = 200
        except Shed as e:
            if tlabel is None:
                e.count()
            else:
                tenant_metric(
                    "counter", "serving_requests_shed_total",
                    _SHED_HELP, tlabel, self.tenancy.labels,
                    reason=e.reason).inc()
            status = 503
            reason = e.reason
            # jittered: a synchronized shed (open breaker, drain) must
            # not teach every client the same retry instant
            headers["Retry-After"] = str(retry_after_seconds(
                e.retry_after_s))
            body = json.dumps({"error": str(e), "shed": e.reason,
                               "trace_id": trace.trace_id}
                              ).encode() + b"\n"
        except DeadlineExceeded as e:
            status = 504
            reason = "deadline_expired"
            self.flight.event("deadline_expired",
                              trace_id=trace.trace_id, endpoint=endpoint)
            body = json.dumps({"error": f"deadline exceeded: {e}",
                               "trace_id": trace.trace_id}
                              ).encode() + b"\n"
        except _HTTPError as e:
            status = e.code
            body = json.dumps({"error": str(e),
                               "trace_id": trace.trace_id}
                              ).encode() + b"\n"
        except FaultInjected as e:
            # chaos drills must surface as honest errors, never hangs
            status = 500
            body = json.dumps({"error": f"FaultInjected: {e}",
                               "trace_id": trace.trace_id}
                              ).encode() + b"\n"
        except Exception as e:  # noqa: BLE001 — 500, not a torn socket
            status = 500
            body = json.dumps({"error": f"{type(e).__name__}: {e}",
                               "trace_id": trace.trace_id}
                              ).encode() + b"\n"
        finally:
            t_end = time.perf_counter()
            total = t_end - t0
            root.attrs["status"] = status
            root.__exit__(None, None, None)
            # snapshot: the batcher dispatcher can still write phase
            # keys for a request that exited early via the result
            # backstop — iterating the live dict could raise mid-walk
            phases = dict(list(phases.items()))
            # instants, not phases: `device_end` is left where a request
            # went before its batch settled (the result backstop) and
            # never subtracted it; `respond_from` is where the handler
            # thread ran again, and `respond` ends with `total`
            phases.pop("device_end", None)
            t_back = phases.pop("respond_from", None)
            if t_back is not None:
                phases["respond"] = t_end - t_back
            for phase, dur in phases.items():
                _H_PHASE[phase].observe(dur)
            if tlabel is None:
                _total_hist(str(status)).observe(total)
                _requests_counter(endpoint, str(status)).inc()
            else:
                # tenancy on: the terminal-status families carry a
                # `tenant` label (bounded by the policy's closed set;
                # serving/tenancy.tenant_metric is the guard). The
                # per-phase histograms above stay tenant-free — phases
                # are a pipeline property, not a tenant one.
                tenant_metric(
                    "histogram", "serving_request_seconds",
                    _PHASE_HELP, tlabel, self.tenancy.labels,
                    phase="total", status=str(status)).observe(total)
                tenant_metric(
                    "counter", "serving_requests_total",
                    _REQUESTS_HELP, tlabel, self.tenancy.labels,
                    endpoint=endpoint, status=str(status)).inc()
            self.flight.record_request(
                trace_id=trace.trace_id, endpoint=endpoint,
                status=status, duration_s=total, phases=phases,
                reason=reason, fingerprint=self.model_fingerprint,
                **({} if tlabel is None else {"tenant": tenant}))
            headers.setdefault("X-Trace-Id", trace.trace_id)
            headers.setdefault("traceparent", trace.traceparent())
        return status, body, headers

    def _neighbor_knobs(self, params: Optional[Dict]) -> Dict:
        """Per-request retrieval knobs (JSON body `k`/`nprobe`),
        defaulted and clamped; part of the cache key — a different k or
        nprobe is a different answer."""
        params = params or {}
        try:
            k = int(params.get("k", self.retrieval.default_topk))
            nprobe = params.get("nprobe")
            nprobe = None if nprobe is None else int(nprobe)
        except (TypeError, ValueError):
            raise _HTTPError(400, "k and nprobe must be integers")
        if k < 1 or (nprobe is not None and nprobe < 1):
            raise _HTTPError(400, "k and nprobe must be >= 1")
        return {"k": k, "nprobe": nprobe}

    def _handle(self, endpoint: str, code: str,
                deadline: Optional[Deadline],
                phases: Dict[str, float],
                params: Optional[Dict] = None,
                trace: Optional[RequestTrace] = None,
                tenant: Optional[str] = None,
                t0: Optional[float] = None,
                arrived: Callable[[], None] = _nothing) -> bytes:
        if t0 is None:
            t0 = time.perf_counter()
        if trace is None:
            trace = RequestTrace()
        if endpoint not in self.endpoints:
            raise _HTTPError(
                404, f"this model does not serve /{endpoint} (it serves "
                     f"{', '.join('/' + e for e in self.endpoints)})")
        if not code.strip():
            raise _HTTPError(400, "empty request body")
        knobs: Dict = {}
        if endpoint == "neighbors":
            if self.retrieval is None:
                raise _HTTPError(
                    404, "no retrieval index mounted; start the server "
                         "with serve --retrieval_index DIR")
            try:
                self.retrieval.require_attached()
            except Exception as e:
                raise _HTTPError(503, str(e))
            knobs = self._neighbor_knobs(params)
            knobs["index"] = self.retrieval.fingerprint
        model, fp = self._model_ref
        if endpoint == "contexts":
            return self._register_context(model, params, deadline, trace,
                                          tenant)
        score_request = None
        if endpoint == "score":
            knobs = {name: bool((params or {}).get(name))
                     for name in ("return_routing", "return_selected")}
            # before the cache probe: an answer cached while its context
            # was held must not outlive the context
            score_request = self._score_request(model, params)
        # ONE normalization pass per request: the same bytes feed the
        # cache probe here and the hot-swap re-key below.
        normalized = normalize_source(code)
        key = cache_key_normalized(normalized, endpoint=endpoint,
                                   topk=self.topk, model=fp, **knobs)
        # a kept turn changes what it names: it is never answered from,
        # nor stored in, the cache of answers
        kept_turn = bool(score_request is not None
                         and getattr(score_request, "keep", False))
        with trace.span("cache_lookup") as sp:
            cached = None if kept_turn else self.cache.get(key)
            sp.attrs["hit"] = cached is not None
        if cached is not None:
            # Cache hits serve BEFORE admission and breakers: graceful
            # degradation — a dead extractor pool cannot take the hit
            # path down with it (pinned in tests/test_serving_chaos.py).
            phases["admit"] = time.perf_counter() - t0
            return cached  # type: ignore[return-value]
        with trace.span("admission"):
            self.admission.admit(deadline, tenant=tenant)
        t_admit = time.perf_counter()
        phases["admit"] = t_admit - t0
        worked = True
        try:
            if endpoint == "score":
                lines, hash_to_string = [score_request], {}
            else:
                arrived()           # in the extractor pool: not en route
                lines, hash_to_string = self._extract(
                    code, deadline, phases, trace=trace)
                # the whole extractor call less the extraction itself:
                # the wait for a worker AND the pool's bookkeeping
                # around it (liveness polls, the release), so that no
                # time lies between two phases
                phases["queue_wait"] = (time.perf_counter() - t_admit
                                        - phases.get("extract", 0.0))
                if self.traffic is not None:
                    self.traffic.record(lines)
            future = self.batcher.submit(lines, phases=phases,
                                         deadline=deadline, trace=trace,
                                         tenant=tenant)
            arrived()               # AFTER the submit: pending, then gone
            try:
                if deadline is not None and deadline.bounded:
                    # Backstop: the batcher settles expired futures
                    # itself; this bounds a wedged device call so the
                    # CLIENT still gets its 504 near the deadline.
                    raw = future.result(
                        timeout=max(deadline.remaining(), 0) + 5.0)
                else:
                    raw = future.result()
            except _FutureTimeout:
                expired_counter("device").inc()
                raise DeadlineExceeded(
                    "request expired waiting on the device step")
            except RuntimeError as e:
                if "draining" in str(e):
                    raise Shed("draining", str(e))
                raise
            t_back = time.perf_counter()
            # the dispatcher stamped where its model call ended
            # (serving/batcher.py), this thread runs again here
            device_end = phases.pop("device_end", None)
            if device_end is not None:
                phases["handoff"] = t_back - device_end
            results = [r for r, _ in raw]
            result_fp = raw[0][1] if raw else fp
            with trace.span("render"):
                body = json.dumps(
                    self._render(endpoint, results, hash_to_string,
                                 result_fp, knobs=knobs, trace=trace),
                    sort_keys=True).encode() + b"\n"
            if result_fp != fp:
                # the model was hot-swapped between our cache probe and
                # the device batch: key the entry by the weights that
                # actually computed it, never the stale fingerprint
                key = cache_key_normalized(normalized,
                                           endpoint=endpoint,
                                           topk=self.topk,
                                           model=result_fp, **knobs)
            if not kept_turn:
                self.cache.put(key, body)
            # `respond` ends where `total` does: handle_request takes
            # one reading for both
            phases["respond_from"] = t_back
            return body
        except Shed:
            # a post-admission shed (batcher DeadlineInfeasible, an
            # open breaker, draining) refused the request instead of
            # working it: feeding its ~0ms turnaround into the
            # queue-wait EWMA would make the admission estimate wildly
            # optimistic under overload
            worked = False
            raise
        finally:
            self.admission.finish(
                (time.perf_counter() - t_admit) if worked else -1.0,
                tenant=tenant)

    @staticmethod
    def _score_request(model, params: Optional[Dict]):
        """The body of POST /score, `{"ids": [...], "top_k": 10}`, as the
        model's own request object."""
        params = params or {}
        if "ids" not in params:
            raise _HTTPError(400, 'JSON body must be {"ids": [...], '
                                  '"top_k": N}')
        try:
            # `keep` only where it is asked for: a model's `validate`
            # that knows no kept turns is called as it always was
            keep = {"keep": True} if params.get("keep") else {}
            return model.validate(params["ids"],
                                  params.get("top_k", model.top_k),
                                  params.get("context"), **keep)
        except ValueError as e:
            raise _HTTPError(400, str(e))
        except LookupError as e:        # a context that is not held
            raise _HTTPError(404, str(e.args[0]))

    def _register_context(self, model, params: Optional[Dict],
                          deadline: Optional[Deadline],
                          trace: RequestTrace,
                          tenant: Optional[str]) -> bytes:
        """POST /contexts, `{"ids": [...]}`: the model runs the tokens
        once and keeps their state on the device; the answer names the
        context for later /score requests. It passes admission like any
        request, not the batcher and not the response cache: the model
        interleaves its chunks with scoring steps itself."""
        if "ids" not in (params or {}):
            raise _HTTPError(400, 'JSON body must be {"ids": [...]}')
        with trace.span("admission"):
            self.admission.admit(deadline, tenant=tenant)
        t_admit = time.perf_counter()
        try:
            with trace.span("register"):
                out = model.register_context(params["ids"])
        except ValueError as e:
            raise _HTTPError(400, str(e))
        except LookupError as e:        # every slot is being filled
            raise _HTTPError(503, str(e.args[0]))
        finally:
            self.admission.finish(time.perf_counter() - t_admit,
                                  tenant=tenant)
        out["model_fingerprint"] = self._model_ref[1]
        return json.dumps(out, sort_keys=True).encode() + b"\n"

    def _extract(self, code: str, deadline: Optional[Deadline],
                 phases: Dict[str, float],
                 trace: Optional[RequestTrace] = None):
        """Extractor-pool call behind its circuit breaker, with the
        request's remaining deadline budget as the per-request
        timeout."""
        self.extractor_breaker.check()
        try:
            result = self.pool.extract_source(code, phases=phases,
                                              deadline=deadline,
                                              trace=trace)
        except DeadlineExceeded:
            # the request's budget, not the extractor's health: no
            # verdict recorded — but a half-open probe slot must be
            # re-armed or the breaker wedges in half_open forever
            self.extractor_breaker.abort()
            raise
        except FileNotFoundError as e:
            self.extractor_breaker.record(ok=False)
            raise _HTTPError(503, f"no extractor available: {e}")
        except (ExtractorCrash, OSError) as e:
            # infra failure (workers dying through every retry), NOT the
            # client's source: 503 tells a well-behaved client to retry.
            # Must precede the ValueError arm — ExtractorCrash subclasses
            # it so the REPL's catch-all keeps working.
            self.extractor_breaker.record(ok=False)
            raise _HTTPError(503, f"extractor unavailable: {e}")
        except ExtractionTimeout as e:
            # a hang is an infra failure for breaker purposes, but the
            # client's source MIGHT be the pathological input: 422
            self.extractor_breaker.record(ok=False)
            raise _HTTPError(422, f"extraction failed: {e}")
        except ValueError as e:
            # deterministic parse rejection: the extractor is HEALTHY
            # (it answered); a storm of bad client input must not open
            # the breaker and shed good clients.
            self.extractor_breaker.record(ok=True)
            raise _HTTPError(422, f"extraction failed: {e}")
        except Exception:
            # anything else (pool closed mid-drain, acquire timeout
            # with an unbounded deadline) carries no dependency
            # verdict — but a half-open probe slot must still re-arm
            # or the breaker wedges shedding forever
            self.extractor_breaker.abort()
            raise
        self.extractor_breaker.record(ok=True)
        return result

    def _render(self, endpoint: str, raw, hash_to_string,
                fingerprint: str, knobs: Optional[Dict] = None,
                trace: Optional[RequestTrace] = None) -> dict:
        if endpoint == "score":
            # one token sequence a request: the top-k next-token logits
            # at its last position, probabilities over the rows held
            [r] = raw
            if r.unknown_context is not None:
                raise _HTTPError(
                    404, f"context {r.unknown_context!r} was evicted, or "
                         f"extended by a kept turn, before this request's "
                         f"step: name the id that turn answered with, or "
                         f"register it again (POST /contexts)")
            if getattr(r, "refused", None) is not None:
                raise _HTTPError(
                    409, f"this turn cannot be kept: {r.refused}")
            out = {"model": self._model_ref[0].model_name,
                   "model_fingerprint": fingerprint,
                   "tokens": r.tokens, "context_tokens": r.context_tokens,
                   **({} if getattr(r, "kept_as", None) is None
                      else {"context": r.kept_as}),
                   "top": [{"id": int(i), "logit": float(v),
                            "probability": float(p)}
                           for i, v, p in zip(r.token_ids, r.logits,
                                              r.probabilities)]}
            if (knobs or {}).get("return_routing"):
                # the router's choice at the last position, by expert
                # layer: what a comparison with a reference reads
                out["routing_last"] = r.routing_last.tolist()
            if ((knobs or {}).get("return_selected")
                    and r.selected_last is not None):
                # the key positions (of context ++ question) the last
                # position attended, by layer, where attention selects
                out["selected_last"] = [
                    at.tolist() for at in
                    self._model_ref[0].selected_positions(r)]
            return out
        if endpoint == "embed":
            # embedding_fingerprint is the embedding-SPACE identity —
            # the same field /neighbors stamps — so a client holding
            # vectors from two /embed calls (or an offline store) can
            # detect cross-model vector mixing before cosine math lies
            # to it.
            return {"model": "code2vec_tpu",
                    "model_fingerprint": fingerprint,
                    "embedding_fingerprint": fingerprint,
                    "vectors": [
                        ([] if r.code_vector is None
                         else [float(v) for v in r.code_vector])
                        for r in raw],
                    "method_names": [r.original_name for r in raw]}
        if endpoint == "neighbors":
            from code2vec_tpu.retrieval.api import EmbeddingSpaceMismatch
            knobs = knobs or {}
            k = knobs.get("k") or self.retrieval.default_topk
            nprobe = knobs.get("nprobe")
            if not raw:
                # zero extracted methods (an empty class, an interface):
                # an empty answer, not a search over a (0, ?) batch
                return {"model": "code2vec_tpu",
                        "model_fingerprint": fingerprint,
                        "embedding_fingerprint":
                            self.retrieval.index.fingerprint,
                        "index": {"rows": self.retrieval.index.rows,
                                  "backend": self.retrieval.index.backend,
                                  "metric": self.retrieval.index.metric,
                                  "k": k,
                                  "nprobe": (self.retrieval.index.nprobe
                                             if nprobe is None
                                             else nprobe)},
                        "methods": []}
            vectors = np.asarray(
                [r.code_vector for r in raw], dtype=np.float32)
            try:
                neighbor_lists = self.retrieval.neighbors(
                    vectors, fingerprint, k=k, nprobe=nprobe,
                    trace=trace)
            except EmbeddingSpaceMismatch as e:
                raise _HTTPError(503, str(e))
            return {
                "model": "code2vec_tpu",
                "model_fingerprint": fingerprint,
                "embedding_fingerprint":
                    self.retrieval.index.fingerprint,
                "index": {"rows": self.retrieval.index.rows,
                          "backend": self.retrieval.index.backend,
                          "metric": self.retrieval.index.metric,
                          "k": k,
                          "nprobe": (self.retrieval.index.nprobe
                                     if nprobe is None else nprobe)},
                "methods": [
                    {"original_name": r.original_name,
                     "neighbors": neighbors}
                    for r, neighbors in zip(raw, neighbor_lists)],
            }
        oov = self.model.vocabs.target_vocab.special_words.oov
        methods = []
        for r, parsed in zip(raw, parse_prediction_results(
                raw, hash_to_string, oov, topk=10)):
            entry = {
                "original_name": r.original_name,
                "predictions": [
                    {"name": p["name"], "probability": p["probability"]}
                    for p in parsed.predictions],
                "attention_paths": parsed.attention_paths,
            }
            # /predict ships vectors only when the model was created
            # with --export_code_vectors (/embed always does).
            if (self.config.export_code_vectors
                    and r.code_vector is not None):
                entry["code_vector"] = [float(v) for v in r.code_vector]
            methods.append(entry)
        return {"model": "code2vec_tpu",
                "model_fingerprint": fingerprint, "methods": methods}

    def handle(self, endpoint: str, code: str,
               deadline: Optional[Deadline] = None,
               params: Optional[Dict] = None) -> bytes:
        """Body-or-raise convenience used by in-process callers; HTTP
        goes through handle_request (which owns the SLO accounting)."""
        return self._handle(endpoint, code, deadline, {}, params=params)

    def handle_embed(self, code: str) -> bytes:
        return self.handle("embed", code)

    # ------------------------------------------------------------- http

    def healthz(self) -> dict:
        model = self.model
        return {
            "status": "draining" if self._draining else "serving",
            "uptime_s": time.time() - self.started_at,
            "pid": os.getpid(),
            "model": {
                "fingerprint": self.model_fingerprint,
                "swap_status": self.swap.status(),
            },
            # kept at top level too: deploy tooling from PR 8 reads it
            "model_fingerprint": self.model_fingerprint,
            "extractor_pool": ({"size": self.pool.size,
                                "warm": self.pool.warm}
                               if self.pool is not None else None),
            "batcher": {"max_batch_rows": self.batcher.max_batch_rows,
                        "batches_dispatched":
                            self.batcher.batches_dispatched},
            "cache": {"capacity": self.cache.capacity,
                      "entries": len(self.cache)},
            "admission": {
                "depth": self.admission.depth,
                "max_depth": self.admission.max_depth,
                "estimated_wait_ms": (
                    None if (w := self.admission.estimated_wait_s())
                    is None else w * 1000.0),
            },
            "deadlines": {
                "default_ms": self.config.serve_deadline_ms,
                "max_ms": self.config.serve_deadline_max_ms,
            },
            "breakers": {"extractor": self.extractor_breaker.state,
                         "device": self.device_breaker.state},
            # weighted-fair tenancy (README "Multi-tenancy"); absent
            # key semantics preserved for tenancy-off deployments by
            # only adding it when a policy is configured
            **({} if self.tenancy is None
               else {"tenancy": self.tenancy.healthz()}),
            # request-scoped telemetry (README "Telemetry"): whether
            # ?debug=trace is honored, and the flight recorder's state
            "telemetry": {
                "debug_trace": bool(getattr(self.config,
                                            "serve_debug_trace", False)),
                "flight": {
                    "dump_dir": self.flight.dump_dir,
                    "requests_recorded": self.flight.requests_recorded,
                    "events_recorded": self.flight.events_recorded,
                },
            },
            # /neighbors data plane: attached/detached (+ the detach
            # reason — deploy tooling reads this after a hot-swap)
            "retrieval": (None if self.retrieval is None
                          else self.retrieval.status()),
            "buckets": list(model.context_buckets),
            # compiled shapes AT THE SERVE BATCH SIZE — the serving
            # compilation budget, bounded by len(buckets). (An offline
            # predict through the same facade at another batch size
            # adds its own bounded set; predict_compile_count() has the
            # overall number.) list() snapshots the dict atomically —
            # the batcher thread inserts newly compiled shapes
            # concurrently, and a generator over the live dict could
            # raise mid-iteration.
            "compiled_predict_steps": sum(
                1 for rows, _ in list(model._predict_steps)
                if rows == self.config.serve_batch_size),
            "compiled_predict_steps_all": (
                model.predict_compile_count()),
            "inflight": self._inflight,
        }

    def start(self, port: Optional[int] = None,
              host: Optional[str] = None) -> int:
        """Bind + serve on a daemon thread; returns the bound port
        (port 0 picks a free one). With C2V_SERVE_REUSEPORT=1 in the
        environment (set by the serving supervisor) the socket binds
        with SO_REUSEPORT so replica processes share the port."""
        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):  # per-request stderr silenced
                pass

            def parse_request(self):
                """The request line and headers are read: a POST to an
                endpoint that ends in the batcher is en route from here
                (a kept-alive connection's next request: the earliest
                the server sees of it); anything else never reaches the
                batcher and leaves the count its connection entered."""
                ok = super().parse_request()
                if (ok and self.command == "POST"
                        and self.path.partition("?")[0].lstrip("/")
                        in server.batched_endpoints):
                    server._en_route_enter(self.connection)
                else:
                    server._en_route_leave(self.connection)
                return ok

            def handle_one_request(self):
                try:
                    super().handle_one_request()
                finally:
                    # however the request ended: the count cannot leak
                    server._en_route_leave(self.connection)

            def _respond(self, code: int, body: bytes,
                         ctype: str = "application/json",
                         extra_headers: Optional[Dict[str, str]] = None
                         ) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _error(self, code: int, message: str,
                       extra_headers: Optional[Dict[str, str]] = None
                       ) -> None:
                self._respond(code, json.dumps(
                    {"error": message}).encode() + b"\n",
                    extra_headers=extra_headers)

            def do_GET(self):  # noqa: N802 (stdlib API name)
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/healthz":
                        hz = server.healthz()
                        # the load-balancer eviction contract: a
                        # draining replica is NOT ready — probes must
                        # see 503 the moment SIGTERM lands, body still
                        # carrying the full introspection payload
                        code = 503 if hz["status"] == "draining" else 200
                        self._respond(code, json.dumps(
                            hz, sort_keys=True).encode() + b"\n")
                    elif path in ("/metrics", "/"):
                        self._respond(
                            200, obs.default_registry()
                            .render_prometheus().encode(),
                            ctype="text/plain; version=0.0.4; "
                                  "charset=utf-8")
                    else:
                        self._error(404, f"no such endpoint: {path}")
                except Exception as e:  # noqa: BLE001 — a probe must get
                    # an HTTP response, never a torn connection (a failed
                    # liveness probe can restart-loop the replica)
                    self._error(500, f"{type(e).__name__}: {e}")

            def do_POST(self):  # noqa: N802 (stdlib API name)
                t_in = time.perf_counter()
                path, _, query = self.path.partition("?")
                endpoint = path.lstrip("/")
                if path == "/admin/reload":
                    self._admin_reload()
                    return
                if path == "/admin/dump":
                    self._admin_dump()
                    return
                if endpoint not in ("predict", "embed", "neighbors",
                                    "score", "contexts"):
                    self._error(404, f"no such endpoint: {path}")
                    return
                # Inbound W3C traceparent joins the caller's distributed
                # trace; otherwise a trace id is minted. Either way the
                # id is echoed in X-Trace-Id + traceparent (even on the
                # shed/error paths below).
                trace = RequestTrace.from_headers(
                    self.headers.get("traceparent"))

                def trace_headers(**extra):
                    # built lazily: the fallback traceparent span id is
                    # only minted on the early-terminal paths that
                    # answer before handle_request opens the root span
                    return dict({"X-Trace-Id": trace.trace_id,
                                 "traceparent": trace.traceparent()},
                                **extra)

                deadline = deadline_from_request(
                    server.config, self.headers.get("X-Deadline-Ms"))
                # tenant identity is parsed ONCE here at the edge; the
                # fleet router / supervisor proxy forward the header
                # verbatim (forwarding.REQUEST_FORWARD_HEADERS)
                tenant = self.headers.get(TENANT_HEADER)
                if not server._enter_request():
                    if server.tenancy is None:
                        Shed("draining", "").count()
                        _requests_counter(endpoint, "draining").inc()
                    else:
                        tl = server.tenancy.label(tenant)
                        tenant_metric(
                            "counter", "serving_requests_shed_total",
                            _SHED_HELP, tl, server.tenancy.labels,
                            reason="draining").inc()
                        tenant_metric(
                            "counter", "serving_requests_total",
                            _REQUESTS_HELP, tl,
                            server.tenancy.labels, endpoint=endpoint,
                            status="draining").inc()
                    self._error(503, "server is draining",
                                extra_headers=trace_headers(
                                    **{"Retry-After": str(
                                        retry_after_seconds(1.0))}))
                    return
                try:
                    try:
                        length = int(self.headers.get(
                            "Content-Length", 0))
                        raw = self.rfile.read(length)
                        code_text, params = server._decode_body(
                            raw, self.headers, endpoint)
                    except _HTTPError as e:
                        _requests_counter(endpoint, str(e.code)).inc()
                        self._error(e.code, str(e),
                                    extra_headers=trace_headers())
                        return
                    t_call = time.perf_counter()
                    status, body, headers = server.handle_request(
                        endpoint, code_text, deadline, params=params,
                        trace=trace, tenant=tenant,
                        arrived=lambda: server._en_route_leave(
                            self.connection))
                    t_done = time.perf_counter()
                    if ("debug=trace" in query.split("&")
                            and server.config.serve_debug_trace):
                        # post-cache injection: hits and misses both
                        # carry THIS request's tree, and the cached
                        # bytes stay trace-free/byte-stable
                        body = server._inject_trace(body, trace)
                    self._respond(status, body, extra_headers=headers)
                    _H_PHASE["http"].observe(
                        (t_call - t_in) + (time.perf_counter() - t_done))
                finally:
                    server._exit_request()

            def _admin_dump(self) -> None:
                """POST /admin/dump: write the flight-recorder rings to
                a timestamped JSON file now; body {"path": ...}."""
                try:
                    # drain the (ignored) request body: unread bytes
                    # would desync the next request on this HTTP/1.1
                    # keep-alive connection
                    length = int(self.headers.get("Content-Length", 0))
                    if length:
                        self.rfile.read(length)
                    path = server.flight.dump(reason="admin")
                    # counts from the file itself, so the response can
                    # never disagree with what was actually written
                    with open(path) as f:
                        written = json.load(f)
                except Exception as e:  # noqa: BLE001
                    self._error(500, f"{type(e).__name__}: {e}")
                else:
                    self._respond(200, json.dumps(
                        {"path": path,
                         "requests": len(written["requests"]),
                         "events": len(written["events"])},
                        sort_keys=True).encode() + b"\n")

            def _admin_reload(self) -> None:
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(
                        self.rfile.read(length).decode("utf-8",
                                                       errors="replace")
                        or "{}")
                    if not isinstance(payload, dict):
                        raise _HTTPError(
                            400, 'body must be {"artifact": DIR}')
                    target = payload.get("artifact")
                    status = server.swap.request_reload(
                        target,
                        retrieval_index=payload.get("retrieval_index"))
                except json.JSONDecodeError as e:
                    self._error(400, f"bad JSON body: {e}")
                except SwapError as e:
                    code = 409 if "in flight" in str(e) else 400
                    self._error(code, str(e))
                except _HTTPError as e:
                    self._error(e.code, str(e))
                except Exception as e:  # noqa: BLE001
                    self._error(500, f"{type(e).__name__}: {e}")
                else:
                    self._respond(202, json.dumps(
                        {"accepted": True, "swap_status": status},
                        sort_keys=True).encode() + b"\n")

        reuseport = os.environ.get(REUSEPORT_ENV) == "1"

        class _Listener(http.server.ThreadingHTTPServer):
            # the stdlib default accept backlog (5) refuses connections
            # at the KERNEL under a burst — overload must reach the
            # admission gate so it can shed honestly with a 503
            request_queue_size = 128

            def server_bind(self):
                if reuseport:
                    try:
                        self.socket.setsockopt(
                            socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
                    except (AttributeError, OSError) as e:
                        server.log(f"SO_REUSEPORT unavailable ({e}); "
                                   f"plain bind")
                http.server.ThreadingHTTPServer.server_bind(self)

            def process_request(self, request, client_address):
                # the earliest the server sees of a request: most
                # clients open one connection a request
                server._en_route_enter(request)
                try:
                    super().process_request(request, client_address)
                except BaseException:
                    server._en_route_leave(request)
                    raise

            def process_request_thread(self, request, client_address):
                try:
                    super().process_request_thread(request,
                                                   client_address)
                finally:
                    server._en_route_leave(request)

        httpd = _Listener(
            (host if host is not None else self.config.serve_host,
             port if port is not None else self.config.serve_port),
            Handler)
        httpd.daemon_threads = True
        self._httpd = httpd
        # collections and host stalls while this server listens
        # (obs/stalls.py); drain stops what this call started
        self._host_watch = obs.default_host_watch()
        self._host_watch.start(self.log)
        self.port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever,
                         name="serving-http", daemon=True).start()
        self.log(f"Prediction server listening on "
                 f"http://{httpd.server_address[0]}:{self.port} "
                 f"(POST /predict, POST /embed, POST /admin/reload, "
                 f"GET /healthz, GET /metrics"
                 f"{', SO_REUSEPORT' if reuseport else ''})"
                 f"{self._device_suffix()}")
        return self.port

    def _device_suffix(self) -> str:
        """Where the serving model's arrays live, for the ready and
        drain lines (test fakes carry no arrays and say nothing)."""
        describe = getattr(self.model, "describe_devices", None)
        return f"; {describe()}" if describe else ""

    @staticmethod
    def _inject_trace(body: bytes, trace: RequestTrace) -> bytes:
        """`?debug=trace` (gated by --serve_debug_trace): append the
        request's span tree to the JSON response. Runs AFTER the cache
        layer, so cached bytes never embed a stale trace and the hit
        path stays byte-equal to the miss path for normal requests."""
        try:
            payload = json.loads(body)
        except ValueError:
            return body
        if not isinstance(payload, dict):
            return body
        payload["trace"] = trace.to_dict()
        return json.dumps(payload, sort_keys=True).encode() + b"\n"

    @staticmethod
    def _decode_body(raw: bytes, headers, endpoint: str = ""
                     ) -> Tuple[str, Optional[Dict]]:
        """(code, extra params). JSON bodies may carry per-request
        knobs beside "code" (today: /neighbors' `k` and `nprobe`);
        plain-text bodies have none. A /score body is JSON whatever its
        content type says: the text is the cache's key, the parsed
        object the parameters."""
        text = raw.decode("utf-8", errors="replace")
        if endpoint in ("score", "contexts"):
            try:
                payload = json.loads(text)
            except json.JSONDecodeError as e:
                raise _HTTPError(400, f"bad JSON body: {e}")
            if not isinstance(payload, dict):
                raise _HTTPError(400, "JSON body must be an object")
            return text, payload
        ctype = (headers.get("Content-Type") or "").split(";")[0].strip()
        if ctype == "application/json":
            try:
                payload = json.loads(text)
            except json.JSONDecodeError as e:
                raise _HTTPError(400, f"bad JSON body: {e}")
            if not isinstance(payload, dict) or "code" not in payload:
                raise _HTTPError(400, 'JSON body must be {"code": "..."}')
            params = {k: v for k, v in payload.items()
                      if k in ("k", "nprobe")}
            return str(payload["code"]), (params or None)
        return text, None

    def _enter_request(self) -> bool:
        with self._inflight_cond:
            if self._draining:
                return False
            self._inflight += 1
            return True

    def _exit_request(self) -> None:
        with self._inflight_cond:
            self._inflight -= 1
            self._inflight_cond.notify_all()

    # ------------------------------------------------------------ drain

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful stop: refuse new requests, wait for in-flight ones
        (bounded), flush the batcher, tear down pool + listener.
        Idempotent; returns True when everything in flight finished
        inside the budget. On timeout, `abandoned_requests` records how
        many were left behind (surfaced in the final heartbeat)."""
        with self._inflight_cond:
            if self._draining:
                self._drained.wait(timeout)
                return self._inflight == 0
            self._draining = True
        budget = (timeout if timeout is not None
                  else self.config.serve_drain_timeout_s)
        self.log(f"Drain: refusing new requests, waiting up to "
                 f"{budget:g}s for {self._inflight} in-flight")
        self.flight.event("drain_start", inflight=self._inflight,
                          budget_s=budget)
        deadline = time.monotonic() + budget
        clean = True
        with self._inflight_cond:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    clean = False
                    self.abandoned_requests = self._inflight
                    self.log(f"Drain timeout: {self._inflight} "
                             f"request(s) still in flight (abandoned)")
                    self.flight.incident(
                        "drain_timeout", immediate=True,
                        abandoned=self._inflight)
                    break
                self._inflight_cond.wait(timeout=remaining)
        self.batcher.drain(timeout=max(deadline - time.monotonic(), 1.0))
        if self.traffic is not None:
            self.traffic.flush()
        if self.pool is not None:
            self.pool.close()
        if self._httpd is not None:
            try:
                self._httpd.shutdown()
                self._httpd.server_close()
            except Exception:
                pass  # teardown must never mask the drain result
        if self._host_watch is not None:
            self._host_watch.stop()
        self._drained.set()
        self.log(f"Drain complete ({'clean' if clean else 'timed out'})"
                 f"{self._device_suffix()}")
        return clean


RELOAD_TARGET_FILENAME = "reload-target.json"


def reload_target_info(config) -> Optional[dict]:
    """The reload-target payload a SIGHUP should act on, when the
    supervisor dropped a reload-target file into the run dir (next to
    this replica's heartbeat file): {"artifact": DIR} plus an optional
    "retrieval_index" DIR to remount atomically with the swap (the
    pipeline's retrieval-refresh stage). None otherwise."""
    if not config.heartbeat_file:
        return None
    path = os.path.join(
        os.path.dirname(os.path.abspath(config.heartbeat_file)),
        RELOAD_TARGET_FILENAME)
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) or not payload.get("artifact"):
        return None
    return {"artifact": str(payload["artifact"]),
            "retrieval_index": (str(payload["retrieval_index"])
                                if payload.get("retrieval_index")
                                else None)}


def _heartbeat_fields(server: PredictionServer) -> dict:
    reg = obs.default_registry().collect()

    def total(name):
        fam = reg.get(name, {})
        return int(sum(child.value for child in fam.values()))

    swap_status = server.swap.status()
    return {
        "port": server.port,
        "inflight": server._inflight,
        "model_fingerprint": server.model_fingerprint,
        "swap_state": swap_status["state"],
        # which artifact the swap state refers to: the fleet swap
        # driver keys its convergence poll on this, so a replica still
        # showing LAST rollout's "ready" can never satisfy a new one
        "swap_target": swap_status["target"],
        # ...and which index rode along (None for a plain model swap):
        # a retrieval-refresh rollout re-targets the SAME artifact, so
        # the driver needs this to tell the new rollout's "ready" from
        # the promote rollout's
        "swap_retrieval_index": swap_status.get("retrieval_index"),
        "breakers": {"extractor": server.extractor_breaker.state,
                     "device": server.device_breaker.state},
        "requests_total": total("serving_requests_total"),
        "requests_shed_total": total("serving_requests_shed_total"),
        "requests_expired_total": total("serving_requests_expired_total"),
        # span-ring pressure: lets /fleet show which replica's trace
        # export is truncated when a stitched trace is missing spans
        "spans_dropped": obs.default_tracer().dropped,
        "span_ring_high_water": obs.default_tracer().high_water,
    }


def serve_main(config, model=None, *, stop: Optional[threading.Event]
               = None, install_signals: Optional[bool] = None,
               swap_build_model=None, swap_mount_index=None) -> int:
    """The `serve` CLI subcommand body: build the model, start the
    server, park until SIGTERM/SIGINT (or the injected `stop` event —
    the testable form), drain, exit. Returns the process exit code.

    While parked, a heartbeat ticker rewrites --heartbeat_file every
    config.serve_heartbeat_interval_s (the supervisor's staleness
    signal — a replica whose heartbeat stops is HUNG and gets
    restarted; fault point `replica_heartbeat` in utils/faults.py
    simulates exactly that). SIGHUP triggers a live hot-swap re-reading
    --artifact."""
    from code2vec_tpu.utils.faults import fault_point

    if model is None:
        from code2vec_tpu.model_facade import Code2VecModel
        model = Code2VecModel(config)
    server = PredictionServer(model, config,
                              swap_build_model=swap_build_model,
                              swap_mount_index=swap_mount_index)
    if stop is None:
        stop = threading.Event()
    if install_signals is None:
        install_signals = (threading.current_thread()
                           is threading.main_thread())

    def _on_signal(signum, frame):
        config.log(f"Signal {signal.Signals(signum).name} received: "
                   f"draining")
        stop.set()

    def _on_hup(signum, frame):
        # Reload target: a `reload-target.json` next to the heartbeat
        # file (written by the supervisor's fleet-wide reload fan-out —
        # under SO_REUSEPORT a POST /admin/reload reaches one
        # kernel-chosen replica, so the file + SIGHUP is how EVERY
        # replica learns a NEW artifact dir) wins over the boot-time
        # --artifact.
        info = reload_target_info(config)
        target = (info["artifact"] if info else None) \
            or config.serve_artifact
        if target:
            config.log(f"SIGHUP: reloading artifact {target}")
            try:
                server.swap.request_reload(
                    target,
                    retrieval_index=(info or {}).get("retrieval_index"))
            except SwapError as e:
                config.log(f"SIGHUP reload rejected: {e}")
        else:
            config.log("SIGHUP ignored: no --artifact or reload-target "
                       "file to reload (use POST /admin/reload)")

    prev_term = prev_int = prev_hup = None
    if install_signals:
        prev_term = signal.signal(signal.SIGTERM, _on_signal)
        prev_int = signal.signal(signal.SIGINT, _on_signal)
        if hasattr(signal, "SIGHUP"):
            prev_hup = signal.signal(signal.SIGHUP, _on_hup)
    if config.trace_export:
        # bulk per-request span trees ride the same ring the trainer
        # uses; exported as one Chrome trace every heartbeat tick (so
        # live `fleet trace` stitching and a crash both see recent
        # spans) and finally at shutdown
        obs.default_tracer().enable()
    warmup = getattr(model, "warmup", None)
    if warmup is not None:
        # every predict bucket compiled (or loaded from the compile
        # cache) and run once BEFORE the port opens: a cold bucket's
        # compile outlasts the default request deadline
        with obs.startup_phase("serve_warm") as warm:
            warmup()
        describe_head = getattr(model, "describe_head", None)
        config.log(f"Predict buckets warmed in {warm.seconds:.2f}s"
                   + (f" ({describe_head()})" if describe_head else ""))
    server.start()
    obs.log_compiles_from_now(config.log)

    hb_stop = threading.Event()

    def _publish():
        if config.heartbeat_file:
            obs.exporters.write_heartbeat(
                config.heartbeat_file,
                status="draining" if server._draining else "serving",
                **_heartbeat_fields(server))
        if config.metrics_file:
            # the replica's fleet-telemetry feed: an atomic snapshot the
            # supervisor merges into its /metrics and /fleet views
            # (serving/telemetry.py) — rewritten every ticker interval,
            # not just at exit
            obs.exporters.write_prometheus(config.metrics_file)
        if config.trace_export and len(obs.default_tracer()):
            try:
                obs.default_tracer().export_chrome_trace(
                    config.trace_export)
            except OSError:
                pass  # next tick retries; shutdown still exports

    def _heartbeat_loop():
        while not hb_stop.wait(config.serve_heartbeat_interval_s):
            # An armed fault here kills the ticker (raise) or the whole
            # replica (exit) — the supervisor's stale-heartbeat /
            # crash detection drills.
            fault_point("replica_heartbeat")
            _publish()

    ticker = None
    if config.heartbeat_file or config.metrics_file:
        _publish()
        ticker = threading.Thread(target=_heartbeat_loop,
                                  name="serving-heartbeat", daemon=True)
        ticker.start()
    try:
        stop.wait()
    finally:
        clean = server.drain()
        hb_stop.set()
        if ticker is not None:
            # a tick caught mid-write must not land its "draining" after
            # the final heartbeat below
            ticker.join(timeout=5.0)
        obs.log_compiles_from_now(None)
        if install_signals:
            signal.signal(signal.SIGTERM, prev_term)
            signal.signal(signal.SIGINT, prev_int)
            if prev_hup is not None:
                signal.signal(signal.SIGHUP, prev_hup)
        if config.metrics_file:
            obs.exporters.write_prometheus(config.metrics_file)
        if config.trace_export:
            obs.default_tracer().export_chrome_trace(config.trace_export)
            config.log(f"Serving span trace written to "
                       f"{config.trace_export}")
        if config.heartbeat_file:
            obs.exporters.write_heartbeat(
                config.heartbeat_file,
                status="done" if clean else "error",
                abandoned_requests=server.abandoned_requests,
                **_heartbeat_fields(server))
    return 0 if clean else 1
