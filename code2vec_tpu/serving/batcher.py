"""Dynamic request batcher: coalesce concurrent predicts into device
batches under a latency budget, with bucketed context counts.

Why this shape: the device step is sized for a batch of rows (its
rate is not measured on the current machine) and only earns it when
fed BATCHES — a per-request jitted call wastes the
chip on dispatch overhead, and letting every request shape hit pjit
would recompile per distinct (rows, contexts) pair. So:

- Requests (groups of extracted method lines) enqueue; a single
  dispatcher thread runs one model call over the coalesced rows.
  THE DISPATCH RULE: a dispatcher that is free and has a live request
  pending cuts a batch at once UNLESS the server reports requests EN
  ROUTE (seen by the server, not yet submitted here: the `en_route`
  callable `DynamicBatcher` is given) and the batch still has room.
  Then it waits on the batcher's condition until no request is en
  route, or the batch is full (the row cap, `_fits` under
  `max_batch_tokens`), or a ceiling has passed since the oldest pending
  request was submitted, whichever comes first, and cuts. The ceiling
  is read from what the batcher observes: half the pending bucket's
  tracked device time (a wait longer than the step it saves can never
  pay, and a bucket's cheapest step, one row, is a third to a half of
  its fullest; a tracker still cold means no wait at all), and never
  more than `GATHER_CAP_S` (below, with the chip measurement that chose
  it). So a burst whose requests reach the server together rides ONE
  step, and a lone request on an idle server still finds nothing at the
  door and waits for nothing. The model call
  in flight stays the batching window behind a busy dispatcher: what
  arrives behind it piles up and is cut together (inside the row cap
  and the token budget) the moment it returns, so batches grow with the
  backlog by themselves. Without `en_route` (standalone construction)
  and in `ContinuousBatcher`, which runs in no cell of the benchmark,
  a free dispatcher always cuts at once. Two histograms say, once a
  dispatched batch, what happened: `serving_batch_cut_idle_ratio` 1
  when the batch's oldest request was submitted with no call in flight
  and none ran before it was cut, 0 when it was cut behind a call;
  `serving_batch_gathered_ratio` 1 when the dispatcher waited for
  requests en route before this cut (the span `serve.delay`), else 0.
- The model call itself buckets the context axis (model_facade.predict
  `context_buckets`): rows are padded to the smallest configured bucket
  that fits their deepest valid context, so the number of compiled
  shapes is bounded by len(buckets) — shared with offline predict,
  which routes through the same compiled-step cache.

`submit()` returns a concurrent.futures.Future resolving to the list of
per-line results; an optional `phases` dict receives the `batch_wait`
(submit -> dispatch) and `device` SLO phases. `device` is the FULL
duration of the coalesced model call the request rode in — that is the
latency the request actually experienced (phases sum to ~total); the
per-batch cost lives in `serving_device_seconds`, and amortized
per-row cost is that divided by `serving_batch_rows`. Beside `device`
the dict gets `device_end`, the `perf_counter` instant at which that
call ended: not a phase, the submitter subtracts it from the instant its
own thread runs again (the server's `handoff` phase). `drain()` stops
intake, flushes everything pending, and joins the dispatcher — the
SIGTERM-grace path.

Deadline propagation (serving/admission.py): `submit()` takes the
request's Deadline. A request whose remaining budget cannot cover its
context bucket's observed p95 device time is REFUSED up front
(`DeadlineInfeasible`, an honest 503 shed — coalescing it would only
burn a device slot on a guaranteed 504); a request that expires
behind the call in flight settles as `DeadlineExceeded` (504) and never
reaches the device. Per-bucket
device times come from a small rolling window of dispatched-batch
durations — no estimate, no refusal (a cold batcher never sheds on a
bogus p95).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from code2vec_tpu import obs
from code2vec_tpu.obs import reqtrace, tracer
from code2vec_tpu.serving.admission import (
    Deadline, DeadlineExceeded, DeadlineInfeasible, expired_counter,
)

_H_BATCH_ROWS = obs.histogram(
    "serving_batch_rows",
    "rows per dispatched device batch (coalescing effectiveness)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
_H_DEVICE = obs.histogram(
    "serving_device_seconds",
    "one coalesced model call: parse + pad + device step + unpack")
_DISPATCHER_HELP = (
    "time a dispatcher thread spent in one state, observed on leaving "
    "it: idle (nothing pending), delay (a request is pending and the "
    "free dispatcher holds the cut: the dynamic batcher while the "
    "server reports requests en route, the continuous batcher while a "
    "parse is still writing into the head slot), "
    "dispatch (inside the coalesced model call and its fan-out). "
    "dispatch over wall time is the busy share of the thread every "
    "request passes through")
_H_STATE = {state: obs.histogram("serving_dispatcher_seconds",
                                 _DISPATCHER_HELP, state=state)
            for state in ("idle", "delay", "dispatch")}


_H_COMPILES = obs.compiles_during("serve.dispatch")


def _state(state: str):
    return obs.span("serve." + state, hist=_H_STATE[state],
                    compiles=_H_COMPILES if state == "dispatch" else None)


_C_BATCHES = obs.counter("serving_batches_total",
                         "device batches dispatched by the batcher")
_G_INFLIGHT = obs.gauge(
    "serving_batch_inflight_steps",
    "device steps currently in flight (continuous batching)")
_C_RIDES = obs.counter(
    "serving_batch_inflight_rides_total",
    "admissions that arrived while a step was in flight and ride the "
    "next one (continuous batching)")
_H_CUT_IDLE = obs.histogram(
    "serving_batch_cut_idle_ratio",
    "one observation a dispatched batch: 1 when its oldest request "
    "found the dispatcher free and went straight through, 0 when it "
    "was cut behind a model call. The mean is the share of batches "
    "that a fixed coalescing delay would have held back",
    buckets=(0.0, 1.0))
_H_GATHERED = obs.histogram(
    "serving_batch_gathered_ratio",
    "one observation a dispatched batch: 1 when the free dispatcher "
    "waited for requests the server reported en route before it cut "
    "this batch, else 0. The mean is the share of batches the gather "
    "engaged for",
    buckets=(0.0, 1.0))

# The most a free dispatcher waits for requests en route, counted from
# the submit of the oldest pending request: this share of the pending
# bucket's tracked device time, and never more than the cap. What chose
# them (my chip runs, PR 42, `brumby-14b-pp8.serve_score_rerank_burst`,
# one warm server, 10 s windows of 60 bursts of sixteen, two machines):
# the server takes a burst in at 0.7-0.8 ms a request (accept, thread,
# headers, JSON, admission: serial under the interpreter lock), so the
# sixteenth is submitted 11.3-12.7 ms after the first in the median,
# 13.2-14.5 ms at the 90th percentile. Under a ceiling of 4 ms a step
# was cut with 7-8 rows and the median request moved by nothing (60.9-
# 61.7 ms against 60.0-61.3 with no gather), at 10-12 ms with 12-15 rows
# and a second step of 1-4 (61.2-63.4 against 65.7-67.8 on that
# machine), at 16 ms 41-43 of 49 bursts of the 16 bucket rode ONE step
# (58.2-60.3, the slowest request of a burst 69.4-69.6 against 73.6-
# 86.9); 24 ms read no better (62.8). A quarter of the tracked step
# (40 ms: ~10 ms) cuts the burst short; half of it is above the cap in
# every token cell and 3-4 ms in `java14m.serve_open`.
GATHER_STEP_SHARE = 0.5
GATHER_CAP_S = 0.016


def _cut_idle(items, t_free: float, in_flight: int = 0) -> float:
    """What `serving_batch_cut_idle_ratio` observes for a batch that is
    being cut: 1.0 when no model call is in flight (`in_flight`: the
    continuous batcher's other workers) and the oldest of `items` was
    submitted after the last one returned (`t_free`), else 0.0."""
    return float(in_flight == 0
                 and min(i.t_submit for i in items) >= t_free)


def parse_buckets(spec, max_contexts: int, cp: int = 1) -> Tuple[int, ...]:
    """Normalize a bucket spec ("32,64,128" string or int sequence) into
    a sorted tuple capped at `max_contexts` (always included, so every
    legal row fits some bucket) and filtered to multiples of the
    context-parallel degree (a cp-sharded step needs the context axis
    divisible by cp)."""
    if isinstance(spec, str):
        vals = [int(v) for v in spec.replace(" ", "").split(",") if v]
    else:
        vals = [int(v) for v in (spec or ())]
    vals = sorted({v for v in vals if 0 < v < max_contexts
                   and v % max(cp, 1) == 0})
    return tuple(vals) + (max_contexts,)


def bucket_for(n_contexts: int, buckets: Sequence[int]) -> int:
    """Smallest bucket holding a row whose deepest valid context sits at
    index n_contexts-1. Callers guarantee buckets[-1] == max_contexts."""
    for b in buckets:
        if b >= n_contexts:
            return b
    return buckets[-1]


class _Pending:
    __slots__ = ("lines", "future", "t_submit", "phases", "deadline",
                 "bucket", "trace", "settled", "tenant")

    def __init__(self, lines: List[str], phases: Optional[dict],
                 deadline: Optional[Deadline] = None,
                 bucket: Optional[int] = None,
                 trace=None, tenant: Optional[str] = None):
        self.lines = lines
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.phases = phases
        self.deadline = deadline
        self.bucket = bucket
        self.trace = trace
        # collapsed tenant label (serving/tenancy.py) — the batchers'
        # DWRR fill and per-slot share caps key on it; None when the
        # tenancy layer is off
        self.tenant = tenant
        # continuous batcher: an item settled early (504 / parse error)
        # stays in its slot (its rows are reserved in the fixed-shape
        # buffer, mask-zeroed) but is skipped at result fan-out
        self.settled = False


class _DeviceTimeTracker:
    """Rolling per-bucket device-call durations -> p95 estimate. Small
    fixed windows (32 samples) so the estimate tracks the CURRENT
    device behavior — a transient slowdown ages out in 32 batches."""

    MIN_SAMPLES = 4

    def __init__(self, window: int = 32):
        self._window = window
        self._lock = threading.Lock()
        self._samples: Dict[Optional[int], deque] = {}
        # p95 runs on EVERY bounded-deadline admission but samples only
        # arrive once per dispatched batch, so the sorted view is cached
        # per bucket and invalidated on record() — the admission path is
        # O(1) dict lookups unless a new sample landed since last read.
        self._sorted: Dict[Optional[int], List[float]] = {}

    def record(self, bucket: Optional[int], duration_s: float) -> None:
        with self._lock:
            d = self._samples.get(bucket)
            if d is None:
                d = self._samples[bucket] = deque(maxlen=self._window)
            d.append(float(duration_s))
            self._sorted.pop(bucket, None)

    def p95(self, bucket: Optional[int]) -> Optional[float]:
        with self._lock:
            d = self._samples.get(bucket)
            if d is None or len(d) < self.MIN_SAMPLES:
                return None
            ordered = self._sorted.get(bucket)
            if ordered is None:
                ordered = self._sorted[bucket] = sorted(d)
            return ordered[min(int(round(0.95 * (len(ordered) - 1))),
                               len(ordered) - 1)]


class DynamicBatcher:
    """Single dispatcher thread over a condition-guarded pending queue.

    `predict_fn(lines) -> List[result]` is the facade's batched predict:
    it must return exactly one result per input line, in order. All
    pending groups are dispatched together in FIFO order up to
    `max_batch_rows` rows; one oversized group (a file with more methods
    than the cap) dispatches alone — predict_fn chunks internally, so
    correctness never depends on the cap.

    With `tenancy` (serving/tenancy.TenantPolicy) a batch with MORE
    than one tenant pending fills in deficit-weighted-round-robin
    order across per-tenant sub-queues (tenancy.dwrr_take) instead of
    global FIFO, so one tenant's backlog cannot monopolize a device
    batch; a single tenant (or no policy) keeps the exact FIFO path.

    `en_route(within_s) -> int` is the server's count of requests it has
    seen and not yet submitted, those seen in the last `within_s`
    seconds: what a free dispatcher gathers before it cuts (the dispatch
    rule, module docstring). The server calls `en_route_changed()` when
    the count falls. None: a free dispatcher always cuts at once.
    """

    def __init__(self, predict_fn: Callable[[List[str]], List],
                 max_batch_rows: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 tenancy=None,
                 bucket_of: Optional[Callable[[object], int]] = None,
                 max_batch_tokens: Optional[int] = None,
                 en_route: Optional[Callable[[float], int]] = None):
        self.predict_fn = predict_fn
        self._en_route = en_route
        self._gathering = False     # the dispatcher is inside a gather
        self._gathered = False      # ... and was, before the last cut
        self.max_batch_rows = max(1, int(max_batch_rows))
        self.tenancy = tenancy
        self._dwrr_state: dict = {}
        # A model whose rows are not extractor lines says how one row
        # buckets (`bucket_of(row)`: a token sequence by its length) and
        # may cap a batch's padded size: rows x deepest bucket <=
        # max_batch_tokens (a step of a sequence model costs by tokens,
        # not by rows).
        self._bucket_fn = bucket_of
        self.max_batch_tokens = (None if max_batch_tokens is None
                                 else max(1, int(max_batch_tokens)))
        # Context-bucket list (model.context_buckets) for per-bucket
        # device-time estimates; None = one global estimate (the
        # standalone/unit-test construction).
        self.buckets = tuple(buckets) if buckets else None
        self.device_times = _DeviceTimeTracker()
        self._cond = threading.Condition()
        self._pending: List[_Pending] = []
        self._pending_rows = 0
        self._draining = False
        self._closed = False
        self.batches_dispatched = 0
        self._t_free = 0.0      # when the last model call returned
        self._thread = threading.Thread(target=self._run,
                                        name="serving-batcher", daemon=True)
        self._thread.start()

    # -------------------------------------------------------------- API

    def _bucket_of(self, lines: Sequence[str]) -> Optional[int]:
        """Context bucket this request's rows would pad to (the deepest
        line decides, exactly as model_facade._predict_chunk buckets a
        chunk). Extractor lines are space-separated `name ctx ctx ...`
        padded with trailing blanks, so a whitespace split counts the
        real contexts."""
        if self.buckets is None:
            return None
        if self._bucket_fn is not None:
            return max(self._bucket_fn(line) for line in lines)
        deepest = max((len(line.split()) - 1 for line in lines),
                      default=1)
        return bucket_for(max(deepest, 1), self.buckets)

    def submit(self, lines: Sequence[str],
               phases: Optional[dict] = None,
               deadline: Optional[Deadline] = None,
               trace=None, tenant: Optional[str] = None) -> Future:
        item = _Pending(list(lines), phases, deadline, trace=trace,
                        tenant=tenant)
        if not item.lines:
            item.future.set_result([])
            return item.future
        if deadline is not None and deadline.bounded:
            if deadline.expired():
                expired_counter("batch_wait").inc()
                item.future.set_exception(DeadlineExceeded(
                    "request deadline expired before batching"))
                return item.future
            item.bucket = self._bucket_of(item.lines)
            p95 = self.device_times.p95(item.bucket)
            if p95 is not None and deadline.remaining() < p95:
                # Fail-fast refusal: even an immediate solo dispatch
                # cannot finish inside the budget, so coalescing this
                # request would spend a device slot on a sure 504.
                item.future.set_exception(DeadlineInfeasible(
                    f"remaining deadline budget "
                    f"{deadline.remaining() * 1e3:.0f}ms is below the "
                    f"bucket's observed p95 device time "
                    f"{p95 * 1e3:.0f}ms", retry_after_s=p95))
                return item.future
        elif self.buckets is not None:
            item.bucket = self._bucket_of(item.lines)
        with self._cond:
            if self._draining:
                item.future.set_exception(
                    RuntimeError("batcher is draining; not accepting "
                                 "new requests"))
                return item.future
            self._pending.append(item)
            self._pending_rows += len(item.lines)
            # A gathering dispatcher wakes for a full batch, for the
            # server's word that nobody is en route any more
            # (`en_route_changed`) and at its ceiling: a wake-up a
            # submit costs every request of a burst a hand-over of the
            # interpreter lock on the path the gather waits for.
            if not self._gathering or self._full_locked():
                self._cond.notify_all()
        return item.future

    def rebucket(self, buckets: Optional[Sequence[int]]) -> None:
        """Hot-swap support: adopt a new model's context-bucket grid
        and drop the device-time samples keyed to the old one (a cold
        tracker refuses nothing until it has real samples; stale p95s
        on a changed grid would misprice every feasibility check)."""
        self.buckets = tuple(buckets) if buckets else None
        self.device_times = _DeviceTimeTracker()

    def drain(self, timeout: Optional[float] = None) -> None:
        """Stop intake, flush every pending request, join the thread.
        Idempotent; safe from signal-handler-adjacent threads."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        self._thread.join(timeout)

    def en_route_changed(self) -> None:
        """The server's count of requests en route fell to nothing: a
        dispatcher that is gathering looks again at once. (`_gathering`
        is set under the lock BEFORE the dispatcher reads the count, so
        a fall it did not see finds the flag up.)"""
        if self._gathering:
            with self._cond:
                self._cond.notify_all()

    # -------------------------------------------------------- dispatcher

    def _run(self) -> None:
        while True:
            batch = self._collect()
            if batch is None:
                return
            with _state("dispatch"):
                self._dispatch(batch)
            self._t_free = time.perf_counter()

    def _collect(self) -> Optional[List[_Pending]]:
        """Block until a live request is pending, gather what the
        server reports en route, and cut a batch (the dispatch rule,
        module docstring): this thread being here means no model call
        is in flight. Expired items are settled as 504 here, before
        they can occupy a device slot; draining flushes what is pending
        and then ends the thread."""
        with self._cond:
            while True:
                if self._pending:
                    self._expire_locked()
                    self._gathered = self._gather_locked()
                    if self._pending:
                        return self._take_locked()
                elif self._draining:
                    self._closed = True
                    return None
                else:
                    with _state("idle"):
                        self._cond.wait()

    def _gather_locked(self) -> bool:
        """Hold the cut while `_gather_left_locked` says so, in the
        dispatcher state `delay`; True when it waited. What expires
        meanwhile is settled before the cut, as behind a call."""
        if self._en_route is None:
            return False
        self._gathering = True
        try:
            left = self._gather_left_locked()
            if left <= 0.0:
                return False
            with _state("delay"):
                while left > 0.0:
                    self._cond.wait(left)
                    self._expire_locked()
                    left = self._gather_left_locked()
            return True
        finally:
            self._gathering = False

    def _gather_left_locked(self) -> float:
        """Seconds a free dispatcher may still wait before it cuts; 0
        when nothing is pending or en route, the batch is full, the
        batcher drains, the pending bucket's device time is not tracked
        yet, or the ceiling has passed."""
        if not self._pending or self._draining or self._full_locked():
            return 0.0
        step = self.device_times.p95(self._deepest_locked())
        if step is None:
            return 0.0
        ceiling = min(GATHER_CAP_S, step * GATHER_STEP_SHARE)
        left = self._pending[0].t_submit + ceiling - time.perf_counter()
        if left <= 0.0 or self._en_route(ceiling) <= 0:
            return 0.0
        return left

    def _deepest_locked(self) -> Optional[int]:
        """The deepest bucket pending: the shape a cut now would run."""
        return max((i.bucket for i in self._pending
                    if i.bucket is not None), default=None)

    def _full_locked(self) -> bool:
        """No room for one more row: by the row cap, or by the token
        budget at the deepest bucket pending."""
        return (self._pending_rows >= self.max_batch_rows
                or not self._fits(self._pending_rows + 1,
                                  self._deepest_locked() or 0))

    def _expire_locked(self) -> None:
        alive: List[_Pending] = []
        for item in self._pending:
            if item.deadline is not None and item.deadline.expired():
                self._pending_rows -= len(item.lines)
                expired_counter("batch_wait").inc()
                if item.future.set_running_or_notify_cancel():
                    item.future.set_exception(DeadlineExceeded(
                        "request deadline expired behind the model "
                        "call in flight"))
            else:
                alive.append(item)
        self._pending = alive

    def _take_locked(self) -> List[_Pending]:
        if self.tenancy is not None:
            from code2vec_tpu.serving.tenancy import dwrr_take
            picked = dwrr_take(self._pending, self.max_batch_rows,
                               self.tenancy.weight, self._dwrr_state)
            if picked is not None:
                # >1 tenant pending: weighted-fair interleave. None ⇒
                # a single tenant's queue — the FIFO loop below is
                # byte-identical to the tenancy-free batcher.
                chosen = set(picked)
                take = [self._pending[i] for i in picked]
                self._pending = [item for j, item
                                 in enumerate(self._pending)
                                 if j not in chosen]
                self._pending_rows -= sum(len(i.lines) for i in take)
                return take
        take: List[_Pending] = []
        rows = deepest = 0
        while self._pending:
            nxt = self._pending[0]
            n = rows + len(nxt.lines)
            if take and (n > self.max_batch_rows or not self._fits(
                    n, max(deepest, nxt.bucket or 0))):
                break
            take.append(self._pending.pop(0))
            rows, deepest = n, max(deepest, nxt.bucket or 0)
        self._pending_rows -= rows
        return take

    def _fits(self, rows: int, bucket: int) -> bool:
        """Whether `rows` rows padded to `bucket` stay inside the token
        budget (always, where the model set none)."""
        return (self.max_batch_tokens is None
                or rows * bucket <= self.max_batch_tokens)

    def _dispatch(self, batch: List[_Pending]) -> None:
        t_dispatch = time.perf_counter()
        # Last expiry check before device work: an item that ran out of
        # budget between collection and dispatch settles as 504 here
        # rather than burning rows in the device batch.
        live: List[_Pending] = []
        for item in batch:
            if item.deadline is not None and item.deadline.expired():
                expired_counter("batch_wait").inc()
                if item.future.set_running_or_notify_cancel():
                    item.future.set_exception(DeadlineExceeded(
                        "request deadline expired at dispatch"))
            else:
                live.append(item)
        batch = live
        if not batch:
            return
        all_lines: List[str] = []
        for item in batch:
            wait = t_dispatch - item.t_submit
            if item.phases is not None:
                item.phases["batch_wait"] = wait
            if item.trace is not None:
                item.trace.add_span("batch_wait", item.t_submit, wait)
            all_lines.extend(item.lines)
        _C_BATCHES.inc()
        _H_CUT_IDLE.observe(_cut_idle(batch, self._t_free))
        _H_GATHERED.observe(float(self._gathered))
        self.batches_dispatched += 1
        batch_id = self.batches_dispatched
        _H_BATCH_ROWS.observe(len(all_lines))
        try:
            with tracer.collect() as stages:
                results = self.predict_fn(all_lines)
            if len(results) != len(all_lines):
                raise RuntimeError(
                    f"predict_fn returned {len(results)} results for "
                    f"{len(all_lines)} lines")
        except BaseException as e:  # noqa: BLE001 — futures must settle
            for item in batch:
                if not item.future.set_running_or_notify_cancel():
                    continue
                item.future.set_exception(e)
            return
        t_end = time.perf_counter()
        dur = t_end - t_dispatch
        _H_DEVICE.observe(dur)
        # The deepest bucket in the batch is the shape the device call
        # compiled/ran at — that is the bucket this duration informs.
        batch_bucket = max((i.bucket for i in batch
                            if i.bucket is not None), default=None)
        self.device_times.record(batch_bucket, dur)
        self._record_batch_spans(batch, batch_id, batch_bucket,
                                 len(all_lines), t_dispatch, dur, stages)
        off = 0
        for item in batch:
            n = len(item.lines)
            if item.phases is not None:
                item.phases["device"] = dur
                item.phases["device_end"] = t_end
            if item.future.set_running_or_notify_cancel():
                item.future.set_result(results[off:off + n])
            off += n

    def _record_batch_spans(self, batch: List[_Pending], batch_id: int,
                            bucket: Optional[int], rows: int,
                            t_dispatch: float, dur: float,
                            stages=()) -> None:
        _record_batch_spans(batch, batch_id, bucket, rows, t_dispatch,
                            dur, stages)


def _record_batch_spans(batch: List[_Pending], batch_id: int,
                        bucket: Optional[int], rows: int,
                        t_dispatch: float, dur: float,
                        stages=()) -> None:
    """Fan the coalesced device call into the member traces: ONE
    shared batch span id is stamped into every member request's
    trace (the batch node N request trees share), each member's
    `device` span hangs under it with the model call's stages
    (`stages`: what `tracer.collect` gathered around the call, the
    facade's predict.parse / .assemble / .device / .render) as its
    children and the device stage's parts (predict.device.put /
    .enqueue / .wait / .fetch) under the `predict.device` stage, and
    the process tracer records the batch exactly once —
    tagged with every member trace id so the bulk Chrome trace links
    batch to requests."""
    traced = [item for item in batch if item.trace is not None]
    if not traced:
        return
    batch_span_id = reqtrace.mint_span_id()
    device_span_id = reqtrace.mint_span_id() if stages else None
    # a span closes behind its children, so the parts of a stage come
    # BEFORE it in `stages`: the stages that have parts get their ids
    # first (`predict.device.put` hangs under `predict.device`)
    names = {name for name, _, _ in stages}
    stage_ids = {name: reqtrace.mint_span_id()
                 for name in names & {n.rpartition(".")[0] for n in names}}
    members = [item.trace.trace_id for item in traced]
    attrs = {"batch_id": batch_id, "rows": rows,
             "requests": len(batch)}
    if bucket is not None:
        attrs["bucket"] = bucket
    # reqtrace stores attrs BY REFERENCE, so the whole batch shares ONE
    # attrs dict built here on the dispatch thread (N spans, one dict +
    # one members list — not N dict constructions; same memoization as
    # the tracer-export fix). It only gets serialized per response on
    # the --serve_debug_trace + ?debug=trace path.
    span_attrs = dict(attrs, members=members)
    for item in traced:
        item.trace.add_span("batch", t_dispatch, dur,
                            span_id=batch_span_id,
                            attrs=span_attrs,
                            forward=False)
        item.trace.add_span("device", t_dispatch, dur,
                            span_id=device_span_id,
                            parent_id=batch_span_id)
        for name, start, seconds in stages:
            # the ring already has each stage once, from the span itself
            item.trace.add_span(
                name, start, seconds, span_id=stage_ids.get(name),
                parent_id=stage_ids.get(name.rpartition(".")[0],
                                        device_span_id),
                forward=False)
    tracer.default_tracer().maybe_record(
        "serving_batch", t_dispatch, dur, span_id=batch_span_id,
        attrs=dict(attrs, member_trace_ids=members))


class StaleParse(RuntimeError):
    """Raised by a backend's `predict_rows` when the live model's
    fingerprint no longer matches the slot's parse-time fingerprint (a
    hot-swap landed between parse and dispatch): the slot's int rows
    were built against the OLD vocab tables and must not run under the
    new weights. The worker falls back to the lines path, re-parsing
    under the current model — so the batch still answers with exactly
    one fingerprint."""


class _Slot:
    """One forming/in-flight device batch of the continuous batcher.

    `rows` rows of the fixed-shape buffer are reserved (parse writes
    land in disjoint row ranges, so only the RESERVATION is locked —
    the parse itself runs on the submitter thread outside the lock,
    tracked by `pending_writes`)."""

    __slots__ = ("kind", "items", "offsets", "rows", "buffer",
                 "pending_writes", "sealed", "cut_idle", "fps")

    def __init__(self, kind: str, buffer=None):
        self.kind = kind              # "rows" (zero-copy) | "lines"
        self.items: List[_Pending] = []
        self.offsets: List[Tuple[int, int]] = []   # (row_offset, n)
        self.rows = 0
        self.buffer = buffer
        self.pending_writes = 0
        self.sealed = False
        self.cut_idle = 0.0           # _cut_idle(), set when a worker takes it
        self.fps: set = set()         # model fingerprints seen at parse


class ContinuousBatcher:
    """Slot-reservation dispatcher: continuous batching for the serve
    path (--serve_continuous).

    The collect-then-dispatch DynamicBatcher parses a batch's lines
    inside its one model call. Here the next batch is always forming:
    `submit()` reserves rows in the tail slot under the lock, parses
    the extractor lines straight into the slot's padded (rows,
    contexts) buffer OUTSIDE the lock (zero-copy:
    reader.parse_context_lines(out=...) — no per-request RowBatch
    between extractor_pool and the device step), and up to
    `inflight_steps` worker threads follow the module's dispatch rule:
    the head slot is due as soon as a worker is free and no parse is
    still writing into it (`serve.delay` is the wait for that parse). A
    row that arrives while every worker is inside a step rides the
    next one with whatever else arrived behind it. A serial client
    gets byte-identical responses from both batchers.

    Admission control is re-expressed against the in-flight step's ETA:
    a bounded-deadline request is refused (`DeadlineInfeasible`) when
    `remaining < eta + p95(bucket)` where eta is 0 if a worker is free,
    else the soonest in-flight step's expected completion. Cold
    tracker => no refusal, as in the classic batcher.

    `backend` is the model adapter (serving/server.py) with:
    alloc(rows), parse_into(lines, buffer, row_offset) -> fingerprint,
    predict_rows(buffer, n_rows, fingerprint) -> results (raising
    StaleParse when `fingerprint` is no longer the live model's), and
    predict_lines(lines) -> results. Without a backend (unit tests)
    every slot is a "lines" slot dispatched through `predict_fn`,
    exercising the continuous machinery alone. Oversized requests
    (> max_batch_rows) and slots whose parse-time fingerprint no longer
    matches the live model (mid-batch hot-swap) fall back to the lines
    path — predict_lines re-parses under the CURRENT model, so every
    response batch still carries exactly one fingerprint.
    """

    def __init__(self, predict_fn: Optional[Callable[[List[str]], List]]
                 = None,
                 max_batch_rows: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 inflight_steps: int = 2, backend=None, tenancy=None):
        if predict_fn is None and backend is None:
            raise ValueError("ContinuousBatcher needs a predict_fn or "
                             "a backend")
        self.predict_fn = predict_fn
        self.backend = backend
        self.tenancy = tenancy
        self.max_batch_rows = max(1, int(max_batch_rows))
        self.buckets = tuple(buckets) if buckets else None
        self.inflight_steps = max(1, int(inflight_steps))
        self.device_times = _DeviceTimeTracker()
        self._cond = threading.Condition()
        self._slots: deque = deque()
        self._pool: List = []
        self._pool_cap = self.inflight_steps + 2
        self._inflight = 0
        self._inflight_meta: List[List] = []   # [t_launch, bucket]
        self._draining = False
        self.batches_dispatched = 0
        self.rides = 0
        self._t_free = 0.0      # when the last model call returned
        self._workers = [
            threading.Thread(target=self._worker,
                             name=f"serving-batcher-{i}", daemon=True)
            for i in range(self.inflight_steps)]
        for t in self._workers:
            t.start()

    # -------------------------------------------------------------- API

    _bucket_of = DynamicBatcher._bucket_of
    _bucket_fn = None       # rows are extractor lines (no token budget)

    def _tenant_cap_hit_locked(self, slot: "_Slot",
                               tenant: Optional[str], n: int) -> bool:
        """Per-slot share cap: in a slot already SHARED by other
        tenants, one tenant may reserve at most its weighted share of
        the slot's rows — overflow opens the next slot instead of
        squeezing batch-mates out. A slot holding a single tenant (the
        common case, and every tenancy-off run) is never capped, so
        the classic fill behavior is untouched."""
        if self.tenancy is None or not slot.items:
            return False
        tenants = {i.tenant for i in slot.items}
        if tenants == {tenant}:
            return False
        held = sum(len(i.lines) for i in slot.items
                   if i.tenant == tenant)
        total_w = sum(self.tenancy.weight(t)
                      for t in tenants | {tenant})
        cap = max(1, int(self.max_batch_rows
                         * self.tenancy.weight(tenant)
                         / (total_w or 1.0)))
        return held + n > cap

    def submit(self, lines: Sequence[str],
               phases: Optional[dict] = None,
               deadline: Optional[Deadline] = None,
               trace=None, tenant: Optional[str] = None) -> Future:
        item = _Pending(list(lines), phases, deadline, trace=trace,
                        tenant=tenant)
        if not item.lines:
            item.future.set_result([])
            return item.future
        item.bucket = self._bucket_of(item.lines)
        if deadline is not None and deadline.bounded:
            if deadline.expired():
                expired_counter("batch_wait").inc()
                item.future.set_exception(DeadlineExceeded(
                    "request deadline expired before batching"))
                return item.future
            p95 = self.device_times.p95(item.bucket)
            if p95 is not None:
                eta = self._inflight_eta()
                if deadline.remaining() < eta + p95:
                    # The request cannot finish inside its budget even
                    # riding the very next step: the soonest in-flight
                    # step completes in `eta`, then its own bucket's
                    # p95 device time runs.
                    item.future.set_exception(DeadlineInfeasible(
                        f"remaining deadline budget "
                        f"{deadline.remaining() * 1e3:.0f}ms is below "
                        f"the in-flight step ETA {eta * 1e3:.0f}ms + "
                        f"bucket p95 device time {p95 * 1e3:.0f}ms",
                        retry_after_s=eta + p95))
                    return item.future
        n = len(item.lines)
        kind = ("rows" if self.backend is not None
                and n <= self.max_batch_rows
                and getattr(self.backend, "supports_rows",
                            lambda: True)() else "lines")
        with self._cond:
            if self._draining:
                item.future.set_exception(
                    RuntimeError("batcher is draining; not accepting "
                                 "new requests"))
                return item.future
            slot = self._slots[-1] if self._slots else None
            if (slot is None or slot.sealed or slot.kind != kind
                    or slot.rows + n > self.max_batch_rows
                    or self._tenant_cap_hit_locked(slot, tenant, n)):
                if slot is not None and not slot.sealed:
                    slot.sealed = True
                buffer = self._get_buffer_locked() if kind == "rows" \
                    else None
                slot = _Slot(kind, buffer)
                self._slots.append(slot)
            off = slot.rows
            slot.items.append(item)
            slot.offsets.append((off, n))
            slot.rows += n
            if slot.rows >= self.max_batch_rows:
                slot.sealed = True
            if self._inflight > 0:
                # this row arrived while a step was on device
                self.rides += 1
                _C_RIDES.inc()
            if kind == "rows":
                slot.pending_writes += 1
            self._cond.notify_all()
        if kind != "rows":
            return item.future
        # Zero-copy parse, outside the lock: this submitter thread
        # writes its own disjoint row range of the slot buffer.
        try:
            fp = self.backend.parse_into(item.lines, slot.buffer, off)
        except BaseException as e:  # noqa: BLE001 — future must settle
            with self._cond:
                slot.pending_writes -= 1
                slot.buffer.context_valid_mask[off:off + n] = 0.0
                slot.buffer.example_valid[off:off + n] = False
                item.settled = True
                if item.future.set_running_or_notify_cancel():
                    item.future.set_exception(e)
                self._cond.notify_all()
            return item.future
        with self._cond:
            slot.pending_writes -= 1
            slot.fps.add(fp)
            self._cond.notify_all()
        return item.future

    def rebucket(self, buckets: Optional[Sequence[int]]) -> None:
        """Hot-swap support: adopt the new model's bucket grid, drop
        device-time samples keyed to the old one, and drop pooled
        buffers (they were allocated by the old model's backend). Slots
        already forming keep their parse-time fingerprints — the worker
        notices the mismatch and re-parses via the lines path, so a
        batch never mixes weights generations."""
        with self._cond:
            self.buckets = tuple(buckets) if buckets else None
            self.device_times = _DeviceTimeTracker()
            self._pool = []

    def drain(self, timeout: Optional[float] = None) -> None:
        """Stop intake, flush every forming slot (partially filled
        included), join the workers. Idempotent."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        for t in self._workers:
            t.join(None if deadline is None
                   else max(deadline - time.monotonic(), 0.0))

    # -------------------------------------------------------- dispatch

    def _inflight_eta(self) -> float:
        """Seconds until the soonest in-flight step is expected to
        free a worker; 0 when a worker is idle or the tracker is cold
        for any in-flight bucket (never refuse on a guess)."""
        with self._cond:
            if self._inflight < self.inflight_steps:
                return 0.0
            meta = [tuple(m) for m in self._inflight_meta]
        now = time.perf_counter()
        eta = None
        for t_launch, bucket, _slot in meta:
            p95 = self.device_times.p95(bucket)
            if p95 is None:
                return 0.0
            done_in = max(t_launch + p95 - now, 0.0)
            eta = done_in if eta is None else min(eta, done_in)
        return eta or 0.0

    def _get_buffer_locked(self):
        if self._pool:
            return self._pool.pop()
        return self.backend.alloc(self.max_batch_rows)

    def _release_buffer(self, buffer, rows: int) -> None:
        if buffer is None:
            return
        # wipe the used rows' validity so a pooled buffer can never
        # inflate the next batch's bucket (indices are re-PADded per
        # claim by parse_into)
        buffer.context_valid_mask[:rows] = 0.0
        buffer.example_valid[:rows] = False
        with self._cond:
            if len(self._pool) < self._pool_cap:
                self._pool.append(buffer)

    def _expire_head_locked(self, slot: _Slot) -> None:
        if slot.pending_writes:
            return   # a parse is writing; next pass catches expiries
        for (off, n), item in zip(slot.offsets, slot.items):
            if item.settled or item.deadline is None \
                    or not item.deadline.expired():
                continue
            expired_counter("batch_wait").inc()
            item.settled = True
            if slot.buffer is not None:
                slot.buffer.context_valid_mask[off:off + n] = 0.0
                slot.buffer.example_valid[off:off + n] = False
            if item.future.set_running_or_notify_cancel():
                item.future.set_exception(DeadlineExceeded(
                    "request deadline expired behind the model "
                    "call in flight"))

    def _worker(self) -> None:
        while True:
            slot = self._next_slot()
            if slot is None:
                return
            try:
                with _state("dispatch"):
                    self._run_slot(slot)
            finally:
                self._release_buffer(slot.buffer, slot.rows)
                with self._cond:
                    self._inflight -= 1
                    self._t_free = time.perf_counter()
                    self._inflight_meta = [
                        m for m in self._inflight_meta
                        if m[2] is not slot]
                    _G_INFLIGHT.set(self._inflight)
                    self._cond.notify_all()

    def _next_slot(self) -> Optional[_Slot]:
        with self._cond:
            while True:
                slot = self._slots[0] if self._slots else None
                if slot is None:
                    if self._draining:
                        return None
                    with _state("idle"):
                        self._cond.wait()
                    continue
                self._expire_head_locked(slot)
                if all(i.settled for i in slot.items) \
                        and not slot.pending_writes:
                    self._slots.popleft()
                    self._release_buffer_nolock_queue(slot)
                    continue
                if slot.pending_writes == 0:
                    # the dispatch rule: this worker is free, the head
                    # slot holds a live request and nothing is writing
                    self._slots.popleft()
                    slot.sealed = True
                    slot.cut_idle = _cut_idle(
                        [i for i in slot.items if not i.settled],
                        self._t_free, self._inflight)
                    self._inflight += 1
                    bucket = max((i.bucket for i in slot.items
                                  if i.bucket is not None
                                  and not i.settled), default=None)
                    self._inflight_meta.append(
                        [time.perf_counter(), bucket, slot])
                    _G_INFLIGHT.set(self._inflight)
                    return slot
                with _state("delay"):
                    self._cond.wait()

    def _release_buffer_nolock_queue(self, slot: _Slot) -> None:
        # called with the lock held for a fully-expired slot: return
        # the (already mask-wiped) buffer straight to the pool
        if slot.buffer is not None \
                and len(self._pool) < self._pool_cap:
            self._pool.append(slot.buffer)
            slot.buffer = None

    def _run_slot(self, slot: _Slot) -> None:
        t_dispatch = time.perf_counter()
        with self._cond:
            self._expire_head_locked(slot)
        live = [i for i in slot.items if not i.settled]
        if not live:
            return
        for item in live:
            wait = t_dispatch - item.t_submit
            if item.phases is not None:
                item.phases["batch_wait"] = wait
            if item.trace is not None:
                item.trace.add_span("batch_wait", item.t_submit, wait)
        rows_live = sum(len(i.lines) for i in live)
        _C_BATCHES.inc()
        _H_CUT_IDLE.observe(slot.cut_idle)
        _H_GATHERED.observe(0.0)    # this batcher never gathers
        self.batches_dispatched += 1
        batch_id = self.batches_dispatched
        _H_BATCH_ROWS.observe(rows_live)
        use_rows = slot.kind == "rows" and len(slot.fps) == 1
        try:
            with tracer.collect() as stages:
                if use_rows:
                    try:
                        results = self.backend.predict_rows(
                            slot.buffer, slot.rows, next(iter(slot.fps)))
                    except StaleParse:
                        use_rows = False
                    else:
                        if len(results) < slot.rows:
                            raise RuntimeError(
                                f"predict_rows returned {len(results)} "
                                f"results for {slot.rows} rows")
                if not use_rows:
                    # lines fallback: plain lines slot, a rows slot that
                    # straddled a hot-swap (mixed parse fingerprints or
                    # StaleParse), — re-parse under the CURRENT model so
                    # the batch answers with one fingerprint
                    all_lines = [l for i in live for l in i.lines]
                    fn = (self.backend.predict_lines
                          if self.backend is not None else self.predict_fn)
                    results = fn(all_lines)
                    if len(results) != len(all_lines):
                        raise RuntimeError(
                            f"predict_fn returned {len(results)} results "
                            f"for {len(all_lines)} lines")
        except BaseException as e:  # noqa: BLE001 — futures must settle
            for item in live:
                if item.future.set_running_or_notify_cancel():
                    item.future.set_exception(e)
            return
        t_end = time.perf_counter()
        dur = t_end - t_dispatch
        _H_DEVICE.observe(dur)
        batch_bucket = max((i.bucket for i in live
                            if i.bucket is not None), default=None)
        self.device_times.record(batch_bucket, dur)
        _record_batch_spans(live, batch_id, batch_bucket, rows_live,
                            t_dispatch, dur, stages)
        if use_rows:
            for (off, n), item in zip(slot.offsets, slot.items):
                if item.settled:
                    continue
                if item.phases is not None:
                    item.phases["device"] = dur
                    item.phases["device_end"] = t_end
                if item.future.set_running_or_notify_cancel():
                    item.future.set_result(results[off:off + n])
        else:
            off = 0
            for item in live:
                n = len(item.lines)
                if item.phases is not None:
                    item.phases["device"] = dur
                    item.phases["device_end"] = t_end
                if item.future.set_running_or_notify_cancel():
                    item.future.set_result(results[off:off + n])
                off += n
