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
    "time the dispatcher thread spent in one state, observed on leaving "
    "it: idle (nothing pending), delay (a request is pending and the "
    "free dispatcher holds the cut while the server reports requests "
    "en route), "
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


def _cut_idle(items, t_free: float) -> float:
    """What `serving_batch_cut_idle_ratio` observes for a batch that is
    being cut: 1.0 when the oldest of `items` was submitted after the
    last model call returned (`t_free`), else 0.0."""
    return float(min(i.t_submit for i in items) >= t_free)


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
                 "bucket", "trace", "tenant")

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
        # collapsed tenant label (serving/tenancy.py) — the batcher's
        # DWRR fill keys on it; None when the tenancy layer is off
        self.tenant = tenant


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
        _record_batch_spans(batch, batch_id, batch_bucket,
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
