"""Stalls and collections: when a run stood still, whether the program
or the machine did, and what Python's collector cost.

Two always-on series, started by `Trainer.train` and
`PredictionServer.start` and stopped where they stop (`HostWatch`, one a
process, counted by its users):

- `python_gc_pause_seconds{generation}`: every collection, from the
  `gc.callbacks` hook's `start` to its `stop` on `perf_counter` (and 0
  under `generation="none"` once a tick without one: a train window of
  20 s can pass without a single collection, and a series nobody
  observed reads as nothing where 0 is the truth). The
  hook itself takes NO lock: a collection starts wherever a container
  is allocated, also under a histogram's or the ring's own lock, and
  a callback that took that lock again would hang its thread. It only
  appends to a deque; the ticker below observes, logs (50 ms or more)
  and records what it finds there, at most one tick later.
- `host_stall_seconds{kind}`: a daemon thread sleeps 20 ms at a time
  and looks how LATE it woke. 100 ms or more is a stall, observed with
  its lateness under `kind="descheduled"` when the process burnt under a
  quarter of that wall time on its CPU clock (`time.process_time`: the
  machine, or a stopped process group, held it) and under `kind="busy"`
  otherwise (something in the process held the interpreter: a
  collection, a long call into C that keeps the lock). A wake-up on
  time observes 0 under `kind="none"`: the sum over a window is then 0
  BECAUSE the thread ran, where a missing series says it did not. A
  stall is one log line, the ring span `host.stall` and a flight
  recorder event.

The same thread observes, once a tick, the backend compiles that
finished since its tick before into `jax_compiles_during{span="process"}`
(obs/tracer.py): a compile ANYWHERE in the process, where the spans
`step_dispatch` and `serve.dispatch` see only those inside a step or a
dispatched batch. One observation a tick, so that the sum over a window
reads 0 when nothing compiled (a series nobody observed reads nothing).

The ticker records after the fact, on the tracer's `perf_counter` axis
(which `otherData.profiler_sessions` lays over a profiler's trace). It
must never open an `obs.span`: a `c2v.*` annotation that is always open
and always the latest begun would take every idle gap of a device trace
under the innermost-span rule.
"""

from __future__ import annotations

import collections
import gc
import threading
import time
from typing import Callable, Optional

from code2vec_tpu.obs import flight as _flight
from code2vec_tpu.obs import metrics as _metrics
from code2vec_tpu.obs import tracer as _tracer

TICK_S = 0.02               # the ticker's sleep
STALL_S = 0.1               # lateness from which a wake-up is a stall
GC_LOG_S = 0.05             # pause from which a collection is logged
DESCHEDULED_CPU_SHARE = 0.25

_GC_HELP = (
    "wall time of one collection of Python's cyclic collector, by the "
    "generation collected (gc.callbacks, start to stop; observed by the "
    "host watch's thread within one tick); generation=none: 0, once a "
    "tick in which no collection ended, so that a stretch without one "
    "sums to 0 and is not a missing series")
_STALL_HELP = (
    "how late the host watch's thread woke from a 20 ms sleep, one "
    "observation a wake-up: 0 under kind=none when on time (under 100 "
    "ms late), else the lateness under kind=descheduled (the process "
    "burnt under a quarter of it on its CPU clock: the machine held "
    "it) or kind=busy (the process held the interpreter)")


class HostWatch:
    """The collector's hook and the ticker thread. `start` / `stop`
    count their callers: the first start installs both, the last stop
    removes both, so a trainer and servers of one process share one
    thread and one callback. `clock`, `cpu_clock` and `sleep` are the
    ticker's only view of time (tests hand it their own)."""

    def __init__(self, tracer: Optional[_tracer.SpanTracer] = None,
                 flight: Optional[_flight.FlightRecorder] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 cpu_clock: Callable[[], float] = time.process_time,
                 sleep: Optional[Callable[[float], object]] = None):
        reg = _metrics.default_registry()
        self._h_gc = {g: reg.histogram("python_gc_pause_seconds", _GC_HELP,
                                       generation=str(g))
                      for g in (0, 1, 2, "none")}
        self._h_stall = {kind: reg.histogram("host_stall_seconds",
                                             _STALL_HELP, kind=kind)
                         for kind in ("none", "descheduled", "busy")}
        self._h_compiles = _tracer.compiles_during("process")
        self._compiles_seen = 0
        # `is None`: an empty ring has a length of 0 and is falsy
        self._tracer = (tracer if tracer is not None
                        else _tracer.default_tracer())
        self._flight = (flight if flight is not None
                        else _flight.default_flight_recorder())
        self._clock, self._cpu_clock = clock, cpu_clock
        self._stop = threading.Event()
        self._sleep = sleep or self._stop.wait
        self._log: Optional[Callable[[str], None]] = None
        self._lock = threading.Lock()       # users, thread, callback
        self._users = 0
        self._thread: Optional[threading.Thread] = None
        self._gc_t0 = 0.0
        self._collections: collections.deque = collections.deque()

    # ---------------------------------------------------------- lifetime

    def start(self, log: Optional[Callable[[str], None]] = None) -> None:
        with self._lock:
            if log is not None:
                self._log = log
            self._users += 1
            if self._users > 1:
                return
            gc.callbacks.append(self._on_gc)
            self._compiles_seen = _tracer.backend_compiles()
            self._stop.clear()
            self._thread = threading.Thread(
                target=self.run, name="host-watch", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if self._users == 0:
                return
            self._users -= 1
            if self._users:
                return
            thread, self._thread = self._thread, None
            self._stop.set()
            gc.callbacks.remove(self._on_gc)
        thread.join(timeout=1.0)
        self.flush()

    # --------------------------------------------------------- collector

    def _on_gc(self, phase: str, info: dict) -> None:
        # no lock, no log, no registry here (module docstring)
        if phase == "start":
            self._gc_t0 = self._clock()
        else:
            self._collections.append(
                (info["generation"], self._gc_t0,
                 self._clock() - self._gc_t0, info["collected"]))

    def flush(self) -> float:
        """Observe the collections gathered since the last call (0 under
        `generation="none"` where there was none); their seconds."""
        total = 0.0
        if not self._collections:
            self._h_gc["none"].observe(0.0)
        while self._collections:
            generation, t0, seconds, collected = self._collections.popleft()
            total += seconds
            self._h_gc[generation].observe(seconds)
            if seconds >= GC_LOG_S:
                self._tracer.maybe_record(
                    "gc.pause", t0, seconds,
                    attrs={"generation": generation, "collected": collected})
                self._say(f"Collection of generation {generation} paused "
                          f"the interpreter {seconds:.2f} s ({collected} "
                          f"objects collected)")
        return total

    def _say(self, text: str) -> None:
        log = self._log
        if log is not None:
            log(text)

    # ------------------------------------------------------------ ticker

    def run(self) -> None:
        """The ticker's loop, until `stop` (or the handed `sleep`) sets
        the flag."""
        woke, cpu = self._clock(), self._cpu_clock()
        while not self._stop.is_set():
            self._sleep(TICK_S)
            now, cpu_now = self._clock(), self._cpu_clock()
            self.tick(woke + TICK_S, now, cpu_now - cpu)
            woke, cpu = now, cpu_now

    def tick(self, due: float, now: float, cpu_s: float) -> None:
        """One wake-up that was due at `due` and came at `now`, the
        process having burnt `cpu_s` since the wake-up before."""
        gc_s = self.flush()
        seen = _tracer.backend_compiles()
        self._h_compiles.observe(seen - self._compiles_seen)
        self._compiles_seen = seen
        late = now - due
        if late < STALL_S:
            self._h_stall["none"].observe(0.0)
            return
        kind = ("descheduled"
                if cpu_s < DESCHEDULED_CPU_SHARE * (late + TICK_S)
                else "busy")
        self._h_stall[kind].observe(late)
        self._tracer.maybe_record("host.stall", due, late,
                                  attrs={"kind": kind, "gc_s": gc_s})
        self._flight.event("host_stall", seconds=round(late, 4), cause=kind,
                           gc_seconds=round(gc_s, 4))
        self._say(f"Host stalled {late:.2f} s ({kind}; gc {gc_s:.2f} s "
                  f"inside it)")


_DEFAULT: Optional[HostWatch] = None
_default_lock = threading.Lock()


def default_host_watch() -> HostWatch:
    """The process's one watch (made on first use: importing this
    module registers nothing)."""
    global _DEFAULT
    with _default_lock:
        if _DEFAULT is None:
            _DEFAULT = HostWatch()
        return _DEFAULT
