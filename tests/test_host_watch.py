"""The host watch (obs/stalls.py): the ticker against a clock, a sleep
and a CPU clock of the test's own; the collector's hook; one thread and
one callback however often it is started."""

import gc
import threading

import pytest

from code2vec_tpu import obs
from code2vec_tpu.obs import stalls, tracer
from code2vec_tpu.obs.flight import FlightRecorder


def _stall(kind):
    return obs.histogram("host_stall_seconds", kind=kind)


class _Clocks:
    """A wall clock that a scripted sleep advances, and a CPU clock that
    burns a given share of each sleep."""

    def __init__(self, watch_ref, sleeps, cpu_share):
        self.now, self.cpu = 100.0, 5.0
        self.sleeps, self.cpu_share = list(sleeps), cpu_share
        self.watch_ref = watch_ref

    def clock(self):
        return self.now

    def cpu_clock(self):
        return self.cpu

    def sleep(self, asked):
        assert asked == stalls.TICK_S
        took = self.sleeps.pop(0)
        self.now += took
        self.cpu += took * self.cpu_share
        if not self.sleeps:
            self.watch_ref[0]._stop.set()


def _run(sleeps, cpu_share, **kwargs):
    ref, said = [], []
    clocks = _Clocks(ref, sleeps, cpu_share)
    watch = stalls.HostWatch(clock=clocks.clock, cpu_clock=clocks.cpu_clock,
                             sleep=clocks.sleep, **kwargs)
    watch._log = said.append
    ref.append(watch)
    watch.run()
    return said


@pytest.mark.parametrize("cpu_share,kind", [(0.0, "descheduled"),
                                            (0.2, "descheduled"),
                                            (0.3, "busy"), (1.0, "busy")])
def test_a_late_wake_up_is_one_stall_labelled_by_the_cpu_clock(cpu_share,
                                                               kind):
    """Five sleeps of 20 ms, the third 320 ms long: one observation of
    300 ms under the kind the CPU clock gives, four of 0 under `none`,
    one log line, one ring span, one flight event."""
    hists = {k: _stall(k) for k in ("none", "descheduled", "busy")}
    before = {k: (h.count, h.sum) for k, h in hists.items()}
    ring, flight = tracer.SpanTracer(16), FlightRecorder()
    ring.enable()
    said = _run([0.02, 0.02, 0.32, 0.02, 0.02], cpu_share, tracer=ring,
                flight=flight)
    got = {k: (h.count - before[k][0], h.sum - before[k][1])
           for k, h in hists.items()}
    other = "busy" if kind == "descheduled" else "descheduled"
    assert got["none"] == (4, 0.0) and got[other] == (0, 0.0)
    assert got[kind][0] == 1 and got[kind][1] == pytest.approx(0.3)
    assert said == [f"Host stalled 0.30 s ({kind}; gc 0.00 s inside it)"]
    [event] = [e for e in ring.chrome_trace()["traceEvents"]
               if e["ph"] == "X"]
    assert event["name"] == "host.stall"
    assert event["dur"] == pytest.approx(0.3e6)
    assert event["args"] == {"kind": kind, "gc_s": 0.0}
    [record] = [e for e in flight.snapshot()["events"]
                if e["kind"] == "host_stall"]
    assert record["cause"] == kind and record["seconds"] == 0.3


def test_a_wake_up_under_the_threshold_is_no_stall():
    before = {k: _stall(k).count for k in ("none", "descheduled", "busy")}
    assert _run([0.02, 0.11, 0.02], 0.0) == []
    assert _stall("none").count - before["none"] == 3
    assert _stall("descheduled").count == before["descheduled"]
    assert _stall("busy").count == before["busy"]


def test_the_watch_opens_no_profiler_annotation(monkeypatch):
    """A `c2v.*` annotation that is always open would take every idle
    gap of a device trace: with the annotation class replaced by a
    recorder, a stall, a long collection and a running thread reach it
    under no name."""
    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer, "_ANNOTATION", Recorder)
    with obs.span("probe"):
        pass
    assert opened == ["c2v.probe"]
    _run([0.02, 0.32, 0.02], 0.0)
    watch = stalls.HostWatch()
    watch._collections.append((2, 1.0, 0.2, 7))    # a 200 ms collection
    watch.start(lambda text: None)
    try:
        gc.collect()
        watch.tick(0.0, 0.5, 0.5)
    finally:
        watch.stop()
    assert opened == ["c2v.probe"]
    assert not [n for n in opened if n.startswith(("c2v.host", "c2v.gc"))]


def test_a_forced_collection_lands_in_generation_two():
    hist = obs.histogram("python_gc_pause_seconds", generation="2")
    watch = stalls.HostWatch()
    said = []
    watch.start(said.append)
    try:
        count, total = hist.count, hist.sum
        gc.collect()
        watch.flush()
    finally:
        watch.stop()
    assert hist.count >= count + 1 and hist.sum > total
    # a collection of 50 ms or more is also a line of the log
    watch._log = said.append
    watch._collections.append((1, 3.0, 0.25, 11))
    assert watch.flush() == 0.25
    assert said[-1] == ("Collection of generation 1 paused the interpreter "
                        "0.25 s (11 objects collected)")


def test_a_tick_without_a_collection_observes_zero():
    """A train window can pass without one collection: the series must
    read 0 there, not nothing."""
    none = obs.histogram("python_gc_pause_seconds", generation="none")
    two = obs.histogram("python_gc_pause_seconds", generation="2")
    watch = stalls.HostWatch()
    count, total, real = none.count, none.sum, two.count
    watch.tick(0.0, 0.001, 0.0)
    watch._collections.append((2, 1.0, 0.004, 3))
    watch.tick(0.02, 0.021, 0.0)
    assert (none.count - count, none.sum - total) == (1, 0.0)
    assert two.count - real == 1


def test_the_seconds_of_collections_stand_in_the_stalls_line():
    watch = stalls.HostWatch()
    said = []
    watch._log = said.append
    watch._collections.append((2, 10.0, 0.28, 5))
    watch.tick(10.02, 10.32, 0.3)
    assert said[-1] == "Host stalled 0.30 s (busy; gc 0.28 s inside it)"


def _threads():
    return [t for t in threading.enumerate() if t.name == "host-watch"]


def test_started_twice_it_is_one_thread_and_one_callback():
    watch = stalls.HostWatch()
    mine = lambda: [c for c in gc.callbacks     # noqa: E731
                    if getattr(c, "__self__", None) is watch]
    others = len(_threads())
    watch.start()
    watch.start()
    assert len(mine()) == 1 and len(_threads()) == others + 1
    watch.stop()
    assert len(mine()) == 1 and len(_threads()) == others + 1
    watch.stop()
    assert mine() == [] and len(_threads()) == others
    watch.stop()                        # one stop too many: nothing
    watch.start()
    assert len(mine()) == 1 and len(_threads()) == others + 1
    watch.stop()
    assert mine() == [] and len(_threads()) == others


def test_a_compile_anywhere_is_counted_once_a_tick(monkeypatch):
    hist = obs.compiles_during("process")
    watch = stalls.HostWatch()
    seen = iter([3, 3, 5])
    monkeypatch.setattr(tracer, "backend_compiles", lambda: next(seen))
    count, total = hist.count, hist.sum
    watch._compiles_seen = 3
    for _ in range(3):
        watch.tick(0.0, 0.001, 0.0)
    assert hist.count - count == 3 and hist.sum - total == 2


def test_the_trainer_and_the_server_share_the_default_watch():
    assert obs.default_host_watch() is stalls.default_host_watch()
