"""The trace reduction without a chip: interval arithmetic, busy and
idle, a program's time, operations by NAME and by OPCODE with the
asynchronous ones from start to done, the idle gaps divided among the
host's spans, on hand-made event lists and on three small traces
recorded on the chip (TPU v5 lite): 100 ms of java14m.train_hostfed
(PR 23), and PR 36's 100 ms of java14m.serve_open with the program's
`c2v.*` spans and 112 ms of one chip of java14m.train_dp4's four."""

import json
import os
import re

import pytest

from bench_testlib import ROOT

from benchmarks import common, readers
from benchmarks import trace_reduce as tr

MS = 1e6    # ns
COLLECTIVES = common.load_json(os.path.join(
    ROOT, "benchmarks", "layer_metrics",
    "collective_exposed_ms.train.json"))["args"]


def synthetic():
    """Two chips, two runs of jit_train_step each, 10 ms a run: compute
    0-6 ms, an all-reduce 5-9 ms (1 ms of it under compute), idle 9-10."""
    planes = []
    for chip in range(2):
        ops, mods = [], []
        for run in range(2):
            t = run * 10 * MS
            mods.append(["jit_train_step(123)", t, 10 * MS])
            ops.append(["fusion.1 = f32[8] fusion(f32[8] p)", t, 6 * MS])
            ops.append(["all-reduce-start.1 = f32[8] all-reduce-start(x)",
                        t + 5 * MS, 0.1 * MS])
            ops.append(["all-reduce-done.1 = f32[8] all-reduce-done(x)",
                        t + 8.9 * MS, 0.1 * MS])
        mods.append(["jit_unpack(9)", 20 * MS, 1 * MS])
        ops.append(["copy.1 = s32[4] copy(s32[4] q)", 20 * MS, 1 * MS])
        planes.append({"name": f"/device:TPU:{chip}", "lines": {
            tr.OPS_LINE: ops, tr.MODULES_LINE: mods}})
    planes.append({"name": "/host:CPU", "lines": {"python3": [
        ["bench.next_batch", 9.2 * MS, 0.7 * MS],
        ["bench.next_batch", 19.1 * MS, 0.8 * MS]]}})
    return {"planes": planes}


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_union_and_idle_share_synthetic():
    got = tr.busy_and_window(synthetic())
    # busy per chip: each run's 6 ms of compute (the all-reduce-start
    # at 5.0-5.1 lies inside it) and its 0.1 ms all-reduce-done, then
    # the 1 ms copy: 6.1 + 6.1 + 1
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(21e-3)
    assert got["busy_s"] == pytest.approx(13.2e-3)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(1 - 13.2 / 21)


def test_program_time_counts_only_the_named_program():
    got = tr.program_time(synthetic(), "^jit_train_step")
    assert got["runs"] == 4
    assert got["seconds_per_run"] == pytest.approx(6.1e-3)
    assert tr.program_time(synthetic(), "^jit_unpack")[
        "seconds_per_run"] == pytest.approx(1e-3)
    assert tr.program_time(synthetic(), "^jit_nothing") is None


def test_exposed_collective_time():
    # the halves are 0.1 ms each: the 4 ms are the span `async_spans` builds
    whole = tr.op_time(synthetic(), "^all-reduce", "^jit_train_step")
    bare = tr.op_time(synthetic(), "^all-reduce", "^jit_train_step",
                      exposed_only=True)
    assert whole["seconds_per_run"] == pytest.approx(4e-3)
    # 5-9 ms span, compute covers 5-6: 3 ms with nothing else running
    assert bare["seconds_per_run"] == pytest.approx(3e-3)
    assert tr.op_time(synthetic(), "^all-gather", "^jit_train_step")[
        "seconds_per_run"] == 0.0


def test_breakdown_names_ops_and_divides_gaps():
    got = tr.breakdown(synthetic())
    assert got["device_ops"][0][0].startswith("fusion.1")
    assert got["device_ops"][0][1] == pytest.approx(4 * 6e-3)
    gaps = dict(got["idle_gaps"])
    # each chip idles 6.0-8.9 twice (host:other) and 9.0-10 / 19-20, of
    # which the host fetched the next batch during 9.2-9.9 and 19.1-19.9:
    # the annotation takes the instants it covers, no longer the gap
    assert gaps["bench.next_batch"] == pytest.approx(2 * (0.7e-3 + 0.8e-3))
    assert gaps["host:other"] == pytest.approx(
        2 * (2 * 2.9e-3 + 0.3e-3 + 0.2e-3))
    busy = tr.busy_and_window(synthetic())
    assert sum(gaps.values()) == pytest.approx(
        2 * (busy["window_s"] - busy["busy_s"]))
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_op_label_keeps_the_name_first_and_drops_layouts():
    label = tr.op_label("%all-reduce.5 = f32[1301136,128]{1,0:T(8,128)} "
                        "all-reduce(f32[1301136,128]{1,0} %fusion.5)")
    assert label.startswith("all-reduce.5 = f32[1301136,128] all-reduce(")
    assert "{" not in label and "%" not in label
    assert tr.op_parts(label) == ("all-reduce.5", "all-reduce")


@pytest.mark.parametrize("hlo, name, opcode", [
    ("%psum.25 = f32[911417,128]{1,0} all-reduce(f32[911417,128]{1,0} "
     "%conditional.4), channel_id=1, replica_groups={}", "psum.25",
     "all-reduce"),
    ("%all-reduce.36 = bf16[261245,384]{1,0} all-reduce(f32[261245,384] "
     "%fusion.372), channel_id=2", "all-reduce.36", "all-reduce"),
    # a fusion that READS an all-reduce's result is a fusion
    ("%fusion.380 = (bf16[1301136,128]{1,0}, f32[1301136,128]{1,0}, "
     "bf16[1301136,128]{1,0}) fusion(f32[1301136,128] %all-reduce.36, "
     "bf16[1301136,128] %p)", "fusion.380", "fusion"),
    # a tuple type longer than the label: the opcode survives the cut
    ("%async-collective-start = (f32[1301136,128]{1,0}, f32[1301136,128]"
     "{1,0}, s32[2]{0}, u32[], u32[], /*index=5*/u32[], u32[], u32[], "
     "u32[]) fusion(f32[1301136,128] %x), kind=kCustom",
     "async-collective-start", "fusion"),
    ("%while.11 = (s32[], bf16[64,3200,128]{2,1,0}, bf16[64,3200,128], "
     "s32[], (f32[2], f32[3])) while((s32[], bf16[64,3200,128]) %t), "
     "condition=%c, body=%b", "while.11", "while"),
    ("%all-gather-start.2 = (f32[8], f32[32]) all-gather-start(f32[8] %x)",
     "all-gather-start.2", "all-gather-start"),
    # a combined asynchronous all-reduce: a tuple of tuples, cut at depth 2
    ("%all-reduce-start.3 = ((f32[1301136,128]{1,0}, f32[911417,128]{1,0}), "
     "(f32[1301136,128]{1,0}, f32[911417,128]{1,0})) all-reduce-start("
     "f32[1301136,128] %a, f32[911417,128] %b), channel_id=3",
     "all-reduce-start.3", "all-reduce-start"),
    # a loop whose state is a tuple of tuples, the inner one before the cut
    ("%while.12 = (s32[], (bf16[64,3200,128]{2,1,0}, bf16[64,3200,128]), "
     "(f32[2], f32[3]), s32[]) while((s32[], (bf16[64,3200,128], bf16[64,"
     "3200,128])) %t), condition=%c, body=%b", "while.12", "while"),
    ("bench.next_batch", "bench.next_batch", None)])
def test_op_parts_reads_name_and_opcode_off_a_label(hlo, name, opcode):
    label = tr.op_label(hlo)
    assert len(label) <= tr.LABEL_WIDTH
    assert tr.op_parts(label) == (name, opcode)
    assert label.count("(") >= label.count(")")     # the cut closes what it
    if " = (" in label:                             # opened, and no more
        assert tr._type_end(label.partition(" = ")[2]) <= tr.TYPE_WIDTH + 4
    # a label cut the old way (whole type kept) loses a long tuple's
    # opcode, and says so rather than guess
    if name == "while.11":
        old_cut = re.sub(r"\{[^{}]*\}", "", hlo.replace("%", ""))[:60]
        assert tr.op_parts(old_cut) == ("while.11", None)


def recorded():
    path = os.path.join(ROOT, "tests", "benchmark", "data",
                        "trace_v5e_java14m_train_100ms.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces():
    trace = recorded()
    assert [p["name"] for p in tr.device_planes(trace)] == ["/device:TPU:0"]
    got = tr.busy_and_window(trace)
    assert got["window_s"] == pytest.approx(0.1, rel=1e-6)
    assert 0.99 < got["busy_s"] / got["window_s"] <= 1.0
    step = tr.program_time(trace, "^jit_train_step")
    assert step["runs"] == 3            # the third is cut by the 100 ms
    assert 0.030 < step["seconds_per_run"] < 0.045
    unpack = tr.program_time(trace, "^jit_unpack")
    assert unpack["seconds_per_run"] < 1e-4
    # one chip: no collective ran, by the old rule and by the metric's
    assert tr.op_time(trace, "^(all-reduce|reduce-scatter|all-gather)",
                      "^jit_train_step", True)["seconds_per_run"] == 0.0
    assert tr.op_time(trace, COLLECTIVES["ops"], COLLECTIVES["program"],
                      True, COLLECTIVES["opcodes"])["seconds_per_run"] == 0.0
    top = tr.breakdown(trace)["device_ops"]
    assert top[0][0].startswith("fusion.") and top[0][1] > 0


# ------------------------------------------------- the idle-gap rule

def spans_trace():
    """One chip: ops 0-1, 1.03-2, 10-11, 20-21 ms; idle 30 us, 8 ms and
    9 ms between them. The dispatcher thread idles 0-1.5, dispatches
    1.5-13 with a parse 2-3 and the model call 4-12 inside; a pool
    thread works 2.5-3.5; the harness's main thread waits in two
    slices, 0-5 and 5-15, the second of which begins INSIDE the model
    call; nothing covers 15-20."""
    ops = [["fusion.1 = f32[8] fusion(f32[8] p)", 0, 1 * MS],
           ["fusion.2 = f32[8] fusion(f32[8] p)", 1.03 * MS, 0.97 * MS],
           ["fusion.3 = f32[8] fusion(f32[8] p)", 10 * MS, 1 * MS],
           ["fusion.4 = f32[8] fusion(f32[8] p)", 20 * MS, 1 * MS]]
    host = {"dispatcher": [["c2v.serve.idle", 0, 1.5 * MS],
                           ["c2v.serve.dispatch", 1.5 * MS, 11.5 * MS],
                           ["c2v.predict.parse", 2 * MS, 1 * MS],
                           ["c2v.predict.device", 4 * MS, 8 * MS]],
            "pool-0": [["c2v.extract", 2.5 * MS, 1 * MS]],
            "python3": [["bench.serve_window", 0, 5 * MS],
                        ["bench.serve_window", 5 * MS, 10 * MS]]}
    return {"planes": [
        {"name": "/device:TPU:0", "lines": {
            tr.OPS_LINE: ops,
            tr.MODULES_LINE: [["jit_eval_step(1)", 0, 21 * MS]]}},
        {"name": "/host:CPU", "lines": host}]}


IDLE_BY_SPAN = {
    "between_ops_under_50us": 0.03,     # a gap under 50 us: not divided
    "c2v.predict.parse": 0.5,           # 2-2.5, until the pool thread's
    "c2v.extract": 1.0,                 # later-begun span takes over
    "c2v.serve.dispatch": 0.5 + 1.0,    # 3.5-4 and 12-13: its own
    "c2v.predict.device": 6.0 + 1.0,    # 4-10 and 11-12: the innermost
    "bench.serve_window": 2.0,          # 13-15: no span of the program
    "host:other": 5.0}                  # 15-20: no span at all


@pytest.mark.parametrize("name", sorted(IDLE_BY_SPAN))
def test_an_idle_instant_goes_to_the_innermost_span(name):
    gaps = dict(tr.breakdown(spans_trace())["idle_gaps"])
    assert set(gaps) == set(IDLE_BY_SPAN)
    assert gaps[name] == pytest.approx(IDLE_BY_SPAN[name] * 1e-3)


def test_idle_gaps_sum_to_the_idle_and_name_the_trace_s_own_spans():
    trace = spans_trace()
    got = tr.breakdown(trace)["idle_gaps"]
    busy = tr.busy_and_window(trace)
    assert sum(s for _, s in got) == pytest.approx(
        busy["window_s"] - busy["busy_s"])
    assert got[0][0] == "c2v.predict.device"        # ranked, as printed
    # the harness's second slice began later than the model call it
    # interrupts and is not inside it: the program's span keeps 5-10
    assert dict(got)["c2v.predict.device"] == pytest.approx(7e-3)
    # deterministic: the same lists in another order read the same
    again = spans_trace()
    for plane in again["planes"]:
        plane["lines"] = {k: v[::-1] for k, v in
                          reversed(list(plane["lines"].items()))}
    assert tr.breakdown(again)["idle_gaps"] == got


def test_ties_go_to_the_program_then_to_the_span_that_ends_first():
    inner = tr._innermost
    assert inner([("bench.train_step", 5.0, 9.0),
                  ("c2v.step_dispatch", 1.0, 20.0)]) == "c2v.step_dispatch"
    assert inner([("c2v.a", 3.0, 9.0), ("c2v.b", 3.0, 4.0)]) == "c2v.b"
    assert inner([("c2v.a", 3.0, 4.0), ("c2v.b", 3.0, 4.0)]) == "c2v.b"
    assert inner([("bench.x", 1.0, 4.0), ("bench.y", 2.0, 1.0)]) == "bench.y"


def test_the_loader_keeps_the_programs_spans_and_the_harness_s():
    keep = re.compile(tr.HOST_SPANS)
    for name in ("c2v.serve.idle", "c2v.predict.device", "bench.next_batch"):
        assert keep.search(name)
    for name in ("PjitFunction(step)", "xc2v.serve.idle", "bench"):
        assert not keep.search(name)


# ---------------------------------- collectives by opcode, and by span

def dp_step():
    """One chip-step of 52 ms as dp4's compiled step orders it: compute,
    two synchronous all-reduces (one under jax's own name), a fusion
    that reads the second's result, then an asynchronous collective
    FUSION carried by two Adam fusions and closed by its `done`; last a
    `while` that spans an all-gather and a fusion of its body (no cell's
    step has a collective in a loop: the limit `op_time` states)."""
    def op(hlo, start, ms):
        return [tr.op_label(hlo), start * MS, ms * MS]
    ops = [
        op("%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 0, 5),
        op("%psum.25 = f32[911417,128]{1,0} all-reduce(f32[911417,128] "
           "%conditional.4), channel_id=1", 5, 8),
        op("%all-reduce.36 = bf16[261245,384]{1,0} all-reduce(f32[261245,"
           "384] %fusion.372), channel_id=2", 13, 7),
        op("%fusion.380 = (bf16[8], f32[8], bf16[8]) fusion(bf16[261245,"
           "384] %all-reduce.36, bf16[8] %p)", 20, 1),
        op("%async-collective-start = (f32[1301136,128]{1,0}, f32[1301136,"
           "128], s32[2], u32[], u32[], /*index=5*/u32[], u32[], u32[], "
           "u32[]) fusion(f32[1301136,128] %x), kind=kCustom", 21, 0.002),
        op("%fusion.491 = f32[261245,384] fusion(f32[261245,384] %a)",
           21.002, 2.998),
        op("%fusion.492 = f32[911417,128] fusion(f32[911417,128] %b)",
           24, 3.5),
        op("%async-collective-done = f32[1301136,128]{1,0} fusion((f32["
           "1301136,128], f32[1301136,128], s32[2]) %async-collective-start"
           ")", 27.5, 2.4),
        op("%while.7 = (s32[], f32[8]) while((s32[], f32[8]) %t), "
           "condition=%c, body=%b", 40, 10),
        op("%all-gather.2 = f32[32]{0} all-gather(f32[8] %x), dimensions="
           "{0}", 42, 2),
        op("%fusion.9 = f32[8] fusion(f32[32] %all-gather.2)", 44, 2)]
    return {"planes": [{"name": "/device:TPU:0", "lines": {
        tr.OPS_LINE: ops,
        tr.MODULES_LINE: [["jit_train_step(7)", 0, 52 * MS]]}}]}


@pytest.mark.parametrize("exposed_only, ms", [
    # 8 + 7 synchronous, the asynchronous span 21-29.9 less its two
    # carriers (6.498): its start, its done and nothing else; the
    # all-gather hides behind the event of the `while` around it
    (True, 8 + 7 + (8.9 - 6.498)),
    (False, 8 + 7 + 8.9 + 2)])
def test_collectives_are_matched_by_opcode_and_an_async_one_by_its_span(
        exposed_only, ms):
    got = tr.op_time(dp_step(), COLLECTIVES["ops"], COLLECTIVES["program"],
                     exposed_only, COLLECTIVES["opcodes"])
    assert got["seconds_per_run"] == pytest.approx(ms * 1e-3)


def test_the_old_rule_by_name_counted_one_all_reduce_of_three():
    old = tr.op_time(dp_step(), "^(all-reduce|reduce-scatter|all-gather)",
                     "^jit_train_step", True)
    # all-reduce.36 alone: psum.25 and the asynchronous fusion keep
    # other names, and the all-gather hides behind its `while`'s event
    assert old["seconds_per_run"] == pytest.approx(7e-3)
    by_name_only = tr.op_time(dp_step(), "^fusion\\.380 ", "^jit_train_step")
    assert by_name_only["seconds_per_run"] == pytest.approx(1e-3)


def test_async_spans_pair_a_start_with_the_next_done_of_its_stem():
    events = [("all-reduce-start.1 = f32[8] all-reduce-start(x)", 5.0, 0.1),
              ("async-collective-start = (f32[8]) fusion(x)", 6.0, 0.1),
              ("all-reduce-done.1 = f32[8] all-reduce-done(x)", 8.9, 0.1),
              ("async-collective-done = f32[8] fusion(x)", 9.5, 1.0),
              # a done whose start the trace no longer holds
              ("all-gather-done.4 = f32[8] all-gather-done(x)", 12.0, 1.0),
              ("fusion.1 = f32[8] fusion(f32[8] all-reduce-done.1)", 3, 1)]
    assert sorted(tr.async_spans(events)) == [(5.0, 9.0), (6.0, 10.5)]


def test_the_reader_hands_name_and_opcode_patterns_through():
    class Cell:
        config = {}
    m = readers.Measured(Cell(), "TPU v5 lite", None, 20.0, trace=dp_step())
    assert readers.trace_op_time(m, **COLLECTIVES) == pytest.approx(
        8 + 7 + (8.9 - 6.498))                      # ms: scale 1000
    assert readers.trace_op_time(
        readers.Measured(Cell(), "TPU v5 lite", None, 20.0),
        **COLLECTIVES) is None


# ------------------------- PR 36's recorded serve sample, c2v.* spans

def recorded_serve():
    """100 ms of java14m.serve_open's traced window (seed 3600200011, my
    chip run, PR 36; `trace_reduce.cut`): four batches, one long wait."""
    path = os.path.join(ROOT, "tests", "benchmark", "data",
                        "trace_v5e_java14m_serve_open_100ms.json")
    assert os.path.getsize(path) < 1_000_000
    with open(path) as f:
        return json.load(f)


def test_recorded_serve_trace_divides_its_idle_among_the_programs_spans():
    trace = recorded_serve()
    busy = tr.busy_and_window(trace)
    assert 0.099 < busy["window_s"] <= 0.1 and busy["chips"] == 1
    step = tr.program_time(trace, "^jit_eval_step")
    assert step["runs"] == 4 and 0.0045 < step["seconds_per_run"] < 0.0050
    gaps = dict(tr.breakdown(trace)["idle_gaps"])
    idle = busy["window_s"] - busy["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert {n for n in gaps if n.startswith("c2v.")} == {
        "c2v.serve.idle", "c2v.serve.dispatch", "c2v.predict.parse",
        "c2v.predict.assemble", "c2v.predict.device", "c2v.predict.render"}
    # one wait of 58.6 ms between two batches, whole
    assert gaps["c2v.serve.idle"] == pytest.approx(0.058617365)
    # the host's half of four model calls: transfer in, dispatch, fetch
    assert 0.0035 < gaps["c2v.predict.device"] / 4 < 0.0040
    # dispatch keeps what its children do not cover, under a millisecond
    assert gaps["c2v.serve.dispatch"] < 0.001 < gaps["c2v.predict.parse"]
    harness = gaps.get("bench.serve_window", 0.0) + gaps.get("host:other", 0.0)
    assert harness < 0.001 * idle


def test_recorded_serve_trace_three_spans_begin_at_the_cut():
    """The cut clips `bench.serve_window`, `c2v.serve.dispatch` and
    `c2v.predict.device` to one start: the program's go first, and of
    those the one that ends first, the model call."""
    events = tr.host_spans(recorded_serve())
    first = [e for e in events if e[1] == events[0][1]]
    assert {e[0] for e in first} == {"bench.serve_window",
                                     "c2v.serve.dispatch",
                                     "c2v.predict.device"}
    assert tr._innermost(first) == "c2v.predict.device"


def test_without_the_programs_spans_the_harness_s_wait_held_everything():
    """What the loader kept until PR 36 (`^bench\\.`): the same trace read
    `bench.serve_window` and nothing else."""
    trace = recorded_serve()
    for plane in trace["planes"]:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            plane["lines"] = {k: [e for e in v if e[0].startswith("bench.")]
                              for k, v in plane["lines"].items()}
    gaps = dict(tr.breakdown(trace)["idle_gaps"])
    assert set(gaps) == {"bench.serve_window", "between_ops_under_50us"}


# ---------------------- PR 36's recorded dp4 sample: chip 0 of four

def recorded_dp4():
    """112 ms of java14m.train_dp4's traced window (seed 3600600019, my
    chip run, PR 36), chip 0's plane and the host's: two whole steps
    and the first 9 ms of a third."""
    path = os.path.join(ROOT, "tests", "benchmark", "data",
                        "trace_v5e_java14m_train_dp4_chip0_112ms.json")
    assert os.path.getsize(path) < 1_000_000
    with open(path) as f:
        return json.load(f)


def test_recorded_dp4_trace_counts_all_three_table_all_reduces():
    trace = recorded_dp4()
    runs = tr.program_time(trace, "^jit_train_step")["runs"]
    assert runs == 3
    new = tr.op_time(trace, COLLECTIVES["ops"], COLLECTIVES["program"], True,
                     COLLECTIVES["opcodes"])["seconds_per_run"] * runs
    # the two synchronous all-reduces and the asynchronous fusion's
    # `done`, as `breakdown.device_ops` has them: within 5 % (the small
    # dense all-reduce and the `start` make the rest)
    ops = dict(tr.breakdown(trace)["device_ops"])
    three = sum(s for label, s in ops.items()
                if tr.op_parts(label)[0] in (
                    "all-reduce.36", "all-reduce.37",
                    "async-collective-done"))
    assert 0.046 < three < 0.049                # 23.8 ms a whole step
    assert three <= new < 1.05 * three
    # the rule until PR 36, by NAME: the `done` fusion is not counted
    old = tr.op_time(trace, "^(all-reduce|reduce-scatter|all-gather)",
                     "^jit_train_step", True)["seconds_per_run"] * runs
    assert old == pytest.approx(new - ops[next(
        k for k in ops if k.startswith("async-collective-done")
    )], rel=0.01)
    # the asynchronous span is longer than what is exposed of it: two
    # Adam fusions carry it
    whole = tr.op_time(trace, COLLECTIVES["ops"], COLLECTIVES["program"],
                       False, COLLECTIVES["opcodes"])["seconds_per_run"]
    assert whole * runs > new + 0.010


def test_recorded_dp4_trace_the_async_line_does_not_hold_the_fusion_pair():
    """Why `async_spans` pairs the halves by name and `op_time` does not
    read the `Async XLA Ops` line: in this trace it knows copies and the
    generic `async-start` of four slices, and no collective."""
    plane = tr.device_planes(recorded_dp4())[0]
    on_async_line = {tr.op_parts(e[0])
                     for e in plane["lines"]["Async XLA Ops"]}
    assert {opcode for _, opcode in on_async_line} == {"copy-start",
                                                       "async-start"}
    assert not any("collective" in name for name, _ in on_async_line)
    halves = [e for e in plane["lines"][tr.OPS_LINE]
              if e[0].startswith("async-collective-")]
    assert len(tr.async_spans(halves)) == 2
    assert all(15e6 < b - a < 17e6 for a, b in tr.async_spans(halves))


def test_recorded_dp4_trace_a_loop_s_event_spans_its_body_s_events():
    """Why an operation inside a loop's body would read as hidden under
    `exposed_only`: the `while` and `conditional` events lie on the same
    line as their bodies' events and span them. None spans a collective
    here, so the metric reads the same whether they count as other work."""
    ops = tr.device_planes(recorded_dp4())[0]["lines"][tr.OPS_LINE]
    loops = [e for e in ops if tr.op_parts(e[0])[1] in ("while", "conditional")]
    assert len(loops) == 15
    spanned = tr.union(tr._intervals(loops))
    bodies = tr.union(tr._intervals(e for e in ops if e not in loops))
    assert tr.total(tr.subtract(spanned, bodies)) < 0.01 * tr.total(spanned)
    mine_is = tr._matcher(COLLECTIVES["ops"], COLLECTIVES["opcodes"])
    collectives = tr.union(tr._intervals(e for e in ops if mine_is(e[0])))
    assert tr.subtract(collectives, spanned) == collectives
