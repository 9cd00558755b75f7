"""From a `jax.profiler` trace to numbers: busy union, idle share, the
time of one program, the time of operations by name, the part of
collectives that nothing hides, and the breakdown for the ledger.

The reduction works on a neutral form, so that a test can feed it a
synthetic event list and a small trace recorded on the chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": {"XLA Ops": [[name, start_ns, duration_ns], ...],
                           "XLA Modules": [...]}},
                {"name": "/host:CPU", "lines": {"<thread>": [...]}}]}

On the TPU a device plane is `/device:TPU:<n>`; its `XLA Modules` line
holds one event per run of a compiled program, named as XLA prints it
(`jit_train_step(<fingerprint>)`), and its `XLA Ops` line one event per
HLO operation, named by its whole HLO line (`op_label` shortens it to
`fusion.12 = f32[...] fusion(...)`, `op_parts` takes the name and the
opcode out again); an asynchronous operation shows as its `-start` and
`-done` halves there (`async_spans` pairs them; the `Async XLA Ops` line
is not read: it holds copies and no collective fusion). Host
planes hold one line per thread; the harness's own
`jax.profiler.TraceAnnotation`s (`bench.*`) and the program's spans
(every `obs.span` is the annotation `c2v.<span>`) land there, on the
device lines' clock.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SHORT_GAP_NS = 50_000.0     # shorter idle gaps are summed, not attributed
HOST_SPANS = r"^(bench|c2v)\."   # the harness's annotations, the program's
LABEL_WIDTH, TYPE_WIDTH = 120, 48   # an op's label, and its result type in it


def load_xplane(trace_dir: str, keep_host: str = HOST_SPANS) -> Dict:
    """The newest `.xplane.pb` under `trace_dir` in the neutral form.
    Device lines are kept whole; of the host's events only those whose
    name matches `keep_host`: the harness's annotations and the
    program's own spans."""
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    host = re.compile(keep_host)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            events = [(op_label(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if device or host.search(e.name)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_label(name: str) -> str:
    """XLA prints an operation as its whole HLO line, `%fusion.5 =
    f32[1301136,128]{1,0:T(8,128)} fusion(...)`. The label keeps the
    form `name = type opcode(operands`: the name first (so a pattern
    such as `^all-reduce` finds it), what it makes and reads after it,
    without layouts, cut to `LABEL_WIDTH`. A result type longer than
    `TYPE_WIDTH` (a tuple of many) is cut short, `...`, and closed by
    as many brackets as the cut left open, so that `op_parts` finds the
    opcode behind it whatever the tuple's depth."""
    text = re.sub(r"\{[^{}]*\}", "", name.replace("%", ""))
    head, eq, rest = text.partition(" = ")
    end = _type_end(rest) if eq else None
    if end is not None and end > TYPE_WIDTH:
        kept = rest[:TYPE_WIDTH - 4]
        still_open = kept.count("(") - kept.count(")")
        text = f"{head} = {kept}...{')' * still_open}{rest[end:]}"
    return text[:LABEL_WIDTH]


def _type_end(rest: str) -> Optional[int]:
    """Where the result type of `type opcode(operands` ends: behind the
    closing bracket of a tuple, else at the first space."""
    if not rest.startswith("("):
        end = rest.find(" ")
        return end if end > 0 else None
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i + 1
    return None


@functools.lru_cache(maxsize=None)     # a trace repeats its labels every run
def op_parts(label: str) -> Tuple[str, Optional[str]]:
    """(name, opcode) of a label `name = type opcode(operands`. The
    opcode is None where the label does not hold it: a label that is a
    bare name, or one whose long tuple type an older cut ended in."""
    head, eq, rest = label.partition(" = ")
    if not eq:
        return label, None
    end = _type_end(rest)
    if end is None:
        return head, None
    found = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest[end:])
    return head, (found.group(1) if found else None)


def device_planes(trace: Dict) -> List[Dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of `intervals`."""
    out: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the disjoint sorted cover `a` that `b` leaves bare."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _intervals(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def _clip(events: Iterable[Event], window: Interval) -> List[Event]:
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def traced_window(trace: Dict) -> Optional[Interval]:
    """From the first device operation's start to the last one's end,
    over all chips: the slice the busy and idle shares are taken over."""
    spans = [iv for p in device_planes(trace)
             for iv in _intervals(p["lines"].get(OPS_LINE, []))]
    if not spans:
        return None
    return min(a for a, _ in spans), max(b for _, b in spans)


def busy_and_window(trace: Dict) -> Optional[Dict[str, float]]:
    """`busy_s`: seconds in which an operation ran on the device (the
    union of its operations' intervals), averaged over the chips used;
    `window_s`: the length of the traced window. None when no operation
    ran on a device."""
    window = traced_window(trace)
    if window is None:
        return None
    busy = [total(union(_intervals(_clip(p["lines"].get(OPS_LINE, []),
                                         window))))
            for p in device_planes(trace)]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9,
            "chips": len(busy)}


def program_runs(plane: Dict, pattern: str) -> List[Interval]:
    """The runs of the compiled programs whose printed name matches."""
    rx = re.compile(pattern)
    return sorted((s, s + d) for name, s, d
                  in plane["lines"].get(MODULES_LINE, []) if rx.search(name))


def program_time(trace: Dict, pattern: str) -> Optional[Dict[str, float]]:
    """Per run of the program: the summed device time of its operations
    (the union of the operations that lie inside the run, so a loop's
    body is not counted twice), averaged over runs and chips."""
    per_run: List[float] = []
    for plane in device_planes(trace):
        runs = program_runs(plane, pattern)
        if not runs:
            continue
        ops = union(_intervals(plane["lines"].get(OPS_LINE, [])))
        idle = subtract(runs, ops)
        busy = total(runs) - total(idle)
        per_run.append(busy / len(runs))
    if not per_run:
        return None
    return {"seconds_per_run": sum(per_run) / len(per_run) / 1e9,
            "runs": sum(len(program_runs(p, pattern))
                        for p in device_planes(trace))}


def _matcher(op_pattern: str, opcode_pattern: Optional[str]):
    """Whether a label is one of the operations meant: its NAME matches
    `op_pattern`, or its OPCODE (`op_parts`) matches `opcode_pattern`.
    jax names an instruction as it likes (`psum.25` is an all-reduce)
    and a fusion that reads `all-reduce.36` carries that name among its
    operands: the opcode is neither."""
    by_name = re.compile(op_pattern)
    by_opcode = re.compile(opcode_pattern) if opcode_pattern else None

    def matches(label: str) -> bool:
        if by_name.search(label):
            return True
        if by_opcode is None:
            return False
        opcode = op_parts(label)[1]
        return opcode is not None and bool(by_opcode.search(opcode))
    return matches


def _half(label: str) -> Tuple[Optional[str], str]:
    """("start" | "done" | None, stem) of an asynchronous operation's
    half, by its name, else by its opcode: `all-reduce-start.3` and
    `async-collective-done` are halves of `all-reduce` and
    `async-collective`."""
    name, opcode = op_parts(label)
    for text in (re.sub(r"\.\d+$", "", name), opcode or ""):
        for half in ("start", "done"):
            if text.endswith("-" + half):
                return half, text[:-len(half) - 1]
    return None, ""


def async_spans(events: Iterable[Event]) -> List[Interval]:
    """From each `-start` half among `events` to the end of the next
    `-done` half of the same stem: the whole span of an asynchronous
    operation, the one way a span is built (an asynchronous collective
    FUSION is a pair of fusions by name only, and the `Async XLA Ops`
    line does not hold it). A start whose done the trace does not hold
    any more spans nothing."""
    open_since: Dict[str, List[float]] = {}
    out = []
    for label, s, d in sorted(events, key=lambda e: e[1]):
        half, stem = _half(label)
        if half == "start":
            open_since.setdefault(stem, []).append(s)
        elif half == "done" and open_since.get(stem):
            out.append((open_since[stem].pop(0), s + d))
    return out


def op_time(trace: Dict, op_pattern: str, program_pattern: str,
            exposed_only: bool = False,
            opcode_pattern: Optional[str] = None
            ) -> Optional[Dict[str, float]]:
    """Per run of the program: time inside the operations whose name
    matches `op_pattern` or whose opcode matches `opcode_pattern`, an
    asynchronous one counted from its start to its done
    (`async_spans`); with `exposed_only`, only the part of it during
    which no other operation runs on that device. Every other event of
    the line is other work, a `while` or `conditional` too: its event
    spans its body's, so an operation meant INSIDE a loop's body reads
    as hidden (no cell's step has one)."""
    mine_is = _matcher(op_pattern, opcode_pattern)
    per_run: List[float] = []
    for plane in device_planes(trace):
        runs = program_runs(plane, program_pattern)
        if not runs:
            continue
        ops = plane["lines"].get(OPS_LINE, [])
        matched, rest = [], []
        for e in ops:
            (matched if mine_is(e[0]) else rest).append(e)
        mine = union(_intervals(matched) + async_spans(matched))
        if exposed_only:
            mine = subtract(mine, union(_intervals(rest)))
        inside = total(mine) - total(subtract(mine, runs))
        per_run.append(inside / len(runs))
    if not per_run:
        return None
    return {"seconds_per_run": sum(per_run) / len(per_run) / 1e9}


def host_spans(trace: Dict) -> List[Event]:
    """The host's kept events of every plane and thread, by start."""
    return sorted((e for plane in trace["planes"]
                   if not DEVICE_PLANE.match(plane["name"])
                   for events in plane["lines"].values() for e in events),
                  key=lambda e: e[1])


def _innermost(covering: List[Event]) -> str:
    """Of the spans that cover an instant: a span of the program
    (`c2v.*`) before one of the harness; among those the one that began
    last; at a tie the one that ends first, then by name."""
    return max(covering, key=lambda e: (
        e[0].startswith("c2v."), e[1], -(e[1] + e[2]), e[0]))[0]


def divide_gaps(gaps: List[Interval], spans: List[Event]
                ) -> Dict[str, float]:
    """The sorted disjoint `gaps` divided among `spans` (sorted by
    start), instant by instant: see `breakdown`."""
    out: Dict[str, float] = {}
    active: List[Event] = []
    i = 0
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][1] < g1:
            active.append(spans[i])
            i += 1
        active = [e for e in active if e[1] + e[2] > g0]
        cuts = sorted({g0, g1} | {t for e in active
                                  for t in (e[1], e[1] + e[2])
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            covering = [e for e in active if e[1] <= a and e[1] + e[2] >= b]
            name = _innermost(covering) if covering else "host:other"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def breakdown(trace: Dict, top: int = 10) -> Dict[str, List]:
    """`device_ops`: the operations that took most device time, under
    XLA's printed names, seconds summed over chips. `idle_gaps`: the
    device's idle time inside the traced window by what the host was
    doing meanwhile, seconds summed over chips and by name. Every
    instant of an idle gap of 50 us or more goes to the INNERMOST host
    span that covers it: the spans of all threads are pooled; a span of
    the program (`c2v.*`) goes before one of the harness (`bench.*`),
    and among those the one that began last takes the instant;
    `host:other` where none covers it. The harness's annotations are
    waits of its own main thread (`bench.serve_window` is entered anew
    every quarter of a second), so they would begin later than the
    program's span they interrupt without being inside it (by "began
    last" alone `bench.serve_window` read 21-72 % of the idle of the
    cells whose waits last 80-300 ms: benchmarks/README.md): they keep
    only what no span of the program covers. Among the program's, a wait
    (`c2v.serve.idle`) keeps what no later-begun span covers, and work
    on ANY thread takes its instants from a span that began earlier on
    another: the device is idle for the host as a whole, and the latest
    thing any thread began says most nearly what it was busy with. (The
    cost: two threads working at once, and the later-begun one is
    named.) Shorter gaps are summed as `between_ops_under_50us`."""
    window = traced_window(trace)
    if window is None:
        return {"device_ops": [], "idle_gaps": []}
    by_op: Dict[str, float] = {}
    by_host: Dict[str, float] = {}
    spans = host_spans(trace)
    for plane in device_planes(trace):
        ops = _clip(plane["lines"].get(OPS_LINE, []), window)
        for name, _, d in ops:
            by_op[name] = by_op.get(name, 0.0) + d
        gaps = subtract([window], union(_intervals(ops)))
        shares = divide_gaps(
            [g for g in gaps if g[1] - g[0] >= SHORT_GAP_NS], spans)
        shares["between_ops_under_50us"] = sum(
            b - a for a, b in gaps if b - a < SHORT_GAP_NS)
        for name, ns in shares.items():
            if ns:
                by_host[name] = by_host.get(name, 0.0) + ns

    def ranked(d: Dict[str, float]) -> List:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked(by_op), "idle_gaps": ranked(by_host)}


def cut(trace: Dict, seconds: float) -> Dict:
    """The first `seconds` of the traced window, for a recorded sample."""
    window = traced_window(trace)
    if window is None:
        return trace
    lim = (window[0], window[0] + seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        lines = {k: [[n, s, d] for n, s, d in _clip(v, lim)]
                 for k, v in p["lines"].items()}
        planes.append({"name": p["name"],
                       "lines": {k: v for k, v in lines.items() if v}})
    return {"planes": planes}
