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
`fusion.12 = f32[...] fusion(...)`); an asynchronous operation shows as
its `-start` and `-done` halves there and as one span on the `Async XLA
Ops` line. Host planes hold one
line per thread; the harness's own `jax.profiler.TraceAnnotation`s
(`bench.*`) land there.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"    # the whole span of start/done pairs
SHORT_GAP_NS = 50_000.0     # shorter idle gaps are summed, not attributed


def load_xplane(trace_dir: str, keep_host: str = r"^bench\.") -> Dict:
    """The newest `.xplane.pb` under `trace_dir` in the neutral form.
    Device lines are kept whole; of the host's events only those whose
    name matches `keep_host` (the harness's annotations)."""
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


def op_label(name: str, width: int = 120) -> str:
    """XLA prints an operation as its whole HLO line, `%fusion.5 =
    f32[1301136,128]{1,0:T(8,128)} fusion(...)`. The label keeps the
    name first (so a pattern such as `^all-reduce` finds it) and what it
    makes and reads after it, without layouts, cut to `width`."""
    text = re.sub(r"\{[^{}]*\}", "", name.replace("%", ""))
    return text[:width]


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


def op_time(trace: Dict, op_pattern: str, program_pattern: str,
            exposed_only: bool = False) -> Optional[Dict[str, float]]:
    """Per run of the program: time inside the operations whose name
    matches `op_pattern`; with `exposed_only`, only the part of it during
    which no other operation runs on that device."""
    rx = re.compile(op_pattern)
    per_run: List[float] = []
    for plane in device_planes(trace):
        runs = program_runs(plane, program_pattern)
        if not runs:
            continue
        ops = plane["lines"].get(OPS_LINE, [])
        spans = ops + plane["lines"].get(ASYNC_LINE, [])
        mine = union(_intervals(e for e in spans if rx.search(e[0])))
        if exposed_only:
            others = union(_intervals(e for e in ops if not rx.search(e[0])))
            mine = subtract(mine, others)
        inside = total(mine) - total(subtract(mine, runs))
        per_run.append(inside / len(runs))
    if not per_run:
        return None
    return {"seconds_per_run": sum(per_run) / len(per_run) / 1e9}


def breakdown(trace: Dict, top: int = 10) -> Dict[str, List]:
    """`device_ops`: the operations that took most device time, under
    XLA's printed names, seconds summed over chips. `idle_gaps`: the
    device's idle time inside the traced window by what the host was
    doing meanwhile: the harness's annotation that covers most of each
    gap, or `host:other`."""
    window = traced_window(trace)
    if window is None:
        return {"device_ops": [], "idle_gaps": []}
    by_op: Dict[str, float] = {}
    gaps: List[Interval] = []
    for plane in device_planes(trace):
        ops = _clip(plane["lines"].get(OPS_LINE, []), window)
        for name, _, d in ops:
            by_op[name] = by_op.get(name, 0.0) + d
        gaps.extend(subtract([window], union(_intervals(ops))))
    host: Dict[str, List[Interval]] = {}
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for events in plane["lines"].values():
            for name, s, d in events:
                host.setdefault(name, []).append((s, s + d))
    host = {k: union(v) for k, v in host.items()}
    by_host: Dict[str, float] = {}
    for gap in gaps:
        if gap[1] - gap[0] < SHORT_GAP_NS:
            by_host["between_ops_under_50us"] = by_host.get(
                "between_ops_under_50us", 0.0) + (gap[1] - gap[0])
            continue
        best, best_cover = "host:other", 0.0
        for name, cover in host.items():
            c = total(cover) - total(subtract(cover, [gap]))
            if c > best_cover:
                best, best_cover = name, c
        by_host[best] = by_host.get(best, 0.0) + (gap[1] - gap[0])

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
