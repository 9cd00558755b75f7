"""Device time by `jax.named_scope`, from a `jax.profiler` trace.

`jax.profiler.ProfileData` shows an op event's own stats only; the scope
an operation was traced under is the `tf_op` stat of the event's
METADATA (PERF.md section 5), which the XSpace proto holds. This reads
the proto itself. Where the proto's Python module is not installed, or
the trace holds no run of the program, it returns None and the metrics
that read it are left out of the line.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence

from benchmarks import trace_reduce


def _xspace(trace_dir: str):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:   # noqa: BLE001 — not installed, or fails to load
        return None
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    space = xplane_pb2.XSpace()
    with open(files[-1], "rb") as f:
        space.ParseFromString(f.read())
    return space


def _scope_of(plane, metadata) -> str:
    for stat in metadata.stats:
        if plane.stat_metadata[stat.metadata_id].name == "tf_op":
            if stat.str_value:
                return stat.str_value
            return plane.stat_metadata[stat.ref_value].name
    return ""


def scope_seconds(trace_dir: str, program: str, scopes: Sequence[str],
                  kernels: Optional[Dict[str, str]] = None
                  ) -> Optional[Dict]:
    """{"runs": runs of the program whose printed name matches `program`,
    "seconds": {scope: device seconds inside those runs in operations
    traced under `/<scope>/`}}; the union of their intervals, so a loop
    and its body are not counted twice; summed over chips. `kernels`
    maps a scope to a pattern on the operation's own name: a custom call
    that carries no scope of its own (the chip's grouped-matmul kernels)
    is counted with the scope that made it."""
    space = _xspace(trace_dir)
    if space is None:
        return None
    rx = re.compile(program)
    named = {s: re.compile(p) for s, p in (kernels or {}).items()}
    runs_total, seconds = 0, {s: 0.0 for s in scopes}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs: List = []
        by_scope: Dict[str, List] = {s: [] for s in scopes}
        for line in plane.lines:
            if line.name not in (trace_reduce.MODULES_LINE,
                                 trace_reduce.OPS_LINE):
                continue
            base = line.timestamp_ns * 1000
            for e in line.events:
                meta = plane.event_metadata[e.metadata_id]
                span = (base + e.offset_ps, base + e.offset_ps
                        + e.duration_ps)
                if line.name == trace_reduce.MODULES_LINE:
                    if rx.search(meta.name):
                        runs.append(span)
                    continue
                scope = _scope_of(plane, meta)
                for s in scopes:
                    if (f"/{s}/" in scope or scope.endswith("/" + s)
                            or (s in named and named[s].search(
                                meta.name.lstrip("%")))):
                        by_scope[s].append(span)
        if not runs:
            continue
        runs = trace_reduce.union(runs)
        runs_total += len(runs)
        for s, spans in by_scope.items():
            mine = trace_reduce.union(spans)
            inside = (trace_reduce.total(mine)
                      - trace_reduce.total(trace_reduce.subtract(mine, runs)))
            seconds[s] += inside / 1e12
    if not runs_total:
        return None
    return {"runs": runs_total, "seconds": seconds}
