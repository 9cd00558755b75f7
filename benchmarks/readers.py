"""The reader kinds of per-layer metrics. A metric is a file of its own,
`layer_metrics/<metric>.json`, that names one of these kinds under
`reader` with its arguments under `args`; adding a metric of an existing
kind adds a file and APPENDS an entry to BENCHMARK.json's `per_layer`
(entries are found by name, never by place) and edits nothing. A
reader that finds nothing to read returns None, and the harness leaves
the metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks import roofline, trace_reduce


class Measured:
    """What one run hands the readers."""

    def __init__(self, cell, device_kind: str, registry, window_s: float,
                 trace: Optional[Dict] = None,
                 facts: Optional[Dict[str, Any]] = None,
                 late_ms: Optional[List[float]] = None):
        self.cell = cell
        self.device_kind = device_kind
        self.registry = registry        # common.RegistryWindow
        self.window_s = window_s        # the timed window, host clock
        self.trace = trace              # neutral form, traced runs only
        self.facts = facts or {}
        self.late_ms = late_ms
        self.notes: Dict[str, str] = {}


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def registry_histogram(m: Measured, name: str, stat: str,
                       labels: Optional[Dict] = None, scale: float = 1.0):
    """Of a histogram of the program's registry, inside the window: the
    exact `mean` (sum over count; the buckets give no exact quantile),
    the `sum`, the `count`, or `sum_over_window` (sum over the window's
    length)."""
    got = m.registry.histogram(name, labels)
    if got is None:
        return None
    total, count = got
    value = {"mean": total / count, "sum": total, "count": float(count),
             "sum_over_window": total / m.window_s}[stat]
    return value * scale


def registry_gauge(m: Measured, name: str, labels: Optional[Dict] = None,
                   scale: float = 1.0):
    value = m.registry.gauge(name, labels)
    return None if value is None else value * scale


def trace_program_time(m: Measured, program: str, scale: float = 1.0):
    """Device time of one run of the compiled program whose printed
    name matches `program`."""
    if m.trace is None:
        return None
    got = trace_reduce.program_time(m.trace, program)
    return None if got is None else got["seconds_per_run"] * scale


def trace_op_time(m: Measured, program: str, ops: str,
                  opcodes: Optional[str] = None,
                  exposed_only: bool = False, scale: float = 1.0):
    """Per run of `program`: device time inside the operations whose
    NAME matches `ops` or whose OPCODE matches `opcodes`, an
    asynchronous one from its start to its done; with `exposed_only`,
    only while nothing else runs on that device."""
    if m.trace is None:
        return None
    got = trace_reduce.op_time(m.trace, ops, program, exposed_only, opcodes)
    return None if got is None else got["seconds_per_run"] * scale


def trace_program_roofline(m: Measured, program: str, floor: str):
    """The least time the chip could take for one run of `program` (the
    `floor` function of benchmarks/roofline.py at the configuration's
    shapes and the traffic's mean valid contexts) over its device time,
    in percent. The note says which bound it was."""
    if m.trace is None:
        return None
    got = trace_reduce.program_time(m.trace, program)
    if got is None or got["seconds_per_run"] <= 0:
        return None
    least = getattr(roofline, floor + "_floor")(
        m.cell.config, m.facts["rows_per_chip"],
        m.facts["mean_valid_contexts"], m.device_kind)
    m.notes[f"{floor}_floor"] = (
        f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
        f"({least['flops']:.3e} flops, {least['bytes']:.3e} bytes)")
    return 100.0 * least["seconds"] / got["seconds_per_run"]


def run_fact(m: Measured, key: str, scale: float = 1.0):
    """A number the runner itself took over the timed window and put
    under `facts`: a statistic that stands beside an end-to-end metric
    without a bound of its own (the 95th percentile of a serve window)."""
    value = m.facts.get(key)
    return None if value is None else value * scale


def generator_lateness(m: Measured, q: float):
    """How late the load generator sent, due instant to actual send: its
    q-th percentile over the window's requests."""
    if not m.late_ms:
        return None
    return percentile(m.late_ms, q)


KINDS = {f.__name__: f for f in (
    registry_histogram, registry_gauge, trace_program_time, trace_op_time,
    trace_program_roofline, generator_lateness, run_fact)}


def read_traced(cell, device_kind: str, registry, window_s: float,
                trace_dir: str, **measured) -> Dict[str, Any]:
    """What a `--trace 1` line carries beyond the checks: the per-layer
    metrics, the device's busy seconds over the traced window, the
    breakdown, and the readers' notes."""
    from benchmarks import common
    trace = trace_reduce.load_xplane(trace_dir)
    busy = trace_reduce.busy_and_window(trace)
    if busy is None:
        raise common.NoResult("the trace holds no device operation")
    m = Measured(cell, device_kind, registry, window_s, trace, **measured)
    return {"values": read_all(m), "notes": m.notes,
            "device": {"busy_s": busy["busy_s"],
                       "window_s": busy["window_s"]},
            "breakdown": trace_reduce.breakdown(trace)}


def read_all(m: Measured) -> Dict[str, float]:
    """Every per-layer metric of the cell that has something to read."""
    out = {}
    for metric in m.cell.per_layer():
        spec = m.cell.layer_metric_spec(metric["name"])
        if spec["reader"] not in KINDS:
            raise KeyError(f"{metric['name']}: unknown reader kind "
                           f"{spec['reader']!r} (have: {sorted(KINDS)})")
        value = KINDS[spec["reader"]](m, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = float(value)
    return out
