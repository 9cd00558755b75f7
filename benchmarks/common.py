"""What every runner shares: where files are found, the chip check, the
compile cache, the window's counters and the result line.

Everything that belongs to one cell is found by NAME from
`BENCHMARK.json`: the configuration's file is the entry's `file`, a
traffic mix is `traffic/<mix>.json`, a per-layer metric is
`layer_metrics/<metric>.json`, a runner kind is `runners/<kind>.py`.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HOME = os.path.dirname(os.path.abspath(__file__))
PROCESS_START = time.time()     # set-up is counted from here
T0 = time.perf_counter()        # the same instant on the timers' clock


class NoResult(SystemExit):
    """Stop with a non-zero exit code and print no result line."""

    def __init__(self, message: str, code: int = 3):
        print(f"benchmark: {message}", file=sys.stderr, flush=True)
        super().__init__(code)


def say(message: str) -> None:
    print(f"[bench +{time.time() - PROCESS_START:6.1f}s] {message}",
          file=sys.stderr, flush=True)


def program_log_to(path: str) -> None:
    """The program logs through one named logger and, left alone, to
    stdout; its lines go to a file of the cell's work directory instead,
    so that stdout holds the checks and the result line."""
    import logging
    from code2vec_tpu import config as program_config
    logger = logging.getLogger(program_config._LOGGER_NAME)
    if not logger.handlers:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        handler = logging.FileHandler(path, mode="w")
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)-8s %(message)s"))
        logger.setLevel(logging.INFO)
        logger.propagate = False
        logger.addHandler(handler)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with everything its name leads to."""

    def __init__(self, root: str, name: str):
        self.root = root
        self.home = os.path.join(root, "benchmarks")
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise NoResult(f"no workload {name!r} in BENCHMARK.json "
                           f"(have: {', '.join(sorted(cells))})", 2)
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        config_entry = next(c for c in self.bench["configs"]
                            if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, config_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.home, "traffic", self.entry["traffic"] + ".json"))
        self.runner = self.traffic["runner"]
        self.work = os.path.join(self.home, ".work", name)

    def reports(self, metric: Dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[Dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose `moves` it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.reports(m) and m["moves"] in mine]

    def layer_metric_spec(self, name: str) -> Dict:
        return load_json(os.path.join(self.home, "layer_metrics",
                                      name + ".json"))

    def limits(self) -> Dict:
        """The limits `correct` holds this cell to: its own file, else
        its runner kind's default."""
        own = os.path.join(self.home, "limits", self.name + ".json")
        default = os.path.join(self.home, "limits",
                               self.runner + ".default.json")
        return load_json(own if os.path.exists(own) else default)["limits"]

    def run_module(self):
        return importlib.import_module(f"benchmarks.runners.{self.runner}")


# ------------------------------------------------------------- the chip

def compile_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` when the environment sets it, else one
    fixed directory inside the checkout (the path is part of the key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HOME, ".work", "jax_cache"))


def configure_jax() -> None:
    """Before the first compile: the persistent cache, storing every
    program however quickly it compiled. The program's own
    `configure_compile_cache` honours the same variable."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.config.jax_platforms == "cpu":
        return      # a CPU rehearsal keeps no cache (XLA:CPU logs an
        # error on every hit), as the program's own set-up does
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int, require_tpu: bool = True) -> Dict:
    """The device as JAX reports it. Anything but a TPU with exactly the
    cell's chips ends the run: the benchmark never falls back."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoResult(f"JAX found no device: {e}")
    first = devices[0]
    found = {"platform": first.platform, "kind": first.device_kind,
             "count": len(devices)}
    if require_tpu and (first.platform != "tpu" or len(devices) != chips):
        raise NoResult(
            f"this cell needs {chips} TPU chip(s); JAX resolved "
            f"{found['platform']} {found['kind']!r} x{found['count']} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')})")
    return found


def memory_peak_bytes() -> Optional[int]:
    """Peak on the fullest chip: live arrays plus what compiled programs
    reserved for temporaries (this TPU runtime counts them apart; PERF.md
    finding of PR 21). None where the backend keeps no statistics."""
    import jax
    peaks = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" in s:
            peaks.append(int(s["peak_bytes_in_use"])
                         + int(s.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


# ------------------------------------------------------ registry windows

class RegistryWindow:
    """Sum and count of the program's histograms (and the value of its
    gauges) over the timed window: a snapshot when the window opens and
    one when it closes."""

    def __init__(self, registry):
        self.registry = registry
        self._open: Dict = {}
        self._close: Dict = {}

    def _snapshot(self) -> Dict:
        out = {}
        for name, family in self.registry.collect().items():
            for labels, metric in family.items():
                if hasattr(metric, "sum") and hasattr(metric, "count"):
                    out[(name, labels)] = (float(metric.sum),
                                           int(metric.count))
                else:
                    out[(name, labels)] = (float(metric.value), None)
        return out

    def open(self) -> None:
        self._open = self._snapshot()

    def close(self) -> None:
        self._close = self._snapshot()

    def histogram(self, name: str, labels: Optional[Dict] = None):
        """(sum, count) inside the window, over every series of `name`
        whose labels include `labels`; None when none was observed."""
        want = set((labels or {}).items())
        total, count, seen = 0.0, 0, False
        for (n, lab), (s, c) in self._close.items():
            if n != name or c is None or not want <= set(lab):
                continue
            s0, c0 = self._open.get((n, lab), (0.0, 0))
            total += s - s0
            count += c - (c0 or 0)
            seen = True
        return (total, count) if seen and count > 0 else None

    def gauge(self, name: str, labels: Optional[Dict] = None):
        want = set((labels or {}).items())
        for (n, lab), (value, c) in self._close.items():
            if n == name and c is None and want <= set(lab):
                return value
        return None


# ------------------------------------------------------- the result line

def emit(correct: bool, attempted: int, failed: int, metrics: Dict[str, Dict],
         device: Dict, breakdown: Optional[Dict] = None,
         checks: Optional[List[Dict]] = None) -> None:
    """Each number compared beside its limit, then the one JSON line."""
    for c in checks or []:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}"
              + (f" ({c['note']})" if c.get("note") else ""), flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if checks:
        line["checks"] = checks
    print(json.dumps(line), flush=True)


def metric_values(names: List[Dict], values: Dict[str, float]
                  ) -> Dict[str, Dict]:
    """`values` cut to the metrics BENCHMARK.json lists, with units."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in names if values.get(m["name"]) is not None}
