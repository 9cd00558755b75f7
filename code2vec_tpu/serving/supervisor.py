"""Supervised multi-replica serving: `serve --replicas N`.

One serving process is one failure domain: an OOM kill, a wedged device
call or a poisoned request takes the whole service down until an
operator notices. The supervisor turns the single-process server into a
self-healing N-replica service:

- **Fork**: the parent never builds a model. It re-execs N copies of
  its own command (``--replicas`` stripped, ``C2V_SERVE_REPLICA=<i>``
  set so a replica can never recurse into supervising), each a full
  single-model server with its own extractor pool and cache.
- **Share the port**: every replica binds the SAME listen port with
  ``SO_REUSEPORT`` (the kernel load-balances accepted connections).
  Where the platform lacks it — or when ``C2V_SERVE_FORCE_PROXY=1``
  forces the fallback, which the chaos suite uses for deterministic
  routing — replicas bind free ports and the supervisor runs its own
  lightweight round-robin HTTP proxy on the public port, skipping dead
  replicas and retrying the next one on connection failure.
- **Monitor**: each replica writes the PR-2 JSON heartbeat
  (``--heartbeat_file``, rewritten every serve_heartbeat_interval_s)
  and inherits a liveness pipe. A replica whose process exits is
  CRASHED; one whose heartbeat goes ~3 intervals stale is HUNG (killed,
  then treated as crashed). Either is restarted with exponential
  backoff, up to ``--serve_max_restarts`` restarts per replica — after
  which the supervisor ESCALATES: kills everything and exits nonzero
  (a replica that cannot stay up is a deploy problem, and pretending
  otherwise hides it from the rollout system).
- **Drain**: SIGTERM to the supervisor fans out as SIGTERM to every
  replica (each runs its own in-flight drain bounded by
  serve_drain_timeout_s); the supervisor exits 0 only when every
  replica exited 0.
- **Fleet telemetry** (serving/telemetry.py, README "Telemetry"): each
  replica publishes an atomic Prometheus snapshot (--metrics_file,
  appended per replica below) every heartbeat interval; the supervisor
  serves the MERGE at ``GET /metrics`` on its telemetry listener
  (--serve_telemetry_port, default public port + 1) plus a
  ``GET /fleet`` JSON view (per-replica breaker state, shed rate,
  heartbeat staleness, restarts, fingerprint). This is the documented
  scrape address under reuseport — a scrape of the shared public port
  reaches ONE kernel-chosen replica and samples a random shard of the
  fleet. In proxy mode the public port answers both paths itself.
  Replica restarts are flight-recorder events and an escalation is an
  incident with a synchronous ring dump into the run dir
  (obs/flight.py).

The supervisor's own heartbeat records per-replica pid/port/restarts so
"which replica is which process" is answerable from the file alone —
the serving chaos suite (tests/test_serving_chaos.py) reads it to pick
a SIGKILL victim and to assert convergence back to N live replicas.
"""

from __future__ import annotations

import json
import os
import select
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from code2vec_tpu import obs
from code2vec_tpu.obs.reqtrace import RequestTrace
from code2vec_tpu.serving.admission import (
    deadline_from_request, retry_after_seconds,
)
from code2vec_tpu.serving.forwarding import (
    REQUEST_FORWARD_HEADERS, forward_with_retry, handle_admin_post,
)

REPLICA_ENV = "C2V_SERVE_REPLICA"
FORCE_PROXY_ENV = "C2V_SERVE_FORCE_PROXY"
# Seconds a replica gets from spawn to its first heartbeat before the
# supervisor declares a hung STARTUP (model build + jit warmup can
# legitimately take tens of seconds on a cold replica).
STARTUP_GRACE_S = 120.0
# Cache-warmth window for scale-down victim selection: the monitor
# loop re-baselines every replica's cache-hit counter at this cadence,
# so "warmth" means hits over the last window (up to 2x this), not
# lifetime.
_WARMTH_WINDOW_S = 60.0
# Hard ceiling on /admin/scale: the per-host replica count is bounded
# by cores/HBM, not ambition — a runaway autoscaler must not fork-bomb
# the host.
MAX_REPLICAS = 64

_C_RESTARTS = obs.counter(
    "serving_replica_restarts_total",
    "replica processes restarted by the serving supervisor "
    "(crash or stale heartbeat)")


def _c_scale(direction: str):
    return obs.counter(
        "serving_replica_scale_total",
        "supervisor replica-count changes applied via /admin/scale "
        "(up = spawned, down = drained and retired)",
        direction=direction)


def _c_snapshot_skipped(replica) -> obs.Counter:
    return obs.counter(
        "serving_telemetry_snapshots_skipped_total",
        "per-replica metrics snapshots the merged /metrics scrape "
        "skipped because the file was torn or unparsable (the scrape "
        "serves the surviving replicas' truth instead of 500ing)",
        replica=str(replica))


def strip_flag(argv: List[str], flag: str,
               has_value: bool = True) -> List[str]:
    """Remove every occurrence of `flag` (and its value, both
    `--flag V` and `--flag=V` forms) from an argv list."""
    out: List[str] = []
    skip = False
    for arg in argv:
        if skip:
            skip = False
            continue
        if arg == flag:
            skip = has_value
            continue
        if has_value and arg.startswith(flag + "="):
            continue
        out.append(arg)
    return out


def child_env(base_env: Dict[str, str]) -> Dict[str, str]:
    """Copy of `base_env` with this package's parent dir on
    PYTHONPATH: the supervisor/fleet re-exec children via
    `python -m code2vec_tpu.cli`, and a parent launched from OUTSIDE
    the repo (cwd anywhere, repo importable only via its own
    sys.path) would otherwise spawn children that cannot import the
    package at all."""
    import code2vec_tpu
    env = dict(base_env)
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(code2vec_tpu.__file__)))
    pythonpath = env.get("PYTHONPATH", "")
    if root not in pythonpath.split(os.pathsep):
        env["PYTHONPATH"] = (root + (os.pathsep + pythonpath
                                     if pythonpath else ""))
    return env


def exclusive_chips() -> Optional[int]:
    """How many accelerator chips JAX sees on this host when a chip
    belongs to ONE process at a time (TPU), else None (CPU, or devices
    processes can share). Asked in a short-lived child that has exited
    before the first replica starts: this parent must never open the
    chip its replicas need. A process pinned to the CPU platform skips
    the child."""
    import jax
    if jax.config.jax_platforms == "cpu":
        return None
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        env=child_env(os.environ), capture_output=True, text=True)
    if probe.returncode != 0:
        raise RuntimeError(
            "cannot open the accelerator the replicas need: "
            + (probe.stderr.strip().splitlines() or ["no output"])[-1])
    platform, count = probe.stdout.split()[-2:]
    return int(count) if platform == "tpu" else None


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that binds one process to TPU chip `chip` of this
    host (libtpu's one-chip-per-process settings; each process needs
    its own mesh-controller port)."""
    port = str(8476 + chip)
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": "localhost:" + port,
            "TPU_MESH_CONTROLLER_PORT": port}


def _free_port(host: str) -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class _Replica:
    def __init__(self, index: int, heartbeat_path: str, log_path: str,
                 metrics_path: Optional[str] = None):
        self.index = index
        self.heartbeat_path = heartbeat_path
        self.log_path = log_path
        self.metrics_path = metrics_path
        self.proc: Optional[subprocess.Popen] = None
        self.pipe_r: Optional[int] = None
        self.port: Optional[int] = None
        # TPU chip this replica is bound to (Supervisor.chips hosts)
        self.chip: Optional[int] = None
        self.restarts = 0
        self.spawned_at = 0.0
        self.restart_at: Optional[float] = None  # backoff gate
        # scale-down lifecycle: a draining replica finishes in-flight
        # work (its own SIGTERM drain), then is RETIRED — never
        # restarted, never counted against the desired replica count
        self.draining = False
        self.drain_started = 0.0
        # reload fan-out deferred until the replica's first heartbeat:
        # a SIGHUP before serve_main installs its handler would KILL a
        # still-starting replica (default SIGHUP disposition)
        self.pending_reload = False
        # cache-warmth window baseline: serving_cache_hits_total at the
        # last warmth sample (monitor loop, ~every _WARMTH_WINDOW_S).
        # Scale-down ranks replicas by hits SINCE this baseline — the
        # lifetime counter measures uptime, not current hit rate, and
        # would protect a long-lived replica whose cache stopped
        # absorbing traffic an hour ago.
        self.warmth_prev = 0.0

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def heartbeat(self) -> Optional[dict]:
        try:
            with open(self.heartbeat_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


class Supervisor:
    """Owns N replica processes + (in proxy mode) the public listener."""

    def __init__(self, config, argv: Optional[List[str]] = None,
                 child_command: Optional[List[str]] = None):
        self.config = config
        self.log = config.log
        self.n = int(config.serve_replicas)
        if child_command is not None:
            self.child_command = list(child_command)
        else:
            stripped = strip_flag(list(argv or []), "--replicas")
            # each replica gets its OWN --metrics_file (the fleet
            # telemetry feed) and --trace_export — a user-supplied path
            # would have every replica overwrite the same file (the
            # atomic tmp+rename makes the clobber silent: last replica
            # to exit wins)
            stripped = strip_flag(stripped, "--metrics_file")
            stripped = strip_flag(stripped, "--trace_export")
            # ...and --serve_traffic_sample: every replica rewriting
            # ONE ring file would silently reduce the shadow-eval
            # corpus to whichever replica flushed last
            stripped = strip_flag(stripped, "--serve_traffic_sample")
            self.child_command = ([sys.executable, "-m",
                                   "code2vec_tpu.cli"] + stripped)
        self.trace_export = bool(getattr(config, "trace_export", None))
        # the supervisor's OWN span ring (proxy forwards, reload
        # fan-outs) exports to the --trace_export path the control
        # plane assigned this host; replicas get derived per-replica
        # paths in the same run dir
        self.trace_export_path = getattr(config, "trace_export", None)
        if self.trace_export_path:
            obs.default_tracer().enable()
        self.traffic_sample = getattr(config,
                                      "serve_traffic_sample_file", None)
        base = (os.path.dirname(os.path.abspath(config.heartbeat_file))
                if config.heartbeat_file else None)
        self.run_dir = base or tempfile.mkdtemp(prefix="c2v-serve-sup-")
        os.makedirs(self.run_dir, exist_ok=True)
        self.heartbeat_path = (config.heartbeat_file or os.path.join(
            self.run_dir, "supervisor.heartbeat.json"))
        self.reuseport = (hasattr(socket, "SO_REUSEPORT")
                          and os.environ.get(FORCE_PROXY_ENV) != "1")
        self.port = int(config.serve_port)
        if self.reuseport and self.port == 0:
            # replicas must all bind ONE concrete port; resolve now
            self.port = _free_port(config.serve_host)
        # One process per chip: on a TPU host replica i is bound to a
        # chip of its own through its environment, and more replicas
        # than chips is a start-up error, not a restart storm.
        self.chips = exclusive_chips()
        self.replicas = [self._make_replica(i) for i in range(self.n)]
        # /admin/scale: the monitor loop reconciles the live replica set
        # toward `_desired` (spawn up, drain down); indices only ever
        # grow so a retiring replica's run files never collide with a
        # newly spawned one's
        self._desired = self.n
        self._next_index = self.n
        self._scale_lock = threading.Lock()
        self._last_reload: Optional[dict] = None
        self._stop = threading.Event()
        self._escalated = False
        self._proxy = None
        self._rr_lock = threading.Lock()
        self._rr_next = 0
        self._telemetry = None
        # Supervisor-side flight recorder: replica restarts are anomaly
        # events, an escalation is an incident with a synchronous dump
        # into the run dir (the replicas' own dumps land there too when
        # --heartbeat_file puts their run files in one place).
        self.flight = obs.default_flight_recorder()
        self.flight.configure(
            dump_dir=self.run_dir,
            max_dumps=getattr(config, "serve_flight_max_dumps", 64),
            log=self.log)

    # ------------------------------------------------------------ spawn

    def _make_replica(self, index: int) -> _Replica:
        return _Replica(
            index,
            os.path.join(self.run_dir, f"replica{index}.heartbeat.json"),
            os.path.join(self.run_dir, f"replica{index}.log"),
            os.path.join(self.run_dir, f"replica{index}.metrics.prom"))

    def _spawn(self, replica: _Replica) -> None:
        try:
            os.remove(replica.heartbeat_path)
        except OSError:
            pass
        replica.port = None
        cmd = list(self.child_command)
        cmd += ["--heartbeat_file", replica.heartbeat_path]
        if replica.metrics_path:
            # the replica's fleet-telemetry feed: an atomic Prometheus
            # snapshot rewritten every heartbeat interval, merged by
            # the supervisor's /metrics + /fleet (serving/telemetry.py).
            # A restarted replica's counters restart from zero — the
            # stale pre-crash file would double-count, so drop it.
            try:
                os.remove(replica.metrics_path)
            except OSError:
                pass
            cmd += ["--metrics_file", replica.metrics_path]
        if self.trace_export:
            cmd += ["--trace_export",
                    os.path.join(self.run_dir,
                                 f"replica{replica.index}.trace.json")]
        if self.traffic_sample:
            # per-replica (and, under a fleet, per-host) traffic
            # sample ring (README "Continuous training"): point the
            # pipeline's --pipeline_traffic at any one of them (or
            # concatenate)
            host = os.environ.get("C2V_FLEET_HOST")
            suffix = (f".{host}" if host else "") + \
                f".replica{replica.index}"
            cmd += ["--serve_traffic_sample",
                    self.traffic_sample + suffix]
        env = child_env(os.environ)
        env[REPLICA_ENV] = str(replica.index)
        if self.chips is not None:
            if replica.chip is None:
                replica.chip = min(set(range(self.chips)) - {
                    r.chip for r in self.replicas})
            env.update(chip_env(replica.chip))
        if self.reuseport:
            cmd += ["--serve_port", str(self.port)]
            env["C2V_SERVE_REUSEPORT"] = "1"
            replica.port = self.port
        else:
            cmd += ["--serve_port", "0"]  # report via heartbeat
            env.pop("C2V_SERVE_REUSEPORT", None)
        r, w = os.pipe()  # liveness pipe: EOF = replica gone
        os.set_inheritable(w, True)
        logf = open(replica.log_path, "ab")
        try:
            replica.proc = subprocess.Popen(
                cmd, env=env, pass_fds=(w,), stdout=logf, stderr=logf)
        finally:
            logf.close()
            os.close(w)
        if replica.pipe_r is not None:
            try:
                os.close(replica.pipe_r)
            except OSError:
                pass
        replica.pipe_r = r
        replica.spawned_at = time.monotonic()
        replica.restart_at = None
        # Desired-state reconciliation: a reload-target file means the
        # fleet's current artifact is NOT the boot artifact this child
        # just loaded (reload_all / the control plane wrote it), so a
        # crash-restarted replica must be swapped onto it at its first
        # heartbeat — otherwise one OOM after a committed rollout
        # silently mixes fingerprints on this host forever.
        from code2vec_tpu.serving.server import RELOAD_TARGET_FILENAME
        replica.pending_reload = os.path.exists(
            os.path.join(self.run_dir, RELOAD_TARGET_FILENAME))
        self.log(f"Replica {replica.index} spawned "
                 f"(pid {replica.proc.pid}"
                 f"{f', port {replica.port}' if replica.port else ''}"
                 f"{'' if replica.chip is None else f', chip {replica.chip}'})")

    def _kill(self, replica: _Replica, sig=signal.SIGKILL) -> None:
        if replica.proc is not None and replica.proc.poll() is None:
            try:
                replica.proc.send_signal(sig)
            except OSError:
                pass

    def _fan_out_sighup(self) -> None:
        self.log("SIGHUP: fanning reload out to all replicas")
        for replica in list(self.replicas):
            if replica.draining:
                continue
            if replica.heartbeat() is None:
                # no heartbeat = serve_main has not installed its
                # SIGHUP handler yet; the default disposition would
                # KILL the starting child — defer to first heartbeat
                replica.pending_reload = True
                continue
            self._kill(replica, signal.SIGHUP)

    # ------------------------------------------------------------ scale

    def request_scale(self, n) -> dict:
        """POST /admin/scale body — set the desired replica count; the
        monitor loop reconciles (spawn up / coordinated-drain down).
        The fleet control plane drives this off the telemetry signals
        (serving/fleet/control.py); operators can too."""
        try:
            n = int(n)
        except (TypeError, ValueError):
            raise ValueError('body must be {"replicas": N}')
        if not (1 <= n <= MAX_REPLICAS):
            raise ValueError(
                f"replicas must be in [1, {MAX_REPLICAS}] (got {n})")
        if self.chips is not None and n > self.chips:
            raise ValueError(
                f"replicas must be <= {self.chips}, the TPU chips on this "
                f"host: a chip belongs to one process (got {n})")
        with self._scale_lock:
            self._desired = n
        self.log(f"Scale request: desired replicas -> {n}")
        return {"desired_replicas": n,
                "current_replicas": len(self.replicas)}

    def _reconcile_scale(self) -> None:
        with self._scale_lock:
            desired = self._desired
        active = [r for r in self.replicas if not r.draining]
        for _ in range(desired - len(active)):
            if self.chips is not None and len(self.replicas) >= self.chips:
                break  # a draining replica still holds its chip
            replica = self._make_replica(self._next_index)
            self._next_index += 1
            self.replicas.append(replica)
            self._spawn(replica)
            _c_scale("up").inc()
            self.flight.event("replica_scale_up", replica=replica.index)
        excess = len(active) - desired
        if excess > 0:
            for replica in self._scale_down_victims(active, excess):
                replica.draining = True
                replica.drain_started = time.monotonic()
                replica.restart_at = None
                self._kill(replica, signal.SIGTERM)
                _c_scale("down").inc()
                self.flight.event("replica_scale_down",
                                  replica=replica.index)
                self.log(f"Replica {replica.index} draining "
                         f"(scale-down)")

    @staticmethod
    def _read_cache_hits(replica: _Replica) -> float:
        """Lifetime serving_cache_hits_total from the replica's
        telemetry snapshot; 0 for a missing/unreadable one (a replica
        still starting has absorbed nothing)."""
        from code2vec_tpu.serving import telemetry
        if not (replica.metrics_path
                and os.path.isfile(replica.metrics_path)):
            return 0.0
        try:
            with open(replica.metrics_path,
                      encoding="utf-8", errors="replace") as f:
                return telemetry.sum_family(
                    f.read(), "serving_cache_hits_total")
        except (OSError, ValueError):
            return 0.0

    def _sample_warmth_baselines(self) -> None:
        """Roll the cache-warmth window: every live replica's current
        lifetime hit count becomes the next window's baseline (monitor
        loop, ~every _WARMTH_WINDOW_S)."""
        for replica in list(self.replicas):
            replica.warmth_prev = self._read_cache_hits(replica)

    def _scale_down_victims(self, active: List[_Replica],
                            excess: int) -> List[_Replica]:
        """Cache-warmth-aware scale-down selection (PR-13 follow-on):
        retire the replicas whose prediction caches absorbed the
        FEWEST hits over the current warmth window (hits since the
        last ~_WARMTH_WINDOW_S baseline — lifetime counters measure
        uptime, not warmth, and the repo's own autoscaler discipline
        is windowed deltas for exactly that reason). A replica without
        a readable snapshot counts 0; a restarted replica's
        counter-reset clamps to 0 (its fresh cache IS cold). Ties (a
        cold host where every window is 0) fall back to newest-first,
        the previous policy: replica 0's compiled steps are the
        oldest."""
        hits = {replica: max(0.0, self._read_cache_hits(replica)
                             - replica.warmth_prev)
                for replica in active}
        victims = sorted(active,
                         key=lambda r: (hits[r], -r.index))[:excess]
        for v in victims:
            self.log(f"Scale-down victim: replica {v.index} "
                     f"(window cache hits {hits[v]:.0f} — fewest "
                     f"among {len(active)} active)")
        return victims

    def _retire(self, replica: _Replica) -> None:
        """A drained (scale-down) replica exited: reap and REMOVE it —
        its exit is policy, not a failure to restart."""
        if replica.proc is not None:
            replica.proc.wait()
        if replica.pipe_r is not None:
            try:
                os.close(replica.pipe_r)
            except OSError:
                pass
            replica.pipe_r = None
        # its metrics snapshot must leave the merge: a retired
        # replica's frozen counters would shadow the live fleet
        if replica.metrics_path:
            try:
                os.remove(replica.metrics_path)
            except OSError:
                pass
        self.replicas.remove(replica)
        self.log(f"Replica {replica.index} retired "
                 f"(rc={replica.proc.returncode if replica.proc else '?'})")

    # ----------------------------------------------------------- reload

    def reload_all(self, artifact, retrieval_index=None) -> dict:
        """Fan a hot-swap to `artifact` out to EVERY live replica —
        the per-host leg of the fleet-wide coordinated swap
        (serving/fleet/swap.py drives this canary-host-first). Proxy
        mode POSTs each replica's own /admin/reload; under SO_REUSEPORT
        one shared port cannot address a specific replica, so the
        target rides a `reload-target.json` in the run dir + SIGHUP
        (serve_main's handler reads the file). Swap RESULTS are
        asynchronous — callers poll /fleet for per-replica swap_state +
        fingerprint convergence."""
        if not artifact:
            raise ValueError('no artifact: body must be '
                             '{"artifact": DIR}')
        import http.client
        artifact = str(artifact)
        targets = [r for r in list(self.replicas)
                   if r.alive and not r.draining]
        results = []
        # the reload target is written in BOTH modes: a replica still
        # STARTING (no heartbeat yet — its SIGHUP handler is not
        # installed, so a signal now would kill it) gets the fan-out
        # DEFERRED to its first heartbeat, delivered as SIGHUP + this
        # file by the monitor loop
        from code2vec_tpu.serving.server import RELOAD_TARGET_FILENAME
        # _atomic_write's thread-unique tmp matters here: the telemetry
        # listener AND the proxy both accept /admin/reload on their own
        # threads of this pid
        target_payload = {"artifact": artifact,
                          "requested_at": time.time()}
        if retrieval_index:
            target_payload["retrieval_index"] = str(retrieval_index)
        obs.exporters._atomic_write(
            os.path.join(self.run_dir, RELOAD_TARGET_FILENAME),
            json.dumps(target_payload) + "\n")
        ready, starting = [], []
        for replica in targets:
            (ready if replica.heartbeat() is not None
             else starting).append(replica)
        for replica in starting:
            replica.pending_reload = True
            results.append({"index": replica.index, "via": "deferred",
                            "accepted": True})
        if self.reuseport:
            for replica in ready:
                self._kill(replica, signal.SIGHUP)
                results.append({"index": replica.index, "via": "sighup",
                                "accepted": True})
        else:
            for replica in ready:
                if replica.port is None:
                    replica.pending_reload = True
                    results.append({"index": replica.index,
                                    "via": "deferred",
                                    "accepted": True})
                    continue
                try:
                    conn = http.client.HTTPConnection(
                        self.config.serve_host, replica.port,
                        timeout=10)
                    try:
                        body = {"artifact": artifact}
                        if retrieval_index:
                            body["retrieval_index"] = str(
                                retrieval_index)
                        conn.request(
                            "POST", "/admin/reload",
                            body=json.dumps(body).encode(),
                            headers={"Content-Type":
                                     "application/json"})
                        resp = conn.getresponse()
                        resp.read()
                        results.append({"index": replica.index,
                                        "via": "http",
                                        "accepted": resp.status == 202,
                                        "status": resp.status})
                    finally:
                        conn.close()
                except (OSError, http.client.HTTPException) as e:
                    results.append({"index": replica.index,
                                    "via": "http", "accepted": False,
                                    "error": f"{type(e).__name__}: "
                                             f"{e}"})
        status = {"artifact": artifact, "requested_at": time.time(),
                  "replicas": results}
        if retrieval_index:
            # the control plane's respawn reconcile compares this
            # reported pair against its committed pair — the artifact
            # alone would read as "index missing" forever
            status["retrieval_index"] = str(retrieval_index)
        self._last_reload = status
        self.flight.event("host_reload_fanout", artifact=artifact,
                          replicas=len(results))
        self.log(f"Reload fan-out: artifact {artifact} -> "
                 f"{len(results)} replica(s)")
        return status

    def _admin_scale(self, payload: dict):
        return 200, self.request_scale(payload.get("replicas"))

    def _admin_reload(self, payload: dict):
        # The fleet swap driver threads its rollout traceparent INSIDE
        # the JSON body (the telemetry listener's post handlers never
        # see HTTP headers): this host's fan-out span parents under the
        # rollout trace, so `fleet trace` shows operator -> router ->
        # swap driver -> every host as one tree.
        trace = RequestTrace.from_headers(payload.get("traceparent"))
        with trace.span("host.reload_fanout",
                        artifact=str(payload.get("artifact"))):
            return 202, self.reload_all(
                payload.get("artifact"),
                retrieval_index=payload.get("retrieval_index"))

    # ---------------------------------------------------------- monitor

    def _stale_after(self) -> float:
        return 3.0 * self.config.serve_heartbeat_interval_s + 2.0

    def _check_replica(self, replica: _Replica, now: float
                       ) -> Optional[str]:
        """Returns a failure description or None (healthy/waiting)."""
        if replica.restart_at is not None:
            return None  # in backoff; spawned when due
        if replica.proc is None:
            return None
        rc = replica.proc.poll()
        if rc is not None:
            return f"exited rc={rc}"
        hb = replica.heartbeat()
        if hb is None:
            if now - replica.spawned_at > STARTUP_GRACE_S:
                self._kill(replica)
                return (f"no heartbeat within the "
                        f"{STARTUP_GRACE_S:g}s startup grace (hung "
                        f"startup; killed)")
            return None
        if replica.port is None:
            port = hb.get("port")
            if port:
                replica.port = int(port)
                self.log(f"Replica {replica.index} listening on port "
                         f"{replica.port}")
        if replica.pending_reload:
            # deferred reload fan-out: the first heartbeat proves
            # serve_main's SIGHUP handler is installed (handlers are
            # set before the server starts publishing), so the signal
            # now triggers a swap instead of killing a starting child
            replica.pending_reload = False
            self._kill(replica, signal.SIGHUP)
            self.log(f"Replica {replica.index} ready; delivering the "
                     f"deferred reload fan-out (SIGHUP)")
        age = time.time() - float(hb.get("wall_time", 0))
        if age > self._stale_after():
            self._kill(replica)
            return (f"heartbeat stale ({age:.1f}s > "
                    f"{self._stale_after():.1f}s; hung; killed)")
        return None

    def _handle_failure(self, replica: _Replica, why: str) -> bool:
        """Schedule a backoff restart; False when the budget is
        exhausted (escalate)."""
        if replica.proc is not None:
            replica.proc.wait()  # reap
        if replica.pipe_r is not None:
            # drop the dead replica's liveness pipe from the monitor's
            # select set NOW: at EOF it is permanently readable, and
            # leaving it in would busy-spin the loop for the whole
            # backoff window
            try:
                os.close(replica.pipe_r)
            except OSError:
                pass
            replica.pipe_r = None
        if replica.restarts >= self.config.serve_max_restarts:
            self.log(f"Replica {replica.index} {why}; restart budget "
                     f"({self.config.serve_max_restarts}) exhausted — "
                     f"escalating to supervisor exit")
            self.flight.incident(
                "replica_escalation", immediate=True,
                replica=replica.index, why=why,
                restarts=replica.restarts)
            return False
        replica.restarts += 1
        _C_RESTARTS.inc()
        self.flight.event("replica_restart", replica=replica.index,
                          why=why, restart=replica.restarts)
        backoff = min(0.5 * (2 ** (replica.restarts - 1)), 10.0)
        replica.restart_at = time.monotonic() + backoff
        self.log(f"Replica {replica.index} {why}; restart "
                 f"{replica.restarts}/{self.config.serve_max_restarts} "
                 f"in {backoff:.1f}s")
        return True

    def _write_heartbeat(self, status: str, **extra) -> None:
        obs.exporters.write_heartbeat(
            self.heartbeat_path, status=status,
            role="serving-supervisor",
            mode="reuseport" if self.reuseport else "proxy",
            port=self.port,
            telemetry_port=(self._telemetry.port
                            if self._telemetry else None),
            desired_replicas=self._desired,
            replicas=[{
                "index": r.index,
                "pid": r.proc.pid if r.proc is not None else None,
                "port": r.port,
                "alive": r.alive,
                "restarts": r.restarts,
                "draining": r.draining,
                "heartbeat_file": r.heartbeat_path,
            } for r in list(self.replicas)], **extra)

    # -------------------------------------------------------- telemetry

    def merged_metrics(self) -> str:
        """Fleet-accurate /metrics: every replica's latest snapshot file
        parsed and merged (counters/histograms summed, gauges labeled
        replica="<i>"), plus the supervisor's own registry as
        replica="supervisor" — fixes the reuseport one-replica-scrape
        gap (README "Telemetry")."""
        from code2vec_tpu.serving import telemetry
        snapshots = {}
        for replica in list(self.replicas):
            if not replica.metrics_path:
                continue
            try:
                # errors="replace": a corrupt byte must surface as an
                # unparsable (skip-and-count) snapshot, not a
                # UnicodeDecodeError 500ing the scrape
                with open(replica.metrics_path,
                          errors="replace") as f:
                    text = f.read()
            except OSError:
                continue  # not written yet / replica restarting
            try:
                families = telemetry.parse_prometheus_text(text)
            except Exception:  # noqa: BLE001 — a torn snapshot must
                # not 500 the whole scrape
                families = None
            if not families:
                if text.strip():
                    # torn / mid-rewrite / foreign garbage:
                    # skip-and-count this replica, serve the others'
                    # truth (pinned in tests/test_telemetry.py)
                    _c_snapshot_skipped(replica.index).inc()
                continue  # empty file = not written yet, no count
            # already-parsed families: the merge accepts them as-is,
            # so validation does not buy a second parse per scrape
            snapshots[str(replica.index)] = families
        snapshots["supervisor"] = \
            obs.default_registry().render_prometheus()
        return telemetry.merge_prometheus_snapshots(snapshots)

    def fleet_view(self) -> dict:
        """GET /fleet: the signal set the ROADMAP fleet item consumes —
        per-replica liveness, heartbeat staleness, breaker state, shed
        rate, restart count and model fingerprint, from the heartbeats
        the supervisor already monitors."""
        from code2vec_tpu.serving import telemetry
        now = time.time()
        replicas = [dict(
            telemetry.fleet_replica_view(r.heartbeat(), now),
            index=r.index,
            pid=r.proc.pid if r.proc is not None else None,
            port=r.port,
            alive=r.alive,
            restarts=r.restarts,
            draining=r.draining,
            in_backoff=r.restart_at is not None,
        ) for r in list(self.replicas)]
        return {
            "mode": "reuseport" if self.reuseport else "proxy",
            "port": self.port,
            "telemetry_port": (self._telemetry.port
                               if self._telemetry else None),
            "replica_count": len(replicas),
            "desired_replicas": self._desired,
            "escalated": self._escalated,
            "stale_after_s": self._stale_after(),
            # the host's fingerprint window: >1 entry = a swap is in
            # flight (or wedged) on this host — the fleet swap driver
            # polls this for convergence
            "fingerprints": sorted({r["model_fingerprint"]
                                    for r in replicas
                                    if r["model_fingerprint"]}),
            "last_reload": self._last_reload,
            "replicas": replicas,
        }

    def _resolve_telemetry_port(self) -> int:
        configured = getattr(self.config, "serve_telemetry_port", None)
        if configured is not None:
            return int(configured)
        # default: the public port + 1 — a deterministic scrape address
        # next to the service (0 below falls back to a free port when
        # the public port was itself dynamic)
        return self.port + 1 if self.port else 0

    def _start_telemetry(self) -> None:
        from code2vec_tpu.serving.telemetry import TelemetryServer
        explicit = getattr(self.config, "serve_telemetry_port",
                           None) is not None
        port = self._resolve_telemetry_port()
        # the control-plane verbs ride the telemetry listener: one port
        # per host is both the scrape address and the fleet control
        # address (serving/fleet/control.py drives these)
        post_handlers = {"/admin/scale": self._admin_scale,
                         "/admin/reload": self._admin_reload}
        try:
            self._telemetry = TelemetryServer(
                self.merged_metrics, self.fleet_view,
                host=self.config.serve_host, port=port,
                post_handlers=post_handlers)
        except OSError as e:
            if explicit or port == 0:
                # an operator-pinned scrape address that cannot bind is
                # a startup error (like the public port) — a silent
                # fallback would leave Prometheus scraping the wrong
                # process while the fleet reports healthy
                raise
            self.log(f"Telemetry port {port} (public port + 1 default) "
                     f"unavailable ({e}); binding a free port instead")
            self._telemetry = TelemetryServer(
                self.merged_metrics, self.fleet_view,
                host=self.config.serve_host, port=0,
                post_handlers=post_handlers)
        self.log(f"Fleet telemetry on http://{self.config.serve_host}:"
                 f"{self._telemetry.port} (GET /metrics merged across "
                 f"replicas, GET /fleet, POST /admin/scale, "
                 f"POST /admin/reload)")

    # ------------------------------------------------------------ proxy

    def _live_ports(self) -> List[int]:
        # draining (scale-down) replicas stop receiving new work; they
        # only finish what they already hold
        return [r.port for r in list(self.replicas)
                if r.alive and r.port is not None and not r.draining]

    def _start_proxy(self) -> None:
        import http.server

        sup = self

        class ProxyHandler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _reply(self, code, body, headers=None,
                       ctype="application/json"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _forward(self, method: str) -> None:
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length) if length else b""
                # proxy-generated terminal statuses carry trace ids
                # too: the correlation contract holds even when no
                # replica ever saw the request
                trace = RequestTrace.from_headers(
                    self.headers.get("traceparent"))
                # the proxy span opens BEFORE the traceparent is
                # re-serialized for the replica: the parent id handed
                # downstream must name a span this process records, or
                # the stitched trace breaks at the host hop
                with trace.span(f"host.proxy {self.path}") as px_span:
                    self._forward_in_span(method, body, trace, px_span)

            def _forward_in_span(self, method, body, trace,
                                 px_span) -> None:
                trace_headers = {"X-Trace-Id": trace.trace_id,
                                 "traceparent": trace.traceparent()}
                deadline = deadline_from_request(
                    sup.config, self.headers.get("X-Deadline-Ms"))
                fwd_headers = {"traceparent": trace.traceparent()}
                for name in REQUEST_FORWARD_HEADERS:
                    if self.headers.get(name):
                        fwd_headers[name] = self.headers[name]
                ports = sup._live_ports()
                if not ports:
                    px_span.attrs["outcome"] = "no_replica"
                    self._reply(503, json.dumps(
                        {"error": "no live replica",
                         "trace_id": trace.trace_id}).encode() + b"\n",
                        dict(trace_headers, **{
                            "Retry-After": str(retry_after_seconds(
                                1.0))}))
                    return
                with sup._rr_lock:
                    start = sup._rr_next
                    sup._rr_next += 1
                # Round-robin order, then the SAME deadline-bounded
                # forward/retry loop the fleet router runs
                # (serving/forwarding.py): this proxy is its
                # single-host degenerate case.
                ordered = [ports[(start + k) % len(ports)]
                           for k in range(len(ports))]
                forward_with_retry(
                    method=method, path=self.path, body=body,
                    fwd_headers=fwd_headers,
                    targets=[(f"replica:{port}", sup.config.serve_host,
                              port) for port in ordered],
                    deadline=deadline, trace=trace,
                    reply=self._reply,
                    what="replicas",
                    unreachable_error="all replicas unreachable",
                    retry_after=str(retry_after_seconds(1.0)),
                    on_outcome=lambda outcome: px_span.attrs.update(
                        outcome=outcome))

            def do_GET(self):  # noqa: N802
                # fleet views are answered HERE, not forwarded: a
                # round-robined /metrics would sample one replica —
                # the exact gap the merged endpoint exists to fix
                path = self.path.split("?", 1)[0]
                if path in ("/metrics", "/fleet"):
                    try:
                        if path == "/metrics":
                            self._reply_raw(
                                200, sup.merged_metrics().encode(),
                                "text/plain; version=0.0.4; "
                                "charset=utf-8")
                        else:
                            self._reply(200, json.dumps(
                                sup.fleet_view(),
                                sort_keys=True).encode() + b"\n")
                    except Exception as e:  # noqa: BLE001 — a scraper
                        # must get an HTTP error, never a torn
                        # connection
                        self._reply(500, json.dumps(
                            {"error": f"{type(e).__name__}: {e}"}
                        ).encode() + b"\n")
                    return
                self._forward("GET")

            def _reply_raw(self, code, body, ctype):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):  # noqa: N802
                # fleet control verbs are answered by the SUPERVISOR:
                # a round-robined /admin/reload would reach ONE replica
                # — the exact gap reload_all exists to fix
                path = self.path.split("?", 1)[0]
                if path in ("/admin/scale", "/admin/reload"):
                    self._admin(path)
                    return
                self._forward("POST")

            def _admin(self, path: str) -> None:
                handle_admin_post(
                    self,
                    (sup._admin_scale if path == "/admin/scale"
                     else sup._admin_reload),
                    lambda code, out: self._reply(code, json.dumps(
                        out, sort_keys=True).encode() + b"\n"))

        class _ProxyServer(http.server.ThreadingHTTPServer):
            # match the replica listeners: a burst must not be refused
            # at the kernel before the proxy can route or 503 it
            request_queue_size = 128

        proxy = _ProxyServer(
            (self.config.serve_host, self.port), ProxyHandler)
        proxy.daemon_threads = True
        self.port = proxy.server_address[1]
        self._proxy = proxy
        threading.Thread(target=proxy.serve_forever,
                         name="serving-supervisor-proxy",
                         daemon=True).start()
        self.log(f"Supervisor proxy on "
                 f"http://{self.config.serve_host}:{self.port} "
                 f"(round-robin over {self.n} replicas)")

    # -------------------------------------------------------------- run

    def run(self) -> int:
        installed = threading.current_thread() is threading.main_thread()
        prev = {}
        if installed:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(
                    sig, lambda s, f: self._stop.set())
            if hasattr(signal, "SIGHUP"):
                # fan a reload out to EVERY replica: in reuseport mode
                # POST /admin/reload reaches whichever replica the
                # kernel hands the connection to, so the supervisor is
                # the one address that can drive a fleet-wide hot-swap
                prev[signal.SIGHUP] = signal.signal(
                    signal.SIGHUP, lambda s, f: self._fan_out_sighup())
        try:
            return self._run_inner()
        finally:
            for sig, handler in prev.items():
                signal.signal(sig, handler)

    def _run_inner(self) -> int:
        if self.chips is not None and self.n > self.chips:
            self.log(f"--replicas {self.n} needs {self.n} TPU chips and "
                     f"this host has {self.chips}: a chip belongs to one "
                     f"process at a time. Start at most {self.chips} "
                     f"replica(s) here.")
            return 1
        if not self.reuseport:
            self._start_proxy()
        self._start_telemetry()
        mode = "SO_REUSEPORT" if self.reuseport else "proxy"
        self.log(f"Serving supervisor: {self.n} replica(s), {mode} on "
                 f"port {self.port}, restart budget "
                 f"{self.config.serve_max_restarts}/replica")
        for replica in self.replicas:
            self._spawn(replica)
        self._write_heartbeat("supervising")
        last_hb = time.monotonic()
        last_warmth = time.monotonic()
        last_trace = time.monotonic()
        try:
            while not self._stop.is_set():
                # liveness pipes double as the wakeup: a dying replica
                # EOFs its pipe and the select returns immediately
                # instead of waiting out the poll tick
                fds = [r.pipe_r for r in self.replicas
                       if r.pipe_r is not None]
                try:
                    select.select(fds, [], [], 0.2)
                except (OSError, ValueError):
                    pass
                now = time.monotonic()
                self._reconcile_scale()
                for replica in list(self.replicas):
                    if replica.draining:
                        if (replica.proc is None
                                or replica.proc.poll() is not None):
                            self._retire(replica)
                        elif (now - replica.drain_started
                              > self.config.serve_drain_timeout_s
                              + 10.0):
                            # a scale-down drain that outlives the
                            # replica's own drain budget is wedged
                            self._kill(replica)
                        continue
                    if (replica.restart_at is not None
                            and now >= replica.restart_at):
                        self._spawn(replica)
                        continue
                    why = self._check_replica(replica, now)
                    if why is not None:
                        if not self._handle_failure(replica, why):
                            self._escalated = True
                            self._stop.set()
                            break
                if now - last_warmth >= _WARMTH_WINDOW_S:
                    self._sample_warmth_baselines()
                    last_warmth = now
                if now - last_hb >= 1.0:
                    self._write_heartbeat("supervising")
                    last_hb = now
                if (self.trace_export_path and now - last_trace >= 5.0
                        and len(obs.default_tracer())):
                    # periodic (not per-request) export: the stitcher
                    # reads files, so a crash loses at most 5s of spans
                    try:
                        obs.default_tracer().export_chrome_trace(
                            self.trace_export_path)
                    except OSError as e:
                        self.log(f"Supervisor trace export failed: {e}")
                    last_trace = now
        finally:
            rc = self._shutdown()
        return rc

    def _shutdown(self) -> int:
        escalated = self._escalated
        self.log("Supervisor shutdown: "
                 + ("restart budget exhausted — killing replicas"
                    if escalated else
                    "fanning SIGTERM out as a coordinated drain"))
        for replica in self.replicas:
            self._kill(replica,
                       signal.SIGKILL if escalated else signal.SIGTERM)
        budget = self.config.serve_drain_timeout_s + 15.0
        deadline = time.monotonic() + budget
        clean = not escalated
        for replica in self.replicas:
            if replica.proc is None:
                continue
            if replica.restart_at is not None:
                # already dead and reaped, waiting out its restart
                # backoff: its stale crash rc is not a DRAIN failure
                # (the crash was handled by the restart policy)
                continue
            try:
                rc = replica.proc.wait(
                    timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                self._kill(replica)
                replica.proc.wait()
                rc = replica.proc.returncode
            if rc == -signal.SIGTERM and replica.heartbeat() is None:
                # the drain SIGTERM landed on a replica still STARTING
                # (no heartbeat yet => no signal handlers, no traffic
                # served, nothing in flight): the default-disposition
                # kill is a clean outcome, not a failed drain
                self.log(f"Replica {replica.index} was still starting "
                         f"at drain; terminated clean")
            elif rc != 0:
                clean = False
                self.log(f"Replica {replica.index} exited rc={rc}")
            if replica.pipe_r is not None:
                try:
                    os.close(replica.pipe_r)
                except OSError:
                    pass
                replica.pipe_r = None
        if self._proxy is not None:
            try:
                self._proxy.shutdown()
                self._proxy.server_close()
            except Exception:
                pass
        if self._telemetry is not None:
            self._telemetry.close()
        if self.trace_export_path and len(obs.default_tracer()):
            try:
                obs.default_tracer().export_chrome_trace(
                    self.trace_export_path)
            except OSError:
                pass  # exiting anyway; the periodic export is recent
        self._write_heartbeat(
            "error" if (escalated or not clean) else "done",
            escalated=escalated)
        self.log(f"Supervisor exit: "
                 f"{'clean' if clean and not escalated else 'FAILED'}")
        return 0 if clean and not escalated else 1


def supervisor_main(config, argv: Optional[List[str]] = None,
                    child_command: Optional[List[str]] = None) -> int:
    """`serve --replicas N` parent body (cli.main dispatches here
    BEFORE building any model). `child_command` overrides the re-exec
    command — the chaos suite points it at a lightweight replica
    driver; production re-execs this CLI."""
    return Supervisor(config, argv=argv,
                      child_command=child_command).run()
