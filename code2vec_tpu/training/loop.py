"""The training loop: jitted steps, throughput tracing, periodic eval+save.

reference: tensorflow_model.py:40-112 — an endless `sess.run` loop with
per-100-batch throughput logs (:83-89), per-epoch checkpoint + eval
(:90-101); keras_model.py:326-369 — mid-epoch evaluation every
`NUM_TRAIN_BATCHES_TO_EVALUATE` batches;
keras_checkpoint_saver_callback.py:92-127 — EMA throughput + epoch-ETA
progress logging. Here the step is one donated jitted call; the host
thread only feeds prefetched batches and reads the loss scalar
asynchronously (fetching it every batch would serialize host and device;
we only block on it at log boundaries).

Epoch boundaries come from `EpochEnd` markers emitted by the data
iterators at actual data-pass boundaries (data/reader.py) — not from a
raw-line steps-per-epoch estimate — so checkpoints and per-epoch evals
fire exactly once per pass regardless of how many rows the filter drops.
"""

from __future__ import annotations

import resource
import signal
import sys
import threading
import time
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.obs import exporters as obs_exporters
from code2vec_tpu.data.reader import EpochEnd
from code2vec_tpu.ops import embed
from code2vec_tpu.ops.head_ce import target_shards
from code2vec_tpu.training.state import TrainState
from code2vec_tpu.training.step import (
    adam_row_list_tables, async_collective_count, gathers_live_rows,
    row_list_runs,
)
from code2vec_tpu.utils.device import describe_devices, shard_layout
from code2vec_tpu.utils.prefetch import DevicePrefetcher

# EMA smoothing for the throughput/ETA log, applied once per log window
# (the reference smooths per-batch with 0.99,
# keras_checkpoint_saver_callback.py:106-113; one window here aggregates
# ~num_batches_to_log_progress batches, so a heavier weight on the new
# observation gives a comparable horizon).
_THROUGHPUT_EMA_ALPHA = 0.5

# Multi-process runs reduce the preemption flag across hosts every this
# many batches (every batch would put a host collective on the step
# path); SIGTERM grace windows are tens of seconds, ~10 batches is
# well under one.
_PREEMPT_SYNC_EVERY = 10

_PAGE_SIZE = resource.getpagesize()


class NonFiniteLossError(RuntimeError):
    """Raised by the trainer's non-finite-loss sentinel under the `halt`
    policy, AFTER a preemption-style checkpoint has been written. Lets
    the process exit nonzero (a pod scheduler restarts/alerts) while
    `--load` can still resume from the last finite state."""


def current_rss_bytes() -> int:
    """Current (not peak) resident set size. /proc/self/statm on Linux;
    falls back to getrusage peak elsewhere (ru_maxrss is KB on Linux,
    bytes on macOS)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return peak if sys.platform == "darwin" else peak * 1024


class PreemptionWatcher:
    """SIGTERM -> checkpoint-and-stop (SURVEY §5 failure detection).

    TPU pods and most cluster schedulers deliver SIGTERM with a grace
    window before killing a preempted worker. The reference has no
    preemption story (single-workstation TF, it simply dies and loses
    the epoch in progress); here the trainer checks the flag at every
    step boundary and, when set, saves a checkpoint and exits the loop
    cleanly so `--load` resumes from the interrupted step's epoch.
    Install is a no-op off the main thread (signals can only be bound
    there); the previous handler is chained, not clobbered."""

    def __init__(self, log=print):
        self._requested = False
        self._log = log
        self._prev = None
        self._installed = False

    def install(self) -> "PreemptionWatcher":
        if threading.current_thread() is not threading.main_thread():
            return self
        self._prev = signal.signal(signal.SIGTERM, self._handle)
        self._installed = True
        return self

    def _handle(self, signum, frame):
        self._requested = True
        self._log("SIGTERM received: will checkpoint at the next step "
                  "boundary and stop")
        if callable(self._prev):
            self._prev(signum, frame)

    @property
    def requested(self) -> bool:
        return self._requested

    def uninstall(self) -> None:
        if self._installed:
            signal.signal(signal.SIGTERM, self._prev or signal.SIG_DFL)
            self._installed = False


class Trainer:
    def __init__(self, config, train_step: Callable, mesh=None,
                 evaluate_fn: Optional[Callable] = None,
                 save_fn: Optional[Callable] = None,
                 profile_dir: Optional[str] = None,
                 initial_epoch: int = 0,
                 steps_per_epoch_hint: Optional[int] = None,
                 stop_fn: Optional[Callable[[], bool]] = None,
                 commit_drain_fn: Optional[Callable[[], None]] = None,
                 heartbeat_extra: Optional[dict] = None):
        self.config = config
        self.train_step = train_step
        self.mesh = mesh
        self.evaluate_fn = evaluate_fn
        self.save_fn = save_fn
        # Async checkpointing: blocks until every in-flight background
        # commit finished, re-raising the first failure. Called before
        # any preemption-path save (the grace-window artifact must land
        # AFTER — never interleaved with — the pending commit) and in
        # the loop's finally (a failed async commit must fail the run,
        # not evaporate with the commit thread).
        self.commit_drain_fn = commit_drain_fn
        # Early stopping: checked after each epoch-boundary eval. The
        # reference has no in-loop auto-stop but its README recommends
        # training past the best epoch and keeping the best checkpoint
        # (README.md:87-88); harnesses supply a patience rule here.
        self.stop_fn = stop_fn
        self.profile_dir = profile_dir
        # Resumed runs continue the reference's `_iter<N>` numbering
        # (keras_model.py:264-274 parses N back from the checkpoint name;
        # here it comes from the loaded artifact's meta).
        self.initial_epoch = initial_epoch
        self.steps_per_epoch_hint = steps_per_epoch_hint
        # Set by train(): the epoch count reached (initial + passes seen),
        # recorded into the final artifact's meta so a later resume
        # continues numbering.
        self.final_epoch = initial_epoch
        # True when train() exited via a preemption checkpoint; callers
        # should skip further (slow) post-training saves — the grace
        # window may not cover a second multi-GB write.
        self.preempted = False
        # Static fields merged into every heartbeat write: the facade
        # passes its resume report (resume_mode exact|resharded|fresh,
        # restored_step), so a watchdog can see from the heartbeat alone
        # whether this run restored what the operator expected or
        # silently fell back/started fresh.
        self.heartbeat_extra = dict(heartbeat_extra or {})

    def _make_tb_writer(self):
        if not self.config.use_tensorboard:
            return None
        from code2vec_tpu.utils.tb import ScalarWriter
        logdir = self.config.tensorboard_dir
        self.config.log(f"Writing TensorBoard scalars to {logdir}")
        return ScalarWriter(logdir)

    def train(self, state: TrainState, batches: Iterable,
              rng: jax.Array) -> TrainState:
        config = self.config
        log = config.log
        log("Starting training"
            + (f" (resuming from epoch {self.initial_epoch})"
               if self.initial_epoch else ""))
        start_time = time.time()
        eval_every = config.num_train_batches_to_evaluate
        tb = self._make_tb_writer()

        # ---- observability (code2vec_tpu/obs) ----------------------------
        # Per-batch host timings go into always-on histograms (handles
        # cached here: the registry lookup takes a lock); spans land in
        # the trace ring buffer only when --trace_export armed it;
        # heartbeat/Prometheus/TB exports happen at log boundaries only.
        reg = obs.default_registry()
        tracer = obs.default_tracer()
        trace_path = getattr(config, "trace_export", None)
        if trace_path:
            tracer.enable()
        metrics_file = getattr(config, "metrics_file", None)
        heartbeat_file = getattr(config, "heartbeat_file", None)
        metrics_server = None
        metrics_port = int(getattr(config, "metrics_port", 0) or 0)
        if metrics_port:
            metrics_server = obs_exporters.start_metrics_server(metrics_port)
            log(f"Serving Prometheus metrics at http://127.0.0.1:"
                f"{metrics_server.server_address[1]}/metrics")
        h_data_wait = reg.histogram(
            "train_data_wait_seconds",
            "host wait for the next prefetched batch")
        h_dispatch = reg.histogram(
            "train_step_dispatch_seconds",
            "host-side dispatch of the jitted train step (async: device "
            "execution overlaps; sync time is train_loss_sync_seconds)")
        h_compiles = obs.compiles_during("step_dispatch")
        h_loss_sync = reg.histogram(
            "train_loss_sync_seconds",
            "blocking device fetch of a window's losses")
        c_batches = reg.counter("train_batches_total",
                                "train batches consumed this process")
        c_epochs = reg.counter("train_epochs_total", "completed data passes")
        c_nonfinite = reg.counter(
            "train_nonfinite_loss_batches_total",
            "individual batches whose loss came back NaN/Inf")
        g_loss = reg.gauge("train_last_avg_loss",
                           "window-average loss at the last drain")
        g_epoch = reg.gauge("train_epoch", "current epoch number")
        g_rss = reg.gauge("process_rss_bytes", "current resident set size")
        g_async = reg.gauge(
            "train_step_async_collectives",
            "collectives of the compiled train step that carry an "
            "asynchronous start (training/step.py "
            "async_collective_count), read once after the first step")
        head_shards = target_shards(self.mesh)
        reg.gauge(
            "train_head_target_shards",
            "chips that share the target rows of the train step's head "
            "(ops/head_ce.py target_shards): every chip of a mesh that "
            "shards the batch's rows and nothing else, else 1").set(
                head_shards)
        row_list_tables = adam_row_list_tables(config, self.mesh)
        reg.gauge(
            "train_adam_row_list_tables",
            "tables whose gradient the train step hands to Adam as the "
            "backward's sorted (key, row) list and never builds as a "
            "table (training/step.py adam_row_list_tables): the token "
            "and path tables where every chip holds them whole, else 0"
            ).set(row_list_tables)
        list_runs = row_list_runs(config, self.mesh)
        reg.gauge(
            "train_row_list_runs",
            "chips whose sorted lists one table's Adam takes in a step "
            "(training/step.py row_list_runs): every chip of a data "
            "mesh, all-gathered; 1 on one chip; 0 where no list is made"
            ).set(list_runs)

        batch_num = 0              # batches this run
        trace_active = False       # profiler trace in flight
        epoch = self.initial_epoch
        batch_in_epoch = 0
        batches_since_eval = 0
        steps_per_epoch = self.steps_per_epoch_hint
        throughput_ema = None
        pending_losses = []
        multi_batch_start = time.time()
        win_data_wait = 0.0        # host-side step-time breakdown,
        win_dispatch = 0.0         # accumulated over the log window
        last_avg_loss = float("nan")
        observe = None
        if gathers_live_rows(config, self.mesh):
            tenths = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)
            h_live = reg.histogram(
                "train_context_blocks_live_ratio",
                "share of a batch's (rows, contexts) block grid that the "
                "train step's embedding lookup gathers (ops/embed.py): "
                "the blocks some row of its group reaches into, rows "
                "ordered by depth on each chip", buckets=tenths)
            h_run = reg.histogram(
                "train_dense_blocks_run_ratio",
                "share of a batch's (rows, contexts) block grid that the "
                "train step's dense chain runs over "
                "(ops/encode_live.py): the live blocks in whole chunks "
                "of slots, each chip's apart", buckets=tenths)
            chips = (1 if self.mesh is None
                     else len(self.mesh.local_devices))

            def observe(batch):
                mask = batch.context_valid_mask
                h_live.observe(embed.live_block_ratio(mask, chips))
                h_run.observe(embed.live_block_ratio(
                    mask, chips, chunk=embed.SLOT_CHUNK))
        prefetcher = DevicePrefetcher(
            batches, self.mesh, depth=config.prefetch_batches,
            observe=observe)
        watcher = None
        if getattr(config, "save_on_preemption", True):
            watcher = PreemptionWatcher(log).install()
        # Host-memory watchdog (SURVEY §5 failure detection, same family
        # as SIGTERM): when process peak RSS crosses the configured
        # limit, ride the preemption path — checkpoint and stop cleanly
        # instead of dying to the kernel OOM killer mid-epoch.
        rss_limit_bytes = int(
            float(getattr(config, "rss_limit_gb", 0.0) or 0.0) * (1 << 30))
        rss_tripped = False

        def local_stop_flag() -> bool:
            """SIGTERM received, or current RSS over the limit (sticky
            once tripped, so the multi-host OR below keeps agreeing on
            every later poll; current — not peak — RSS, so a transient
            startup spike below the limit cannot permanently trip a
            resume cycle)."""
            nonlocal rss_tripped
            if watcher is not None and watcher.requested:
                return True
            if rss_limit_bytes > 0 and not rss_tripped:
                rss = current_rss_bytes()
                if rss > rss_limit_bytes:
                    rss_tripped = True
                    log(f"Host RSS {rss / (1 << 30):.2f} GB exceeds "
                        f"rss_limit_gb="
                        f"{rss_limit_bytes / (1 << 30):.2f}: will "
                        f"checkpoint at the next step boundary and stop")
            return rss_tripped

        def preemption_agreed(batch_num: int) -> bool:
            """Do ALL hosts agree to stop now? Single-process: the local
            flag, checked every step. Multi-process: the flag must be
            reduced across hosts — SIGTERM/RSS pressure lands at
            different wall times per worker, and a host breaking out of
            the collective step loop alone would deadlock the others —
            so every host ORs the flags at the same fixed cadence
            (batch_num is lockstep)."""
            if watcher is None and rss_limit_bytes <= 0:
                return False
            if jax.process_count() == 1:
                return local_stop_flag()
            if batch_num % _PREEMPT_SYNC_EVERY != 0:
                return False
            from code2vec_tpu.parallel import distributed
            flag = np.array([1.0 if local_stop_flag() else 0.0])
            return bool(distributed.allreduce_host_scalars(flag)[0] > 0)

        def drain_commits(where: str) -> None:
            """Complete (never abandon) any in-flight async checkpoint
            commit. On the preemption path a failed commit is logged but
            must not block the grace-window save — the preempt artifact
            is about to supersede whatever the commit was writing."""
            if self.commit_drain_fn is None:
                return
            try:
                self.commit_drain_fn()
            except Exception as e:
                log(f"In-flight async checkpoint commit failed during "
                    f"{where} drain: {type(e).__name__}: {e}")

        def save_preempt(state, epoch, suffix="_preempt"):
            if self.save_fn is None:
                return
            import inspect
            sig_params = inspect.signature(self.save_fn).parameters
            kwargs = {}
            if "suffix" in sig_params:
                # distinct name: never clobbers the clean end-of-epoch
                # artifact the eval log refers to
                kwargs["suffix"] = suffix
            if "cursor_rows" in sig_params:
                # Data cursor for the interrupted epoch: global rows the
                # pod consumed before this save. batch_in_epoch is
                # lockstep across hosts (the preemption OR-reduce fires
                # at a fixed cadence), so every host records the same
                # ordinal; resume remaps it to the new host count.
                kwargs["cursor_rows"] = (batch_in_epoch
                                         * config.train_batch_size)
            self.save_fn(state, epoch, **kwargs)

        def run_eval(state, label):
            if self.evaluate_fn is None:
                return
            # Not span-wrapped here: the Evaluator itself records the
            # `evaluate` span + eval_seconds histogram around the same
            # interval — a trainer-side wrapper would just double it.
            results = self.evaluate_fn(state)
            if results is not None:
                log(f"{label} -- {results}")
                if tb is not None:
                    step = int(np.asarray(jax.device_get(state.step)))
                    for name, value in results.tb_scalars():
                        tb.scalar(f"eval/{name}", value, step)
                    tb.flush()

        def write_heartbeat(status: str, **extra) -> None:
            """Atomic JSON heartbeat: step/epoch/loss plus a wall-time
            stamp an external watchdog compares against now. Uses only
            host-side counters — never syncs the device. `extra` carries
            terminal-state detail (the crash's exception class on
            status=error), so a watchdog can tell a crash from a hang
            from a preemption without parsing logs."""
            if heartbeat_file is None:
                return
            fields = dict(self.heartbeat_extra)
            fields.update(extra)
            obs_exporters.write_heartbeat(
                heartbeat_file,
                status=status,
                step=batch_num,
                epoch=epoch,
                batch_in_epoch=batch_in_epoch,
                last_loss=(None if not np.isfinite(last_avg_loss)
                           else last_avg_loss),
                examples_per_sec=throughput_ema,
                rss_bytes=current_rss_bytes(),
                **fields)

        def drain_losses(where: str):
            """Fetch every pending per-batch loss (the one place the host
            blocks on the device), update the window average, and run the
            non-finite sentinel over EACH batch loss — not just the
            average — so a single poisoned batch trips the policy even in
            windows that are drained early (mid-epoch eval or an epoch
            boundary) whose losses the log-boundary average never sees.
            The check costs no extra sync: `jnp.isfinite` over the
            already-fetched loss vector is host-side arithmetic on
            scalars the drain just paid for. Returns (losses, sync_s)."""
            nonlocal pending_losses, last_avg_loss, trace_active
            if not pending_losses:
                return np.empty((0,)), 0.0
            with obs.span("loss_sync", hist=h_loss_sync) as sync:
                fetched = jax.device_get(pending_losses)
            sync_s = sync.seconds
            pending_losses = []
            losses = np.asarray(fetched, dtype=np.float64)
            last_avg_loss = float(losses.mean())
            g_loss.set(last_avg_loss)
            finite = np.isfinite(losses)
            if finite.all() and np.isfinite(last_avg_loss):
                return losses, sync_s
            n_bad = int((~finite).sum())
            c_nonfinite.inc(max(n_bad, 1))
            first_bad = int(np.argmax(~finite)) if n_bad else losses.size - 1
            bad_batch = batch_num - losses.size + 1 + first_bad
            bad_value = float(losses[first_bad]) if n_bad else last_avg_loss
            policy = getattr(config, "on_nonfinite_loss", "halt")
            log(f"Non-finite average loss ({last_avg_loss}) at batch "
                f"{batch_num} (epoch {epoch}, {where}): {max(n_bad, 1)} "
                f"poisoned batch(es), first is batch {bad_batch} with "
                f"loss {bad_value}; policy: {policy}")
            if policy != "halt":
                return losses, sync_s
            if trace_active:
                jax.profiler.stop_trace()
                trace_active = False
            # Checkpoint through the preemption save path but under a
            # `_nanhalt` suffix: the poisoned params are preserved for
            # post-mortem, yet the name is invisible to resume
            # resolution and rotation (parse_iter_name -> None), so a
            # scheduler auto-restarting with `--load <base>` resumes
            # the last FINITE artifact instead of crash-looping on the
            # NaN state.
            drain_commits("NaN halt")
            save_preempt(state, epoch, suffix="_nanhalt")
            self.preempted = True
            self.final_epoch = epoch
            raise NonFiniteLossError(
                f"training loss became {bad_value} at batch {bad_batch} "
                f"(epoch {epoch}, window average {last_avg_loss}); "
                f"poisoned state kept in an _iter{epoch}_nanhalt "
                f"artifact for post-mortem (excluded from resume). "
                f"`--load` resumes the last clean artifact; rerun with "
                f"--on_nonfinite_loss warn to push through.")

        write_heartbeat("starting")
        # collections and host stalls, counted while the loop runs
        # (obs/stalls.py): a window that lost seconds says in this log
        # whether the machine or the process stood still
        host_watch = obs.default_host_watch()
        host_watch.start(log)
        try:
            batch_iter = iter(prefetcher)
            while True:
                # the histogram takes a wait only once it turned out to
                # be a batch's (below): input_wait reads it
                try:
                    with obs.span("data_wait") as wait:
                        item = next(batch_iter)
                except StopIteration:
                    break
                wait_s = wait.seconds
                if isinstance(item, EpochEnd):
                    # Per-batch sentinel over the partial window the epoch
                    # boundary is about to discard (see drain_losses).
                    tail_losses, _ = drain_losses("epoch boundary")
                    if jax.process_count() > 1:
                        # Lockstep sanity check, on the consumer thread so
                        # it cannot race the step loop's collectives: all
                        # hosts must be crossing the SAME epoch boundary
                        # after the SAME number of batches.
                        from code2vec_tpu.parallel import distributed
                        distributed.assert_host_agreement(
                            item.epoch * 1_000_000 + batch_in_epoch,
                            "epoch boundary (epoch, batches-in-epoch)")
                    epoch = self.initial_epoch + item.epoch
                    if tail_losses.size:
                        # An epoch shorter than the log cadence would
                        # otherwise end without one loss on record. The
                        # drain above waited for every step, so the
                        # peak is the whole epoch's.
                        log(f"Epoch {epoch} ended after {batch_in_epoch} "
                            f"batches; loss over the last "
                            f"{tail_losses.size}: {tail_losses[0]:.6f} -> "
                            f"{tail_losses[-1]:.6f} (mean "
                            f"{last_avg_loss:.6f}); "
                            f"{describe_devices(state)}")
                    c_epochs.inc()
                    g_epoch.set(epoch)
                    if steps_per_epoch is None:
                        steps_per_epoch = batch_in_epoch
                    batch_in_epoch = 0
                    batches_since_eval = 0
                    # Absolute-epoch cadence: stable across resumes; the final
                    # epoch always gets a save+eval even off-cadence.
                    if (epoch % config.save_every_epochs == 0
                            or epoch >= config.num_train_epochs):
                        if self.save_fn is not None:
                            with obs.span("checkpoint_save_epoch"):
                                self.save_fn(state, epoch)
                        run_eval(state, f"After {epoch} epochs")
                        if self.stop_fn is not None and self.stop_fn():
                            log(f"Early stopping after epoch {epoch}")
                            break
                    write_heartbeat("running")
                    win_data_wait = win_dispatch = 0.0
                    multi_batch_start = time.time()
                    continue

                arrays, _ = item
                batch_num += 1
                batch_in_epoch += 1
                batches_since_eval += 1
                h_data_wait.observe(wait_s)
                win_data_wait += wait_s
                if self.profile_dir and batch_num == 10:
                    jax.profiler.start_trace(self.profile_dir)
                    tracer.mark_profiler_start()
                    trace_active = True
                if batch_num == 1:
                    # start-up's last phase: the call (trace, lower,
                    # compile or cache load) until its result is ready
                    with obs.startup_phase("first_step") as first:
                        with obs.span("step_dispatch", hist=h_dispatch,
                                      compiles=h_compiles) as disp:
                            state, loss = self.train_step(state, *arrays,
                                                          rng)
                        jax.block_until_ready(loss)
                        # read off the executable that call compiled
                        n_async = async_collective_count(
                            self.train_step, state, *arrays, rng)
                        if n_async is not None:
                            g_async.set(n_async)
                    said_async = ("" if n_async is None else
                                  f"{n_async} asynchronous collective(s); ")
                    log(f"First train step dispatched in "
                        f"{disp.seconds:.2f}s (trace + compile, or a "
                        f"compile-cache load), ready after "
                        f"{first.seconds:.2f}s; {said_async}"
                        f"head over {head_shards} target shard(s); "
                        f"Adam takes {row_list_tables} table(s)' gradient "
                        f"as a row list, {list_runs} chip(s)' lists each; "
                        f"batch {tuple(arrays[0].shape)}: "
                        f"{shard_layout(arrays[0])}")
                    obs.log_compiles_from_now(log)
                else:
                    with obs.span("step_dispatch", hist=h_dispatch,
                                  compiles=h_compiles) as disp:
                        state, loss = self.train_step(state, *arrays, rng)
                win_dispatch += disp.seconds
                c_batches.inc()
                pending_losses.append(loss)
                if preemption_agreed(batch_num):
                    # Preemption notice: checkpoint what we have and leave
                    # cleanly inside the scheduler's grace window. `--load`
                    # resumes from this epoch's numbering.
                    # Drain FIRST: if the in-flight window is NaN-poisoned
                    # the halt policy must win — it saves under `_nanhalt`
                    # (invisible to resume) and raises, where the preempt
                    # save below would write the poisoned params as a
                    # resume-ELIGIBLE artifact and hand the auto-restart
                    # loop a NaN state to crash-cycle on. The device sync
                    # costs nothing extra: the save fetches the same
                    # state anyway.
                    drain_losses("preemption")
                    if trace_active:
                        jax.profiler.stop_trace()
                        trace_active = False
                    # Complete the in-flight async commit FIRST: all
                    # hosts agreed to stop at the same batch, so every
                    # host drains the same pending save — deterministic
                    # cross-host completion — and only then writes the
                    # (synchronous) preemption artifact.
                    drain_commits("preemption")
                    save_preempt(state, epoch)
                    log(f"Preemption checkpoint saved (epoch {epoch}, "
                        f"batch {batch_num}); stopping")
                    self.preempted = True
                    break
                if self.profile_dir and batch_num == 20:
                    jax.block_until_ready(loss)
                    jax.profiler.stop_trace()
                    trace_active = False
                    log(f"Wrote profiler trace to {self.profile_dir}")
                if batch_num % config.num_batches_to_log_progress == 0:
                    # Blocks on the device only here: the drain fetches
                    # the window's losses and runs the non-finite
                    # sentinel over each batch (config.on_nonfinite_loss:
                    # halt|warn) — a diverged run must never silently
                    # burn a pod-day computing NaNs.
                    losses, sync_s = drain_losses("log boundary")
                    elapsed = time.time() - multi_batch_start
                    n = losses.size * config.train_batch_size
                    throughput = n / max(elapsed, 1e-9)
                    throughput_ema = (
                        throughput if throughput_ema is None else
                        _THROUGHPUT_EMA_ALPHA * throughput
                        + (1 - _THROUGHPUT_EMA_ALPHA) * throughput_ema)
                    contexts_rate = throughput * config.max_contexts
                    eta = ""
                    if steps_per_epoch:
                        remaining = max(steps_per_epoch - batch_in_epoch, 0)
                        eta_s = remaining * config.train_batch_size / max(
                            throughput_ema, 1e-9)
                        eta = (f", epoch {epoch + 1}: "
                               f"{batch_in_epoch}/{steps_per_epoch} batches, "
                               f"ETA {int(eta_s) // 60}m{int(eta_s) % 60:02d}s")
                    # Step-time breakdown: where the window's wall time
                    # went on the host. `device` is the remainder — time
                    # the host sat inside neither wait/dispatch/sync; on
                    # a healthy run it is the device-bound fraction.
                    other_s = max(
                        elapsed - win_data_wait - win_dispatch - sync_s, 0.0)
                    log(f"Average loss at batch {batch_num}: {last_avg_loss:.6f}, "
                        f"\tthroughput: {throughput:.0f} samples/sec "
                        f"({contexts_rate / 1e6:.2f}M path-contexts/sec{eta})"
                        f" [host: data-wait {win_data_wait:.2f}s, dispatch "
                        f"{win_dispatch:.2f}s, loss-sync {sync_s:.2f}s, "
                        f"device/other {other_s:.2f}s]")
                    g_epoch.set(epoch)
                    g_rss.set(current_rss_bytes())
                    # "Is the step loop input-bound at N hosts?" as ONE
                    # number: the share of the window's wall time the
                    # host spent blocked waiting for input. ~0 = device-
                    # bound (scaling out hosts buys nothing on input);
                    # approaching 1 = feed-bound (more corpus shards,
                    # more feeding hosts, before buying more compute).
                    reg.gauge("train_input_bound_fraction",
                              "fraction of the last log window the step "
                              "loop spent blocked on input data"
                              ).set(win_data_wait / max(elapsed, 1e-9))
                    if tb is not None:
                        step = int(np.asarray(jax.device_get(state.step)))
                        tb.scalar("train/loss", last_avg_loss, step)
                        tb.scalar("train/examples_per_sec", throughput, step)
                        # every registered metric (all subsystems) lands
                        # in TB under obs/ at each log boundary
                        obs_exporters.tb_export(tb, step, registry=reg)
                        tb.flush()
                    write_heartbeat("running")
                    if metrics_file:
                        obs_exporters.write_prometheus(metrics_file,
                                                       registry=reg)
                    win_data_wait = win_dispatch = 0.0
                    multi_batch_start = time.time()
                if eval_every and batches_since_eval >= eval_every:
                    # reference: ModelEvaluationCallback fires every
                    # NUM_TRAIN_BATCHES_TO_EVALUATE=1800 train batches
                    # (keras_model.py:326-369, config.py:55).
                    batches_since_eval = 0
                    # Drain first: the eval reset used to DISCARD these
                    # losses unchecked — the window the average masks.
                    drain_losses("mid-epoch eval boundary")
                    run_eval(state, f"Mid-epoch (batch {batch_num}) evaluation")
                    win_data_wait = win_dispatch = 0.0
                    multi_batch_start = time.time()

        finally:
            if trace_active:
                # An exception between start_trace and the batch-20 stop
                # must not leak an open trace (it would poison any later
                # profiler use in this process and lose the collected
                # events). Suppress errors: never mask the original
                # exception with a profiler teardown failure.
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                trace_active = False
            if watcher is not None:
                watcher.uninstall()
            host_watch.stop()
            obs.log_compiles_from_now(None)
            # Flush+close the TB event file HERE, not after the loop: a
            # crash (or the NaN-halt raise) must not lose the tail of the
            # event stream. Same for the final heartbeat/snapshot — the
            # last state an external watchdog sees must say why the
            # process stopped. All teardown is best-effort: it must
            # never mask the in-flight exception.
            if tb is not None:
                try:
                    tb.close()
                except Exception:
                    pass
            # Complete any in-flight async checkpoint commit before the
            # process exits — an abandoned commit thread would leave a
            # manifest-less staging dir (work lost) or a half-run
            # protocol (peers stuck at the barrier). A commit failure
            # with no other exception in flight must fail the run; with
            # one, it is logged and the original exception wins.
            commit_error = None
            if self.commit_drain_fn is not None:
                try:
                    self.commit_drain_fn()
                except Exception as e:
                    commit_error = e
                    log(f"Async checkpoint commit failed at drain: "
                        f"{type(e).__name__}: {e}")
            exc_type, exc_value, _tb = sys.exc_info()
            if exc_type is None and commit_error is not None:
                exc_type, exc_value = type(commit_error), commit_error
            exc_in_flight = exc_type is not None
            status = ("error" if exc_in_flight
                      else "preempted" if self.preempted else "done")
            hb_extra = {}
            if exc_in_flight:
                # the exception CLASS (and a truncated message) makes an
                # unhandled crash distinguishable from a hang and from
                # the clean done/preempted exits in the heartbeat alone
                hb_extra = {"error_type": exc_type.__name__,
                            "error_message": str(exc_value)[:300]}
            try:
                write_heartbeat(status, **hb_extra)
                if metrics_file:
                    obs_exporters.write_prometheus(metrics_file,
                                                   registry=reg)
                if trace_path:
                    tracer.export_chrome_trace(trace_path)
                    log(f"Wrote host-span Chrome trace to {trace_path} "
                        f"({len(tracer)} spans buffered)")
            except Exception:
                if not exc_in_flight:
                    raise
            obs_exporters.stop_metrics_server(metrics_server)
            if commit_error is not None and sys.exc_info()[0] is None:
                raise commit_error

        log("Done training")
        self.final_epoch = epoch
        elapsed = int(time.time() - start_time)
        log("Training time: %sH:%sM:%sS\n" % (
            elapsed // 3600, (elapsed // 60) % 60, elapsed % 60))
        return state
