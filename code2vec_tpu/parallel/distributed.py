"""Multi-host (multi-process) distributed runtime support.

The reference has no distributed runtime at all (SURVEY.md §2.3: no
NCCL/MPI/Gloo — single process, single device). The TPU-native
equivalent needs no hand-written communication backend either: XLA
compiles the collectives; what a multi-host pod needs from the
framework is exactly three things, provided here:

1. `initialize()` — `jax.distributed.initialize` wrapper so every host
   joins the same runtime (coordinator discovery via flags or the
   standard JAX_COORDINATOR_ADDRESS / cloud-TPU auto-detection).
2. per-host data sharding — each host reads a disjoint row subset
   (`host_shard` feeds reader/packed shard_index/num_shards) and a
   per-host slice of the global batch.
3. `global_batch_arrays` — assembles per-host numpy shards into global
   `jax.Array`s over the mesh (`jax.make_array_from_process_local_data`),
   the multi-host replacement for a plain `device_put`.
4. `allreduce_host_scalars` — sums small host-side metric counters
   (eval tp/fp/fn, top-k hits, loss) across processes, so evaluation
   over per-host data shards reports GLOBAL metrics (the evaluator
   reduces its counters through this before computing ratios).

The per-example audit log (`log.txt`) stays per-host by design: each
process logs the examples it scored; metrics are global.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from code2vec_tpu.parallel import mesh as mesh_lib

_initialized = False

# Bounded exponential backoff for jax.distributed.initialize: the
# coordinator may come up seconds after its workers on a real pod (or a
# transient RPC failure may hit the connect), and ONE failed connect
# silently degrading a host to single-process would deadlock its peers'
# collectives at the first training step. Delays in seconds.
_INIT_ATTEMPTS = 4
_INIT_BACKOFF_BASE_S = 0.5
_INIT_BACKOFF_CAP_S = 8.0


def _initialize_with_retries(**kwargs) -> None:
    """`jax.distributed.initialize` with bounded exponential backoff.
    Raises the LAST error after `_INIT_ATTEMPTS` failures — the caller
    decides whether that is fatal (explicit coordinator) or degradable
    (auto-detection heuristic)."""
    import logging
    delay = _INIT_BACKOFF_BASE_S
    for attempt in range(1, _INIT_ATTEMPTS + 1):
        try:
            jax.distributed.initialize(**kwargs)
            return
        except (ValueError, RuntimeError) as e:
            if attempt == _INIT_ATTEMPTS:
                raise
            logging.getLogger("code2vec_tpu").warning(
                "jax.distributed.initialize failed (attempt %d/%d: %s); "
                "retrying in %.1fs", attempt, _INIT_ATTEMPTS, e, delay)
            time.sleep(delay)
            delay = min(delay * 2, _INIT_BACKOFF_CAP_S)


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host runtime. Safe to call unconditionally: a
    no-op for single-process runs with no coordinator configured (the
    common laptop/single-chip case) and idempotent across calls.

    Transient coordinator-connect failures are retried with bounded
    exponential backoff before anything else happens: falling back to
    single-process on a pod host that merely raced its coordinator's
    startup would deadlock every peer's collectives."""
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address is not None:
        # explicitly configured: failures (after retries) are real errors
        _initialize_with_retries(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
        _initialized = True
        return
    # Cloud-TPU-pod heuristic: hostnames present -> try auto-detection.
    # Best-effort: a failed auto-detection continues single-process
    # (pinned by tests/test_chaos.py). Whether a pod host that ends up
    # training alone should instead be fatal is open — ROADMAP S5.
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len(hostnames.split(",")) > 1:
        try:
            _initialize_with_retries()
            _initialized = True
        except (ValueError, RuntimeError) as e:
            import logging
            logging.getLogger("code2vec_tpu").warning(
                "multi-host auto-initialization failed after %d attempts "
                "(%s); continuing single-process", _INIT_ATTEMPTS, e)


def host_shard() -> Tuple[int, int]:
    """(shard_index, num_shards) for this host's data pipeline."""
    return jax.process_index(), jax.process_count()


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


class BarrierTimeout(RuntimeError):
    """A cross-host commit barrier did not complete within its timeout —
    a peer host died, hung, or never reached the same protocol stage.
    The save that hit it must be treated as FAILED on this host (no
    manifest is written after a failed barrier, so resume rejects the
    artifact and the pod falls back collectively)."""


def coordination_client():
    """The jax.distributed coordination-service client, or None outside
    a multi-process runtime. Unlike the device collectives above, its
    barriers and KV store are host-side RPCs — safe to call from a
    background thread (the async checkpoint commit thread) without
    racing the step loop's device collectives."""
    try:
        from jax._src import distributed as _jax_distributed
        return _jax_distributed.global_state.client
    except Exception:
        return None


def commit_barrier(name: str, timeout_s: float) -> None:
    """Rendezvous every process at `name` or raise BarrierTimeout.

    Built on the coordination service (thread-safe, real timeout), NOT
    on device collectives: the checkpoint commit pipeline runs this off
    the main thread while the step loop owns the devices. Single
    process: no-op. Callers must use a name unique to one rendezvous
    (the checkpoint protocol includes a lockstep save ordinal)."""
    if jax.process_count() == 1:
        return
    client = coordination_client()
    if client is None:
        # Multi-process but no coordination client (initialize() was
        # bypassed): fall back to a device-collective sync. Main-thread
        # only — documented limitation of this degraded path.
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
        return
    try:
        client.wait_at_barrier(name, timeout_in_ms=int(timeout_s * 1000))
    except Exception as e:
        raise BarrierTimeout(
            f"cross-host barrier {name!r} failed after {timeout_s:g}s: "
            f"{e}. A peer host likely died or hung mid-protocol; this "
            f"save must be treated as failed.") from e


def broadcast_from_primary(key: str, value: Optional[str],
                           timeout_s: float) -> str:
    """Share one small string from process 0 with every process via the
    coordination KV store (process 0 passes the value, others pass None
    and block until it is published). Used to agree on the shared
    checkpoint staging directory name. Single process: identity."""
    if jax.process_count() == 1:
        assert value is not None
        return value
    client = coordination_client()
    if client is None:
        raise RuntimeError(
            f"broadcast_from_primary({key!r}) requires the jax.distributed "
            f"coordination service; call distributed.initialize() first.")
    if jax.process_index() == 0:
        assert value is not None
        client.key_value_set(key, value, allow_overwrite=True)
        return value
    try:
        return client.blocking_key_value_get(key, int(timeout_s * 1000))
    except Exception as e:
        raise BarrierTimeout(
            f"waiting for broadcast key {key!r} timed out after "
            f"{timeout_s:g}s: {e}") from e


def local_batch_size(global_batch_size: int) -> int:
    """Rows this host must feed per step. The global batch is sharded
    over the `data` mesh axis across all hosts."""
    n = jax.process_count()
    if global_batch_size % n != 0:
        raise ValueError(
            f"global batch size {global_batch_size} is not divisible by "
            f"the number of hosts {n}.")
    return global_batch_size // n


def gather_host_array(values) -> "np.ndarray":
    """All-gather a small 1-D host-side float64 array EXACTLY; returns
    (num_processes, n) float64, row p = process p's values.

    The gather moves the float64 values as their raw bytes (uint8 view)
    because `process_allgather` routes through device arrays, which
    silently downcast float64 -> float32 when jax_enable_x64 is off (the
    default) — integer-valued counters above 2**24 would lose exactness
    and large-corpus eval metrics would drift. Bytes are dtype-exact.
    Single-process: the values as a single row (no collective).
    """
    import numpy as np
    values = np.ascontiguousarray(np.asarray(values, dtype=np.float64))
    if jax.process_count() == 1:
        return values[None, :]
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(values.view(np.uint8))
    return np.ascontiguousarray(np.asarray(gathered)).view(np.float64)


def allreduce_host_scalars(values) -> "np.ndarray":
    """Sum a small 1-D host-side float array across all processes.

    Used by the evaluator to turn per-host metric counters (subtoken
    tp/fp/fn, top-k hit counts, loss sums) into global totals before
    computing ratios — ratios of sums, not means of per-host ratios,
    so the result is exactly what a single-host run over the full data
    would report. Single-process: identity (no collective compiled).
    """
    import numpy as np
    return np.sum(gather_host_array(values), axis=0)


def agree_scalar(value: int, reduce: str = "min") -> int:
    """Collectively agree on one host-side integer: every process calls
    with its local value and all receive the same min/max. The train
    loop agrees its post-filter steps-per-epoch (min: every host can
    feed that many batches) and the eval loop its batch count (max:
    short hosts pad with invalid batches) — the collective step loops
    then run an identical number of iterations on every host, which is
    the lockstep precondition of every construct that keys on a batch
    counter (preemption OR-reduce, mid-epoch eval cadence, per-batch
    eval collectives). Single-process: identity."""
    import numpy as np
    gathered = gather_host_array(np.array([float(value)]))[:, 0]
    return int(gathered.min() if reduce == "min" else gathered.max())


def assert_host_agreement(value: int, what: str) -> None:
    """Collective sanity check: every process must hold the same value.
    Raises on any host whose view diverges (with all per-host values),
    turning a would-be collective deadlock into a loud error."""
    import numpy as np
    gathered = gather_host_array(np.array([float(value)]))[:, 0]
    if not np.all(gathered == gathered[0]):
        raise RuntimeError(
            f"multi-host desync: {what} differs across processes "
            f"(per-host values: {[int(v) for v in gathered]}); "
            f"this would deadlock the pod's collectives.")


def lockstep_train_stream(batches, steps_per_epoch: int,
                          first_epoch_steps: Optional[int] = None):
    """Truncate a marker-bearing train stream to exactly
    `steps_per_epoch` batches per epoch. `first_epoch_steps` overrides
    the expectation for the FIRST epoch only: a cursor-resumed run
    finishes the interrupted pass, which legitimately yields fewer
    batches than a full one (model_facade passes the pod-agreed count
    for both).

    Each host filters its own strided row shard independently, so raw
    post-filter batch counts can differ across hosts (a host whose shard
    holds more OOV-target rows yields fewer batches) — and every batch
    drives a collective step, so divergent counts deadlock the pod.
    Callers pass the `agree_scalar(local_steps, "min")` count; batches
    past it are dropped (the per-epoch reshuffle rotates which rows they
    are, so no row is starved systematically). NO collective runs in
    here: this generator is consumed by the DevicePrefetcher's worker
    thread, and a collective off the main thread would race the step
    loop's own collectives (preemption OR-reduce, mid-epoch eval) with
    host-dependent ordering — the Trainer asserts epoch agreement on the
    consumer side instead (training/loop.py EpochEnd branch)."""
    from code2vec_tpu.data.reader import EpochEnd
    target = (first_epoch_steps if first_epoch_steps is not None
              else steps_per_epoch)
    count = 0
    for item in batches:
        if isinstance(item, EpochEnd):
            if count < target:
                raise RuntimeError(
                    f"epoch {item.epoch} produced only {count} local "
                    f"batches but {target} were collectively "
                    f"agreed; the dataset shrank under the trainer.")
            yield item
            count = 0
            target = steps_per_epoch
        elif count < target:
            count += 1
            yield item
        # else: surplus local batch — other hosts are already done with
        # this epoch; consuming it without yielding keeps the pod in step.


def lockstep_eval_stream(batches, num_batches: int, make_pad_batch):
    """Extend a host's eval stream to exactly `num_batches` batches by
    appending fully-invalid batches (every row masked out).

    Eval batch counts are agreed with `agree_scalar(local, "max")` so no
    real row is dropped; hosts with fewer local batches keep feeding the
    per-step collectives with rows that contribute nothing (the eval
    step's label mask excludes them from the loss, `example_valid`
    excludes them from every host-side metric)."""
    count = 0
    for batch in batches:
        count += 1
        yield batch
    while count < num_batches:
        count += 1
        yield make_pad_batch()


def global_batch_arrays(batch, mesh: Mesh):
    """Multi-host device transfer: each host contributes its local rows
    of the RowBatch; returns global jax.Arrays sharded over the mesh.

    Single-process: plain sharded device_put (identical result).
    """
    specs = mesh_lib.batch_specs()
    names = ("source_token_indices", "path_indices", "target_token_indices",
             "context_valid_mask", "target_index", "example_valid")
    out = []
    multi = jax.process_count() > 1
    for name in names:
        local = getattr(batch, name)
        sharding = NamedSharding(mesh, specs[name])
        if multi:
            out.append(jax.make_array_from_process_local_data(sharding, local))
        else:
            out.append(jax.device_put(local, sharding))
    return tuple(out)
