"""Child process for tests/test_multihost_chaos.py — NOT a pytest module.

Each of two OS processes joins a real `jax.distributed` runtime (CPU
backend, gloo collectives) and exercises the multi-host checkpoint
commit protocol (training/checkpoint.py) under fault injection.

Subcommands:

- `matrix <pid> <port> <base> <kill_point> <victim> <async>` — the kill
  matrix. Both hosts save `_iter1` cleanly, then save `_iter2` with the
  named fault point armed (action `exit`) on the victim host only. The
  victim dies with FAULT_EXIT_CODE mid-protocol; the survivor's commit
  barrier times out, it prints the artifact its LOCAL fallback walk
  lands on (`CHAOS_MH_LATEST`), and exits 0 via os._exit (the normal
  interpreter exit would hang in jax.distributed's shutdown barrier
  against the dead peer). `kill_point=none` runs the protocol clean:
  both hosts commit both artifacts, run the COLLECTIVE resume
  agreement, and print the agreed artifact.

- `desync <pid> <port> <workdir>` — the loud-desync contract: hosts
  intentionally diverge and every path must raise the named desync
  error on EVERY host instead of hanging the pod:
  1. `assert_host_agreement` with per-host values;
  2. the Trainer's epoch-boundary agreement check with per-host batch
     counts (3 vs 2);
  3. the collective `latest_valid_checkpoint` walk with one host
     locally rejecting the newest artifact — both hosts must converge
     on the SAME older artifact.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
for p in (REPO_ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import chaos_child  # noqa: E402  (deterministic state builders)

# Short barrier timeout: a dead peer must fail the save in seconds, well
# inside both the parent's subprocess timeout and the coordination
# service's own missed-heartbeat kill (~100s).
BARRIER_TIMEOUT_S = 8.0


def _die(code: int) -> None:
    """Exit WITHOUT running jax.distributed's shutdown barrier — after a
    peer died mid-protocol that barrier can only time out."""
    sys.stdout.flush()
    os._exit(code)


def cmd_matrix(pid: int, port: str, base: str, kill_point: str,
               victim: int, use_async: bool) -> None:
    import dataclasses

    from code2vec_tpu.parallel import distributed
    from code2vec_tpu.training import checkpoint as ckpt_mod
    from code2vec_tpu.utils import faults

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2

    vocabs = chaos_child.build_vocabs()
    config = dataclasses.replace(chaos_child.build_config(),
                                 save_barrier_timeout_s=BARRIER_TIMEOUT_S,
                                 async_checkpointing=use_async)
    committer = (ckpt_mod.AsyncCommitter(max_in_flight=2)
                 if use_async else None)

    def save(epoch: int) -> None:
        ckpt_mod.save_model(f"{base}_iter{epoch}",
                            chaos_child.build_state(epoch), vocabs, config,
                            epoch=epoch, committer=committer)
        if committer is not None:
            committer.drain()

    save(1)
    print(f"CHAOS_MH_SAVED {pid} 1", flush=True)

    if kill_point != "none" and pid == victim:
        faults.reset(f"{kill_point}=exit")
    try:
        save(2)
    except Exception as e:
        # Survivor path: the victim died mid-protocol and this host's
        # barrier timed out (or its commit errored behind the dead
        # peer). Report what the LOCAL fallback walk finds — the
        # collective walk needs a live pod — and leave fast.
        print(f"CHAOS_MH_SURVIVOR {pid} {type(e).__name__}", flush=True)
        latest = ckpt_mod.latest_valid_checkpoint(base, collective=False)
        print(f"CHAOS_MH_LATEST {pid} {latest}", flush=True)
        _die(0)
    print(f"CHAOS_MH_SAVED {pid} 2", flush=True)

    if kill_point != "none":
        # The victim's armed fault never fired an exception HERE (exit
        # action kills the process); a victim reaching this line means
        # the fault point was never crossed — fail loudly.
        if pid == victim:
            print(f"CHAOS_MH_FAULT_NOT_HIT {pid} {kill_point}", flush=True)
            _die(9)
        # Survivor of a post-commit kill (callback_crash on the other
        # host can leave this host's save fully successful when the
        # victim was a non-committing peer that died after this host
        # passed every barrier). Report and leave like any survivor.
        print(f"CHAOS_MH_SURVIVOR {pid} CleanSave", flush=True)
        latest = ckpt_mod.latest_valid_checkpoint(base, collective=False)
        print(f"CHAOS_MH_LATEST {pid} {latest}", flush=True)
        _die(0)

    # Clean run: both hosts committed both artifacts; the COLLECTIVE
    # resume agreement must hand every host the same newest path.
    agreed = ckpt_mod.latest_valid_checkpoint(base)
    print(f"CHAOS_MH_AGREED {pid} {agreed}", flush=True)
    meta = ckpt_mod.verify_checkpoint(agreed)
    assert meta["epoch"] == 2, meta
    print(f"CHAOS_MH_OK {pid}", flush=True)


def cmd_desync(pid: int, port: str, workdir: str) -> None:
    from code2vec_tpu.config import Config
    from code2vec_tpu.data.reader import EpochEnd
    from code2vec_tpu.parallel import distributed
    from code2vec_tpu.training import checkpoint as ckpt_mod
    from code2vec_tpu.training.loop import Trainer

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2

    # agree_scalar handles divergence by construction (that is its job)
    assert distributed.agree_scalar(10 + pid, "min") == 10
    assert distributed.agree_scalar(10 + pid, "max") == 11

    # 1. assert_host_agreement: divergent values must raise the loud
    # desync error on EVERY host (the gather completes collectively
    # before any host raises, so nobody hangs).
    try:
        distributed.assert_host_agreement(7 + pid, "intentional divergence")
        print(f"CHAOS_MH_DESYNC_ASSERT_MISSED {pid}", flush=True)
        _die(9)
    except RuntimeError as e:
        assert "multi-host desync" in str(e), e
        print(f"CHAOS_MH_DESYNC_ASSERT_OK {pid}", flush=True)

    # 2. the Trainer's epoch-boundary agreement: hosts cross the same
    # epoch boundary after DIFFERENT batch counts (3 vs 2) — the
    # lockstep precondition every collective in the loop relies on —
    # and every host must get the loud error, not a hang.
    class _S:
        step = np.zeros((), np.int32)

    from code2vec_tpu.data.reader import RowBatch

    def _fake_batch(n=2, m=4):
        return RowBatch(
            source_token_indices=np.ones((n, m), np.int32),
            path_indices=np.ones((n, m), np.int32),
            target_token_indices=np.ones((n, m), np.int32),
            context_valid_mask=np.ones((n, m), np.float32),
            target_index=np.ones((n,), np.int32),
            example_valid=np.ones((n,), bool))

    def stream():
        for _ in range(3 if pid == 0 else 2):
            yield _fake_batch()
        yield EpochEnd(1)

    def fake_step(s, *a):
        return s, np.float32(1.0)

    cfg = Config(train_data_path_prefix="unused", train_batch_size=4,
                 max_contexts=4, num_train_epochs=1, verbose_mode=0,
                 save_on_preemption=False)
    try:
        Trainer(cfg, fake_step).train(_S(), stream(),
                                      rng=np.zeros((2,), np.uint32))
        print(f"CHAOS_MH_DESYNC_EPOCH_MISSED {pid}", flush=True)
        _die(9)
    except RuntimeError as e:
        assert "multi-host desync" in str(e), e
        print(f"CHAOS_MH_DESYNC_EPOCH_OK {pid}", flush=True)

    # 3. collective fallback agreement: host 1 locally rejects the
    # newest artifact (simulating per-host verification divergence);
    # BOTH hosts must converge on the same older artifact.
    base = os.path.join(workdir, "m")
    vocabs = chaos_child.build_vocabs()
    config = chaos_child.build_config()
    for epoch in (1, 2):
        # save_model is a collective on a pod: BOTH hosts call it
        ckpt_mod.save_model(f"{base}_iter{epoch}",
                            chaos_child.build_state(epoch), vocabs,
                            config, epoch=epoch)
    if pid == 1:
        real_verify = ckpt_mod._verify_checkpoint_inner

        def biased_verify(path, check_content=False):
            if path.rstrip(os.sep).endswith("_iter2"):
                raise ckpt_mod.CheckpointIntegrityError(
                    f"{path}: injected host-local rejection")
            return real_verify(path, check_content)

        ckpt_mod._verify_checkpoint_inner = biased_verify
    agreed = ckpt_mod.latest_valid_checkpoint(base)
    assert agreed == f"{base}_iter1", agreed
    print(f"CHAOS_MH_DESYNC_FALLBACK_OK {pid} {agreed}", flush=True)
    print(f"CHAOS_MH_OK {pid}", flush=True)


def main() -> None:
    cmd = sys.argv[1]
    if cmd == "matrix":
        cmd_matrix(int(sys.argv[2]), sys.argv[3], sys.argv[4], sys.argv[5],
                   int(sys.argv[6]), bool(int(sys.argv[7])))
    elif cmd == "desync":
        cmd_desync(int(sys.argv[2]), sys.argv[3], sys.argv[4])
    else:
        raise SystemExit(f"unknown chaos_mh_child command: {cmd!r}")


if __name__ == "__main__":
    main()
