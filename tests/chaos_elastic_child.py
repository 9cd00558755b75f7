"""Child process for tests/test_elastic_resume.py — NOT a pytest module.

Each invocation runs the PRODUCTION facade (`Code2VecModel`) over a
pre-packed dataset the parent built, as one member of an N-process pod
(N=1 joins no distributed runtime; N=2 joins a real jax.distributed
pair with gloo collectives, 2 local CPU devices each). The parent
composes invocations into elastic-resume scenarios: train on N, kill
the whole pod mid-run, resume on M != N (or on a reshaped mesh) from
the last committed artifact.

Subcommands (shared argv prefix: `<cmd> <pid> <nprocs> <port> <data_prefix>
<save_base> <dp> <tp> <epochs>`):

- `train [fault_spec]` — facade training with per-epoch checkpoints.
  Every `save_model` call first prints `ELASTIC_SAVED <pid> <epoch>
  digest=<md5-of-params>` — the parent's bit-equality oracle for what
  each committed artifact must restore to. `fault_spec` (e.g.
  `callback_crash@2=exit`) arms a hard kill: with save-per-epoch, hit 2
  fires inside the SECOND save's post-commit window, so the whole pod
  dies mid-run with `_iter2` committed — the canonical "preempted pod"
  fixture. A clean run (no spec) prints `ELASTIC_LOSSES <pid> <json>`
  and serves as the uninterrupted-trajectory reference.

- `resume` — facade construction with `--load <save_base>` (collective
  resolve on a pod), printing `ELASTIC_RESUMED <pid> mode=<resume_mode>
  step=<restored step> epoch=<epoch> digest=<md5-of-params>`; then
  trains the remaining epoch budget and prints `ELASTIC_LOSSES`.
  The parent asserts digest(resumed on M) == digest(saved on N) —
  the restored GLOBAL parameter tree is bit-equal across topologies —
  and that the loss trajectory continues the reference run's.

- `preempt <kill_batch> [load]` — single-process only: trains until the
  wrapped train step SIGTERMs the process at batch `kill_batch` (counted
  from this run's start); the preemption path writes `_iter<E>_preempt`
  with the data cursor (manifest v3), and the run exits cleanly.
  Resuming it (same or other topology) must continue the epoch mid-pass
  via the cursor. With `load`, the run first RESUMES from `save_base` —
  the preempt-again-while-resumed drill, whose recorded cursor must
  accumulate the restored skip plus the newly consumed rows.
"""

import hashlib
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

import numpy as np  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from code2vec_tpu.parallel import distributed  # noqa: E402

# Short commit-barrier timeout: a dead peer must fail a pod save in
# seconds, inside the parent's subprocess timeout.
BARRIER_TIMEOUT_S = 8.0


def params_digest(params) -> str:
    h = hashlib.md5()
    for name in sorted(params):
        h.update(name.encode())
        h.update(np.asarray(jax.device_get(params[name])).tobytes())
    return h.hexdigest()


def build_config(data_prefix: str, save_base: str, dp: int, tp: int,
                 epochs: int, load: bool):
    from code2vec_tpu.config import Config
    return Config(
        train_data_path_prefix=data_prefix,
        model_save_path=save_base,
        model_load_path=save_base if load else None,
        max_contexts=8,
        train_batch_size=8, test_batch_size=8,
        num_train_epochs=epochs,
        save_every_epochs=1,
        num_batches_to_log_progress=10 ** 6,
        compute_dtype="float32",
        dropout_keep_rate=1.0,   # determinism: trajectories comparable
        use_packed_data=True,
        dp=dp, tp=tp, cp=1,
        save_barrier_timeout_s=BARRIER_TIMEOUT_S,
        seed=7,
        verbose_mode=0,
    )


def init_pod(pid: int, nprocs: int, port: str) -> None:
    if nprocs > 1:
        # gloo collectives need the distributed client; the config must
        # land before the (lazy) CPU backend initializes, and must NOT
        # be set for single-process children (no client to hand gloo).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=nprocs, process_id=pid)
        assert jax.process_count() == nprocs


def install_save_recorder(pid: int) -> None:
    """Print a params digest immediately before every checkpoint save:
    the parent's oracle for what each committed artifact must restore
    to, bit-equal, on any later topology."""
    from code2vec_tpu.training import checkpoint as ckpt_mod
    orig_save = ckpt_mod.save_model

    def recording_save(path, state, vocabs, config, **kw):
        print(f"ELASTIC_SAVED {pid} {kw.get('epoch', 0)} "
              f"digest={params_digest(state.params)}", flush=True)
        return orig_save(path, state, vocabs, config, **kw)

    ckpt_mod.save_model = recording_save


def install_loss_recorder(model, losses, on_step=None):
    orig_make = model.builder.make_train_step

    def make_recording(state):
        step = orig_make(state)

        def wrapped(s, *a):
            s2, loss = step(s, *a)
            losses.append(float(loss))
            if on_step is not None:
                on_step(len(losses))
            return s2, loss

        return wrapped

    model.builder.make_train_step = make_recording


def cmd_train(pid, nprocs, port, data_prefix, save_base, dp, tp, epochs,
              fault_spec):
    from code2vec_tpu.model_facade import Code2VecModel
    from code2vec_tpu.utils import faults

    init_pod(pid, nprocs, port)
    install_save_recorder(pid)
    if fault_spec:
        faults.reset(fault_spec)
    model = Code2VecModel(build_config(data_prefix, save_base, dp, tp,
                                       epochs, load=False))
    losses = []
    install_loss_recorder(model, losses)
    model.train()
    print(f"ELASTIC_LOSSES {pid} {json.dumps(losses)}", flush=True)
    print(f"ELASTIC_DONE {pid}", flush=True)


def cmd_resume(pid, nprocs, port, data_prefix, save_base, dp, tp, epochs):
    from code2vec_tpu.model_facade import Code2VecModel

    init_pod(pid, nprocs, port)
    install_save_recorder(pid)
    model = Code2VecModel(build_config(data_prefix, save_base, dp, tp,
                                       epochs, load=True))
    report = model.resume_report
    print(f"ELASTIC_RESUMED {pid} mode={report['resume_mode']} "
          f"step={report['restored_step']} epoch={model.initial_epoch} "
          f"digest={params_digest(model.state.params)}", flush=True)
    losses = []
    install_loss_recorder(model, losses)
    model.train()
    print(f"ELASTIC_LOSSES {pid} {json.dumps(losses)}", flush=True)
    print(f"ELASTIC_DONE {pid}", flush=True)


def cmd_preempt(pid, nprocs, port, data_prefix, save_base, dp, tp, epochs,
                kill_batch, load=False):
    import signal

    from code2vec_tpu.model_facade import Code2VecModel

    assert nprocs == 1, "preempt drill is single-process"
    install_save_recorder(pid)
    model = Code2VecModel(build_config(data_prefix, save_base, dp, tp,
                                       epochs, load=load))
    losses = []

    def sigterm_at(step_count):
        if step_count == kill_batch:
            os.kill(os.getpid(), signal.SIGTERM)

    install_loss_recorder(model, losses, on_step=sigterm_at)
    model.train()
    print(f"ELASTIC_PREEMPTED {pid} after={len(losses)}", flush=True)
    print(f"ELASTIC_LOSSES {pid} {json.dumps(losses)}", flush=True)
    print(f"ELASTIC_DONE {pid}", flush=True)


def main() -> None:
    cmd = sys.argv[1]
    pid, nprocs, port = int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    data_prefix, save_base = sys.argv[5], sys.argv[6]
    dp, tp, epochs = int(sys.argv[7]), int(sys.argv[8]), int(sys.argv[9])
    if cmd == "train":
        cmd_train(pid, nprocs, port, data_prefix, save_base, dp, tp, epochs,
                  sys.argv[10] if len(sys.argv) > 10 else "")
    elif cmd == "resume":
        cmd_resume(pid, nprocs, port, data_prefix, save_base, dp, tp, epochs)
    elif cmd == "preempt":
        cmd_preempt(pid, nprocs, port, data_prefix, save_base, dp, tp,
                    epochs, int(sys.argv[10]),
                    load=(len(sys.argv) > 11 and sys.argv[11] == "load"))
    else:
        raise SystemExit(f"unknown chaos_elastic_child command: {cmd!r}")


if __name__ == "__main__":
    main()
