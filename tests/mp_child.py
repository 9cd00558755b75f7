"""Child process for tests/test_multiprocess.py — NOT a pytest module.

Each of two OS processes runs this script: joins a real
`jax.distributed` runtime (CPU backend, gloo collectives, 2 local
devices -> 4 global), then checks the three multi-host contracts of
parallel/distributed.py against expectations the parent computed
single-process:

1. `allreduce_host_scalars` sums across processes;
2. `global_batch_arrays` (via `device_put_batch`) assembles per-host
   row shards into the right global array — verified end-to-end by
   running the REAL jitted train/eval step on a dp=4 mesh and matching
   the parent's single-device loss (any row scrambling or bad layout
   changes the loss);
3. the Evaluator reports GLOBAL metrics from per-host data shards
   (counter allreduce + host-local row extraction), matching the
   parent's single-process evaluation of the same data bit-for-bit.

Usage: python mp_child.py <process_id> <port> <data.npz> <out.json>
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from code2vec_tpu.parallel import distributed  # noqa: E402


def main():
    pid, port, data_path, out_path = (
        int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4])

    # 1. join the runtime through the framework's own wrapper
    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 4, jax.device_count()
    assert jax.local_device_count() == 2

    # 2. host-scalar allreduce
    reduced = distributed.allreduce_host_scalars(
        np.array([1.0 + pid, 10.0 * (1 + pid)]))
    np.testing.assert_allclose(reduced, [3.0, 30.0])

    import jax.numpy as jnp
    from code2vec_tpu.config import Config
    from code2vec_tpu.data.reader import RowBatch
    from code2vec_tpu.evaluation.evaluator import Evaluator
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    from code2vec_tpu.training.state import create_train_state, make_optimizer
    from code2vec_tpu.training.step import TrainStepBuilder, device_put_batch
    from code2vec_tpu.vocab import Code2VecVocabs, WordFreqDicts

    data = np.load(data_path, allow_pickle=True)
    B = int(data["B"])
    local = slice(pid * B // 2, (pid + 1) * B // 2)

    # dropout off: the loss must be bit-comparable to the parent's
    # single-device run independent of RNG partitioning details
    config = Config(train_data_path_prefix="unused", compute_dtype="float32",
                    train_batch_size=B, test_batch_size=B, max_contexts=8,
                    dp=4, tp=1, cp=1, dropout_keep_rate=1.0)
    dims = ModelDims(token_vocab_size=24, path_vocab_size=16,
                     target_vocab_size=16, token_dim=4, path_dim=4)
    mesh = make_mesh(MeshPlan(dp=4))
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                            dropout_keep_rate=config.dropout_keep_rate)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(7), mesh=mesh)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)

    local_batch = RowBatch(
        source_token_indices=data["src"][local],
        path_indices=data["pth"][local],
        target_token_indices=data["tgt"][local],
        context_valid_mask=data["mask"][local],
        target_index=data["labels"][local],
        example_valid=data["valid"][local],
        target_strings=list(data["names"][local]))

    # 3a. real eval step over the assembled global batch: loss must match
    # the parent's single-device computation on the full batch.
    arrays = device_put_batch(local_batch, mesh)
    eval_step = builder.make_eval_step(state, k=3)
    out = eval_step(state.params, *arrays)
    loss_sum = float(out.loss_sum)
    np.testing.assert_allclose(loss_sum, float(data["expected_loss_sum"]),
                               rtol=1e-5)

    # 3b. Evaluator end-to-end: per-host data shards -> global metrics.
    # (Before the train step: it donates the state's buffers.)
    freq = WordFreqDicts(
        token_to_count={"foo": 10, "bar": 8, "baz": 5, "qux": 2},
        path_to_count={"P1": 9, "P2": 7, "P3": 3},
        target_to_count={f"w{i}": 20 - i for i in range(12)},
        num_train_examples=100)
    vocabs = Code2VecVocabs.create_from_freq_dicts(
        freq, max_token_vocab_size=30, max_path_vocab_size=20,
        max_target_vocab_size=20)
    evaluator = Evaluator(config, vocabs, eval_step, mesh=mesh,
                          log_path=os.path.join(
                              os.path.dirname(out_path), f"log{pid}.txt"))
    results = evaluator.evaluate(state.params, [local_batch])

    # 3b-uneven. THE lockstep case VERDICT flagged: hosts whose
    # post-filter shards yield DIFFERENT batch counts. 18 real rows split
    # 10/8 -> host 0 builds 3 local batches, host 1 only 2; the agreed
    # max (3) pads host 1 with an invalid batch so both hosts drive the
    # same number of collective eval steps, and the global metrics must
    # still match the parent's single-process evaluation of all 18 rows.
    from code2vec_tpu.data.reader import _pad_rows, _select_rows, invalid_batch

    lo, hi = (0, 10) if pid == 0 else (10, 18)
    uneven_local = RowBatch(
        source_token_indices=data["u_src"][lo:hi],
        path_indices=data["u_pth"][lo:hi],
        target_token_indices=data["u_tgt"][lo:hi],
        context_valid_mask=data["u_mask"][lo:hi],
        target_index=data["u_labels"][lo:hi],
        example_valid=np.ones((hi - lo,), bool),
        target_strings=list(data["u_names"][lo:hi]))
    local_bs = B // 2
    local_batches = [
        _pad_rows(_select_rows(uneven_local,
                               np.arange(s, min(s + local_bs, hi - lo))),
                  local_bs)
        for s in range(0, hi - lo, local_bs)]
    assert len(local_batches) == (3 if pid == 0 else 2)
    agreed_eval = distributed.agree_scalar(len(local_batches), "max")
    assert agreed_eval == 3, agreed_eval
    stream = distributed.lockstep_eval_stream(
        iter(local_batches), agreed_eval, lambda: invalid_batch(local_bs, 8))
    ev_uneven = Evaluator(config, vocabs, eval_step, mesh=mesh,
                          log_path=os.path.join(
                              os.path.dirname(out_path), f"log_u{pid}.txt"))
    res_u = ev_uneven.evaluate(state.params, stream)
    np.testing.assert_allclose(res_u.topk_acc, data["u_topk"], atol=1e-12)
    np.testing.assert_allclose(res_u.subtoken_precision,
                               float(data["u_precision"]), atol=1e-12)
    np.testing.assert_allclose(res_u.subtoken_recall,
                               float(data["u_recall"]), atol=1e-12)
    np.testing.assert_allclose(res_u.subtoken_f1, float(data["u_f1"]),
                               atol=1e-12)
    np.testing.assert_allclose(res_u.loss, float(data["u_loss"]), rtol=1e-5)

    # 3c. real train step: parameters update collectively; the returned
    # loss is the same global mean on every host.
    train_step = builder.make_train_step(state)
    _, tr_loss = train_step(state, *arrays, jax.random.PRNGKey(0))
    np.testing.assert_allclose(float(tr_loss),
                               float(data["expected_train_loss"]), rtol=1e-5)

    # 4. preemption agreement: SIGTERM is delivered ONLY to process 0,
    # but both hosts must leave the collective step loop at the same
    # reduce boundary (training/loop.py preemption_agreed) — a lone
    # host breaking out would deadlock the other.
    import signal as _signal
    from code2vec_tpu.data.reader import EpochEnd
    from code2vec_tpu.training.loop import Trainer

    cfg2 = Config(train_data_path_prefix="unused", train_batch_size=B,
                  max_contexts=8, num_train_epochs=1, dp=4)
    steps2, saves2 = [], []

    def stream2():
        for b in range(40):
            if b == 5 and pid == 0:
                os.kill(os.getpid(), _signal.SIGTERM)
            yield local_batch
        yield EpochEnd(1)

    def fake_step(s, *a):
        steps2.append(1)
        return s, np.float32(1.0)

    class _S:
        step = np.zeros((), np.int32)

    tr = Trainer(cfg2, fake_step,
                 save_fn=lambda s, e, suffix="": saves2.append((e, suffix)))
    tr.train(_S(), stream2(), rng=np.zeros((2,), np.uint32))
    assert tr.preempted, f"pid {pid}: no preemption agreement reached"
    assert len(steps2) < 40, f"pid {pid}: ran the whole stream"
    assert saves2 == [(0, "_preempt")], saves2

    # 5. UNEVEN train shards through the full Trainer loop: host 0's
    # post-filter stream yields 7 batches/epoch, host 1 only 5. The
    # agreed minimum truncates both to 5; the step, the mid-epoch eval
    # (every 3 batches) and the preemption OR-reduce (every 10) each run
    # a real host collective, so any residual count divergence hangs the
    # pod (and trips the parent's timeout) instead of passing silently.
    local_steps = 7 if pid == 0 else 5
    agreed_train = distributed.agree_scalar(local_steps, "min")
    assert agreed_train == 5, agreed_train

    def uneven_stream():
        for epoch in (1, 2):
            for _ in range(local_steps):
                yield local_batch
            yield EpochEnd(epoch)

    steps5, evals5 = [], []

    def collective_step(s, *a):
        got = distributed.allreduce_host_scalars(np.ones(1))
        assert got[0] == 2.0
        steps5.append(1)
        return s, np.float32(0.5)

    def collective_eval(state):
        evals5.append(float(distributed.allreduce_host_scalars(
            np.array([2.0]))[0]))
        return None

    cfg5 = Config(train_data_path_prefix="unused", train_batch_size=B,
                  max_contexts=8, num_train_epochs=2, dp=4,
                  num_train_batches_to_evaluate=3)
    tr5 = Trainer(cfg5, collective_step, evaluate_fn=collective_eval,
                  steps_per_epoch_hint=agreed_train)
    tr5.train(_S(), distributed.lockstep_train_stream(
        uneven_stream(), agreed_train), rng=np.zeros((2,), np.uint32))
    # 5 lockstep batches x 2 epochs; 1 mid-epoch + 1 epoch-end eval each
    assert len(steps5) == 10, len(steps5)
    assert len(evals5) == 4 and all(v == 4.0 for v in evals5), evals5

    if pid == 0:
        with open(out_path, "w") as f:
            json.dump({
                "loss_sum": loss_sum,
                "train_loss": float(tr_loss),
                "eval": {
                    "topk_acc": [float(x) for x in results.topk_acc],
                    "precision": float(results.subtoken_precision),
                    "recall": float(results.subtoken_recall),
                    "f1": float(results.subtoken_f1),
                    "loss": float(results.loss),
                },
            }, f)
    print(f"mp_child {pid}: OK", flush=True)


if __name__ == "__main__":
    main()
