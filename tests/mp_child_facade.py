"""Child for test_multiprocess.test_two_process_facade_train — NOT pytest.

Each of two OS processes joins a real `jax.distributed` runtime and runs
the PRODUCTION training entry point — `Code2VecModel.train()` — over an
actual packed dataset whose raw strided shards are UNEVEN (12 vs 8 kept
train rows; the elastic global train order equalizes the per-host batch
counts, while the eval shards stay raw-strided at 3 vs 2 local batches,
exercising the lockstep eval padding). The facade path under test is
the full composition:
vocab load -> packed dataset shard -> `agree_scalar` lockstep truncation
-> jitted collective train steps -> mid-epoch collective eval (with
lockstep eval padding: 3 vs 2 local eval batches) -> per-epoch Orbax
checkpoint saves from both processes -> final save -> restore roundtrip.

Asserted here and in the parent:
- per-step training losses bit-comparable (rtol 1e-5) to the parent's
  single-process run of the same global stream;
- final params BIT-IDENTICAL across the two hosts (digest compare);
- the multi-host-saved artifact restores bit-identically.

Usage: python mp_child_facade.py <pid> <port> <root_dir> <expect.npz>
"""

import hashlib
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


def params_digest(params) -> str:
    h = hashlib.md5()
    for name in sorted(params):
        h.update(name.encode())
        h.update(np.asarray(jax.device_get(params[name])).tobytes())
    return h.hexdigest()


def main():
    pid, port, root, expect_path = (
        int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4])
    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2 and jax.device_count() == 4

    from code2vec_tpu.config import Config
    from code2vec_tpu.model_facade import Code2VecModel
    from code2vec_tpu.training import checkpoint as ckpt_mod

    expect = np.load(expect_path)
    prefix = os.path.join(root, "data")
    save_path = os.path.join(root, "model", "m")

    config = Config(
        train_data_path_prefix=prefix,
        test_data_path=prefix + ".val.c2v",
        model_save_path=save_path,
        max_contexts=8,
        train_batch_size=8, test_batch_size=8,
        num_train_epochs=2,
        num_train_batches_to_evaluate=2,   # mid-epoch collective eval
        save_every_epochs=1,               # per-epoch multi-host saves
        num_batches_to_log_progress=1000,
        compute_dtype="float32",
        dropout_keep_rate=1.0,             # bit-comparability to parent
        use_packed_data=True,
        dp=4, verbose_mode=0,
    )
    model = Code2VecModel(config)

    # Record every training step's loss through the REAL facade path.
    losses = []
    orig_make = model.builder.make_train_step

    def make_recording(state):
        step = orig_make(state)

        def wrapped(s, *a):
            s2, loss = step(s, *a)
            losses.append(float(loss))
            return s2, loss

        return wrapped

    model.builder.make_train_step = make_recording
    model.train()

    # 2 epochs x 2 global batches (elastic global order: 20 filtered
    # rows // global batch 8). rtol 1e-4, not 1e-5: losses after step 1
    # are computed on params that already absorbed cross-topology float
    # summation-order differences (see the params comment below).
    np.testing.assert_allclose(losses, expect["losses"], rtol=1e-4)

    # Hosts hold the same replicated final params, bit for bit.
    digest = params_digest(model.state.params)
    with open(os.path.join(root, f"digest{pid}.txt"), "w") as f:
        f.write(digest)

    # Parent's single-process mimic of the same global stream agrees.
    # Tolerance is cross-TOPOLOGY (4-device psum vs single-device reduce:
    # different float summation order, amplified through 4 Adam steps);
    # the bit-exact claim is the cross-HOST digest above.
    flat = np.concatenate([
        np.asarray(jax.device_get(model.state.params[k])).ravel()
        for k in sorted(model.state.params)])
    np.testing.assert_allclose(flat, expect["final_params"],
                               rtol=2e-3, atol=5e-5)

    # The artifact written collectively by BOTH processes restores
    # bit-identically into the live sharded state template.
    restored = ckpt_mod.load_model(save_path, model.state, config)
    for k in sorted(model.state.params):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored.params[k])),
            np.asarray(jax.device_get(model.state.params[k])))
    assert int(np.asarray(restored.step)) == len(losses)

    if pid == 0:
        with open(os.path.join(root, "facade_out.json"), "w") as f:
            json.dump({"losses": losses, "digest": digest,
                       "epochs": model.initial_epoch}, f)
    print(f"mp_child_facade {pid}: OK", flush=True)


if __name__ == "__main__":
    main()
