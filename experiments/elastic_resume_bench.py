"""Elastic-resume bench: restore wall-time (exact vs resharded) and
train throughput at dp=1 vs dp=2.

Two questions the elastic-restore path (training/checkpoint.py,
ROADMAP "Elastic topology-change resume") raises operationally:

1. What does a RESHARDED restore cost over an exact one? The restore
   targets are abstract arrays carrying the current mesh's shardings, so
   Orbax re-lays the bytes out on read — measured here by saving a
   bench-scale state under a dp=2 mesh plan and restoring it into (a)
   a dp=2 template (exact) and (b) a dp=1/tp=2 row-sharded template
   (resharded), on 4 virtual CPU devices.

2. What does the dp scaling the elastic resume unlocks buy? Steady-state
   jitted train-step throughput of the same model at dp=1 vs dp=2
   (min-of-N timing, first call excluded as compile). Caveat on this
   host: the dp=2 "devices" are VIRTUAL CPU devices sharing the same
   cores, so the ratio measures the dp partition + psum overhead, not
   real scaling — on separate chips the compute halves while this
   overhead is what remains. The number is recorded for exactly that
   reason: it bounds the collective cost the elastic resume lets you
   re-spread over a different dp.

Writes experiments/results/elastic_resume.json and prints a table.

    JAX_PLATFORMS=cpu python experiments/elastic_resume_bench.py
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from code2vec_tpu.config import Config  # noqa: E402
from code2vec_tpu.data.reader import RowBatch  # noqa: E402
from code2vec_tpu.models.code2vec import (  # noqa: E402
    Code2VecModule, ModelDims,
)
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh  # noqa: E402
from code2vec_tpu.training import checkpoint as ckpt_mod  # noqa: E402
from code2vec_tpu.training.state import (  # noqa: E402
    create_train_state, make_optimizer,
)
from code2vec_tpu.training.step import (  # noqa: E402
    TrainStepBuilder, device_put_batch,
)
from code2vec_tpu.vocab import (  # noqa: E402
    Code2VecVocabs, WordFreqDicts,
)

# Bench-scale model: tables big enough that restore I/O and the step's
# table traffic dominate, small enough for CI hardware.
TOKEN_VOCAB, PATH_VOCAB, TARGET_VOCAB = 60_000, 30_000, 16_000
DIM = 128
B, M = 256, 16
N_RESTORES = 4
N_STEPS = 12


def build_vocabs() -> Code2VecVocabs:
    freq = WordFreqDicts(
        token_to_count={f"t{i}": 10 for i in range(32)},
        path_to_count={f"p{i}": 10 for i in range(16)},
        target_to_count={f"w{i}": 10 for i in range(16)},
        num_train_examples=100)
    return Code2VecVocabs.create_from_freq_dicts(
        freq, max_token_vocab_size=40, max_path_vocab_size=20,
        max_target_vocab_size=20)


def build_parts(config):
    dims = ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     token_dim=DIM, path_dim=DIM)
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                            dropout_keep_rate=1.0)
    return module, make_optimizer(config)


def state_on(plan: MeshPlan, config, seed=3):
    module, opt = build_parts(config)
    mesh = make_mesh(plan) if plan.size > 1 else None
    return create_train_state(module, opt, jax.random.PRNGKey(seed),
                              mesh=mesh, config=config), mesh


def measure_restores(tmp: str) -> dict:
    vocabs = build_vocabs()
    cfg_save = Config(train_data_path_prefix="x", dp=2,
                      compute_dtype="float32")
    state, _mesh = state_on(MeshPlan(dp=2), cfg_save)
    path = ckpt_mod.save_model(os.path.join(tmp, "m_iter1"), state, vocabs,
                               cfg_save, epoch=1)
    out = {}
    for label, plan, cfg in (
            ("exact_dp2", MeshPlan(dp=2),
             Config(train_data_path_prefix="x", dp=2,
                    compute_dtype="float32")),
            ("resharded_tp2", MeshPlan(tp=2),
             Config(train_data_path_prefix="x", tp=2,
                    compute_dtype="float32"))):
        template, _ = state_on(plan, cfg, seed=11)
        times = []
        for _ in range(N_RESTORES):
            report = {}
            t0 = time.perf_counter()
            restored = ckpt_mod.load_model(path, template, config=cfg,
                                           report=report)
            jax.block_until_ready(jax.tree.leaves(restored.params))
            times.append(time.perf_counter() - t0)
        assert report["resume_mode"] == label.split("_")[0]
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored.params["token_embedding"])),
            np.asarray(jax.device_get(state.params["token_embedding"])))
        out[label] = {"mode": report["resume_mode"],
                      "restore_mean_s": float(np.mean(times)),
                      "restore_min_s": float(np.min(times)),
                      "n": N_RESTORES}
    out["reshard_over_exact_ratio"] = (
        out["resharded_tp2"]["restore_min_s"]
        / out["exact_dp2"]["restore_min_s"])
    return out


def _batch():
    rng = np.random.default_rng(7)
    return RowBatch(
        source_token_indices=rng.integers(
            0, TOKEN_VOCAB, (B, M)).astype(np.int32),
        path_indices=rng.integers(0, PATH_VOCAB, (B, M)).astype(np.int32),
        target_token_indices=rng.integers(
            0, TOKEN_VOCAB, (B, M)).astype(np.int32),
        context_valid_mask=np.ones((B, M), np.float32),
        target_index=rng.integers(2, TARGET_VOCAB, (B,)).astype(np.int32),
        example_valid=np.ones((B,), bool))


def measure_throughput() -> dict:
    out = {}
    batch = _batch()
    for label, plan in (("dp1", MeshPlan()), ("dp2", MeshPlan(dp=2))):
        cfg = Config(train_data_path_prefix="x", dp=plan.dp,
                     compute_dtype="float32", train_batch_size=B,
                     test_batch_size=B, max_contexts=M,
                     dropout_keep_rate=1.0)
        module, opt = build_parts(cfg)
        mesh = make_mesh(plan) if plan.size > 1 else None
        state = create_train_state(module, opt, jax.random.PRNGKey(1),
                                   mesh=mesh, config=cfg)
        builder = TrainStepBuilder(module, opt, cfg, mesh=mesh)
        step = builder.make_train_step(state)
        arrays = device_put_batch(batch, mesh)
        rng = jax.random.PRNGKey(0)
        state, loss = step(state, *arrays, rng)  # compile
        jax.block_until_ready(loss)
        times = []
        for _ in range(N_STEPS):
            t0 = time.perf_counter()
            state, loss = step(state, *arrays, rng)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        best = float(np.min(times))
        out[label] = {"step_min_s": best,
                      "examples_per_sec": B / best,
                      "n_steps": N_STEPS}
    out["dp2_over_dp1_speedup"] = (out["dp2"]["examples_per_sec"]
                                   / out["dp1"]["examples_per_sec"])
    return out


def main() -> None:
    import tempfile
    results = {"config": {"token_vocab": TOKEN_VOCAB,
                          "path_vocab": PATH_VOCAB,
                          "target_vocab": TARGET_VOCAB, "dim": DIM,
                          "batch": B, "max_contexts": M,
                          "devices": jax.device_count(),
                          "platform": jax.devices()[0].platform}}
    with tempfile.TemporaryDirectory() as tmp:
        results["restore"] = measure_restores(tmp)
    r = results["restore"]
    print(f"restore exact(dp2):     min {r['exact_dp2']['restore_min_s']*1e3:8.1f} ms")
    print(f"restore resharded(tp2): min {r['resharded_tp2']['restore_min_s']*1e3:8.1f} ms "
          f"({r['reshard_over_exact_ratio']:.2f}x exact)")
    results["throughput"] = measure_throughput()
    results["throughput"]["note"] = (
        "virtual CPU devices share the same cores: the dp2/dp1 ratio "
        "measures dp partition + psum overhead, not real chip scaling")
    t = results["throughput"]
    print(f"train dp=1: {t['dp1']['examples_per_sec']:10.0f} examples/s")
    print(f"train dp=2: {t['dp2']['examples_per_sec']:10.0f} examples/s "
          f"({t['dp2_over_dp1_speedup']:.2f}x; virtual-device caveat in "
          f"the JSON note)")
    out = os.path.join(REPO_ROOT, "experiments", "results",
                       "elastic_resume.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
