"""Headline benchmark: flagship-scale train-step throughput on one chip.

Builds the java14m-scale code2vec model (full reference vocab sizes,
reference: config.py:61-63 — token 1,301,136 / path 911,417 / target
261,245; ~385M params) and times the jitted fused
forward/backward/Adam-update train step at the reference batch size 1024
with MAX_CONTEXTS=200.

Baseline: the reference trains java14m (~14M examples) at ~50 min/epoch on
one V100 (reference: README.md:69,127) => ~4,700 examples/sec. BASELINE.json
asks for >=10x on a v5e-16 pod; this script reports single-chip
examples/sec, so vs_baseline is the per-chip speedup over one V100.

Runs on a TPU only: when JAX resolves any other platform it exits
non-zero before timing anything (it never unsets or overrides
JAX_PLATFORMS). Prints exactly ONE JSON line with the driver-contract fields
  {"metric": ..., "value": N, "unit": "examples/sec", "vs_baseline": N}
plus variance fields (value_min/value_max/n_windows/steps_per_window —
`value` is the median of n_windows timed windows), the device the step
ran on (platform/device_kind/n_devices, as JAX reports it), the touched-rows
sparse-Adam counterpart numbers (sparse_adam_*), and a
`flagship_default` note recording which optimizer config the headline
number stands for and why.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

V100_EXAMPLES_PER_SEC = 14_000_000 / (50 * 60)  # ~4,667

BATCH = 1024
CONTEXTS = 200
WARMUP_STEPS = 3
TIMED_STEPS = 20
N_WINDOWS = 5  # value is the median window; min/max are reported beside it


def _build(config):
    import jax
    import jax.numpy as jnp
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.training.state import (create_train_state,
                                             make_optimizer)
    from code2vec_tpu.training.step import TrainStepBuilder

    dims = ModelDims(
        token_vocab_size=config.max_token_vocab_size,
        path_vocab_size=config.max_path_vocab_size,
        target_vocab_size=config.max_target_vocab_size,
        token_dim=config.token_embeddings_size,
        path_dim=config.path_embeddings_size,
    )
    module = Code2VecModule(dims=dims,
                            compute_dtype=jnp.dtype(config.compute_dtype))
    optimizer = make_optimizer(config)
    state = create_train_state(module, optimizer, jax.random.PRNGKey(0),
                               mesh=None, config=config)
    builder = TrainStepBuilder(module, optimizer, config, mesh=None)
    return state, builder.make_train_step(state), dims


def _synthetic_batch(dims, b=BATCH, m=CONTEXTS):
    """Random int batch, device-resident, so timings measure the step."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    src = jax.random.randint(ks[0], (b, m), 0, dims.token_vocab_size, jnp.int32)
    pth = jax.random.randint(ks[1], (b, m), 0, dims.path_vocab_size, jnp.int32)
    tgt = jax.random.randint(ks[2], (b, m), 0, dims.token_vocab_size, jnp.int32)
    mask = jnp.ones((b, m), jnp.float32)
    labels = jax.random.randint(ks[3], (b,), 1, dims.target_vocab_size,
                                jnp.int32)
    valid = jnp.ones((b,), bool)
    return tuple(jax.block_until_ready(x)
                 for x in (src, pth, tgt, mask, labels, valid))


def measure(batch_size: int = BATCH, contexts: int = CONTEXTS,
            target_vocab: int | None = None, n_windows: int = N_WINDOWS,
            sparse: bool = False) -> dict:
    """Time the flagship train step; returns the result dict (the JSON
    contract's fields). Parameterized so experiments (e.g. the
    MAX_CONTEXTS=500 + enlarged-target-vocab stress config, BASELINE
    config #4) reuse the same timing methodology.

    Variance handling: `n_windows` independent timed windows of
    TIMED_STEPS each; `value` is the MEDIAN window's examples/sec, with
    the min/max spread reported alongside (`value_min`/`value_max`)."""
    from code2vec_tpu.config import Config

    config = Config(train_data_path_prefix="<bench>",
                    train_batch_size=batch_size, max_contexts=contexts,
                    compute_dtype="bfloat16",
                    use_sparse_embedding_update=sparse)
    if target_vocab is not None:
        config.max_target_vocab_size = target_vocab
    from code2vec_tpu.training.state import dropout_rng
    state, train_step, dims = _build(config)
    batch = _synthetic_batch(dims, batch_size, contexts)
    rng = dropout_rng(config)

    for _ in range(WARMUP_STEPS):
        state, loss = train_step(state, *batch, rng)
    float(loss)  # host fetch: closes the warm-up before the timed windows

    # Timings also flow through the observability registry
    # (code2vec_tpu/obs): a CI runner pointing C2V_METRICS_FILE at a
    # node-exporter textfile dir gets the same numbers Prometheus-side
    # that the JSON contract line reports.
    from code2vec_tpu import obs
    h_window = obs.histogram(
        "bench_window_seconds",
        f"one timed window of {TIMED_STEPS} flagship train steps")
    window_rates = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        for _ in range(TIMED_STEPS):
            state, loss = train_step(state, *batch, rng)
        # The final loss transitively depends on every prior donated-state
        # update, so fetching it forces the full window's step chain.
        float(loss)
        dt = time.perf_counter() - t0
        h_window.observe(dt)
        obs.default_tracer().maybe_record("bench_window", t0, dt)
        window_rates.append(TIMED_STEPS * batch_size / dt)
    window_rates.sort()
    examples_per_sec = window_rates[len(window_rates) // 2]
    obs.gauge("bench_examples_per_sec",
              "median-window flagship throughput",
              sparse=str(sparse).lower()).set(examples_per_sec)

    import jax

    from code2vec_tpu.utils.device import device_summary

    n_params = sum(p.size
                   for p in jax.tree_util.tree_leaves(state.params)) // 10**6
    device = device_summary(state.params)
    return {
        "metric": "java14m-scale train throughput, 1 chip "
                  f"(batch {batch_size}, {contexts} ctx, {n_params}M params, "
                  f"{config.compute_dtype}"
                  f"{', sparse adam' if sparse else ''})",
        "value": round(examples_per_sec, 1),
        "unit": "examples/sec",
        "vs_baseline": round(examples_per_sec / V100_EXAMPLES_PER_SEC, 3),
        "value_min": round(window_rates[0], 1),
        "value_max": round(window_rates[-1], 1),
        "n_windows": n_windows,
        "steps_per_window": TIMED_STEPS,
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "n_devices": device["n_devices"],
    }


def main() -> None:
    import jax

    from code2vec_tpu.utils.device import configure_compile_cache

    # A throughput of the CPU backend is not a number anyone deploys:
    # refuse it here instead of printing it under the chip's metric name.
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py times the flagship step on a TPU; JAX resolved "
                 f"platform '{platform}' (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS', '<unset>')}). Refusing "
                 f"to time it.")
    configure_compile_cache()
    # Optional observability side-channels (stdout stays exactly one JSON
    # line): C2V_METRICS_FILE gets a Prometheus snapshot of the bench
    # histograms/gauges, C2V_TRACE_EXPORT a Chrome trace of the windows.
    metrics_file = os.environ.get("C2V_METRICS_FILE")
    trace_export = os.environ.get("C2V_TRACE_EXPORT")
    if trace_export:
        from code2vec_tpu import obs
        obs.default_tracer().enable()
    result = measure()
    # Secondary: the touched-rows sparse-Adam step (the advertised
    # pod-scale optimizer, config.use_sparse_embedding_update). Recorded
    # here so its single-chip cost/benefit is a committed number, not a
    # commit-message claim. Dense Adam stays the single-chip flagship
    # default: it is the reference-faithful optimizer
    # (tensorflow_model.py:231), while sparse-Adam's win is the multi-chip
    # (ids,rows) gradient exchange replacing table-shaped psums
    # (training/step.py _make_manual_sparse_train_step).
    sparse_result = measure(sparse=True)
    result["sparse_adam_examples_per_sec"] = sparse_result["value"]
    result["sparse_adam_min"] = sparse_result["value_min"]
    result["sparse_adam_max"] = sparse_result["value_max"]
    result["flagship_default"] = "dense adam (reference-faithful; sparse is the pod-scale opt-in)"
    if metrics_file:
        from code2vec_tpu.obs import exporters
        exporters.write_prometheus(metrics_file)
    if trace_export:
        from code2vec_tpu import obs
        obs.default_tracer().export_chrome_trace(trace_export)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
