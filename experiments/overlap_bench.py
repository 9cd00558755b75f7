"""2-host A/B of the bucketed async all-reduce overlap
(parallel/overlap.py) — the comm/compute lever of the roofline PR.

Spawns TWO real `jax.distributed` processes (CPU backend, gloo
collectives, 1 device each — the same harness the multi-process chaos
suites use) sharing a dp=2 mesh, and times the SAME synthetic training
workload twice in each process:

- **unbucketed** — the stock single-program GSPMD step (backward +
  in-program all-reduce + full Adam sweep, one dispatch);
- **overlap** — the bucketed composite (backward without the gradient
  reduce + per-bucket all-reduce+apply dispatches).

The measurement mirrors the Trainer's host loop exactly (the PR-2
dispatch / loss-sync split): steps are dispatched asynchronously in
windows, per-step host dispatch time and per-window blocking loss-fetch
time are recorded — the same quantities
`train_step_dispatch_seconds` / `train_loss_sync_seconds` histograms
hold in production — and fed through the obs span tracer
(step_dispatch / loss_sync spans; pass --trace_export for the
Chrome-trace files).

Output: experiments/results/overlap.json + a marker-delimited
"Roofline levers: comm/compute overlap" section in BENCH_ROOFLINE.md.
Run via scripts/run_roofline_bench.sh (hard timeout + diagnostics).

Usage:
    python experiments/overlap_bench.py [--steps N] [--batch B]
        [--bucket_mb MB] [--trace_export DIR]
    python experiments/overlap_bench.py --child RANK PORT OUT  (internal)
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

OUT_PATH = os.path.join(REPO, "experiments", "results", "overlap.json")
BENCH_MD = os.path.join(REPO, "BENCH_ROOFLINE.md")
BEGIN = "<!-- overlap-bench:begin -->"
END = "<!-- overlap-bench:end -->"

# Medium synthetic shape: big enough that the per-step gradient
# all-reduce moves tens of MB over gloo (the thing being overlapped),
# small enough that a 2-arm 2-process run finishes in ~a minute on CPU.
TOKEN_VOCAB = 30_000
PATH_VOCAB = 20_000
TARGET_VOCAB = 5_000
DIM = 96
CONTEXTS = 32
WINDOW = 5


def _percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    idx = min(int(q * len(xs)), len(xs) - 1)
    return xs[idx]


# ------------------------------------------------------------- child


def child_main(rank: int, port: str, out_path: str, steps: int,
               batch: int, bucket_mb: float, trace_dir: str) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np

    from code2vec_tpu import obs
    from code2vec_tpu.config import Config
    from code2vec_tpu.data.reader import RowBatch
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.parallel import distributed
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    from code2vec_tpu.training.state import (
        create_train_state, make_optimizer,
    )
    from code2vec_tpu.training.step import TrainStepBuilder, device_put_batch
    import jax.numpy as jnp

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=rank)
    assert jax.process_count() == 2
    mesh = make_mesh(MeshPlan(dp=2))
    tracer = obs.default_tracer()
    tracer.enable()

    dims = ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     token_dim=DIM, path_dim=DIM)
    rng = np.random.default_rng(17 + rank)
    local_rows = batch // 2
    local = RowBatch(
        source_token_indices=rng.integers(
            2, TOKEN_VOCAB, (local_rows, CONTEXTS)).astype(np.int32),
        path_indices=rng.integers(
            2, PATH_VOCAB, (local_rows, CONTEXTS)).astype(np.int32),
        target_token_indices=rng.integers(
            2, TOKEN_VOCAB, (local_rows, CONTEXTS)).astype(np.int32),
        context_valid_mask=np.ones((local_rows, CONTEXTS), np.float32),
        target_index=rng.integers(2, TARGET_VOCAB,
                                  (local_rows,)).astype(np.int32),
        example_valid=np.ones((local_rows,), bool),
        target_strings=None)
    arrays = device_put_batch(local, mesh)
    key = jax.random.PRNGKey(3)

    def run_arm(overlap: bool) -> dict:
        config = Config(train_data_path_prefix="<bench>",
                        train_batch_size=batch, max_contexts=CONTEXTS,
                        compute_dtype="float32", dp=2,
                        overlap_grad_allreduce=overlap,
                        overlap_bucket_mb=bucket_mb, verbose_mode=0)
        module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                                dropout_keep_rate=config.dropout_keep_rate)
        opt = make_optimizer(config)
        state = create_train_state(module, opt, jax.random.PRNGKey(0),
                                   mesh=mesh, config=config)
        step = TrainStepBuilder(module, opt, config,
                                mesh=mesh).make_train_step(state)
        # warmup: compile every dispatch shape, settle gloo
        pending = []
        for _ in range(3):
            state, loss = step(state, *arrays, key)
            pending.append(loss)
        jax.device_get(pending)

        dispatch_s, sync_s = [], []
        pending = []
        t_arm = time.perf_counter()
        for i in range(steps):
            t0 = time.perf_counter()
            state, loss = step(state, *arrays, key)
            d = time.perf_counter() - t0
            dispatch_s.append(d)
            tracer.maybe_record("step_dispatch", t0, d)
            pending.append(loss)
            if (i + 1) % WINDOW == 0:
                t0 = time.perf_counter()
                losses = jax.device_get(pending)
                d = time.perf_counter() - t0
                sync_s.append(d)
                tracer.maybe_record("loss_sync", t0, d)
                pending = []
                assert all(np.isfinite(losses)), losses
        if pending:
            jax.device_get(pending)
        wall = time.perf_counter() - t_arm
        return {
            "overlap": overlap,
            "buckets": getattr(step, "overlap_buckets", 1),
            "steps": steps,
            "wall_s": round(wall, 3),
            "steps_per_s": round(steps / wall, 3),
            "examples_per_s": round(steps * batch / wall, 1),
            "dispatch_sum_s": round(sum(dispatch_s), 3),
            "dispatch_p95_ms": round(
                _percentile(dispatch_s, 0.95) * 1e3, 2),
            "loss_sync_sum_s": round(sum(sync_s), 3),
            "loss_sync_p95_ms": round(
                _percentile(sync_s, 0.95) * 1e3, 2),
            "host_stall_sum_s": round(sum(dispatch_s) + sum(sync_s), 3),
        }

    baseline = run_arm(False)
    overlap = run_arm(True)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        tracer.export_chrome_trace(
            os.path.join(trace_dir, f"overlap_host{rank}.trace.json"))
    result = {"rank": rank, "unbucketed": baseline, "overlap": overlap}
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"child {rank}: unbucketed {baseline['steps_per_s']} st/s "
          f"(host stall {baseline['host_stall_sum_s']}s) vs overlap "
          f"{overlap['steps_per_s']} st/s "
          f"(host stall {overlap['host_stall_sum_s']}s, "
          f"{overlap['buckets']} buckets)", flush=True)


# ------------------------------------------------------------ parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None) -> None:
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--child", nargs=3, metavar=("RANK", "PORT", "OUT"))
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--bucket_mb", type=float, default=8.0)
    p.add_argument("--trace_export", default="",
                   help="directory for per-host Chrome traces")
    args = p.parse_args(argv)

    if args.child:
        rank, port, out = args.child
        child_main(int(rank), port, out, args.steps, args.batch,
                   args.bucket_mb, args.trace_export)
        return

    import tempfile
    port = _free_port()
    tmp = tempfile.mkdtemp(prefix="c2v-overlap-")
    outs = [os.path.join(tmp, f"host{r}.json") for r in (0, 1)]
    procs = []
    for r in (0, 1):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--child", str(r), str(port), outs[r],
               "--steps", str(args.steps), "--batch", str(args.batch),
               "--bucket_mb", str(args.bucket_mb)]
        if args.trace_export:
            cmd += ["--trace_export", args.trace_export]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [proc.wait(timeout=900) for proc in procs]
    if any(rcs):
        raise SystemExit(f"child rc(s) {rcs}")

    hosts = []
    for out in outs:
        with open(out) as f:
            hosts.append(json.load(f))
    base = hosts[0]["unbucketed"]
    over = hosts[0]["overlap"]
    result = {
        "bench": "overlap_allreduce",
        "topology": "2 processes x 1 CPU device, gloo collectives, "
                    "dp=2 mesh",
        "model": {"token_vocab": TOKEN_VOCAB, "path_vocab": PATH_VOCAB,
                  "target_vocab": TARGET_VOCAB, "dim": DIM,
                  "contexts": CONTEXTS, "batch": args.batch,
                  "grad_bytes_per_step": 4 * (
                      TOKEN_VOCAB * DIM + PATH_VOCAB * DIM
                      + TARGET_VOCAB * 3 * DIM
                      + 9 * DIM * DIM + 3 * DIM)},
        "bucket_mb": args.bucket_mb,
        "window": WINDOW,
        "hosts": hosts,
        "speedup_steps_per_s": round(
            over["steps_per_s"] / base["steps_per_s"], 3),
        "host_stall_reduction": round(
            1 - over["host_stall_sum_s"]
            / max(base["host_stall_sum_s"], 1e-9), 3),
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    _update_bench_md(result)
    print(json.dumps({k: result[k] for k in
                      ("speedup_steps_per_s", "host_stall_reduction")}))
    print(f"Wrote {OUT_PATH} and the BENCH_ROOFLINE.md overlap section")
    diag = os.environ.get("C2V_CHAOS_DIAG_DIR")
    if diag:
        from code2vec_tpu import obs
        obs.exporters.write_prometheus(
            os.path.join(diag, "overlap_bench_metrics.prom"))


def _update_bench_md(result: dict) -> None:
    base, over = (result["hosts"][0]["unbucketed"],
                  result["hosts"][0]["overlap"])
    section = "\n".join([
        BEGIN,
        "## Roofline levers: comm/compute overlap (2-host A/B)",
        "",
        "Produced by `scripts/run_roofline_bench.sh` → "
        "`experiments/overlap_bench.py` → "
        "`experiments/results/overlap.json` — 2 real jax.distributed "
        "processes (gloo, dp=2 mesh), same synthetic workload, both "
        "arms in ONE run per process "
        f"(~{result['model']['grad_bytes_per_step'] / 1e6:.0f} MB of "
        "gradients all-reduced per step; host dispatch / loss-sync "
        "split measured exactly as the Trainer's PR-2 histograms "
        "record it).",
        "",
        "| arm | steps/s | host dispatch sum | loss-sync sum | "
        "host stall total |",
        "|---|---|---|---|---|",
        f"| unbucketed single program | {base['steps_per_s']} | "
        f"{base['dispatch_sum_s']}s | {base['loss_sync_sum_s']}s | "
        f"{base['host_stall_sum_s']}s |",
        f"| bucketed overlap ({over['buckets']} buckets, "
        f"{result['bucket_mb']:g} MB) | {over['steps_per_s']} | "
        f"{over['dispatch_sum_s']}s | {over['loss_sync_sum_s']}s | "
        f"{over['host_stall_sum_s']}s |",
        "",
        f"Overlap-on speedup {result['speedup_steps_per_s']}x "
        f"steps/s; host dispatch+loss-sync stall reduced "
        f"{result['host_stall_reduction'] * 100:.0f}% "
        "(`--overlap_allreduce`; dense GSPMD data-parallel only — "
        "see config.py).",
        END,
    ])
    text = ""
    if os.path.exists(BENCH_MD):
        with open(BENCH_MD) as f:
            text = f.read()
    if BEGIN in text:
        head, rest = text.split(BEGIN, 1)
        _, tail = rest.split(END, 1)
        text = head + section + tail
    else:
        text = (text.rstrip() + "\n\n" if text else "") + section + "\n"
    with open(BENCH_MD, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
