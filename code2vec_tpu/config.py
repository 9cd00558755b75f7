"""Configuration for the TPU-native code2vec framework.

Mirrors the knob surface of the reference ``Config`` class
(reference: config.py:46-70 for defaults, config.py:10-44 for CLI flags,
config.py:143-230 for derived path conventions) as a frozen-free dataclass,
and adds TPU-specific knobs (mesh shape, compute dtype, packed-data paths)
that have no reference equivalent (the reference is single-device,
reference: SURVEY.md §2.3).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import sys
from typing import Optional, Tuple


_LOGGER_NAME = "code2vec_tpu"


@dataclasses.dataclass
class Config:
    # -- training schedule (reference: config.py:46-57) --
    num_train_epochs: int = 20
    save_every_epochs: int = 1
    # Checkpoint-and-stop on SIGTERM (preempted TPU workers get a grace
    # window; training/loop.py PreemptionWatcher). No reference analog —
    # the reference loses the epoch in progress on preemption.
    save_on_preemption: bool = True
    # Host-memory watchdog: when process peak RSS crosses this many GB
    # the trainer checkpoints and stops via the same path as SIGTERM
    # (clean resumable stop instead of a kernel OOM kill mid-epoch).
    # 0 disables. No reference analog.
    rss_limit_gb: float = 0.0
    # Non-finite-loss sentinel policy (training/loop.py): "halt"
    # checkpoints via the preemption save path and exits nonzero the
    # first time a log-window average loss is NaN/Inf; "warn" logs and
    # keeps going. No reference analog — a diverged reference run just
    # prints NaN losses forever.
    on_nonfinite_loss: str = "halt"
    # Seconds before a hung serving-side path extraction is killed
    # (serving/extractor_bridge.py). The offline preprocess pipeline has
    # its own kill-timer (data/preprocess.py); this covers the
    # interactive/serving bridge, where one wedged extractor child would
    # otherwise hang the predict request forever. 0 disables.
    extractor_timeout_s: float = 120.0
    # Retries (beyond the first attempt) when the serving-side extractor
    # subprocess fails to launch or crashes (nonzero exit / no output),
    # with bounded exponential backoff between attempts. Distinct from
    # the timeout above: a HUNG child is killed and NOT retried (the
    # next one would likely hang the same way and double the stall);
    # a crashed child usually hit a transient (OOM, fork pressure).
    # 0 disables retries.
    extractor_retries: int = 2
    # Defer the checkpoint commit (Orbax flush wait + cross-host commit
    # barrier + manifest + atomic rename) to a background commit thread
    # (training/checkpoint.py AsyncCommitter) with bounded in-flight
    # depth, so the step loop's save stall shrinks to staging + array
    # dispatch. Crash-atomicity is unchanged: the manifest still lands
    # only after the flush + barrier, and the trainer drains the
    # pipeline before exiting (incl. on preemption). No reference
    # analog.
    async_checkpointing: bool = False
    # Seconds each cross-host checkpoint commit barrier waits for every
    # host before declaring the save failed (a peer died or hung
    # mid-protocol). Generous by default: the barrier only fires after
    # each host's own Orbax flush, so it usually completes in
    # milliseconds; stragglers flushing multi-GB shards to cold storage
    # are the long tail it must tolerate.
    save_barrier_timeout_s: float = 600.0
    # Resume the input pipeline from the checkpoint's data cursor
    # (manifest v3 `data_cursor`): a run resumed from a mid-epoch
    # (preemption) artifact skips the global rows the interrupted epoch
    # already consumed — remapped exactly onto the current host count —
    # so the pass neither skips nor double-reads rows. False re-runs the
    # interrupted epoch from its start (the pre-v3 behavior). Only the
    # packed (.c2vb) pipeline supports the cursor; the streaming text
    # reader always restarts the epoch. No reference analog.
    cursor_resume: bool = True
    train_batch_size: int = 1024
    test_batch_size: int = 1024
    top_k_words_considered_during_prediction: int = 10
    num_batches_to_log_progress: int = 100
    num_train_batches_to_evaluate: int = 1800
    reader_num_workers: int = 6
    shuffle_buffer_size: int = 10000
    csv_buffer_size: int = 100 * 1024 * 1024
    max_to_keep: int = 10

    # -- model hyper-params (reference: config.py:59-70) --
    max_contexts: int = 200
    max_token_vocab_size: int = 1301136
    max_target_vocab_size: int = 261245
    max_path_vocab_size: int = 911417
    # Reference semantics (config.py:64-66): token/path embedding sizes
    # default to DEFAULT_EMBEDDINGS_SIZE; set either explicitly to
    # override just that table. Resolved in __post_init__.
    default_embeddings_size: int = 128
    token_embeddings_size: Optional[int] = None
    path_embeddings_size: Optional[int] = None
    dropout_keep_rate: float = 0.75
    separate_oov_and_pad: bool = False

    # -- CLI-filled run mode (reference: config.py:72-87) --
    predict: bool = False
    # Run the batched prediction HTTP server (serving/server.py) on the
    # loaded/trained model. No reference analog.
    serve: bool = False
    model_save_path: Optional[str] = None
    model_load_path: Optional[str] = None
    train_data_path_prefix: Optional[str] = None
    test_data_path: str = ""
    release: bool = False
    export_code_vectors: bool = False
    save_w2v: Optional[str] = None
    save_t2v: Optional[str] = None
    verbose_mode: int = 1
    logs_path: Optional[str] = None
    use_tensorboard: bool = False

    # -- TPU-native knobs (no reference equivalent) --
    # Mesh axis sizes: data parallel, tensor/model parallel (row-sharded
    # embedding tables + target softmax), context/sequence parallel
    # (shards the MAX_CONTEXTS axis; SURVEY.md §5 long-context plan).
    dp: int = 1
    tp: int = 1
    cp: int = 1
    # Computation dtype for matmuls (params stay float32). bfloat16 maps
    # onto the MXU natively; accumulation is forced to float32.
    compute_dtype: str = "bfloat16"
    # Adam hyper-params (reference uses tf.compat.v1.train.AdamOptimizer()
    # defaults, tensorflow_model.py:231).
    learning_rate: float = 0.001
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    # Use the hand-written shard_map tensor-parallel kernels instead of
    # relying purely on GSPMD sharding propagation (only matters if tp>1).
    use_manual_tp_kernels: bool = True
    # Touched-rows (lazy) Adam for the token/path embedding tables
    # (training/sparse_adam.py) instead of a dense update over all ~285M
    # of their parameters. Default OFF on single-chip after measurement:
    # XLA's fused scatter+Adam already runs at the HBM roofline
    # (~670 GB/s on a v5e chip), while the sparse path's sort/permute/
    # segment/scatter chain is bound per *index-array row* (~70-120M
    # rows/s) regardless of how few unique rows a batch touches — it
    # measured slower at java14m scale on both uniform and Zipf(1.07) id
    # distributions. Where it genuinely wins is the manual
    # tensor-parallel path at pod scale: the sparse (ids, grad-rows)
    # all-gather exchanged per step is ~5x smaller than a dense psum of
    # the two table-shaped gradients. Semantics are lazy Adam (TF's
    # LazyAdam; the reference's tf.train.AdamOptimizer
    # (tensorflow_model.py:231) decays moments and updates vars densely
    # even for sparse grads, matching our dense default's cost model).
    use_sparse_embedding_update: bool = False
    # Storage dtype for Adam's first moment (optax mu_dtype). bfloat16
    # halves its HBM traffic in the memory-bound update (+~5% step
    # throughput at java14m scale) with negligible effect on convergence;
    # set "float32" for bit-strict Adam.
    adam_mu_dtype: str = "bfloat16"
    # Storage dtype for Adam's second moment (nu). bfloat16 halves the
    # bytes the memory-bound update reads and writes for it (2 bytes of
    # 4 per parameter, each way; step-time effect not measured on the
    # current machine) and was validated end-to-end: the accuracy
    # harness converges to the same test F1 as with f32 nu. nu sets the
    # per-parameter step size through a sqrt, so its rounding is more
    # consequential than mu's — set "float32" (with adam_mu_dtype
    # "float32") for bit-strict optax.adam. The sparse touched-rows path
    # keeps its nu in f32 regardless (training/sparse_adam.py).
    adam_nu_dtype: str = "bfloat16"
    # PRNG implementation for the per-step dropout key. The TPU hardware
    # generator ("rbg") produces the ~78M dropout bits per flagship step
    # far faster than the default threefry (+~5% step throughput);
    # parameter initialization always uses threefry for reproducibility.
    dropout_prng_impl: str = "rbg"
    # Prefer the packed int32 binary sidecar (.c2vb) when present.
    use_packed_data: bool = True
    # Train from a corpus MANIFEST (data/packed.py ShardedCorpus): a
    # JSON file listing N .c2vb shards — the incumbent pack plus any
    # continuous-training delta shards — presented as one logical row
    # space with the same epoch-keyed global shuffle as a single pack
    # (the PR-6 cursor laws hold verbatim across shard counts). Built
    # and grown with the `corpus` subcommand / pipeline ingest stage.
    # Overrides --data's packed file for training when set.
    train_corpus_manifest: Optional[str] = None
    # Host worker processes for the offline data compile: the on-demand
    # .c2v -> .c2vb pack at training startup (model_facade) and the
    # fused raw-corpus compiler (data/preprocess.py compile_corpus).
    # Output is byte-identical at any worker count; 0 = in-process
    # serial. No reference analog (the reference preprocesses in awk +
    # single-process Python).
    preprocess_workers: int = 0
    # Number of batches the host pipeline keeps in flight ahead of device.
    prefetch_batches: int = 4
    # When set, a jax.profiler trace of train batches 10-20 is written
    # here (viewable in TensorBoard / Perfetto).
    profile_dir: Optional[str] = None

    # -- observability (code2vec_tpu/obs; no reference equivalent) --
    # Prometheus text-format snapshot, rewritten atomically at every log
    # boundary (node-exporter textfile-collector style). None disables.
    metrics_file: Optional[str] = None
    # Localhost HTTP port serving the same snapshot at /metrics for a
    # direct Prometheus scrape. 0 disables.
    metrics_port: int = 0
    # JSON heartbeat file {step, epoch, last_loss, wall_time, ...},
    # rewritten atomically each log window so an external watchdog can
    # detect a hung trainer by staleness alone. None disables.
    heartbeat_file: Optional[str] = None
    # Chrome trace-event JSON of host-side spans (data wait / dispatch /
    # loss sync / checkpoint / eval), written when training ends —
    # loadable in Perfetto, complementing the device-side --profile_dir
    # trace. None disables span buffering entirely.
    trace_export: Optional[str] = None
    # -- serving (code2vec_tpu/serving; no reference equivalent — the
    # reference "serves" through a one-file interactive REPL) --
    # HTTP bind for the prediction server (`serve` subcommand /
    # --serve). Port 0 picks a free port (logged + returned by
    # PredictionServer.start); localhost by default — fronting proxies
    # own external exposure/TLS.
    serve_port: int = 8800
    serve_host: str = "127.0.0.1"
    # Rows per coalesced device batch: the most the batcher cuts into
    # one model call from what piled up behind the call in flight (a
    # free dispatcher dispatches at once, serving/batcher.py). Also the
    # padded row count of every compiled predict shape — smaller than
    # test_batch_size because serving favors latency over peak
    # throughput.
    serve_batch_size: int = 64
    # Padded-context-count buckets for the predict path (comma list;
    # max_contexts is always appended, entries >= max_contexts or not
    # divisible by cp are dropped): every predict batch pads its context
    # axis up to the smallest bucket that holds its deepest valid
    # context, so the number of pjit compilations the serving path can
    # trigger is bounded by len(buckets) instead of one per request
    # shape.
    serve_buckets: str = "32,64,128"
    # A model of models/ other than code2vec, named by its
    # model-configuration file (cli --model_config; lm_facade.py). Its
    # length buckets come from that file; a scoring step holds at most
    # serve_token_budget tokens (rows x padded length), which is also
    # the longest request it takes.
    model_config: Optional[str] = None
    serve_token_budget: int = 8192
    # LRU prediction-cache capacity (entries), keyed by normalized
    # method-body hash (serving/cache.py). 0 disables.
    serve_cache_entries: int = 4096
    # Warm extractor worker processes kept resident by the serving pool
    # (serving/extractor_pool.py).
    extractor_pool_size: int = 2
    # Seconds the SIGTERM drain waits for in-flight requests before
    # giving up (mirrors the trainer's preemption grace pattern).
    serve_drain_timeout_s: float = 30.0
    # -- serving resilience (serving/admission.py, serving/breaker.py,
    # serving/supervisor.py, serving/swap.py; README "Operating the
    # server") --
    # Default end-to-end deadline per request, in milliseconds. Clients
    # override per request via the `X-Deadline-Ms` header; both are
    # clamped by serve_deadline_max_ms. The deadline propagates through
    # the whole pipeline (extractor timeout, batcher coalescing, device
    # wait); expiry is an honest 504 that never occupies a device slot.
    # 0 = no default deadline (the max still applies when set).
    serve_deadline_ms: float = 2000.0
    # Hard ceiling on any request's deadline — a client cannot pin a
    # pipeline slot forever by asking for an hour.
    serve_deadline_max_ms: float = 30000.0
    # Admission bound: maximum requests admitted into the cache-miss
    # pipeline at once. Beyond it (or when the estimated queue wait
    # exceeds a request's remaining budget) requests are SHED with
    # 503 + Retry-After instead of queueing unboundedly
    # (serving_requests_shed_total{reason=...}).
    serve_queue_depth: int = 64
    # Weighted-fair multi-tenancy (serving/tenancy.py; README
    # "Multi-tenancy"): "name=weight,..." declares the tenants sharing
    # this server and their relative admission shares (the `X-Tenant`
    # request header names the tenant; absent = "default"; tenants not
    # listed here collapse into one "other" bucket). Each
    # recently-active tenant owns weight/sum(active weights) of
    # serve_queue_depth; an over-share tenant sheds as 503
    # shed_reason=tenant_quota while in-share tenants keep their full
    # deadline budget. Empty (the default) disables the tenancy layer
    # entirely — responses are byte-identical to a tenancy-free build.
    serve_tenants: str = ""
    # Admission-share weight for tenants NOT named in serve_tenants
    # (the "default" tenant and the collapsed "other" bucket).
    serve_tenant_default_weight: float = 1.0
    # Per-tenant rate quota (deterministic token bucket, qps): either
    # one bare number applied to every tenant, or "name=qps,..." per
    # tenant. 0 / unset = uncapped. An over-quota request sheds as
    # tenant_quota with Retry-After derived from that tenant's own
    # bucket refill time. Only read when serve_tenants is set.
    serve_tenant_qps: str = ""
    # Circuit breakers (extractor pool + device step): rolling failure
    # window length, the failure ratio that opens the breaker once
    # min_requests samples exist, and the open->half-open probe
    # cooldown. An open breaker fails requests fast (503); cache hits
    # still serve.
    serve_breaker_window_s: float = 10.0
    serve_breaker_failure_ratio: float = 0.5
    serve_breaker_min_requests: int = 4
    serve_breaker_cooldown_s: float = 5.0
    # Supervised multi-replica serving (`serve --replicas N`,
    # serving/supervisor.py): a parent supervisor forks N single-model
    # replicas sharing the listen port (SO_REUSEPORT; falls back to
    # per-replica ports behind the supervisor's round-robin proxy),
    # restarts crashed/hung replicas with exponential backoff, and
    # fans SIGTERM out as a coordinated drain.
    serve_replicas: int = 1
    # Restarts the supervisor grants EACH replica before escalating to
    # supervisor exit (a replica that cannot stay up is a deploy
    # problem, not a restart-loop problem).
    serve_max_restarts: int = 5
    # Seconds between serving heartbeat rewrites (--heartbeat_file).
    # The supervisor treats a heartbeat older than ~3 intervals as a
    # HUNG replica and restarts it.
    serve_heartbeat_interval_s: float = 5.0
    # -- serving telemetry (obs/reqtrace.py, obs/flight.py,
    # serving/telemetry.py; README "Telemetry") --
    # Honor `?debug=trace` on /predict//embed//neighbors: the response
    # gains a `trace` field with the request's full span tree. OFF by
    # default — the tree exposes internals (worker pids, batch
    # composition, cache behavior) that do not belong on a public
    # endpoint; enable on debug/staging replicas only.
    serve_debug_trace: bool = False
    # Directory for flight-recorder dumps (incident-triggered and
    # POST /admin/dump). None = next to --heartbeat_file when set,
    # else incident auto-dumps are disabled (/admin/dump still writes,
    # into the system temp dir).
    serve_flight_dir: Optional[str] = None
    # Terminal request records the flight recorder retains (the black
    # box ring; anomaly events ring separately at 256).
    serve_flight_records: int = 512
    # Flight dumps retained per dump directory: past the cap the
    # OLDEST flight-*.json files are deleted after each new dump, so a
    # long-running supervisor run dir cannot grow without bound.
    # 0 = unbounded.
    serve_flight_max_dumps: int = 64
    # Supervisor telemetry listener (merged GET /metrics + GET /fleet —
    # the documented scrape address under --replicas, fixing the
    # SO_REUSEPORT one-replica-scrape gap). None = public port + 1;
    # 0 = pick a free port (logged + in the supervisor heartbeat's
    # telemetry_port).
    serve_telemetry_port: Optional[int] = None
    # -- serving fleet (code2vec_tpu/serving/fleet; README "Fleet") --
    # Run the fleet control plane + router (`fleet` subcommand): N
    # host supervisors per model group behind one health-gated router,
    # telemetry-driven per-host replica scaling, canary-first
    # coordinated hot-swap.
    fleet: bool = False
    # Hosts launched per model group. The default LocalHostLauncher
    # runs them as local processes (dev/test/single-machine); remote
    # substrates plug in through fleet/control.py's HostLauncher seam.
    fleet_hosts: int = 2
    # Router public port. None = serve_port (the fleet takes over the
    # serving stack's public address); 0 picks a free port.
    fleet_port: Optional[int] = None
    # Multi-model fleet: comma list of name=artifact_dir groups, each
    # getting fleet_hosts hosts; the router keys on the X-Model
    # request header. Empty = one "default" group from --artifact.
    fleet_models: str = ""
    # Seconds between control-plane polls of each host's /fleet +
    # /metrics (also the scaling decision cadence).
    fleet_poll_interval_s: float = 1.0
    # Per-host replica-count bounds for telemetry-driven scaling (and
    # the sanity bounds for manual POST /admin/scale overrides).
    fleet_scale_min: int = 1
    fleet_scale_max: int = 4
    # Scale-up triggers, evaluated over the window since the previous
    # poll tick: shed rate above this fraction...
    fleet_scale_up_shed_rate: float = 0.05
    # ...or total-phase p95 above this many milliseconds (0 disables
    # the p95 trigger; shed rate alone then drives scale-up). Default
    # MEASURED, not guessed (the PR-13 "defaults off pending a
    # threshold" follow-on): `serving_bench.py p95` records the healthy
    # 4-client cache-off total-phase p95 (~48 ms on the dev harness)
    # and ships 10x rounded up — unambiguous sustained distress the
    # shed-rate trigger cannot see (slow-but-not-yet-shedding), still
    # a quarter of the default 2000 ms deadline so scale-up fires
    # before requests expire (experiments/results/serving_p95.json).
    fleet_scale_up_p95_ms: float = 500.0
    # Hysteresis: consecutive over-threshold ticks required to scale
    # up, consecutive zero-request ticks required to scale down, and a
    # cooldown after every action so a noisy signal cannot flap the
    # replica count.
    fleet_scale_up_ticks: int = 2
    fleet_scale_down_ticks: int = 10
    fleet_scale_cooldown_s: float = 15.0
    # Seconds the coordinated-swap driver waits for ONE host's
    # replicas to converge on the new fingerprint before declaring the
    # rollout failed (halt at the canary; rollback past it).
    fleet_swap_timeout_s: float = 120.0
    # Restarts the control plane grants each host before escalating to
    # fleet exit (the supervisor's deploy-problem philosophy, one
    # level up).
    fleet_max_host_restarts: int = 5
    # -- edge tier (code2vec_tpu/serving/fleet/edge.py; README
    # "Edge") --
    # Public router processes. 1 (default) = the classic embedded
    # router on the fleet port. N >= 2 = N stateless router AGENTS on
    # consecutive ports (fleet_port..fleet_port+N-1; 0 = all auto),
    # each polling the control plane's private control listener for
    # the shared fleet view — any router serves any request (put them
    # behind one DNS name / L4 VIP), and the control plane respawns a
    # dead one with the host backoff/escalation policy.
    fleet_routers: int = 1
    # Control-listener address (HOST:PORT) a router agent polls; set
    # by the control plane on the re-exec command line, not by
    # operators.
    fleet_control: str = ""
    # Consistent-hash cache affinity (--fleet_no_affinity to disable):
    # routers hash each request's normalized source onto a ring of the
    # fully-healthy hosts and try that host first, so repeat traffic
    # lands on the replica whose LRU cache already holds the entry;
    # unhealthy/draining hosts leave the ring and selection falls back
    # to weighted sampling. Response bytes are unaffected (the cache
    # keys on fingerprint + normalized source per host).
    fleet_cache_affinity: bool = True
    # Remote HostLauncher wrapper template (empty = local processes):
    # e.g. "ssh {address}" or "docker exec {address}" — {address} is
    # each host's address from fleet_addresses. Contract: the fleet
    # run dir on a shared filesystem (heartbeats readable) and
    # reported ports reachable at the host's address.
    fleet_launcher: str = ""
    # Comma list of addresses hosts are placed on (round-robin) and
    # reached at; empty = serve_host for every host.
    fleet_addresses: str = ""
    # -- telemetry history + SLO engine (obs/tsdb.py + obs/slo.py;
    # README "SLO & history") --
    # Window of poll-tick history the control plane keeps (memory +
    # on-disk segment ring under <run dir>/tsdb/), and the ring's
    # byte cap (oldest segments evicted first).
    fleet_tsdb_retention_s: float = 3600.0
    fleet_tsdb_max_mb: float = 64.0
    # Availability objective: target fraction of non-5xx/non-shed
    # requests (0 disables the objective).
    fleet_slo_availability: float = 0.999
    # Latency objective: target fraction of requests completing under
    # the threshold (either at 0 disables the objective).
    fleet_slo_latency_ms: float = 500.0
    fleet_slo_latency_target: float = 0.95
    # Error-budget period the slo_error_budget_remaining gauge is
    # computed over (default 30 days), and a uniform scale applied to
    # EVERY burn window — production keeps 1.0; tests/benches shrink
    # it so a page fires in seconds through the real window pairing.
    fleet_slo_period_s: float = 2592000.0
    fleet_slo_window_scale: float = 1.0
    # `fleet trace` collector inputs: the trace id to stitch, and
    # either a run dir to walk locally or --fleet_control to ask a
    # live control plane via GET /trace?id=.
    fleet_trace_id: str = ""
    fleet_trace_dir: str = ""
    # Rows per streamed target-table block in the blockwise top-k
    # prediction head (ops/topk.py): the eval/predict steps fold the
    # ~246K-name classifier through a running top-k merge + logsumexp
    # instead of materializing the (B, target_vocab) logit row (~1 GB
    # of HBM traffic per flagship eval batch, written once and read
    # twice). Indices/values are exactly the full path's (pinned in
    # tests/test_quant.py). Engages only when the target vocab exceeds
    # one block and the table is unsharded over `model` (tp == 1);
    # 0 forces the classic full-logits path. 16,384 since PR 40 (4,096
    # until then): a trip's merge costs several times its matmul, so
    # fewer, wider trips win (the head alone on a v5e, 64 rows: 2.13 ms
    # at 4,096, 0.87 at 16,384; 1,024 rows: 20.2 -> 9.6 ms; PERF.md
    # section 6), and 1,024 rows of live logits are 67 MB.
    topk_block_size: int = 16384

    # -- release artifacts (code2vec_tpu/release; no reference
    # equivalent — the reference's --release only strips optimizer
    # state from a checkpoint) --
    # `export` subcommand output: write a self-contained quantized
    # inference artifact (int8 tables + per-row scales, vocabs, AOT
    # serve lowerings) here. Requires --load.
    export_artifact_path: Optional[str] = None
    # `serve`/eval input: run from a release artifact instead of a
    # checkpoint (serving/server.py gets a release/runtime.py model).
    serve_artifact: Optional[str] = None
    # Quantize the three embedding tables to per-row symmetric int8 in
    # the exported artifact (ops/quant.py). False exports fp32 tables
    # (same layout, 4x the bytes) — the control arm of BENCH_QUANT.md.
    release_quantize: bool = True
    # Quantization scheme of the exported tables (release/artifact.py):
    # int8 (1 byte/weight, the default), fp8_e4m3 / fp8_e5m2 (1
    # byte/weight with a relative error profile), int4 (two weights per
    # byte — another ~2x below int8), or float32 (= --no_quantize).
    # Per-scheme accuracy deltas vs same-run fp32 in BENCH_QUANT.md.
    release_scheme: str = "int8"
    # Also AOT-export (jax.export) the bucketed serve functions into
    # the artifact, one per (serve_batch_size, context bucket) shape,
    # so a serving replica cold-starts from deserialized lowerings
    # instead of retracing each bucket. Artifacts embed the lowering
    # platform; a replica on a different backend falls back to jit.
    release_aot: bool = True
    # -- retrieval (code2vec_tpu/retrieval; no reference equivalent —
    # the reference only dumps code vectors as text via
    # --export_code_vectors) --
    # `embed` subcommand output: write the corpus's code vectors into a
    # sharded vector store here (retrieval/store.py). The corpus is
    # --test's packed .c2vb; the model is --load or --artifact.
    embed_out: Optional[str] = None
    # Vector-store payload dtype: float16 halves the store (and the
    # index's HBM footprint) at ~1e-3 cosine error; float32 is exact.
    embed_dtype: str = "float32"
    # Rows per committed store shard — the embed job's resume
    # granularity (a killed job re-embeds at most this many rows).
    embed_shard_rows: int = 65536
    # --export_code_vectors compat: write the reference's `.vectors`
    # text layout (one space-joined vector per line) instead of the
    # sharded store format.
    vectors_text: bool = False
    # `export-embeddings` subcommand output dir: token + target
    # embedding tables in word2vec text format (the reference's
    # --save_w2v/--save_t2v pair as one artifact).
    embeddings_out: Optional[str] = None
    # `index-build` subcommand input/output: the vector store to index
    # and the index artifact dir to write (retrieval/index.py).
    index_vectors: Optional[str] = None
    index_out: Optional[str] = None
    # IVF coarse-quantizer size; 0 = sqrt(rows) auto. Small corpora
    # (or nlist <= 1) fall back to the brute-force exact backend.
    index_nlist: int = 0
    # Inverted lists probed per query (recall/latency knob; clients
    # override per request via the JSON body's `nprobe`). The default
    # is recorded into the index artifact at build time.
    index_nprobe: int = 8
    # Jitted Lloyd iterations for the coarse quantizer.
    index_kmeans_iters: int = 10
    # Similarity metric baked into the index: cosine (vectors
    # normalized at build, distance = 1 - score) or raw dot.
    index_metric: str = "cosine"
    # `serve` input: mount a built index so the server answers
    # POST /neighbors (retrieval/api.py). The index's recorded
    # embedding fingerprint must match the serving model's.
    retrieval_index: Optional[str] = None
    # Default neighbors returned per method by /neighbors (JSON body
    # `k` overrides per request).
    retrieval_topk: int = 10
    # What a model hot-swap does when the new weights' fingerprint
    # diverges from the mounted index's: "refuse" rejects the swap
    # (the index is part of the serving contract), "detach" commits
    # the swap and detaches the index (reason in /healthz; /neighbors
    # answers 503 until a matching index is mounted). Either way,
    # neighbors are NEVER computed across embedding spaces.
    retrieval_swap_policy: str = "refuse"

    # -- continuous-training pipeline (code2vec_tpu/pipeline; README
    # "Continuous training"; no reference equivalent — the reference's
    # model is one-shot) --
    # Run the crash-safe pipeline supervisor (`pipeline` subcommand):
    # ingest delta -> fine-tune -> export -> shadow-eval -> canary
    # promote -> retrieval refresh, journaled per stage.
    pipeline: bool = False
    # `corpus` subcommand: manifest tooling for the sharded training
    # corpus (--train_corpus_manifest) — list shards, create a
    # manifest, append a delta shard, validate shard headers and vocab
    # fingerprints. Never builds a model.
    corpus: bool = False
    # Comma-separated .c2vb shard paths to build a new manifest from
    # (`corpus --corpus_create`). Shard order defines global row ids.
    corpus_create: Optional[str] = None
    # One .c2vb delta shard to append to the manifest (`corpus
    # --corpus_add`); refused on vocab-fingerprint mismatch.
    corpus_add: Optional[str] = None
    # Re-read every listed shard's header and meta and fail on any
    # drift (rows changed, mixed vocab); plain `corpus` only prints
    # the manifest.
    corpus_validate: bool = False
    # Pipeline state root: journaled manifest, per-stage work dirs,
    # candidate checkpoint/artifact. One dir = one run; a killed run
    # rerun with the SAME inputs resumes from the last committed stage.
    pipeline_dir: Optional[str] = None
    # New raw extractor output to ingest as a delta shard against the
    # FROZEN incumbent vocab (OOV rate exported through obs — the
    # "vocabulary aging out" signal).
    pipeline_raw: Optional[str] = None
    # The incumbent release artifact the fleet serves today: the
    # shadow-eval baseline and the implicit rollback identity.
    pipeline_incumbent: Optional[str] = None
    # Recorded live-traffic sample (what serving replicas write at
    # --serve_traffic_sample) replayed through incumbent AND candidate
    # at shadow-eval. None = gate on the accuracy harness alone.
    pipeline_traffic: Optional[str] = None
    # Max traffic lines replayed (deterministically sampled by seed,
    # so a rerun of a killed shadow-eval replays the same slice).
    pipeline_shadow_samples: int = 256
    # Epochs the fine-tune stage trains on the delta shard, resumed
    # from the latest committed checkpoint via the elastic-restore
    # path (any host count / mesh shape the child runs on).
    pipeline_finetune_epochs: int = 1
    # Quality-gate regression bars: largest tolerated drop (candidate
    # minus incumbent) per metric, and the smallest tolerated top-k
    # agreement over the replayed traffic. Any tripped bar REFUSES
    # promotion (terminal; incumbent keeps serving).
    pipeline_gate_top1_drop: float = 0.01
    pipeline_gate_topk_drop: float = 0.01
    pipeline_gate_f1_drop: float = 0.01
    pipeline_gate_min_agreement: float = 0.98
    # Fleet router admin address (host:port) the promote stage drives
    # the canary-first coordinated swap through. Empty = the pipeline
    # stops after shadow-eval with a gated candidate on disk.
    pipeline_fleet: str = ""
    # Fleet model group to promote into (the router's X-Model key).
    pipeline_model: str = "default"
    # Budget for one fleet rollout (promote or index remount) to reach
    # a terminal state before the stage fails.
    pipeline_promote_timeout_s: float = 600.0
    # After promotion: re-embed the delta shard with the candidate,
    # build a fresh ANN index behind its fingerprint, and remount it
    # fleet-wide through the reload fan-out (each replica mounts the
    # index atomically with its model flip; the refuse/detach policy
    # guards every transition).
    pipeline_refresh_retrieval: bool = False
    # -- live-traffic sampling (serving/traffic.py) --
    # Record every Nth cache-miss request's EXTRACTED lines into this
    # bounded ring file — the shadow-eval replay corpus. None = off.
    serve_traffic_sample_file: Optional[str] = None
    serve_traffic_sample_every: int = 10
    serve_traffic_sample_cap: int = 4096

    # Knob names the user set EXPLICITLY on the command line (filled by
    # cli.config_from_args). Lets a consumer distinguish "holds the
    # dataclass default because nobody asked" from "the operator typed
    # exactly the default value": ReleaseModel only adopts an artifact's
    # AOT-exported serve_batch_size when the flag was never given.
    explicit_knobs: Tuple[str, ...] = ()

    # Full-content sha256 of every checkpoint file (including the
    # multi-GB Orbax shards, chunked + hashed on a thread pool) recorded
    # into the manifest AFTER the atomic commit, so it stays off the
    # save critical path; resume verifies the hashes when present
    # (training/checkpoint.py). Default off: the manifest's
    # existence+size probe already rejects truncation, and Orbax
    # checksums its own payloads — this adds bit-rot/corruption
    # detection for long-lived artifacts.
    checkpoint_hash_content: bool = False
    # Random seed for params/dropout.
    seed: int = 42

    # -- filled at runtime (reference: config.py:130-132) --
    num_train_examples: int = 0
    num_test_examples: int = 0

    def __post_init__(self):
        # reference config.py:64-66: per-table sizes fall back to
        # DEFAULT_EMBEDDINGS_SIZE unless set explicitly.
        if self.token_embeddings_size is None:
            self.token_embeddings_size = self.default_embeddings_size
        if self.path_embeddings_size is None:
            self.path_embeddings_size = self.default_embeddings_size

    # ---------------------------------------------------------------- derived

    @property
    def context_vector_size(self) -> int:
        # concat of source-token, path and target-token embeddings
        # (reference: config.py:143-147).
        return self.path_embeddings_size + 2 * self.token_embeddings_size

    @property
    def code_vector_size(self) -> int:
        return self.context_vector_size

    @property
    def target_embeddings_size(self) -> int:
        return self.code_vector_size

    @property
    def is_training(self) -> bool:
        return bool(self.train_data_path_prefix)

    @property
    def is_loading(self) -> bool:
        return bool(self.model_load_path)

    @property
    def is_saving(self) -> bool:
        return bool(self.model_save_path)

    @property
    def is_testing(self) -> bool:
        return bool(self.test_data_path)

    @property
    def train_steps_per_epoch(self) -> int:
        # reference: config.py:165-167
        if not self.train_batch_size:
            return 0
        return math.ceil(self.num_train_examples / self.train_batch_size)

    @property
    def test_steps(self) -> int:
        if not self.test_batch_size:
            return 0
        return math.ceil(self.num_test_examples / self.test_batch_size)

    @property
    def train_data_path(self) -> Optional[str]:
        # reference: config.py:179-183 — `<prefix>.train.c2v`
        if not self.is_training:
            return None
        return f"{self.train_data_path_prefix}.train.c2v"

    @property
    def word_freq_dict_path(self) -> Optional[str]:
        # reference: config.py:185-189 — `<prefix>.dict.c2v`
        if not self.is_training:
            return None
        return f"{self.train_data_path_prefix}.dict.c2v"

    def data_path(self, is_evaluating: bool = False) -> Optional[str]:
        return self.test_data_path if is_evaluating else self.train_data_path

    def batch_size(self, is_evaluating: bool = False) -> int:
        return self.test_batch_size if is_evaluating else self.train_batch_size

    @staticmethod
    def get_vocabularies_path_from_model_path(model_file_path: str) -> str:
        # Our model artifacts are directories carrying their own
        # `dictionaries.bin`; the reference instead stores it as a sibling
        # of the checkpoint file (reference: config.py:191-194). Accept both
        # so reference-layout model dirs remain loadable.
        inside = os.path.join(model_file_path, "dictionaries.bin")
        if os.path.isfile(inside):
            return inside
        return os.path.join(os.path.dirname(model_file_path), "dictionaries.bin")

    @property
    def model_load_dir(self) -> str:
        return os.path.dirname(self.model_load_path or "")

    @property
    def tensorboard_dir(self) -> str:
        # reference: keras_model.py:158-163 roots the TensorBoard callback
        # next to the model artifacts.
        base = self.model_save_path or self.model_load_path or "code2vec"
        return base + "_tb"

    @property
    def mesh_size(self) -> int:
        return self.dp * self.tp * self.cp

    # ---------------------------------------------------------------- checks

    def verify(self) -> None:
        # reference: config.py:232-239, plus mesh-shape checks.
        if self.model_config and not os.path.isfile(self.model_config):
            raise ValueError(
                f"Model configuration `{self.model_config}` does not exist.")
        if self.serve_token_budget < 1:
            raise ValueError("serve_token_budget must be >= 1.")
        if (not self.is_training and not self.is_loading
                and not self.serve_artifact and not self.index_out
                and not self.corpus
                and not (self.model_config and self.is_saving)
                and not (self.fleet and self.fleet_models)
                and not (self.fleet and self.fleet_trace_id)):
            raise ValueError(
                "Must train or load a model (or serve a release "
                "artifact via --artifact; `index-build` and `corpus` "
                "alone need no model; `fleet` may carry its models in "
                "--fleet_models; `fleet --fleet_trace_id` only "
                "stitches trace files).")
        if self.is_loading and not os.path.isdir(self.model_load_dir):
            raise ValueError(
                f"Model load dir `{self.model_load_dir}` does not exist.")
        if self.dp < 1 or self.tp < 1 or self.cp < 1:
            raise ValueError("Mesh axis sizes dp/tp/cp must be >= 1.")
        if self.max_contexts % self.cp != 0:
            raise ValueError(
                f"max_contexts ({self.max_contexts}) must be divisible by the "
                f"context-parallel degree cp ({self.cp}).")
        if self.compute_dtype not in ("bfloat16", "float32"):
            raise ValueError("compute_dtype must be bfloat16 or float32.")
        if self.adam_mu_dtype not in ("bfloat16", "float32"):
            raise ValueError("adam_mu_dtype must be bfloat16 or float32.")
        if self.adam_nu_dtype not in ("bfloat16", "float32"):
            raise ValueError("adam_nu_dtype must be bfloat16 or float32.")
        if self.dropout_prng_impl not in ("rbg", "threefry2x32",
                                          "unsafe_rbg"):
            raise ValueError(
                "dropout_prng_impl must be rbg, threefry2x32 or unsafe_rbg.")
        if self.rss_limit_gb < 0:
            raise ValueError("rss_limit_gb must be >= 0 (0 disables).")
        if self.on_nonfinite_loss not in ("halt", "warn"):
            raise ValueError("on_nonfinite_loss must be halt or warn.")
        if self.extractor_timeout_s < 0:
            raise ValueError(
                "extractor_timeout_s must be >= 0 (0 disables).")
        if self.extractor_retries < 0:
            raise ValueError(
                "extractor_retries must be >= 0 (0 disables retries).")
        if self.save_barrier_timeout_s <= 0:
            raise ValueError(
                "save_barrier_timeout_s must be > 0 (a barrier that "
                "never times out turns a dead peer into a pod hang).")
        if not (0 <= self.metrics_port <= 65535):
            raise ValueError(
                "metrics_port must be in [0, 65535] (0 disables).")
        if self.preprocess_workers < 0:
            raise ValueError(
                "preprocess_workers must be >= 0 (0 = in-process serial).")
        if not (0 <= self.serve_port <= 65535):
            raise ValueError(
                "serve_port must be in [0, 65535] (0 picks a free port).")
        if self.serve_batch_size < 1:
            raise ValueError("serve_batch_size must be >= 1.")
        if self.serve_cache_entries < 0:
            raise ValueError(
                "serve_cache_entries must be >= 0 (0 disables the "
                "prediction cache).")
        if self.extractor_pool_size < 1:
            raise ValueError("extractor_pool_size must be >= 1.")
        try:
            from code2vec_tpu.serving.batcher import parse_buckets
            parse_buckets(self.serve_buckets, self.max_contexts, cp=self.cp)
        except ValueError:
            raise ValueError(
                f"serve_buckets must be a comma-separated list of ints "
                f"(got {self.serve_buckets!r}).")
        if self.serve_drain_timeout_s <= 0:
            raise ValueError(
                "serve_drain_timeout_s must be > 0 (a drain that never "
                "times out can outlive the SIGTERM grace window).")
        if self.serve_deadline_ms < 0:
            raise ValueError(
                "serve_deadline_ms must be >= 0 (0 = no default "
                "deadline).")
        if self.serve_deadline_max_ms < 0:
            raise ValueError(
                "serve_deadline_max_ms must be >= 0 (0 = no ceiling).")
        if (self.serve_deadline_ms > 0 and self.serve_deadline_max_ms > 0
                and self.serve_deadline_ms > self.serve_deadline_max_ms):
            raise ValueError(
                "serve_deadline_ms must not exceed serve_deadline_max_ms "
                "(the default deadline would be clamped below itself).")
        if self.serve_queue_depth < 1:
            raise ValueError(
                "serve_queue_depth must be >= 1 (the admission gate "
                "needs room for at least one request).")
        try:
            from code2vec_tpu.serving.tenancy import (
                parse_tenant_qps, parse_tenant_weights,
            )
            parse_tenant_weights(self.serve_tenants)
            parse_tenant_qps(self.serve_tenant_qps)
        except ValueError as e:
            # a typo'd tenant spec must fail at startup, not skew
            # production fairness silently
            raise ValueError(str(e))
        if self.serve_tenant_default_weight <= 0:
            raise ValueError(
                "serve_tenant_default_weight must be > 0 (it is the "
                "admission share of every unconfigured tenant).")
        if self.serve_breaker_window_s <= 0:
            raise ValueError("serve_breaker_window_s must be > 0.")
        if not (0 < self.serve_breaker_failure_ratio <= 1):
            raise ValueError(
                "serve_breaker_failure_ratio must be in (0, 1].")
        if self.serve_breaker_min_requests < 1:
            raise ValueError("serve_breaker_min_requests must be >= 1.")
        if self.serve_breaker_cooldown_s <= 0:
            raise ValueError(
                "serve_breaker_cooldown_s must be > 0 (an open breaker "
                "must eventually probe for recovery).")
        if self.serve_replicas < 1:
            raise ValueError("serve_replicas (--replicas) must be >= 1.")
        if self.serve_replicas > 1 and not self.serve:
            raise ValueError(
                "--replicas applies to the serve subcommand only "
                "(supervised multi-replica serving).")
        if self.serve_max_restarts < 0:
            raise ValueError(
                "serve_max_restarts must be >= 0 (0 = never restart, "
                "escalate on first replica death).")
        if self.serve_heartbeat_interval_s <= 0:
            raise ValueError("serve_heartbeat_interval_s must be > 0.")
        if self.serve_flight_records < 1:
            raise ValueError(
                "serve_flight_records must be >= 1 (the flight "
                "recorder ring needs at least one slot).")
        if self.serve_flight_max_dumps < 0:
            raise ValueError(
                "serve_flight_max_dumps must be >= 0 (0 = unbounded, "
                "no retention sweep).")
        if self.fleet and not self.serve:
            raise ValueError(
                "fleet knobs apply to the `fleet` subcommand (which "
                "implies serving).")
        if self.fleet_hosts < 1:
            raise ValueError("fleet_hosts must be >= 1.")
        if self.fleet_port is not None and not (
                0 <= self.fleet_port <= 65535):
            raise ValueError(
                "fleet_port must be in [0, 65535] (0 picks a free "
                "port; unset defaults to serve_port).")
        if self.fleet_models:
            try:
                from code2vec_tpu.serving.fleet.control import (
                    parse_fleet_models,
                )
                parse_fleet_models(self.fleet_models)
            except ValueError as e:
                raise ValueError(str(e))
        if self.fleet_poll_interval_s <= 0:
            raise ValueError("fleet_poll_interval must be > 0.")
        if self.fleet_scale_min < 1:
            raise ValueError("fleet_scale_min must be >= 1.")
        if self.fleet_scale_max < self.fleet_scale_min:
            raise ValueError(
                "fleet_scale_max must be >= fleet_scale_min.")
        if not (0 <= self.fleet_scale_up_shed_rate <= 1):
            raise ValueError(
                "fleet_scale_up_shed_rate must be in [0, 1].")
        if self.fleet_scale_up_p95_ms < 0:
            raise ValueError(
                "fleet_scale_up_p95_ms must be >= 0 (0 disables the "
                "p95 scale-up trigger).")
        if self.fleet_scale_up_ticks < 1 or self.fleet_scale_down_ticks < 1:
            raise ValueError(
                "fleet_scale_up_ticks and fleet_scale_down_ticks must "
                "be >= 1 (they are the hysteresis).")
        if self.fleet_scale_cooldown_s < 0:
            raise ValueError("fleet_scale_cooldown must be >= 0.")
        if self.fleet_swap_timeout_s <= 0:
            raise ValueError(
                "fleet_swap_timeout must be > 0 (a rollout that never "
                "times out wedges the swap driver on a dead host).")
        if self.fleet_max_host_restarts < 0:
            raise ValueError(
                "fleet_max_host_restarts must be >= 0 (0 = escalate "
                "on first host death).")
        if self.fleet_routers < 1:
            raise ValueError(
                "fleet_routers must be >= 1 (1 = the embedded router; "
                "N >= 2 = the edge router tier).")
        if self.fleet_control and (
                ":" not in self.fleet_control
                or not self.fleet_control.rsplit(":", 1)[1].isdigit()):
            raise ValueError(
                "fleet_control must be HOST:PORT (it is set by the "
                "control plane on router re-exec commands).")
        if self.fleet_tsdb_retention_s <= 0:
            raise ValueError(
                "fleet_tsdb_retention must be > 0 (the history window "
                "the SLO engine and /query read from).")
        if self.fleet_tsdb_max_mb <= 0:
            raise ValueError(
                "fleet_tsdb_max_mb must be > 0 (the on-disk segment "
                "ring's byte cap).")
        if not (0 <= self.fleet_slo_availability < 1):
            raise ValueError(
                "fleet_slo_availability must be in [0, 1) "
                "(0 disables the objective; 1 allows no errors ever "
                "and pages forever).")
        if not (0 <= self.fleet_slo_latency_target < 1):
            raise ValueError(
                "fleet_slo_latency_target must be in [0, 1) "
                "(0 disables the objective).")
        if self.fleet_slo_latency_ms < 0:
            raise ValueError("fleet_slo_latency_ms must be >= 0.")
        if self.fleet_slo_period_s <= 0:
            raise ValueError("fleet_slo_period must be > 0.")
        if self.fleet_slo_window_scale <= 0:
            raise ValueError(
                "fleet_slo_window_scale must be > 0 (1.0 = the "
                "standard SRE windows; smaller = faster drills).")
        if self.fleet_launcher and "{address}" in self.fleet_launcher \
                and not self.fleet_addresses:
            raise ValueError(
                "fleet_launcher template uses {address} but "
                "fleet_addresses is empty — list the machines hosts "
                "should land on (comma-separated).")
        if self.serve_telemetry_port is not None and not (
                0 <= self.serve_telemetry_port <= 65535):
            raise ValueError(
                "serve_telemetry_port must be in [0, 65535] "
                "(0 picks a free port; unset defaults to the public "
                "port + 1).")
        if self.topk_block_size < 0:
            raise ValueError(
                "topk_block_size must be >= 0 (0 forces the full-logits "
                "top-k path).")
        if self.pipeline:
            if not self.pipeline_dir:
                raise ValueError(
                    "pipeline requires --pipeline_dir DIR (the "
                    "journaled state root a killed run resumes from).")
            if self.serve or self.predict or self.is_training:
                raise ValueError(
                    "the `pipeline` subcommand is a standalone "
                    "supervisor: it re-execs train/export/embed "
                    "children itself and cannot be combined with "
                    "--serve/--predict/--data.")
            if (self.export_artifact_path or self.embed_out
                    or self.index_out or self.embeddings_out
                    or self.fleet):
                raise ValueError(
                    "pipeline cannot be combined with the one-shot "
                    "export/embed/index-build/export-embeddings jobs "
                    "or `fleet`: it drives those itself as stages.")
            if not self.is_loading:
                raise ValueError(
                    "pipeline requires --load CKPT: the incumbent "
                    "checkpoint is the fine-tune starting point and "
                    "the frozen-vocab source.")
            if not self.pipeline_raw:
                raise ValueError(
                    "pipeline requires --pipeline_raw FILE (the new "
                    "raw extractor output to ingest as a delta "
                    "shard).")
            if not self.pipeline_incumbent:
                raise ValueError(
                    "pipeline requires --pipeline_incumbent DIR (the "
                    "release artifact the fleet serves today — "
                    "shadow-eval's baseline).")
            if not self.is_testing:
                raise ValueError(
                    "pipeline requires --test FILE: the accuracy "
                    "harness shadow-eval scores both models on.")
            if self.serve_artifact:
                raise ValueError(
                    "pipeline takes the incumbent artifact via "
                    "--pipeline_incumbent, not --artifact (which "
                    "conflicts with the --load'ed checkpoint).")
        if self.pipeline_shadow_samples < 0:
            raise ValueError(
                "pipeline_shadow_samples must be >= 0 (0 = gate on "
                "the accuracy harness alone).")
        if self.pipeline_finetune_epochs < 1:
            raise ValueError("pipeline_finetune_epochs must be >= 1.")
        for bar in ("pipeline_gate_top1_drop", "pipeline_gate_topk_drop",
                    "pipeline_gate_f1_drop"):
            if getattr(self, bar) < 0:
                raise ValueError(f"{bar} must be >= 0 (the largest "
                                 f"tolerated drop).")
        if not (0 <= self.pipeline_gate_min_agreement <= 1):
            raise ValueError(
                "pipeline_gate_min_agreement must be in [0, 1].")
        if self.pipeline_promote_timeout_s <= 0:
            raise ValueError(
                "pipeline_promote_timeout must be > 0 (a rollout poll "
                "that never times out wedges the pipeline on a dead "
                "fleet).")
        if self.serve_traffic_sample_file and not self.serve:
            raise ValueError(
                "--serve_traffic_sample applies to the serve "
                "subcommand (it records the serving extract path).")
        if self.serve_traffic_sample_every < 1:
            raise ValueError(
                "serve_traffic_sample_every must be >= 1 (1 = sample "
                "every request).")
        if self.serve_traffic_sample_cap < 1:
            raise ValueError(
                "serve_traffic_sample_cap must be >= 1.")
        if self.release_scheme not in ("int8", "fp8_e4m3", "fp8_e5m2",
                                       "int4", "float32"):
            raise ValueError(
                "release_scheme must be one of int8, fp8_e4m3, "
                "fp8_e5m2, int4, float32.")
        if self.train_corpus_manifest and not self.use_packed_data:
            raise ValueError(
                "--train_corpus_manifest requires packed data: the "
                "manifest lists .c2vb shards (drop --no_packed_data).")
        if self.export_artifact_path and not self.is_loading:
            raise ValueError(
                "export (--artifact_out) requires --load: the artifact "
                "is built from a trained checkpoint.")
        if self.export_artifact_path and self.is_training:
            raise ValueError(
                "export (--artifact_out) cannot be combined with training "
                "(--data): main() exports the --load'ed checkpoint and "
                "exits, so the training run would be silently skipped. "
                "Train first, then `export --load CKPT --artifact_out "
                "DIR`.")
        if self.export_artifact_path and (self.serve or self.predict
                                          or self.is_testing):
            raise ValueError(
                "export (--artifact_out) is a one-shot job and cannot be "
                "combined with serve/--predict/--test in the same run; "
                "run those against the exported artifact (--artifact) or "
                "the checkpoint (--load) separately.")
        if self.serve_artifact and self.is_loading:
            raise ValueError(
                "--artifact and --load are mutually exclusive: a release "
                "artifact carries its own tables and vocabularies.")
        if self.serve_artifact and (self.save_w2v or self.save_t2v):
            raise ValueError(
                "--artifact cannot be combined with --save_w2v/--save_t2v: "
                "the vector writers read the fp32 checkpoint tables and "
                "the artifact branch in main() would silently skip them; "
                "run them against --load.")
        if self.serve_artifact and self.is_training:
            raise ValueError(
                "--artifact is inference-only (serve/--predict/--test) "
                "and cannot be combined with training (--data): a "
                "release artifact has no optimizer state to train.")
        if self.embed_dtype not in ("float32", "float16"):
            raise ValueError("embed_dtype must be float32 or float16.")
        if self.embed_shard_rows < 1:
            raise ValueError(
                "embed_shard_rows must be >= 1 (it is the embed job's "
                "resume granularity).")
        if self.embed_out and not self.is_testing:
            raise ValueError(
                "embed (--embed_out) needs a corpus: pass --test FILE "
                "(its packed .c2vb is the embed input).")
        if self.embed_out and not (self.is_loading or self.serve_artifact):
            raise ValueError(
                "embed (--embed_out) needs a model: --load CKPT or "
                "--artifact DIR (an untrained model's vectors index "
                "noise).")
        if self.embed_out and self.is_training:
            raise ValueError(
                "embed (--embed_out) is a one-shot job and cannot be "
                "combined with training (--data); train first, then "
                "embed the corpus.")
        if self.embed_out and (self.serve or self.predict):
            raise ValueError(
                "embed (--embed_out) is a one-shot job and cannot be "
                "combined with serve/--predict: main() runs the embed "
                "job and exits, so the server/REPL would be silently "
                "skipped. Run them as separate invocations.")
        if self.index_out and (self.is_training or self.serve
                               or self.predict or self.is_testing
                               or self.embed_out or self.embeddings_out):
            raise ValueError(
                "index-build (--index_out) is a standalone job and "
                "cannot be combined with training/serve/--predict/"
                "--test/--embed_out/--embeddings_out: main() builds "
                "the index and exits, silently skipping the rest. Run "
                "them as separate invocations.")
        if self.embeddings_out and (self.is_training or self.serve
                                    or self.predict or self.is_testing
                                    or self.embed_out):
            raise ValueError(
                "export-embeddings (--embeddings_out) is a one-shot "
                "job and cannot be combined with training/serve/"
                "--predict/--test/--embed_out: main() writes the "
                "tables and exits, silently skipping the rest. Run "
                "them as separate invocations.")
        if self.index_out and not self.index_vectors:
            raise ValueError(
                "index-build (--index_out) requires --vectors DIR (the "
                "store the `embed` subcommand wrote).")
        if self.index_vectors and not self.index_out:
            raise ValueError(
                "--vectors is only consumed by index-build; pass "
                "--index_out DIR for the artifact to write.")
        if self.index_nlist < 0:
            raise ValueError(
                "index_nlist must be >= 0 (0 = sqrt(rows) auto).")
        if self.index_nprobe < 1:
            raise ValueError("index_nprobe must be >= 1.")
        if self.index_kmeans_iters < 1:
            raise ValueError("index_kmeans_iters must be >= 1.")
        if self.index_metric not in ("cosine", "dot"):
            raise ValueError("index_metric must be cosine or dot.")
        if self.retrieval_index and not self.serve:
            raise ValueError(
                "--retrieval_index applies to the serve subcommand "
                "only (it mounts the /neighbors index).")
        if self.retrieval_topk < 1:
            raise ValueError("retrieval_topk must be >= 1.")
        if self.retrieval_swap_policy not in ("refuse", "detach"):
            raise ValueError(
                "retrieval_swap_policy must be refuse or detach.")
        if self.embeddings_out and not self.is_loading:
            raise ValueError(
                "export-embeddings (--embeddings_out) requires --load: "
                "the tables come from a trained checkpoint.")
        if self.embeddings_out and self.serve_artifact:
            raise ValueError(
                "export-embeddings (--embeddings_out) reads the fp32 "
                "checkpoint tables; a release artifact's are quantized "
                "— run it against --load.")

    # ---------------------------------------------------------------- logging

    def get_logger(self) -> logging.Logger:
        logger = logging.getLogger(_LOGGER_NAME)
        if not logger.handlers:
            logger.setLevel(logging.INFO)
            logger.propagate = False
            formatter = logging.Formatter("%(asctime)s %(levelname)-8s %(message)s")
            if self.verbose_mode >= 1:
                ch = logging.StreamHandler(sys.stdout)
                ch.setFormatter(formatter)
                logger.addHandler(ch)
            if self.logs_path:
                fh = logging.FileHandler(self.logs_path)
                fh.setFormatter(formatter)
                logger.addHandler(fh)
        return logger

    def log(self, msg: str) -> None:
        self.get_logger().info(msg)

    def items(self):
        return dataclasses.asdict(self).items()
