"""CLI entry point, flag-compatible with the reference
(reference: config.py:10-44 for the flags, code2vec.py:16-37 for the
dispatch), plus TPU mesh/precision knobs."""

from __future__ import annotations

import os
import sys
from argparse import ArgumentParser

from code2vec_tpu.config import Config
from code2vec_tpu.vocab import VocabType


def arguments_parser() -> ArgumentParser:
    parser = ArgumentParser(prog="code2vec_tpu")
    # reference flags (config.py:10-44)
    parser.add_argument("-d", "--data", dest="data_path",
                        help="path prefix to preprocessed dataset", required=False)
    parser.add_argument("-te", "--test", dest="test_path", metavar="FILE",
                        required=False, default="",
                        help="path to test/validation .c2v file")
    parser.add_argument("-s", "--save", dest="save_path", metavar="FILE",
                        required=False, help="path to save the model")
    parser.add_argument("-l", "--load", dest="load_path", metavar="FILE",
                        required=False, help="path to load the model from")
    parser.add_argument("--save_w2v", dest="save_w2v", metavar="FILE",
                        required=False,
                        help="save token embeddings in word2vec format")
    parser.add_argument("--save_t2v", dest="save_t2v", metavar="FILE",
                        required=False,
                        help="save target embeddings in word2vec format")
    parser.add_argument("--export_code_vectors", action="store_true",
                        help="export code vectors for the given examples")
    parser.add_argument("--release", action="store_true",
                        help="release the loaded model (strip optimizer "
                             "state for a smaller artifact)")
    parser.add_argument("--predict", action="store_true",
                        help="run the interactive prediction shell")
    parser.add_argument("--serve", action="store_true",
                        help="run the batched prediction HTTP server "
                             "(POST /predict, POST /embed, GET /healthz, "
                             "GET /metrics) on the loaded model; also "
                             "reachable as the `serve` subcommand "
                             "(`code2vec_tpu serve --load ...`). "
                             "SIGTERM drains gracefully")
    parser.add_argument("--serve_port", type=int, default=None,
                        metavar="PORT",
                        help="HTTP port for --serve (default: config.py's "
                             "8800; 0 picks a free port)")
    parser.add_argument("--serve_host", default=None, metavar="HOST",
                        help="HTTP bind address for --serve (default "
                             "127.0.0.1; put a proxy in front for "
                             "external exposure)")
    parser.add_argument("--serve_batch_size", type=int, default=None,
                        metavar="ROWS",
                        help="rows per coalesced serving device batch "
                             "(also the padded row count of every "
                             "compiled predict shape; default 64)")
    parser.add_argument("--serve_buckets", default=None, metavar="LIST",
                        help="comma-separated padded-context-count "
                             "buckets for the predict path (default "
                             "'32,64,128'; max_contexts is always "
                             "appended) — bounds the number of pjit "
                             "compilations serving can trigger")
    parser.add_argument("--model_config", default=None, metavar="FILE",
                        help="a model-configuration file (JSON, the "
                             "published config.json keys plus the share "
                             "held here) naming a model of models/ other "
                             "than code2vec, chosen by the file's own "
                             "model_type: the hybrid state-space / "
                             "latent-expert language model "
                             "(models/hybrid_lm.py), the latent-"
                             "attention / gated-expert one "
                             "(models/latent_moe_lm.py) or the grouped-"
                             "query / selected-key / softmax-expert one "
                             "(models/sparse_gqa_moe_lm.py), served for "
                             "scoring on POST /score")
    parser.add_argument("--serve_token_budget", type=int, default=None,
                        metavar="N",
                        help="most tokens (rows x padded length) in one "
                             "scoring step of a --model_config model, "
                             "and so its longest request (default 8192)")
    parser.add_argument("--serve_cache_entries", type=int, default=None,
                        metavar="N",
                        help="LRU prediction-cache capacity keyed by "
                             "normalized method-body hash (default "
                             "4096; 0 disables)")
    parser.add_argument("--extractor_pool_size", type=int, default=None,
                        metavar="N",
                        help="warm extractor worker processes kept "
                             "resident by the serving pool (default 2)")
    parser.add_argument("--serve_drain_timeout_s", type=float,
                        default=None, metavar="SECONDS",
                        help="SIGTERM grace: seconds the drain waits "
                             "for in-flight requests (default 30)")
    parser.add_argument("--serve_deadline_ms", type=float, default=None,
                        metavar="MS",
                        help="default end-to-end deadline per serving "
                             "request (default 2000; clients override "
                             "via the X-Deadline-Ms header; 0 = no "
                             "default deadline). Expiry mid-pipeline "
                             "is an honest 504")
    parser.add_argument("--serve_deadline_max_ms", type=float,
                        default=None, metavar="MS",
                        help="hard ceiling on any request deadline, "
                             "header-supplied included (default 30000; "
                             "0 = no ceiling)")
    parser.add_argument("--serve_queue_depth", type=int, default=None,
                        metavar="N",
                        help="admission bound: max requests in the "
                             "cache-miss pipeline before excess load "
                             "is shed with 503 + Retry-After "
                             "(default 64)")
    parser.add_argument("--serve_tenants", type=str, default=None,
                        metavar="NAME=W,...",
                        help="named tenants and their admission "
                             "weights (e.g. acme=4,dev=1; bare name = "
                             "weight 1). Unset = tenancy off: serving "
                             "behavior is byte-identical to a build "
                             "without the feature")
    parser.add_argument("--serve_tenant_default_weight", type=float,
                        default=None, metavar="W",
                        help="admission weight for tenants not named "
                             "in --serve_tenants, including the "
                             "implicit 'default' tenant (default 1.0)")
    parser.add_argument("--serve_tenant_qps", type=str, default=None,
                        metavar="NAME=QPS,...",
                        help="per-tenant token-bucket rate quotas "
                             "(e.g. acme=50,dev=5, or a bare number "
                             "applied to every tenant); 0 = uncapped "
                             "(the default). Over-quota requests are "
                             "shed 503 shed_reason=tenant_quota with "
                             "Retry-After from the bucket refill")
    parser.add_argument("--serve_breaker_window",
                        dest="serve_breaker_window_s", type=float,
                        default=None, metavar="SECONDS",
                        help="circuit-breaker rolling failure window "
                             "(default 10)")
    parser.add_argument("--serve_breaker_failure_ratio", type=float,
                        default=None, metavar="RATIO",
                        help="failure ratio over the window that opens "
                             "a breaker (default 0.5)")
    parser.add_argument("--serve_breaker_min_requests", type=int,
                        default=None, metavar="N",
                        help="minimum samples in the window before a "
                             "breaker can open (default 4)")
    parser.add_argument("--serve_breaker_cooldown",
                        dest="serve_breaker_cooldown_s", type=float,
                        default=None, metavar="SECONDS",
                        help="seconds an open breaker waits before the "
                             "half-open recovery probe (default 5)")
    parser.add_argument("--replicas", dest="serve_replicas", type=int,
                        default=None, metavar="N",
                        help="supervised multi-replica serving: fork N "
                             "single-model replicas sharing the listen "
                             "port (SO_REUSEPORT, else a supervisor "
                             "round-robin proxy), restart crashed/hung "
                             "ones with backoff, drain all on SIGTERM "
                             "(default 1 = no supervisor)")
    parser.add_argument("--serve_max_restarts", type=int, default=None,
                        metavar="N",
                        help="restarts the supervisor grants each "
                             "replica before escalating to supervisor "
                             "exit (default 5)")
    parser.add_argument("--serve_heartbeat_interval",
                        dest="serve_heartbeat_interval_s", type=float,
                        default=None, metavar="SECONDS",
                        help="seconds between serving heartbeat "
                             "rewrites; the supervisor restarts a "
                             "replica whose heartbeat goes ~3 "
                             "intervals stale (default 5)")
    parser.add_argument("--serve_debug_trace", action="store_true",
                        default=None,
                        help="honor ?debug=trace on serving endpoints: "
                             "the JSON response gains a `trace` field "
                             "with the request's span tree. OFF by "
                             "default (exposes worker pids / batch "
                             "composition; debug replicas only — "
                             "README 'Telemetry')")
    parser.add_argument("--serve_flight_dir", metavar="DIR",
                        help="directory for flight-recorder dumps "
                             "(incident-triggered + POST /admin/dump); "
                             "default: next to --heartbeat_file")
    parser.add_argument("--serve_flight_records", type=int, default=None,
                        metavar="N",
                        help="terminal request records the incident "
                             "flight recorder retains (default 512)")
    parser.add_argument("--serve_flight_max_dumps", type=int,
                        default=None, metavar="N",
                        help="flight dumps retained per dump dir: past "
                             "the cap the oldest flight-*.json files "
                             "are deleted after each new dump "
                             "(default 64; 0 = unbounded)")
    parser.add_argument("--serve_telemetry_port", type=int, default=None,
                        metavar="PORT",
                        help="supervisor fleet-telemetry listener "
                             "(merged GET /metrics + GET /fleet under "
                             "--replicas); default: public port + 1, "
                             "0 picks a free port")
    # -- cross-host serving fleet (README "Fleet") --
    parser.add_argument("--fleet_hosts", type=int, default=None,
                        metavar="N",
                        help="`fleet` subcommand: host supervisors "
                             "launched per model group (default 2); "
                             "each host is a full `serve --replicas N` "
                             "supervisor behind the fleet router")
    parser.add_argument("--fleet_port", type=int, default=None,
                        metavar="PORT",
                        help="fleet router public port (default: "
                             "--serve_port; 0 picks a free port)")
    parser.add_argument("--fleet_models", default=None, metavar="LIST",
                        help="multi-model fleet: comma list of "
                             "name=artifact_dir groups, each getting "
                             "--fleet_hosts hosts; the router keys on "
                             "the X-Model request header (empty = one "
                             "'default' group from --artifact)")
    parser.add_argument("--fleet_poll_interval",
                        dest="fleet_poll_interval_s", type=float,
                        default=None, metavar="SECONDS",
                        help="control-plane poll + scaling-decision "
                             "cadence (default 1)")
    parser.add_argument("--fleet_scale_min", type=int, default=None,
                        metavar="N",
                        help="per-host replica floor for "
                             "telemetry-driven scaling (default 1)")
    parser.add_argument("--fleet_scale_max", type=int, default=None,
                        metavar="N",
                        help="per-host replica ceiling for "
                             "telemetry-driven scaling (default 4)")
    parser.add_argument("--fleet_scale_up_shed_rate", type=float,
                        default=None, metavar="RATIO",
                        help="scale a host up when its window shed "
                             "rate exceeds this fraction (default "
                             "0.05)")
    parser.add_argument("--fleet_scale_up_p95_ms", type=float,
                        default=None, metavar="MS",
                        help="scale a host up when its window "
                             "total-phase p95 exceeds this many ms "
                             "(default 500 = 10x the measured healthy "
                             "p95, serving_bench.py p95 mode; 0 "
                             "disables the trigger)")
    parser.add_argument("--fleet_scale_up_ticks", type=int,
                        default=None, metavar="N",
                        help="consecutive over-threshold ticks before "
                             "a scale-up (hysteresis; default 2)")
    parser.add_argument("--fleet_scale_down_ticks", type=int,
                        default=None, metavar="N",
                        help="consecutive zero-request ticks before a "
                             "scale-down (hysteresis; default 10)")
    parser.add_argument("--fleet_scale_cooldown",
                        dest="fleet_scale_cooldown_s", type=float,
                        default=None, metavar="SECONDS",
                        help="cooldown after every scaling action "
                             "(default 15)")
    parser.add_argument("--fleet_swap_timeout",
                        dest="fleet_swap_timeout_s", type=float,
                        default=None, metavar="SECONDS",
                        help="per-host convergence budget of the "
                             "canary-first coordinated hot-swap "
                             "(default 120)")
    parser.add_argument("--fleet_max_host_restarts", type=int,
                        default=None, metavar="N",
                        help="restarts the control plane grants each "
                             "host before escalating to fleet exit "
                             "(default 5)")
    parser.add_argument("--fleet_routers", type=int, default=None,
                        metavar="N",
                        help="public edge router processes (README "
                             "'Edge'): 1 (default) = the embedded "
                             "router; N >= 2 spawns N stateless "
                             "router agents on consecutive ports "
                             "(--fleet_port..+N-1) sharing the fleet "
                             "view, supervised with the host "
                             "backoff/escalation policy")
    parser.add_argument("--fleet_control", default=None,
                        metavar="HOST:PORT",
                        help="control-listener address a router agent "
                             "polls for the shared fleet view "
                             "(set by the control plane on router "
                             "re-exec commands, not by operators)")
    parser.add_argument("--fleet_no_affinity",
                        action="store_true", default=None,
                        help="disable consistent-hash cache affinity "
                             "(routers then always weighted-sample; "
                             "fleet-level cache hit rate decays "
                             "as 1/N — see BENCH_SERVING.md)")
    parser.add_argument("--fleet_launcher", default=None,
                        metavar="TEMPLATE",
                        help="remote HostLauncher wrapper template, "
                             "e.g. 'ssh {address}' or 'docker exec "
                             "{address}' (empty = local processes); "
                             "needs the fleet run dir on a shared "
                             "filesystem and reachable host ports")
    parser.add_argument("--fleet_addresses", default=None,
                        metavar="LIST",
                        help="comma list of addresses hosts are "
                             "placed on round-robin and reached at "
                             "(default: --serve_host for every host)")
    parser.add_argument("--fleet_tsdb_retention",
                        dest="fleet_tsdb_retention_s", type=float,
                        default=None, metavar="SECONDS",
                        help="telemetry-history window the control "
                             "plane keeps (obs/tsdb.py segment ring "
                             "under the run dir; default 3600)")
    parser.add_argument("--fleet_tsdb_max_mb", type=float,
                        default=None, metavar="MB",
                        help="byte cap on the on-disk history ring "
                             "(oldest segments evicted first; "
                             "default 64)")
    parser.add_argument("--fleet_slo_availability", type=float,
                        default=None, metavar="RATIO",
                        help="availability SLO target: fraction of "
                             "non-5xx/non-shed requests (default "
                             "0.999; 0 disables the objective)")
    parser.add_argument("--fleet_slo_latency_ms", type=float,
                        default=None, metavar="MS",
                        help="latency SLO threshold: requests "
                             "completing under this many ms count as "
                             "good (default 500; 0 disables)")
    parser.add_argument("--fleet_slo_latency_target", type=float,
                        default=None, metavar="RATIO",
                        help="latency SLO target: fraction of "
                             "requests that must beat the threshold "
                             "(default 0.95; 0 disables)")
    parser.add_argument("--fleet_slo_period",
                        dest="fleet_slo_period_s", type=float,
                        default=None, metavar="SECONDS",
                        help="error-budget period for "
                             "slo_error_budget_remaining (default "
                             "2592000 = 30 days)")
    parser.add_argument("--fleet_slo_window_scale", type=float,
                        default=None, metavar="FACTOR",
                        help="uniform scale on every burn-rate "
                             "window (default 1.0 = the standard SRE "
                             "5m/1h + 30m/6h pairs; shrink for "
                             "drills so a page fires in seconds)")
    parser.add_argument("--fleet_trace_id", default=None,
                        metavar="HEX32",
                        help="`fleet trace` collector: stitch this "
                             "trace id's spans from every process's "
                             "trace files into one Chrome trace on "
                             "stdout (use with --fleet_trace_dir or "
                             "--fleet_control)")
    parser.add_argument("--fleet_trace_dir", default=None,
                        metavar="DIR",
                        help="fleet run dir to walk for *.trace.json "
                             "span files when stitching locally "
                             "(default: ask the live control plane "
                             "at --fleet_control via GET /trace)")
    parser.add_argument("--artifact", dest="serve_artifact", metavar="DIR",
                        help="serve/evaluate from a release artifact "
                             "(produced by the `export` subcommand) "
                             "instead of --load: int8 tables with fused "
                             "dequant, blockwise top-k, AOT cold-start")
    parser.add_argument("--artifact_out", dest="export_artifact_path",
                        metavar="DIR",
                        help="write a release artifact of the --load'ed "
                             "model here (the `export` subcommand body): "
                             "quantized tables + vocabularies + AOT "
                             "serve lowerings, see README 'Release "
                             "artifacts'")
    parser.add_argument("--no_quantize", action="store_true",
                        help="export fp32 tables instead of per-row "
                             "symmetric int8 (the artifact stays "
                             "self-contained, just 4x the bytes; the "
                             "control arm of BENCH_QUANT.md)")
    parser.add_argument("--release_scheme",
                        choices=["int8", "fp8_e4m3", "fp8_e5m2", "int4",
                                 "float32"],
                        default=None,
                        help="quantization scheme of the exported "
                             "tables (default int8; fp8 keeps 1 "
                             "byte/weight with a relative error "
                             "profile, int4 packs two weights per byte "
                             "for another ~2x — per-scheme accuracy "
                             "deltas in BENCH_QUANT.md)")
    parser.add_argument("--no_aot", action="store_true",
                        help="skip the jax.export AOT lowerings in the "
                             "exported artifact (consumers then always "
                             "trace+compile at cold start)")
    # -- retrieval stack (README "Retrieval") --
    parser.add_argument("--embed_out", dest="embed_out", metavar="DIR",
                        help="batch embedding job (the `embed` "
                             "subcommand body): run the --test corpus's "
                             "packed .c2vb through the eval pipeline at "
                             "device speed and write a sharded vector "
                             "store here (resumable per shard; model "
                             "from --load or --artifact)")
    parser.add_argument("--embed_dtype", choices=["float32", "float16"],
                        default=None,
                        help="vector-store payload dtype (default "
                             "float32; float16 halves the store)")
    parser.add_argument("--embed_shard_rows", type=int, default=None,
                        metavar="N",
                        help="rows per committed vector-store shard — "
                             "the embed job's resume granularity "
                             "(default 65536)")
    parser.add_argument("--vectors_text", action="store_true",
                        help="--export_code_vectors compat: write the "
                             "reference's `.vectors` text layout "
                             "instead of the sharded store format")
    parser.add_argument("--embeddings_out", dest="embeddings_out",
                        metavar="DIR",
                        help="dump the token + target embedding tables "
                             "in word2vec text format here (the "
                             "`export-embeddings` subcommand body; the "
                             "reference's --save_w2v/--save_t2v pair)")
    parser.add_argument("--vectors", dest="index_vectors", metavar="DIR",
                        help="index-build input: the vector store the "
                             "`embed` subcommand wrote")
    parser.add_argument("--index_out", dest="index_out", metavar="DIR",
                        help="index-build output: write the ANN index "
                             "artifact here (IVF-flat, or brute-force "
                             "on small corpora)")
    parser.add_argument("--nlist", dest="index_nlist", type=int,
                        default=None, metavar="N",
                        help="IVF coarse-quantizer size (default 0 = "
                             "sqrt(rows) auto)")
    parser.add_argument("--nprobe", dest="index_nprobe", type=int,
                        default=None, metavar="N",
                        help="inverted lists probed per query — the "
                             "recall/latency knob (default 8; baked "
                             "into the index as its default, clients "
                             "override per request)")
    parser.add_argument("--kmeans_iters", dest="index_kmeans_iters",
                        type=int, default=None, metavar="N",
                        help="jitted Lloyd iterations for the coarse "
                             "quantizer (default 10)")
    parser.add_argument("--index_metric", dest="index_metric",
                        choices=["cosine", "dot"], default=None,
                        help="similarity metric baked into the index "
                             "(default cosine)")
    parser.add_argument("--retrieval_index", dest="retrieval_index",
                        metavar="DIR",
                        help="serve: mount this index so the server "
                             "answers POST /neighbors (snippet -> "
                             "embed -> ANN search); the index's "
                             "embedding fingerprint must match the "
                             "serving model's")
    parser.add_argument("--retrieval_topk", dest="retrieval_topk",
                        type=int, default=None, metavar="K",
                        help="default neighbors per method from "
                             "/neighbors (default 10; JSON body `k` "
                             "overrides)")
    parser.add_argument("--retrieval_swap_policy",
                        choices=["refuse", "detach"], default=None,
                        help="hot-swap vs mounted index on fingerprint "
                             "mismatch: refuse the swap (default) or "
                             "commit it and detach the index "
                             "(/neighbors then answers 503)")
    # -- continuous-training pipeline (README "Continuous training") --
    parser.add_argument("--pipeline_dir", metavar="DIR",
                        help="`pipeline` subcommand state root: the "
                             "journaled pipeline manifest, per-stage "
                             "work dirs and the candidate artifacts "
                             "live here; a rerun of a killed pipeline "
                             "resumes from the last committed stage")
    parser.add_argument("--pipeline_raw", metavar="FILE",
                        help="new raw extractor output to ingest as a "
                             "delta shard against the frozen incumbent "
                             "vocab (OOV rate exported through obs)")
    parser.add_argument("--pipeline_incumbent", metavar="DIR",
                        help="the incumbent RELEASE ARTIFACT the fleet "
                             "serves today — shadow-eval's baseline "
                             "and the rollback identity")
    parser.add_argument("--pipeline_traffic", metavar="FILE",
                        help="recorded live-traffic sample to replay "
                             "through incumbent and candidate at "
                             "shadow-eval (what --serve_traffic_sample "
                             "records on serving replicas); empty = "
                             "gate on the accuracy harness alone")
    parser.add_argument("--pipeline_shadow_samples", type=int,
                        default=None, metavar="N",
                        help="max traffic lines replayed at shadow-eval "
                             "(deterministically sampled; default 256)")
    parser.add_argument("--pipeline_finetune_epochs", type=int,
                        default=None, metavar="N",
                        help="epochs the fine-tune stage trains on the "
                             "delta shard, resumed from the latest "
                             "committed checkpoint (default 1)")
    parser.add_argument("--pipeline_gate_top1_drop", type=float,
                        default=None, metavar="DELTA",
                        help="largest tolerated top-1 accuracy drop of "
                             "the candidate vs the incumbent before "
                             "the gate refuses promotion (default "
                             "0.01)")
    parser.add_argument("--pipeline_gate_topk_drop", type=float,
                        default=None, metavar="DELTA",
                        help="largest tolerated top-k accuracy drop "
                             "(default 0.01)")
    parser.add_argument("--pipeline_gate_f1_drop", type=float,
                        default=None, metavar="DELTA",
                        help="largest tolerated subtoken-F1 drop "
                             "(default 0.01)")
    parser.add_argument("--pipeline_gate_min_agreement", type=float,
                        default=None, metavar="RATIO",
                        help="smallest tolerated top-k agreement over "
                             "the replayed traffic slice (default "
                             "0.98; only checked when traffic was "
                             "replayed)")
    parser.add_argument("--pipeline_fleet", default=None,
                        metavar="HOST:PORT",
                        help="fleet router admin address the promote "
                             "stage drives the canary-first "
                             "coordinated swap through; empty = stop "
                             "after shadow-eval with a gated candidate "
                             "artifact on disk")
    parser.add_argument("--pipeline_model", default=None,
                        metavar="NAME",
                        help="fleet model group to promote into "
                             "(default 'default')")
    parser.add_argument("--pipeline_promote_timeout",
                        dest="pipeline_promote_timeout_s", type=float,
                        default=None, metavar="SECONDS",
                        help="budget for one fleet rollout to reach a "
                             "terminal state before the stage fails "
                             "(default 600)")
    parser.add_argument("--pipeline_refresh_retrieval",
                        action="store_true", default=None,
                        help="after promotion, re-embed the delta "
                             "shard with the candidate, build a fresh "
                             "ANN index behind its fingerprint and "
                             "remount it fleet-wide (refuse/detach "
                             "policy guards every replica transition)")
    parser.add_argument("--serve_traffic_sample",
                        dest="serve_traffic_sample_file", metavar="FILE",
                        help="record every Nth request's extracted "
                             "lines into this bounded ring file — the "
                             "shadow-eval replay corpus (README "
                             "'Continuous training'; off by default)")
    parser.add_argument("--serve_traffic_sample_every", type=int,
                        default=None, metavar="N",
                        help="sample every Nth cache-miss request into "
                             "the traffic ring (default 10)")
    parser.add_argument("--serve_traffic_sample_cap", type=int,
                        default=None, metavar="N",
                        help="lines the traffic sample ring retains "
                             "(default 4096)")
    parser.add_argument("--topk_block", dest="topk_block_size", type=int,
                        default=None, metavar="ROWS",
                        help="target-table rows per block of the "
                             "blockwise top-k prediction head (default "
                             "16384; 0 forces the classic full-logits "
                             "materialization)")
    parser.add_argument("-fw", "--framework", dest="dl_framework",
                        choices=["jax", "tensorflow", "keras"], default="jax",
                        help="accepted for reference CLI compatibility; this "
                             "framework always runs the JAX/TPU backend")
    parser.add_argument("--tensorboard", dest="use_tensorboard",
                        action="store_true",
                        help="write TensorBoard scalars (train loss/"
                             "throughput + eval metrics) next to the model "
                             "artifacts")
    parser.add_argument("-v", "--verbose", dest="verbose_mode", type=int,
                        default=1, help="verbose mode in {0,1,2}")
    parser.add_argument("-lp", "--logs-path", dest="logs_path", metavar="FILE",
                        required=False, help="log file path")
    # TPU-native knobs
    parser.add_argument("--dp", type=int, default=1,
                        help="data-parallel mesh axis size")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel (row-sharded tables) axis size")
    parser.add_argument("--cp", type=int, default=1,
                        help="context-parallel axis size (shards MAX_CONTEXTS)")
    parser.add_argument("--compute_dtype", choices=["bfloat16", "float32"],
                        default="bfloat16")
    parser.add_argument("--adam_mu_dtype", choices=["bfloat16", "float32"],
                        default=None,
                        help="Adam first-moment storage dtype (default: "
                             "config.py's bfloat16); resuming an artifact "
                             "saved under a different dtype requires "
                             "matching it (checkpoint meta is checked)")
    parser.add_argument("--adam_nu_dtype", choices=["bfloat16", "float32"],
                        default=None,
                        help="Adam second-moment storage dtype (see "
                             "--adam_mu_dtype)")
    parser.add_argument("--batch_size", type=int, default=None)
    parser.add_argument("--test_batch_size", type=int, default=None)
    parser.add_argument("--epochs", type=int, default=None)
    parser.add_argument("--max_contexts", type=int, default=None)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--no_packed_data", action="store_true",
                        help="stream text .c2v instead of packed .c2vb")
    parser.add_argument("--train_corpus_manifest", metavar="FILE",
                        default=None,
                        help="train from a corpus manifest (JSON list "
                             "of .c2vb shards — incumbent pack + delta "
                             "shards) as one logical row space with "
                             "the same epoch-keyed global shuffle as a "
                             "single pack; build/grow it with the "
                             "`corpus` subcommand (README 'Training at "
                             "pod scale')")
    parser.add_argument("--gspmd", action="store_true",
                        help="disable the manual shard_map TP kernels and "
                             "rely on GSPMD sharding propagation")
    parser.add_argument("--sparse_embedding_update", action="store_true",
                        help="touched-rows (lazy) Adam for the token/path "
                             "tables; wins at pod scale with the manual TP "
                             "kernels (see config.py)")
    parser.add_argument("--rss_limit_gb", type=float, default=0.0,
                        help="checkpoint-and-stop (like SIGTERM "
                             "preemption) when process peak RSS crosses "
                             "this many GB; 0 disables")
    parser.add_argument("--on_nonfinite_loss", choices=["halt", "warn"],
                        default=None,
                        help="what to do when a log-window average loss "
                             "is NaN/Inf: halt (default; checkpoint via "
                             "the preemption path and exit nonzero) or "
                             "warn (log and continue)")
    parser.add_argument("--extractor_timeout", dest="extractor_timeout_s",
                        type=float, default=None, metavar="SECONDS",
                        help="kill a hung serving-side path-extractor "
                             "child after this many seconds (default: "
                             "config.py's 120; 0 disables)")
    parser.add_argument("--extractor_retries", dest="extractor_retries",
                        type=int, default=None, metavar="N",
                        help="retry a crashed/failed-to-launch "
                             "serving-side extractor child up to N times "
                             "with exponential backoff (default: "
                             "config.py's 2; timeouts are never retried; "
                             "0 disables)")
    parser.add_argument("--async_checkpointing", action="store_true",
                        help="defer the checkpoint commit (Orbax flush "
                             "wait + cross-host barrier + manifest + "
                             "atomic rename) to a background commit "
                             "thread with bounded in-flight depth; the "
                             "step loop only pays staging + dispatch. "
                             "Crash-atomicity and the multi-host commit "
                             "protocol are unchanged")
    parser.add_argument("--save_barrier_timeout",
                        dest="save_barrier_timeout_s", type=float,
                        default=None, metavar="SECONDS",
                        help="per-barrier timeout of the cross-host "
                             "checkpoint commit protocol (default: "
                             "config.py's 600); on expiry the save "
                             "fails loudly instead of hanging the pod "
                             "on a dead peer")
    parser.add_argument("--no_cursor_resume", action="store_true",
                        help="ignore the checkpoint's saved data cursor "
                             "and re-run an interrupted epoch from its "
                             "start instead of skipping the rows it "
                             "already consumed (cursor resume works on "
                             "any host count; see README 'Elastic "
                             "resume')")
    parser.add_argument("--corpus_create", metavar="SHARD[,SHARD...]",
                        default=None,
                        help="(`corpus` subcommand) build a new "
                             "manifest at --train_corpus_manifest over "
                             "these .c2vb shards, in order (shard "
                             "order defines global row ids); refuses "
                             "mixed-vocab shard sets")
    parser.add_argument("--corpus_add", metavar="SHARD", default=None,
                        help="(`corpus` subcommand) append one .c2vb "
                             "delta shard to the manifest — pure "
                             "append, existing row ids stay stable; "
                             "refused on vocab-fingerprint mismatch")
    parser.add_argument("--corpus_validate", action="store_true",
                        default=None,
                        help="(`corpus` subcommand) re-read every "
                             "listed shard's header/meta and fail on "
                             "drift (row count changed, mixed vocab) "
                             "instead of just printing the manifest")
    parser.add_argument("--preprocess_workers", type=int, default=0,
                        metavar="N",
                        help="host worker processes for the on-demand "
                             ".c2v -> .c2vb pack at training startup "
                             "(and the offline fused corpus compiler); "
                             "output is byte-identical at any worker "
                             "count; 0 = in-process serial")
    parser.add_argument("--checkpoint_hash_content", action="store_true",
                        help="record full-content sha256 of every "
                             "checkpoint file (incl. the Orbax shards, "
                             "hashed on a thread pool AFTER the atomic "
                             "commit) into the manifest; resume "
                             "verifies the hashes when present")
    parser.add_argument("--profile_dir", metavar="DIR",
                        help="write a jax.profiler trace of train batches "
                             "10-20 to DIR (TensorBoard/Perfetto viewable)")
    parser.add_argument("--metrics_file", metavar="FILE",
                        help="write a Prometheus text-format metrics "
                             "snapshot here, atomically rewritten at every "
                             "log boundary (node-exporter textfile style)")
    parser.add_argument("--metrics_port", type=int, default=0,
                        metavar="PORT",
                        help="serve the Prometheus snapshot at "
                             "http://127.0.0.1:PORT/metrics during "
                             "training; 0 disables")
    parser.add_argument("--heartbeat_file", metavar="FILE",
                        help="atomically rewrite a JSON heartbeat {step, "
                             "epoch, last_loss, wall_time, ...} here each "
                             "log window so external watchdogs can detect "
                             "hangs by staleness")
    parser.add_argument("--trace_export", metavar="FILE",
                        help="write host-side wall-time spans (data wait/"
                             "dispatch/loss sync/checkpoint/eval) as Chrome "
                             "trace-event JSON here when training ends "
                             "(Perfetto-loadable; complements "
                             "--profile_dir's device trace)")
    return parser


def config_from_args(argv=None) -> Config:
    if argv is None:
        argv = sys.argv[1:]
    # Subcommand sugar: `code2vec_tpu serve --load M` == `--serve
    # --load M`; `code2vec_tpu export --load M --artifact_out D` builds
    # a release artifact (README "Release artifacts"); `embed`,
    # `index-build` and `export-embeddings` are the retrieval-stack
    # jobs (README "Retrieval").
    subcommands = ("serve", "fleet", "export", "embed", "index-build",
                   "export-embeddings", "pipeline", "corpus")
    subcommand = argv[0] if argv and argv[0] in subcommands else None
    if subcommand:
        argv = argv[1:]
    # `fleet` = a serving deployment whose parent is the control plane
    # (README "Fleet"); each host it launches re-runs this CLI as
    # `serve`.
    serve_subcommand = subcommand in ("serve", "fleet")
    args = arguments_parser().parse_args(argv)
    if subcommand == "export" and not args.export_artifact_path:
        raise SystemExit(
            "the `export` subcommand requires --artifact_out DIR")
    if subcommand == "embed" and not args.embed_out:
        raise SystemExit("the `embed` subcommand requires --embed_out "
                         "DIR (plus --test CORPUS and --load/--artifact)")
    if subcommand == "index-build" and not (args.index_vectors
                                            and args.index_out):
        raise SystemExit("the `index-build` subcommand requires "
                         "--vectors DIR and --index_out DIR")
    if subcommand == "export-embeddings" and not args.embeddings_out:
        raise SystemExit("the `export-embeddings` subcommand requires "
                         "--embeddings_out DIR (plus --load MODEL)")
    if subcommand == "pipeline" and not args.pipeline_dir:
        raise SystemExit(
            "the `pipeline` subcommand requires --pipeline_dir DIR "
            "(plus --load CKPT, --pipeline_raw FILE, "
            "--pipeline_incumbent DIR and --test CORPUS)")
    if subcommand == "corpus" and not args.train_corpus_manifest:
        raise SystemExit(
            "the `corpus` subcommand requires --train_corpus_manifest "
            "FILE (plus --corpus_create/--corpus_add/--corpus_validate "
            "for the mutation/check actions; plain `corpus` lists the "
            "manifest)")
    knobs = {knob: value for knob in ("adam_mu_dtype", "adam_nu_dtype",
                                      "on_nonfinite_loss",
                                      "extractor_timeout_s",
                                      "extractor_retries",
                                      "save_barrier_timeout_s",
                                      "serve_port", "serve_host",
                                      "serve_batch_size",
                                      "serve_buckets",
                                      "model_config",
                                      "serve_token_budget",
                                      "serve_cache_entries",
                                      "extractor_pool_size",
                                      "serve_drain_timeout_s",
                                      "serve_deadline_ms",
                                      "serve_deadline_max_ms",
                                      "serve_queue_depth",
                                      "serve_tenants",
                                      "serve_tenant_default_weight",
                                      "serve_tenant_qps",
                                      "serve_breaker_window_s",
                                      "serve_breaker_failure_ratio",
                                      "serve_breaker_min_requests",
                                      "serve_breaker_cooldown_s",
                                      "serve_replicas",
                                      "serve_max_restarts",
                                      "serve_heartbeat_interval_s",
                                      "serve_debug_trace",
                                      "serve_flight_dir",
                                      "serve_flight_records",
                                      "serve_flight_max_dumps",
                                      "serve_telemetry_port",
                                      "fleet_hosts", "fleet_port",
                                      "fleet_models",
                                      "fleet_poll_interval_s",
                                      "fleet_scale_min",
                                      "fleet_scale_max",
                                      "fleet_scale_up_shed_rate",
                                      "fleet_scale_up_p95_ms",
                                      "fleet_scale_up_ticks",
                                      "fleet_scale_down_ticks",
                                      "fleet_scale_cooldown_s",
                                      "fleet_swap_timeout_s",
                                      "fleet_max_host_restarts",
                                      "fleet_routers",
                                      "fleet_control",
                                      "fleet_launcher",
                                      "fleet_addresses",
                                      "fleet_tsdb_retention_s",
                                      "fleet_tsdb_max_mb",
                                      "fleet_slo_availability",
                                      "fleet_slo_latency_ms",
                                      "fleet_slo_latency_target",
                                      "fleet_slo_period_s",
                                      "fleet_slo_window_scale",
                                      "fleet_trace_id",
                                      "fleet_trace_dir",
                                      "serve_artifact",
                                      "export_artifact_path",
                                      "release_scheme",
                                      "train_corpus_manifest",
                                      "topk_block_size",
                                      "embed_out", "embed_dtype",
                                      "embed_shard_rows",
                                      "embeddings_out",
                                      "index_vectors", "index_out",
                                      "index_nlist", "index_nprobe",
                                      "index_kmeans_iters",
                                      "index_metric",
                                      "retrieval_index",
                                      "retrieval_topk",
                                      "retrieval_swap_policy",
                                      "pipeline_dir", "pipeline_raw",
                                      "pipeline_incumbent",
                                      "pipeline_traffic",
                                      "pipeline_shadow_samples",
                                      "pipeline_finetune_epochs",
                                      "pipeline_gate_top1_drop",
                                      "pipeline_gate_topk_drop",
                                      "pipeline_gate_f1_drop",
                                      "pipeline_gate_min_agreement",
                                      "pipeline_fleet",
                                      "pipeline_model",
                                      "pipeline_promote_timeout_s",
                                      "pipeline_refresh_retrieval",
                                      "serve_traffic_sample_file",
                                      "serve_traffic_sample_every",
                                      "serve_traffic_sample_cap")
             if (value := getattr(args, knob)) is not None}
    if args.fleet_no_affinity:
        knobs["fleet_cache_affinity"] = False
    config = Config(
        predict=args.predict,
        serve=args.serve or serve_subcommand,
        fleet=subcommand == "fleet",
        pipeline=subcommand == "pipeline",
        corpus=subcommand == "corpus",
        corpus_create=args.corpus_create,
        corpus_add=args.corpus_add,
        corpus_validate=bool(args.corpus_validate),
        model_save_path=args.save_path,
        model_load_path=args.load_path,
        train_data_path_prefix=args.data_path,
        test_data_path=args.test_path,
        release=args.release,
        export_code_vectors=args.export_code_vectors,
        save_w2v=args.save_w2v,
        save_t2v=args.save_t2v,
        verbose_mode=args.verbose_mode,
        logs_path=args.logs_path,
        use_tensorboard=args.use_tensorboard,
        use_sparse_embedding_update=args.sparse_embedding_update,
        dp=args.dp, tp=args.tp, cp=args.cp,
        compute_dtype=args.compute_dtype,
        **knobs,
        # A knob present here was typed on the command line — consumers
        # that would otherwise override a config DEFAULT (ReleaseModel
        # adopting the artifact's serve_batch_size) must not override an
        # explicitly-requested value, even one equal to the default.
        explicit_knobs=tuple(sorted(knobs)),
        release_quantize=not args.no_quantize,
        release_aot=not args.no_aot,
        vectors_text=args.vectors_text,
        async_checkpointing=args.async_checkpointing,
        cursor_resume=not args.no_cursor_resume,
        seed=args.seed,
        use_packed_data=not args.no_packed_data,
        preprocess_workers=args.preprocess_workers,
        checkpoint_hash_content=args.checkpoint_hash_content,
        use_manual_tp_kernels=not args.gspmd,
        rss_limit_gb=args.rss_limit_gb,
        profile_dir=args.profile_dir,
        metrics_file=args.metrics_file,
        metrics_port=args.metrics_port,
        heartbeat_file=args.heartbeat_file,
        trace_export=args.trace_export,
    )
    if args.batch_size:
        config.train_batch_size = args.batch_size
        config.test_batch_size = args.batch_size
    if args.test_batch_size:
        config.test_batch_size = args.test_batch_size
    if args.epochs:
        config.num_train_epochs = args.epochs
    if args.max_contexts:
        config.max_contexts = args.max_contexts
    return config


def corpus_main(config) -> int:
    """`corpus` subcommand: sharded-corpus manifest tooling. Never
    builds a model — fingerprints come from the shards' own meta
    sidecars, so the manifest can be managed on a machine that has no
    vocabularies loaded."""
    from code2vec_tpu.data import packed
    manifest_path = config.train_corpus_manifest
    try:
        if config.corpus_create:
            shards = [s for s in config.corpus_create.split(",") if s]
            packed.create_manifest(manifest_path, shards)
            config.log(f"created {manifest_path} "
                       f"({len(shards)} shard(s))")
        if config.corpus_add:
            packed.append_manifest_shard(manifest_path, config.corpus_add)
            config.log(f"appended {config.corpus_add} to {manifest_path}")
        manifest = packed.load_manifest(manifest_path)
        if config.corpus_validate:
            reports = packed.validate_manifest(manifest_path)
        else:
            reports = manifest["shards"]
    except (ValueError, OSError) as e:
        config.log(f"corpus: {e}")
        return 1
    total = sum(r["rows"] for r in reports)
    config.log(f"{manifest_path}: {len(reports)} shard(s), {total} rows, "
               f"max_contexts={manifest['max_contexts']}, vocab "
               f"fingerprint {manifest.get('vocab_fingerprint')}"
               + (" [validated]" if config.corpus_validate else ""))
    for r in reports:
        config.log(f"  {r['path']}: {r['rows']} rows, "
                   f"fingerprint={r.get('vocab_fingerprint')}")
    return 0


def main(argv=None) -> None:
    # dispatch mirrors reference code2vec.py:16-37
    if argv is None:
        argv = sys.argv[1:]
    config = config_from_args(argv)
    config.verify()

    # Corpus manifest tooling: pure file-level job, no model, no
    # distributed runtime (README "Training at pod scale").
    if config.corpus:
        sys.exit(corpus_main(config))

    # Continuous-training pipeline: the supervisor PARENT never builds
    # a model either — each stage re-execs this CLI (train/export/
    # embed/index-build) or drives the fleet router over HTTP, and the
    # journaled manifest makes a killed run resumable
    # (pipeline/supervisor.py, README "Continuous training").
    if config.pipeline:
        from code2vec_tpu.pipeline.supervisor import pipeline_main
        sys.exit(pipeline_main(config, argv=list(argv)))

    # Trace collector: `fleet --fleet_trace_id ID` stitches every
    # process's span files (or a live control plane's, via
    # --fleet_control) into ONE Chrome trace on stdout — it launches
    # nothing. Must dispatch before the router/fleet branches.
    if config.fleet and config.fleet_trace_id:
        from code2vec_tpu.obs.stitch import stitch_main
        sys.exit(stitch_main(config))

    # Edge router agent: a `fleet` re-exec child marked by
    # C2V_FLEET_ROUTER never builds a model — it routes over a polled
    # copy of the fleet view (serving/fleet/edge.py, README "Edge").
    # Must dispatch before the fleet branch: the child's argv still
    # says `fleet`.
    if (config.serve and config.fleet
            and "C2V_FLEET_ROUTER" in os.environ):
        from code2vec_tpu.serving.fleet.edge import router_main
        sys.exit(router_main(config))

    # Cross-host fleet: the control-plane PARENT never builds a model;
    # it launches one `serve` supervisor per host behind the
    # health-gated router and drives scaling + coordinated hot-swap
    # (serving/fleet/, README "Fleet").
    if (config.serve and config.fleet
            and "C2V_FLEET_HOST" not in os.environ
            and "C2V_SERVE_REPLICA" not in os.environ):
        from code2vec_tpu.serving.fleet.control import fleet_main
        sys.exit(fleet_main(config, argv=list(argv)))

    # Supervised multi-replica serving: the PARENT never builds a model
    # (each replica is its own process with its own model + extractor
    # pool); it forks N re-execed copies of this command with
    # --replicas stripped, monitors their heartbeats, restarts crashed
    # or hung ones, and fans SIGTERM out as a coordinated drain. A
    # fleet HOST always supervises (even at --replicas 1) so the
    # control plane gets its telemetry listener + scaling headroom.
    if (config.serve
            and (config.serve_replicas > 1
                 or "C2V_FLEET_HOST" in os.environ)
            and "C2V_SERVE_REPLICA" not in os.environ):
        from code2vec_tpu.serving.supervisor import supervisor_main
        sys.exit(supervisor_main(config, argv=list(argv)))

    # Every branch below may compile (the parents above never do); the
    # persistent cache must be placed before the first jit.
    from code2vec_tpu.utils.device import configure_compile_cache
    config.log(f"Compile cache: "
               f"{configure_compile_cache() or 'off (CPU platform)'}")

    # joins the multi-host runtime when a coordinator is configured;
    # no-op on single-process runs (parallel/distributed.py)
    from code2vec_tpu.parallel import distributed
    distributed.initialize()

    if config.index_out:
        # `index-build` is a pure vector-store -> ANN-artifact job: no
        # model, no checkpoint — the store manifest carries the
        # embedding fingerprint the index inherits.
        from code2vec_tpu.retrieval.index import build_index
        build_index(config.index_vectors, config.index_out,
                    nlist=config.index_nlist,
                    nprobe=config.index_nprobe,
                    kmeans_iters=config.index_kmeans_iters,
                    seed=config.seed, metric=config.index_metric,
                    log=config.log)
        return

    if config.serve_artifact:
        # Release-artifact runtime: no checkpoint, no training state —
        # the artifact carries tables + vocabs + AOT lowerings.
        from code2vec_tpu.release.runtime import ReleaseModel
        model = ReleaseModel(config)
        if config.embed_out:
            # embed from the quantized bundle: fused-dequant tables +
            # blockwise top-k, no checkpoint in RSS
            from code2vec_tpu.retrieval.embed_job import run_embed_job
            run_embed_job(model)
            return
        if not (config.predict or config.serve or config.is_testing):
            config.log("--artifact given without `serve`, --predict or "
                       "--test; nothing to do")
        if config.is_testing:
            eval_results = model.evaluate()
            config.log(
                str(eval_results).replace(
                    "topk",
                    f"top{config.top_k_words_considered_during_prediction}"))
        if config.predict:
            from code2vec_tpu.serving.interactive import InteractivePredictor
            InteractivePredictor(config, model).predict()
        if config.serve:
            from code2vec_tpu.serving.server import serve_main
            sys.exit(serve_main(config, model))
        return

    if config.model_config:
        # a model of models/ behind the one serving interface
        # (lm_facade.py); it trains nothing and predicts no method name
        from code2vec_tpu.lm_facade import ScoringModel
        model = ScoringModel(config)
        if config.is_saving:
            model.save()
        if config.serve:
            from code2vec_tpu.serving.server import serve_main
            sys.exit(serve_main(config, model))
        return

    from code2vec_tpu.model_facade import Code2VecModel
    model = Code2VecModel(config)

    if config.export_artifact_path:
        from code2vec_tpu.release.artifact import export_artifact
        export_artifact(model, config.export_artifact_path)
        return

    if config.embed_out:
        from code2vec_tpu.retrieval.embed_job import run_embed_job
        run_embed_job(model)
        return

    if config.embeddings_out:
        model.export_embeddings(config.embeddings_out)
        return

    if config.is_training:
        model.train()
    if config.save_w2v is not None:
        model.save_word2vec_format(config.save_w2v, VocabType.Token)
        config.log(f"Origin word vectors saved in word2vec text format in: "
                   f"{config.save_w2v}")
    if config.save_t2v is not None:
        model.save_word2vec_format(config.save_t2v, VocabType.Target)
        config.log(f"Target word vectors saved in word2vec text format in: "
                   f"{config.save_t2v}")
    if (config.is_testing and not config.is_training) or config.release:
        eval_results = model.evaluate()
        if eval_results is not None:
            config.log(
                str(eval_results).replace(
                    "topk",
                    f"top{config.top_k_words_considered_during_prediction}"))
    if config.predict:
        from code2vec_tpu.serving.interactive import InteractivePredictor
        predictor = InteractivePredictor(config, model)
        predictor.predict()
    if config.serve:
        from code2vec_tpu.serving.server import serve_main
        sys.exit(serve_main(config, model))


if __name__ == "__main__":
    main()
