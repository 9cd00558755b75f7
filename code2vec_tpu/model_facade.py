"""High-level model API: train / evaluate / predict / save / export.

Mirrors the reference's `Code2VecModelBase` lifecycle (model_base.py:37-182)
with one TPU-native implementation instead of two TF backends: vocabs are
built or loaded, the Flax module + Optax state are created (sharded over
the mesh when dp*tp*cp > 1), and the train/evaluate/predict entry points
drive the jitted steps.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu import common as common_mod
from code2vec_tpu import obs
from code2vec_tpu.common import count_lines_in_file
from code2vec_tpu.config import Config
from code2vec_tpu.data.packed import PackedDataset, pack_c2v
from code2vec_tpu.data.reader import (
    EstimatorAction, PathContextReader, parse_context_lines,
)
from code2vec_tpu.evaluation.evaluator import Evaluator
from code2vec_tpu.evaluation.metrics import ModelEvaluationResults
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.parallel import distributed
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
from code2vec_tpu.training import checkpoint as ckpt_mod
from code2vec_tpu.training.loop import Trainer
from code2vec_tpu.training.state import (
    TrainState, create_train_state, dropout_rng, make_optimizer, num_params,
)
from code2vec_tpu.training.step import (
    EvalOutputs, TrainStepBuilder, device_put_batch,
)
from code2vec_tpu.utils.device import describe_devices, shard_layout
from code2vec_tpu.utils.faults import fault_point
from code2vec_tpu.vocab import Code2VecVocabs, VocabType


class ModelPredictionResults(NamedTuple):
    # reference: model_base.py:29-34
    original_name: str
    topk_predicted_words: List[str]
    topk_predicted_words_scores: np.ndarray
    attention_per_context: Dict[Tuple[str, str, str], float]
    code_vector: Optional[np.ndarray] = None


_STAGE_HELP = (
    "one stage of a coalesced predict call on the dispatcher thread: "
    "parse (extractor lines to id arrays; absent on the pre-parsed "
    "path), assemble (bucket choice, slice, pad), device (transfer "
    "in, step, fetch of the outputs), render (softmax of the top-k, "
    "vocabulary look-ups, attention dictionaries)")
_H_STAGE = {stage: obs.histogram("serving_predict_stage_seconds",
                                 _STAGE_HELP, stage=stage)
            for stage in ("parse", "assemble", "device", "render")}
_DEVICE_PART_HELP = (
    "one part of a served call's device stage "
    "(serving_predict_stage_seconds{stage=device}), which its parts "
    "cover: put (host arrays to the device), lookup (a scoring model "
    "with a context cache: its lock and the rows' slots), enqueue (the "
    "jitted call until it returns), wait (block_until_ready on the "
    "outputs that are fetched: the step's device time plus launch "
    "latency, the one part the chip is busy in), fetch (the copies to "
    "the host)")
_H_DEVICE_PART = {part: obs.histogram("serving_predict_device_seconds",
                                      _DEVICE_PART_HELP, part=part)
                  for part in ("put", "lookup", "enqueue", "wait", "fetch")}
_FILL_HELP = (
    "how much of a predict step's padded shape was real work: rows = "
    "live rows over the padded row shape, contexts = valid contexts "
    "over padded rows x context bucket")
_FILL_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7,
                 0.8, 0.9, 1.0)
_H_FILL = {dim: obs.histogram("serving_batch_fill_ratio", _FILL_HELP,
                              buckets=_FILL_BUCKETS, dim=dim)
           for dim in ("rows", "contexts")}


def _stage(stage: str):
    return obs.span("predict." + stage, hist=_H_STAGE[stage])


def _device_part(part: str):
    """A child span of `predict.device`; a request's tree hangs it
    under that stage by this name (serving/batcher.py)."""
    return obs.span("predict.device." + part, hist=_H_DEVICE_PART[part])


def head_sorted_columns_gauge(step: str):
    """Columns that enter a sort in one trip of a built step's top-k
    head (ops/topk.py `sorted_columns`): which merge the static shapes
    chose, set where the step is built. Label value dynamic, NAME a
    literal, as above."""
    return obs.gauge(
        "head_topk_sorted_columns",
        "columns that enter a sort in one trip of a built step's "
        "blockwise top-k head (step=predict|eval|score): block + k with "
        "the plain merge, block/g + k + k*g behind the exact group "
        "prefilter, the whole row where the blockwise head is off",
        step=step)


class BucketedPredictMixin:
    """The bucketed predict path shared by the training facade and the
    release-artifact runtime (release/runtime.py): line parsing, context
    bucketing, row padding, the (rows, bucket)-keyed compiled-step cache
    and the host-side result assembly are identical in both; only how a
    step is BUILT (`_make_predict_step`) and CALLED
    (`_call_predict_step`) differs — the facade passes live fp32 params
    into a freshly-jitted eval step, the release runtime calls an
    AOT-deserialized (or jitted) quantized step over artifact tables.
    The eval-data plumbing (`_eval_batches` + packed-dataset cache)
    lives here too, so the standard Evaluator can score either model.

    Requires on the host class: config, log, vocabs, mesh,
    _predict_steps (dict)."""

    def _make_predict_step(self, batch_rows: int, m: int):
        raise NotImplementedError

    def _call_predict_step(self, step, arrays):
        raise NotImplementedError

    @staticmethod
    def _count_examples(dataset_path: str) -> int:
        # reference: model_base.py:77-96 (.num_examples sidecar cache)
        sidecar = dataset_path + ".num_examples"
        if os.path.isfile(sidecar):
            with open(sidecar) as f:
                return int(f.readline())
        if not os.path.exists(dataset_path):
            # Fused-compiled datasets (data/preprocess.py compile_corpus)
            # carry no `.c2v` text at all — the row count lives in the
            # packed header.
            packed_path = dataset_path + "b"
            if os.path.exists(packed_path):
                return PackedDataset.read_header(packed_path)[0]
        n = count_lines_in_file(dataset_path)
        try:
            with open(sidecar, "w") as f:
                f.write(str(n))
        except OSError:
            pass
        return n

    def _packed_dataset(self, c2v_path: str) -> PackedDataset:
        # Memoized: mid-epoch eval opens the test set every firing, and a
        # fresh PackedDataset would redo the O(rows) filter scan each time.
        cached = getattr(self, "_packed_cache", None)
        if cached is None:
            cached = self._packed_cache = {}
        if c2v_path in cached:
            return cached[c2v_path]
        packed_path = c2v_path + "b"
        if not os.path.exists(packed_path):
            # Which loader does the work is otherwise invisible: without
            # the shared library the Python parser takes over silently.
            from code2vec_tpu.data import native
            loader = ("native libc2vdata.so"
                      if native.load_library() is not None else
                      "Python parser; libc2vdata.so not built")
            self.log(f"Packing {c2v_path} -> {packed_path} "
                     f"(one-time; {loader})")
            pack_c2v(c2v_path, self.vocabs, self.config.max_contexts,
                     out_path=packed_path,
                     num_workers=self.config.preprocess_workers)
        shard_index, num_shards = distributed.host_shard()
        ds = PackedDataset(packed_path, self.vocabs,
                           shard_index=shard_index, num_shards=num_shards)
        cached[c2v_path] = ds
        return ds

    def _train_corpus(self):
        """The training data source: the sharded corpus view when a
        manifest is configured (--train_corpus_manifest — the incumbent
        pack plus accumulated delta shards as ONE logical row space,
        same epoch-keyed global order as a single pack), else the
        single packed file derived from --data. Memoized alongside
        `_packed_dataset`'s cache: the filter scan is O(rows)."""
        config = self.config
        manifest = getattr(config, "train_corpus_manifest", None)
        if not manifest:
            return self._packed_dataset(config.train_data_path)
        cached = getattr(self, "_packed_cache", None)
        if cached is None:
            cached = self._packed_cache = {}
        if manifest in cached:
            return cached[manifest]
        from code2vec_tpu.data.packed import ShardedCorpus
        shard_index, num_shards = distributed.host_shard()
        ds = ShardedCorpus(manifest, self.vocabs,
                           shard_index=shard_index, num_shards=num_shards)
        self.log(f"Training corpus: {manifest} "
                 f"({ds.num_shard_files} shard(s), "
                 f"{ds.num_rows_total} rows)")
        cached[manifest] = ds
        return ds

    def _require_single_process(self, what: str) -> None:
        """Multi-host training/eval requires packed data: the streaming
        text reader cannot know its post-filter batch count before the
        first pass, so the pod-wide lockstep agreement (see
        `_train_batches`) has nothing to agree on. Packed data is the
        designed pod path anyway — raw-text parsing in Python would be
        feed-bound at pod scale."""
        if jax.process_count() > 1:
            raise RuntimeError(
                f"{what} is not supported with multiple processes; "
                f"pack the dataset first (use_packed_data=True).")

    def _eval_batches(self) -> Iterable:
        config = self.config
        batch_size = distributed.local_batch_size(config.test_batch_size)
        if config.use_packed_data:
            ds = self._packed_dataset(config.test_data_path)
            batches = ds.iter_batches(batch_size,
                                      EstimatorAction.Evaluate,
                                      with_target_strings=True)
            if jax.process_count() > 1:
                # Lockstep contract (max + pad): every host must drive the
                # same number of collective eval steps; no real row may be
                # dropped, so short hosts pad with invalid batches.
                local = ds.steps_per_epoch(batch_size, EstimatorAction.Evaluate)
                agreed = distributed.agree_scalar(local, "max")
                from code2vec_tpu.data.reader import invalid_batch
                return distributed.lockstep_eval_stream(
                    batches, agreed,
                    lambda: invalid_batch(batch_size, config.max_contexts))
            return batches
        self._require_single_process("evaluating from raw .c2v text")
        shard_index, num_shards = distributed.host_shard()
        return PathContextReader(self.vocabs, config, EstimatorAction.Evaluate,
                                 shard_index=shard_index,
                                 num_shards=num_shards,
                                 batch_size=batch_size)

    @property
    def context_buckets(self) -> Tuple[int, ...]:
        """Padded-context-count buckets for the predict path (sorted,
        always ending in max_contexts, filtered to cp multiples) —
        parsed once from config.serve_buckets. One compiled step per
        bucket is the whole compilation budget of the serving path."""
        cached = getattr(self, "_context_buckets", None)
        if cached is None:
            from code2vec_tpu.serving.batcher import parse_buckets
            cached = self._context_buckets = parse_buckets(
                getattr(self.config, "serve_buckets", ""),
                self.config.max_contexts, cp=self.config.cp)
        return cached

    def _get_bucketed_predict_step(self, batch_rows: int, m: int):
        key = (batch_rows, m)
        step = self._predict_steps.get(key)
        if step is None:
            # a FRESH callable per shape: each entry compiles exactly
            # once, so len(_predict_steps) == pjit compilations
            step = self._predict_steps[key] = \
                self._make_predict_step(batch_rows, m)
            self.log(f"Compiling predict step for shape "
                     f"(rows={batch_rows}, contexts={m}) "
                     f"[{len(self._predict_steps)} of "
                     f"<= {len(self.context_buckets)} buckets]")
        return step

    def predict_compile_count(self) -> int:
        """Distinct compiled predict-step shapes so far (bounded by the
        bucket list for a fixed serve batch size; asserted in
        tests/test_serving.py and recorded by the serving bench)."""
        return len(self._predict_steps)

    def _default_predict_batch_size(self) -> int:
        """Rows per predict chunk when the caller didn't pick one. The
        facade pads to the eval batch; ReleaseModel overrides this with
        the artifact's serve_batch_size so `--predict --artifact` and
        offline predict land on the shipped AOT lowerings instead of
        tracing a fresh (test_batch_size, bucket) shape per bucket."""
        return int(self.config.test_batch_size)

    def model_fingerprint(self) -> str:
        """Identity token of the weights this model answers with, mixed
        into every prediction-cache key (serving/cache.py) and surfaced
        in /healthz: a re-exported artifact or a differently-trained
        checkpoint must never satisfy a stale cache entry."""
        raise NotImplementedError

    def smoke_schema(self) -> dict:
        """Run one golden prediction line end to end and report the
        OUTPUT SCHEMA — the hot-swap health gate (serving/swap.py)
        compares a candidate model's schema against the running one's
        before the server's model reference is swapped. The line uses
        deliberately out-of-vocab words: OOV mapping is part of every
        model's contract, so any loadable model can run it, and a model
        whose tables are corrupt surfaces as non-finite scores here
        instead of NaN predictions in production traffic."""
        line = "swapsmoke hotswap,probe,hotswap check,gate,check"
        [r] = self.predict([line], batch_size=1, with_code_vectors=True)
        scores = np.asarray(r.topk_predicted_words_scores, dtype=np.float64)
        return {
            "topk": len(r.topk_predicted_words),
            "code_vector_size": (0 if r.code_vector is None
                                 else int(np.asarray(r.code_vector).size)),
            "scores_finite": bool(np.isfinite(scores).all()),
        }

    def predict(self, predict_data_lines: Iterable[str],
                batch_size: Optional[int] = None,
                with_code_vectors: Optional[bool] = None
                ) -> List[ModelPredictionResults]:
        """reference: tensorflow_model.py:310-367 — per-line predictions
        with top-k words, softmax-normalized scores, attention per context
        and the code vector.

        Accepts any iterable (never materialized whole): lines stream in
        `batch_size`-row chunks, each routed through the bucketed
        compiled-step cache the serving batcher shares, so a million-line
        offline predict and the HTTP server exercise the SAME bounded set
        of compiled shapes. `with_code_vectors` defaults to
        config.export_code_vectors; the serving /embed endpoint forces it
        on (the step computes the vectors either way — the flag only
        gates their host-side materialization)."""
        import itertools
        results: List[ModelPredictionResults] = []
        bs = int(batch_size or self._default_predict_batch_size())
        if with_code_vectors is None:
            with_code_vectors = self.config.export_code_vectors
        it = iter(predict_data_lines)
        while True:
            lines = list(itertools.islice(it, bs))
            if not lines:
                return results
            results.extend(self._predict_chunk(lines, bs,
                                               with_code_vectors))

    def _predict_chunk(self, lines: List[str], bs: int,
                       with_code_vectors: bool
                       ) -> List[ModelPredictionResults]:
        from code2vec_tpu.data.reader import _pad_rows, slice_contexts
        from code2vec_tpu.serving.batcher import bucket_for
        with _stage("parse"):
            chunk = parse_context_lines(lines, self.vocabs,
                                        self.config.max_contexts,
                                        EstimatorAction.Predict,
                                        keep_strings=True)
        n = len(lines)
        with _stage("assemble"):
            # Deepest VALID context column decides the bucket: the slice
            # below only ever removes all-padding columns.
            any_valid_col = chunk.context_valid_mask.any(axis=0)
            deepest = (int(np.nonzero(any_valid_col)[0][-1]) + 1
                       if any_valid_col.any() else 1)
            m = bucket_for(deepest, self.context_buckets)
            chunk = slice_contexts(chunk, m)
            step = self._get_bucketed_predict_step(bs, m)
            # Pad the row count to the step's fixed row shape: row count
            # and context bucket together fully determine the compiled
            # shape.
            padded = _pad_rows(chunk, bs)
            _H_FILL["rows"].observe(n / bs)
            _H_FILL["contexts"].observe(
                float(chunk.context_valid_mask.sum()) / (bs * m))
        with _stage("device"):
            out = self._run_predict_step(step, padded)
            with _device_part("fetch"):
                topk_idx = np.asarray(out.topk_indices)[:n]
                topk_val = np.asarray(out.topk_values)[:n]
                code_vectors = np.asarray(out.code_vectors)[:n]
                attention = np.asarray(out.attention)[:n]
        with _stage("render"):
            return self._render_predictions(
                chunk, n, m, topk_idx, topk_val, code_vectors, attention,
                with_code_vectors)

    def _run_predict_step(self, step, batch) -> EvalOutputs:
        """Transfer in, dispatch, wait until the step's outputs are
        ready: the device stage up to its fetch, part by part. The
        warm-up runs every shape through here too."""
        with _device_part("put"):
            arrays = device_put_batch(batch, self.mesh)
        with _device_part("enqueue"):
            out = self._call_predict_step(step, arrays)
            del arrays      # the inputs go here, not between two parts
        with _device_part("wait"):
            jax.block_until_ready((out.topk_indices, out.topk_values,
                                   out.code_vectors, out.attention))
        return out

    def _render_predictions(self, chunk, n: int, m: int, topk_idx,
                            topk_val, code_vectors, attention,
                            with_code_vectors: bool
                            ) -> List[ModelPredictionResults]:
        results: List[ModelPredictionResults] = []
        # normalize_scores=True in the reference predict graph
        # (tensorflow_model.py:321): softmax over the k values.
        e = np.exp(topk_val - topk_val.max(axis=1, keepdims=True))
        scores = e / e.sum(axis=1, keepdims=True)
        for i in range(n):
            words = [self.vocabs.target_vocab.lookup_word(int(j))
                     for j in topk_idx[i]]
            attention_per_context: Dict[Tuple[str, str, str], float] = {}
            for j in range(m):
                s = chunk.source_strings[i, j]
                p = chunk.path_strings[i, j]
                t = chunk.target_token_strings[i, j]
                if s or p or t:
                    attention_per_context[(s, p, t)] = float(attention[i, j])
            results.append(ModelPredictionResults(
                original_name=(chunk.target_strings[i]
                               if chunk.target_strings else ""),
                topk_predicted_words=words,
                topk_predicted_words_scores=scores[i],
                attention_per_context=attention_per_context,
                code_vector=(code_vectors[i]
                             if with_code_vectors else None)))
        return results


class Code2VecModel(BucketedPredictMixin):
    def __init__(self, config: Config):
        self.config = config
        config.verify()
        self.log = config.log
        self.log("Creating code2vec TPU model")
        # Resume provenance, surfaced in the heartbeat, the metrics
        # registry and the log: which artifacts resume considered and
        # rejected (and why), and whether the restore was exact,
        # resharded (different host count / mesh shape than at save
        # time) or the run started fresh. A rejected artifact must
        # never silently become a fresh start.
        self.resume_report: Dict = {"resume_mode": "fresh",
                                    "restored_step": None,
                                    "restored_epoch": None,
                                    "rejected": []}
        self._resume_cursor: Optional[Dict] = None
        # Set by _train_batches when a cursor skip is applied: the epoch
        # it applies to and the global rows skipped (save_fn adds them
        # back into cursors recorded within that same epoch).
        self._applied_skip_rows = 0
        self._applied_skip_epoch: Optional[int] = None
        if config.is_loading:
            from code2vec_tpu.release.artifact import is_release_artifact
            if is_release_artifact(config.model_load_path):
                # Reject up front with the quantization field named: the
                # fp32 checkpoint loader reading int8 payloads would
                # produce garbage predictions, not an error.
                raise ValueError(
                    f"--load points at a release artifact "
                    f"({config.model_load_path}): its "
                    f"`quantization.scheme` tables are not an fp32 "
                    f"checkpoint. Serve it with `serve --artifact "
                    f"{config.model_load_path}` instead.")
            # `--load` accepts either a concrete artifact directory or a
            # save base: a base resolves to the newest artifact that
            # PASSES its integrity check (walking past any half-written
            # casualty of a mid-save kill). Resolved before vocab
            # loading — dictionaries.bin comes from the same directory.
            trail: List[Dict] = []
            resolved = ckpt_mod.resolve_load_path(config.model_load_path,
                                                  log=self.log, trail=trail)
            rejected = [t for t in trail if t["outcome"] == "rejected"]
            self.resume_report["rejected"] = rejected
            for t in rejected:
                self.log(f"Resume REJECTED candidate {t['path']}: "
                         f"{t['reason']}")
            if rejected:
                self.log(f"Resume fell back past {len(rejected)} "
                         f"rejected artifact(s) to {resolved}")
            if resolved != os.path.abspath(config.model_load_path):
                self.log(f"Resolved --load {config.model_load_path} -> "
                         f"{resolved}")
            config.model_load_path = resolved
        # Full hyperparameter dump at model creation (reference:
        # model_base.py:61-68 logs every config field).
        for name, value in sorted(config.items()):
            self.log(f"    {name}: {value}")
        if not config.release:
            self._init_num_of_examples()
        with obs.startup_phase("vocab_load"):
            self.vocabs = Code2VecVocabs.load_or_create(config)
        self.dims = ModelDims.from_config_and_vocabs(config, self.vocabs)
        self.mesh = (make_mesh(MeshPlan.from_config(config))
                     if config.mesh_size > 1 else None)
        self.module = Code2VecModule(
            dims=self.dims,
            dropout_keep_rate=config.dropout_keep_rate,
            compute_dtype=jnp.dtype(config.compute_dtype))
        self.optimizer = make_optimizer(config)
        # timed until the arrays are there (init is dispatched async);
        # built even when a restore below replaces it
        with obs.startup_phase("state_init"):
            self.state = jax.block_until_ready(create_train_state(
                self.module, self.optimizer,
                jax.random.PRNGKey(config.seed),
                mesh=self.mesh, config=config))
        self.builder = TrainStepBuilder(self.module, self.optimizer, config,
                                        mesh=self.mesh)
        # Epoch numbering continues from the loaded artifact on resume
        # (reference: keras_model.py:264-274 parses the epoch back from
        # the checkpoint name; here it is carried in the artifact meta).
        self.initial_epoch = 0
        if config.is_loading:
            # --release discards the optimizer state, so it loads
            # params-only and must not run the optimizer layout/dtype
            # guards (it is their advertised escape hatch); artifact
            # export likewise only reads the params.
            params_only = config.release or bool(config.export_artifact_path)
            report: Dict = {}
            with obs.startup_phase("restore"):
                self.state = jax.block_until_ready(ckpt_mod.load_model(
                    config.model_load_path, self.state, config=config,
                    params_only=params_only, report=report))
            meta = ckpt_mod.load_model_meta(config.model_load_path)
            self.initial_epoch = int(meta.get("epoch", 0))
            mode = report.get("resume_mode", "exact")
            self.resume_report.update(
                resume_mode=mode,
                restored_step=report.get("restored_step"),
                restored_epoch=self.initial_epoch)
            cursor = report.get("data_cursor")
            # A cursor only applies to the epoch it was recorded in; a
            # stale/foreign cursor (hand-moved artifact) is ignored.
            if (isinstance(cursor, dict)
                    and int(cursor.get("epoch", -1)) == self.initial_epoch):
                self._resume_cursor = cursor
            saved_plan = MeshPlan.from_dict(report.get("saved_mesh_plan"))
            if mode == "resharded":
                self.log(
                    f"RESHARDED restore: artifact was saved by "
                    f"{report.get('saved_process_count', '?')} process(es) "
                    f"at mesh {saved_plan.describe()}; restoring onto "
                    f"{distributed.process_count()} process(es) at mesh "
                    f"dp={config.dp} tp={config.tp} cp={config.cp} via "
                    f"current-mesh abstract restore targets")
            obs.counter("resume_total",
                        "model restores by topology relationship",
                        mode=mode).inc()
            if report.get("restored_step") is not None:
                obs.gauge("resume_restored_step",
                          "global step of the restored artifact"
                          ).set(report["restored_step"])
            obs.gauge("resume_restored_epoch",
                      "epoch recorded in the restored artifact"
                      ).set(self.initial_epoch)
            self.log(f"Loaded model weights from {config.model_load_path} "
                     f"(epoch {self.initial_epoch}, resume mode: {mode})")
        self._eval_step = None
        # Bucketed predict-step cache, shared by offline predict, the
        # interactive REPL and the serving batcher: one freshly-jitted
        # eval step per (batch_rows, context_bucket) shape, so the
        # number of pjit compilations the predict path can trigger is
        # bounded by the configured bucket list instead of growing with
        # request shapes. len == compilations (each entry only ever sees
        # its one shape).
        self._predict_steps: Dict[Tuple[int, int], object] = {}
        # (params, float32 target table, served params): _served_params
        self._served: Optional[tuple] = None
        # Async checkpoint commit pipeline; created by _make_save_fn when
        # config.async_checkpointing, closed when training ends.
        self._committer: Optional[ckpt_mod.AsyncCommitter] = None
        # per-variable shape/param dump (reference: tensorflow_model.py:59-63)
        for name, p in sorted(self.state.params.items()):
            self.log(f"variable name: {name} -- shape: "
                     f"{tuple(p.shape)} -- #params: {p.size:,} -- "
                     f"{shard_layout(p)}")
        self.log(f"Model created: {num_params(self.state):,} parameters "
                 f"(mesh dp={config.dp} tp={config.tp} cp={config.cp}); "
                 f"{self.describe_devices()}")

    def describe_devices(self) -> str:
        return describe_devices(self.state.params)

    def warmup(self, rows: Optional[int] = None) -> None:
        """Build + run every (rows, bucket) serve shape once on an
        all-padding batch (the contract of `ReleaseModel.warmup`):
        `serve` calls it before it listens, so no request pays a
        bucket's compile out of its deadline."""
        from code2vec_tpu.data.reader import invalid_batch, slice_contexts
        rows = int(rows or self.config.serve_batch_size)
        empty = invalid_batch(rows, self.config.max_contexts)
        for m in self.context_buckets:
            self._run_predict_step(
                self._get_bucketed_predict_step(rows, m),
                slice_contexts(empty, m))

    # ------------------------------------------------------------ data

    def _init_num_of_examples(self):
        # reference: model_base.py:77-96 (.num_examples sidecar cache)
        config = self.config
        if config.is_training and getattr(config, "train_corpus_manifest",
                                          None):
            from code2vec_tpu.data.packed import ShardedCorpus
            config.num_train_examples = ShardedCorpus.read_manifest_rows(
                config.train_corpus_manifest)
            self.log(f"    Number of train examples: "
                     f"{config.num_train_examples} (corpus manifest)")
        elif config.is_training:
            config.num_train_examples = self._count_examples(config.train_data_path)
            self.log(f"    Number of train examples: {config.num_train_examples}")
        if config.is_testing:
            config.num_test_examples = self._count_examples(config.test_data_path)
            self.log(f"    Number of test examples: {config.num_test_examples}")

    def _train_batches(self) -> Iterable:
        """Training batch stream with EpochEnd markers at data-pass
        boundaries (the trainer schedules save/eval off those). Also sets
        `self._steps_per_epoch` (exact for packed data, None for the
        streaming reader until its first pass completes)."""
        config = self.config
        # each host feeds its slice of the global batch
        # (parallel/distributed.py)
        batch_size = distributed.local_batch_size(config.train_batch_size)
        self._steps_per_epoch = None
        # `num_train_epochs` is the TOTAL epoch budget: a resumed run
        # trains only the remainder (reference: keras fit(initial_epoch=
        # nr_epochs_trained, epochs=NUM_TRAIN_EPOCHS), keras_model.py:
        # 166-178, 264-274).
        epochs_to_run = max(config.num_train_epochs - self.initial_epoch, 0)
        if config.is_loading and epochs_to_run == 0:
            self.log(f"Loaded model already trained {self.initial_epoch} "
                     f"epochs (budget {config.num_train_epochs}); nothing "
                     f"to train. Raise --epochs to continue.")
        if config.use_packed_data:
            ds = self._train_corpus()
            skip_rows = self._cursor_skip_rows()
            # Remembered for save_fn: a SECOND preemption inside the
            # resumed (still-incomplete) epoch must record the restored
            # skip PLUS the new batches — the trainer's batch_in_epoch
            # restarts at 0 on resume and cannot know about the skip.
            self._applied_skip_rows = skip_rows
            self._applied_skip_epoch = (self.initial_epoch if skip_rows
                                        else None)
            local_steps = ds.steps_per_epoch(batch_size, EstimatorAction.Train)
            batches = ds.iter_batches(batch_size,
                                      EstimatorAction.Train,
                                      num_epochs=epochs_to_run,
                                      seed=config.seed,
                                      yield_epoch_markers=True,
                                      start_epoch=self.initial_epoch,
                                      skip_rows=skip_rows)
            if jax.process_count() > 1:
                # Lockstep contract: the elastic global order makes the
                # per-host batch counts equal by construction, but the
                # agreement stays as the desync tripwire (a host reading
                # a different file/vocab would silently diverge here).
                agreed = distributed.agree_scalar(local_steps, "min")
                if agreed == 0:
                    raise RuntimeError(
                        f"a host's data shard yields zero post-filter "
                        f"batches (local: {local_steps}); the pod-agreed "
                        f"step count would be 0 and training would no-op. "
                        f"Use fewer hosts or a larger dataset.")
                if agreed != local_steps:
                    self.log(f"Host feeds {agreed}/{local_steps} local "
                             f"batches per epoch (pod-agreed minimum)")
                if skip_rows:
                    first_steps = ds.steps_per_epoch(
                        batch_size, EstimatorAction.Train,
                        skip_rows=skip_rows)
                    agreed_first = distributed.agree_scalar(first_steps,
                                                            "min")
                else:
                    agreed_first = agreed
                self._steps_per_epoch = agreed
                return distributed.lockstep_train_stream(
                    batches, agreed, first_epoch_steps=agreed_first)
            # steps_per_epoch_hint stays the FULL-epoch count: only the
            # resumed partial epoch's ETA line transiently overestimates
            # (cosmetic); every later epoch needs the full count.
            self._steps_per_epoch = local_steps
            return batches
        self._require_single_process("training from raw .c2v text")
        # The text reader honors the resume cursor too (PR-6 residue
        # closed): the epoch-keyed shuffled order is deterministic, so
        # skipping the first `skip_rows` post-filter rows of the
        # resumed epoch reproduces exactly the packed reader's cursor
        # laws — no row skipped, none double-read.
        skip_rows = self._cursor_skip_rows()
        self._applied_skip_rows = skip_rows
        self._applied_skip_epoch = (self.initial_epoch if skip_rows
                                    else None)
        shard_index, num_shards = distributed.host_shard()
        return PathContextReader(self.vocabs, config, EstimatorAction.Train,
                                 shard_index=shard_index,
                                 num_shards=num_shards,
                                 batch_size=batch_size,
                                 num_epochs=epochs_to_run,
                                 yield_epoch_markers=True,
                                 start_epoch=self.initial_epoch,
                                 skip_rows=skip_rows)

    def _cursor_skip_rows(self) -> int:
        """Remap the restored artifact's data cursor (global rows the
        interrupted epoch consumed) onto the CURRENT host count: each
        host will skip its stride's share (skip_rows // num_hosts) of
        the epoch's global permutation — which is exactly the set of
        rows the old topology already trained on, since the global
        order is host-count invariant. Returns 0 when there is no
        cursor, it is disabled, or the save was at an epoch boundary."""
        config = self.config
        cursor = self._resume_cursor
        if not cursor or not getattr(config, "cursor_resume", True):
            if cursor and cursor.get("global_row_ordinal"):
                self.log("cursor_resume disabled: re-running the "
                         "interrupted epoch from its start")
            return 0
        skip = int(cursor.get("global_row_ordinal", 0) or 0)
        if skip <= 0:
            return 0
        fault_point("cursor_remap")
        nshards = distributed.process_count()
        # Round DOWN to a multiple of the CURRENT global batch: re-reading
        # a few rows is safe, skipping unseen ones is not. Host-count
        # divisibility alone is not enough — a per-host skip that is not
        # a multiple of the LOCAL batch would leave the epoch's remaining
        # sequence batch-misaligned, and the ragged-tail truncation would
        # silently drop never-trained rows at the epoch's end.
        global_bs = config.train_batch_size
        if skip % global_bs:
            adjusted = (skip // global_bs) * global_bs
            self.log(f"Data cursor {skip} (saved at global batch size "
                     f"{cursor.get('global_batch_size', '?')}) is not a "
                     f"multiple of the current global batch {global_bs}; "
                     f"rounding down to {adjusted} (re-reads "
                     f"{skip - adjusted} row(s))")
            skip = adjusted
        self.log(f"Cursor resume: epoch {self.initial_epoch + 1} "
                 f"continues after {skip} already-consumed global rows "
                 f"({skip // nshards} rows of this host's stride)")
        obs.gauge("resume_cursor_skip_rows",
                  "global rows the resumed epoch skipped as "
                  "already-consumed").set(skip)
        return skip

    # ------------------------------------------------------------ train

    def train(self):
        config = self.config
        train_step = self.builder.make_train_step(self.state)
        save_fn = self._make_save_fn() if config.is_saving else None
        evaluate_fn = ((lambda state: self._evaluate_with_params(state.params))
                       if config.is_testing else None)
        batches = self._train_batches()
        committer = self._committer
        trainer = Trainer(config, train_step, mesh=self.mesh,
                          evaluate_fn=evaluate_fn, save_fn=save_fn,
                          profile_dir=config.profile_dir,
                          initial_epoch=self.initial_epoch,
                          steps_per_epoch_hint=self._steps_per_epoch,
                          commit_drain_fn=(committer.drain if committer
                                           else None),
                          heartbeat_extra={
                              "resume_mode":
                                  self.resume_report["resume_mode"],
                              "restored_step":
                                  self.resume_report["restored_step"],
                          })
        try:
            self.state = trainer.train(self.state, batches,
                                       dropout_rng(config))
        finally:
            if committer is not None:
                # The trainer already drained (its finally); this stops
                # the commit thread and surfaces any failure a killed
                # drain left behind. Never mask an in-flight exception —
                # checked BEFORE the close() attempt (inside the except
                # handler sys.exc_info() would report close's own error).
                exc_in_flight = sys.exc_info()[0] is not None
                try:
                    committer.close()
                except Exception:
                    if not exc_in_flight:
                        raise
                self._committer = None
        self.initial_epoch = trainer.final_epoch
        if trainer.preempted:
            # The preemption checkpoint is already on disk; a second full
            # save here could outlive the scheduler's grace window.
            self.log("Preempted: skipping final save (checkpoint already "
                     "written by the preemption handler)")
        elif config.is_saving:
            self.save()
            self.log(f"Model saved in: {config.model_save_path}")

    def _make_save_fn(self):
        config = self.config
        if getattr(config, "async_checkpointing", False):
            self._committer = ckpt_mod.AsyncCommitter(
                max_in_flight=2, log=self.log)
            self.log("Async checkpointing on: commit barrier + manifest "
                     "+ rename run on a background commit thread")
        else:
            self._committer = None

        def save_fn(state, epoch, suffix="", cursor_rows=0):
            # suffix="_preempt" (preemption checkpoints) keeps the save
            # from clobbering the clean end-of-epoch _iter<N> artifact
            # whose metrics the eval log refers to. cursor_rows (global
            # rows the in-flight epoch consumed; 0 at epoch boundaries)
            # becomes the manifest's data cursor, so an elastic resume
            # on ANY host count can continue the pass without skipping
            # or double-reading rows.
            path = f"{config.model_save_path}_iter{epoch}{suffix}"
            ordinal = int(cursor_rows)
            if epoch == getattr(self, "_applied_skip_epoch", None):
                # Still inside the epoch this run RESUMED mid-pass: the
                # trainer's batch counter restarted at 0, so the rows
                # skipped at resume must be added back or a second
                # preemption would record an undercounted cursor (and
                # the next resume would double-read the difference).
                ordinal += self._applied_skip_rows
            cursor = {"epoch": epoch,
                      "global_row_ordinal": ordinal,
                      "global_batch_size": config.train_batch_size}
            if suffix or self._committer is None:
                # Preemption/NaN-halt saves stay SYNCHRONOUS even in
                # async mode: the grace window ends at process exit, so
                # the artifact must be committed before save_fn returns
                # (the trainer drains in-flight commits first).
                ckpt_mod.save_model(path, state, self.vocabs, config,
                                    epoch=epoch, data_cursor=cursor)
                self.log(f"Saved after {epoch} epochs in: {path}")
                if not suffix:
                    self._rotate_epoch_checkpoints()
            else:
                # Rotation rides the commit thread too — it belongs
                # after the rename, and its glob/verify/rmtree walk is
                # exactly the kind of filesystem stall async mode takes
                # off the step path.
                ckpt_mod.save_model(path, state, self.vocabs, config,
                                    epoch=epoch, committer=self._committer,
                                    on_committed=self._rotate_epoch_checkpoints,
                                    data_cursor=cursor)
                self.log(f"Save after {epoch} epochs dispatched to the "
                         f"async commit pipeline: {path}")

        return save_fn

    def _rotate_epoch_checkpoints(self):
        # Rotation rides the save critical path (the trainer is paused),
        # so its wall time is worth a first-class metric.
        with obs.span("checkpoint_rotate",
                      hist=obs.histogram(
                          "checkpoint_rotate_seconds",
                          "orphan sweep + max_to_keep rotation after a "
                          "clean save")):
            self._rotate_epoch_checkpoints_inner()

    def _rotate_epoch_checkpoints_inner(self):
        # reference keeps MAX_TO_KEEP epoch checkpoints (config.py:57).
        config = self.config
        if distributed.process_count() > 1 and distributed.process_index():
            # On a pod the artifact store is shared: process 0 — the
            # commit-protocol's single committing host — also owns
            # rotation. Peers sweeping concurrently would race the
            # rmtree/promote walk (and mis-probe the liveness of
            # process 0's shared staging dir from another machine).
            return
        pattern = f"{config.model_save_path}_iter*"
        # Sweep orphaned commit-protocol dirs (`.tmp-<pid>` staging /
        # `.old-<pid>` backups) left by killed saves — but never another
        # LIVE process's in-flight staging dir. A complete orphan whose
        # final name sits empty (kill landed between the swap renames)
        # is promoted back rather than deleted; `.tmp-` dirs go first so
        # the NEWER state wins the slot over its `.old-` predecessor.
        orphans = [p for p in glob.glob(pattern)
                   if ckpt_mod.is_staging_path(p)
                   and not ckpt_mod.staging_owner_alive(p)]
        for p in sorted(orphans,
                        key=lambda p: ckpt_mod.BACKUP_INFIX in os.path.basename(p)):
            outcome = ckpt_mod.reclaim_orphan(p, log=self.log)
            obs.counter("checkpoint_orphans_reclaimed_total",
                        "orphaned commit-protocol dirs swept or promoted "
                        "by rotation", outcome=outcome).inc()
            if outcome == "removed":
                self.log(f"Swept orphaned checkpoint staging dir {p}")
        paths = glob.glob(pattern)  # re-glob: promotion adds artifacts
        parsed = {p: ckpt_mod.parse_iter_name(p) for p in paths}

        valid_cache: Dict[str, bool] = {}

        def is_valid(p: str) -> bool:
            if p not in valid_cache:
                try:
                    ckpt_mod.verify_checkpoint(p)
                    valid_cache[p] = True
                except ckpt_mod.CheckpointIntegrityError:
                    valid_cache[p] = False
            return valid_cache[p]

        clean = sorted((p for p, v in parsed.items()
                        if v is not None and not v[1]),
                       key=lambda p: parsed[p][0])
        victims = clean[:-config.max_to_keep] if config.max_to_keep else []
        retained = clean[len(victims):]
        if victims and not any(is_valid(p) for p in retained):
            # Never delete the only valid artifact: if every retained
            # checkpoint fails its integrity check (disk rot, torn
            # writes), keep the newest victim that still verifies —
            # losing rotation hygiene beats losing the run.
            for p in reversed(victims):
                if is_valid(p):
                    self.log(f"Rotation keeping over-quota checkpoint {p}:"
                             f" it is the only one passing verification")
                    victims.remove(p)
                    break
        for stale in victims:
            shutil.rmtree(stale, ignore_errors=True)
        # A clean epoch save supersedes any preemption checkpoint from
        # that epoch or earlier; without this, repeatedly-preempted
        # long runs accumulate unbounded `_iter<N>_preempt` artifacts.
        # Only a clean artifact that VERIFIES supersedes: deleting a
        # preempt checkpoint on the say-so of a corrupt newer save could
        # delete the only loadable state.
        newest_valid_clean = next(
            (parsed[p][0] for p in reversed(clean) if is_valid(p)), None)
        if newest_valid_clean is not None:
            for p, v in parsed.items():
                if v is not None and v[1] and v[0] <= newest_valid_clean:
                    shutil.rmtree(p, ignore_errors=True)

    # ------------------------------------------------------------ eval

    def _get_eval_step(self):
        if self._eval_step is None:
            self._eval_step = self.builder.make_eval_step(self.state)
            head_sorted_columns_gauge("eval").set(
                self.builder.eval_head_sorted_columns(
                    self.config.test_batch_size))
        return self._eval_step

    def evaluate(self) -> Optional[ModelEvaluationResults]:
        config = self.config
        if config.release:
            # reference: tensorflow_model.py:131-135 — re-save weights-only.
            released = ckpt_mod.save_model(
                config.model_load_path, self.state, self.vocabs, config,
                released=True)
            self.log(f"Releasing model, output model: {released}")
            return None
        return self._evaluate_with_params(self.state.params)

    def _evaluate_with_params(self, params) -> ModelEvaluationResults:
        config = self.config
        evaluator = Evaluator(config, self.vocabs, self._get_eval_step(),
                              mesh=self.mesh)
        if not config.export_code_vectors:
            return evaluator.evaluate(params, self._eval_batches())
        vectors_base = config.test_data_path + ".vectors"
        from code2vec_tpu.retrieval.store import (
            MANIFEST_NAME, VectorStoreWriter,
        )
        if getattr(config, "vectors_text", False):
            # reference compat (tensorflow_model.py:160-162): one
            # space-joined vector per line. A prior default-format
            # export left a store DIRECTORY at this path; exporting
            # overwrites its own output either way, so clear it —
            # but only a directory that really is our store.
            if os.path.isdir(vectors_base):
                if not os.path.isfile(os.path.join(vectors_base,
                                                   MANIFEST_NAME)):
                    raise ValueError(
                        f"{vectors_base} is a directory that is not a "
                        f"code2vec vector store; refusing to replace "
                        f"it with the text export")
                shutil.rmtree(vectors_base)
            return evaluator.evaluate(params, self._eval_batches(),
                                      code_vectors_path=vectors_base)
        # Default: the sharded retrieval store format (retrieval/
        # store.py) — the SAME on-disk layout the `embed` batch job
        # writes, so offline export feeds `index-build` directly and
        # carries the embedding fingerprint the index needs. A prior
        # --vectors_text export left a FILE here; same overwrite
        # semantics.
        if os.path.isfile(vectors_base):
            os.unlink(vectors_base)
        writer = VectorStoreWriter(
            vectors_base, dim=config.code_vector_size,
            dtype=getattr(config, "embed_dtype", "float32"),
            model_fingerprint=self.model_fingerprint(),
            source=config.test_data_path,
            shard_rows=getattr(config, "embed_shard_rows", 65536),
            resume=False, log=self.log)
        results = evaluator.evaluate(params, self._eval_batches(),
                                     code_vectors_sink=writer.append)
        manifest = writer.finalize()
        self.log(f"Code vectors exported as a vector store at "
                 f"{vectors_base} ({manifest['rows']} rows, "
                 f"{len(manifest['shards'])} shard(s); --vectors_text "
                 f"restores the reference text layout)")
        return results

    # ---------------------------------------------------------- predict

    def _make_predict_step(self, batch_rows: int, m: int):
        # a FRESH jitted eval step per shape (BucketedPredictMixin): each
        # entry compiles exactly once for its one padded shape
        head_sorted_columns_gauge("predict").set(
            self.builder.eval_head_sorted_columns(batch_rows))
        return self.builder.make_eval_step(self.state)

    def describe_head(self) -> str:
        """The served head in a few words, for the server's start-up
        line: what the head's merge sorts a trip."""
        rows = int(self.config.serve_batch_size)
        return (f"head exact, "
                f"{self.builder.eval_head_sorted_columns(rows)} columns "
                f"sorted a trip at {rows} rows")

    def _call_predict_step(self, step, arrays):
        return step(self._served_params(), *arrays)

    def _served_params(self):
        """The params a SERVED step reads: the live leaves, the target
        table in the module's compute dtype. The head streams that table
        block by block and casts each block; XLA hoists the cast onto
        the whole table and, handed the float32 master, runs it in every
        batch (0.91 of a 4.69 ms step at java14m's sizes, ops/topk.py).
        Serving's weights change only when `self.state` does, so the
        cast is made once per state: the copy is keyed on the identity
        of the params and of the float32 leaf, and a load, a restore, a
        training run or a swapped model is followed by the next call.
        The same `astype` of the same values: every logit is bitwise
        what the step fed the float32 table computes. The training-time
        eval step (`_get_eval_step`) keeps the masters: they change
        every step."""
        live = self.state.params
        table = live["target_embedding"]
        dtype = self.module.compute_dtype
        if table.dtype == dtype:
            return live
        held = self._served
        if held is None or held[0] is not live or held[1] is not table:
            held = self._served = (live, table, dict(
                live, target_embedding=table.astype(dtype)))
        return held[2]

    def eval_callable(self):
        """(eval_step, params) pair for callers that drive the eval step
        directly over packed batches — the Evaluator's division of labor,
        shared with the batch embed job (retrieval/embed_job.py). The
        release runtime exposes the same surface over artifact tables."""
        return self._get_eval_step(), self.state.params

    def model_fingerprint(self) -> str:
        ident = os.path.abspath(self.config.model_load_path
                                or self.config.model_save_path
                                or f"seed{self.config.seed}")
        step = int(jax.device_get(self.state.step))
        return f"ckpt:{ident}@step{step}#p{num_params(self.state)}"

    # ------------------------------------------------------------ save

    def save(self, model_save_path: Optional[str] = None) -> str:
        path = model_save_path or self.config.model_save_path
        return ckpt_mod.save_model(path, self.state, self.vocabs, self.config,
                                   epoch=self.initial_epoch,
                                   data_cursor={
                                       "epoch": self.initial_epoch,
                                       "global_row_ordinal": 0,
                                       "global_batch_size":
                                           self.config.train_batch_size})

    # --------------------------------------------------------- exports

    def _get_vocab_embedding_as_np_array(self, vocab_type: VocabType) -> np.ndarray:
        name = {VocabType.Token: "token_embedding",
                VocabType.Path: "path_embedding",
                VocabType.Target: "target_embedding"}[vocab_type]
        table = np.asarray(jax.device_get(self.state.params[name]))
        real_rows = self.vocabs.get(vocab_type).size
        return table[:real_rows]

    def save_word2vec_format(self, dest_save_path: str, vocab_type: VocabType):
        # reference: model_base.py:176-182
        if vocab_type not in VocabType:
            raise ValueError("`vocab_type` should be a VocabType")
        matrix = self._get_vocab_embedding_as_np_array(vocab_type)
        index_to_word = self.vocabs.get(vocab_type).index_to_word
        with open(dest_save_path, "w") as f:
            common_mod.save_word2vec_file(f, index_to_word, matrix)
        self.log(f"Saved {vocab_type} word2vec format to {dest_save_path}")

    def export_embeddings(self, out_dir: str) -> Dict[str, str]:
        """The `export-embeddings` subcommand body: the reference's
        --save_w2v (token table) and --save_t2v (target table) as one
        artifact directory — `tokens.w2v` + `targets.w2v` in word2vec
        text format, real-vocab rows only
        (_get_vocab_embedding_as_np_array trims the padded tail)."""
        os.makedirs(out_dir, exist_ok=True)
        paths = {"tokens": os.path.join(out_dir, "tokens.w2v"),
                 "targets": os.path.join(out_dir, "targets.w2v")}
        self.save_word2vec_format(paths["tokens"], VocabType.Token)
        self.save_word2vec_format(paths["targets"], VocabType.Target)
        self.log(f"Embedding tables exported to {out_dir} "
                 f"(word2vec text format)")
        return paths
