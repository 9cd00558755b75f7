"""Release-artifact runtime: the quantized serving/eval fast path.

`ReleaseModel` is the serving-side twin of the training facade: it
exposes the exact `predict` surface PredictionServer and the REPL drive
(BucketedPredictMixin in model_facade.py — same line parsing, context
bucketing, compiled-step cache), but is built from a release artifact
(release/artifact.py) instead of a checkpoint:

- tables live on device as int8 + per-row f32 scales (or f32 for an
  unquantized artifact); the fp32 training tables, the Adam state and
  the Orbax machinery are never materialized — a replica's RSS is the
  artifact, not the checkpoint;
- the forward fuses dequant into the gathers (ops/quant.py) and streams
  the target classifier through the blockwise top-k merge (ops/topk.py)
  — the (B, 246K) logit row never exists;
- each (rows, context-bucket) serve shape cold-starts from the
  artifact's AOT lowering (jax.export) when one matches the current
  backend, falling back to a fresh jit otherwise (counted in
  `serving_aot_loads_total{outcome=...}`).

The forward math mirrors models/code2vec.py transform_gathered/encode
with deterministic=True; eval CE comes from the blockwise logsumexp
minus the gathered label logit, so the standard Evaluator can score an
artifact directly through `ReleaseModel.eval_step`.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.model_facade import BucketedPredictMixin
from code2vec_tpu.ops.attention import masked_single_query_attention
from code2vec_tpu.ops.quant import table_gather
from code2vec_tpu.ops.topk import (
    blockwise_matmul_top_k, gathered_label_logits,
)
from code2vec_tpu.release.artifact import (
    QUANTIZED_SCHEMES, SCHEME_FP8_E4M3, SCHEME_FP8_E5M2, SCHEME_INT4,
    SCHEME_INT8, ReleaseArtifact, load_artifact, table_dim,
)
from code2vec_tpu.training.step import EvalOutputs
from code2vec_tpu.utils.device import describe_devices
from code2vec_tpu.vocab import Code2VecVocabs


def _backend_matches(backend: str, platforms) -> bool:
    """True when the current jax backend can run an AOT lowering
    exported for `platforms`. jax.export records lowering platform
    names ('cpu', 'tpu', 'cuda', 'rocm') while jax.default_backend()
    reports the backend family ('cpu', 'tpu', 'gpu') — on GPU the two
    vocabularies differ, so a literal `in` test would send every GPU
    replica down the jit fallback."""
    names = {str(p).lower() for p in platforms if p}
    if backend in names:
        return True
    return backend == "gpu" and bool(names & {"cuda", "rocm"})


def _aot_counter(outcome: str):
    return obs.counter(
        "serving_aot_loads_total",
        "predict-step builds by source: aot (deserialized jax.export "
        "lowering), jit_fallback (no matching lowering / wrong "
        "platform), jit_error (lowering present but unusable)",
        outcome=outcome)


def make_release_step(meta: dict):
    """Pure serve/eval function over artifact params:
    (params, src, pth, tgt, mask, labels, valid) ->
    (topk_values, topk_indices, code_vectors, attention, loss_sum).

    Returns a plain tuple (not EvalOutputs) so jax.export can serialize
    the output pytree without namedtuple registration; callers wrap.
    """
    dims = meta["dims"]
    scheme = meta["quantization"]["scheme"]
    quantized = scheme in QUANTIZED_SCHEMES
    int4 = scheme == SCHEME_INT4
    compute_dtype = jnp.dtype(meta["compute_dtype"])
    k = min(int(meta["topk"]), int(dims["real_target_vocab_size"]))
    raw_block = meta.get("topk_block_size")
    block = 4096 if raw_block is None else int(raw_block)
    if block <= 0:
        # The exporter pinned the classic full-logits path (--topk_block
        # 0): one block spanning the table computes exactly the full
        # matmul + lax.top_k, so honoring it is a block of V rows — not
        # a silent coercion back to the 4096 default.
        block = int(dims["target_vocab_size"])
    oov_floor = int(dims["target_oov_floor"])
    real_v = int(dims["real_target_vocab_size"])

    def scale(params, name):
        return params[f"{name}_scale"] if quantized else None

    def int4_dim(name):
        # int4 tables travel packed; their consumers need the unpacked
        # column count (ops/quant.py unpack_int4)
        return table_dim(dims, name) if int4 else None

    def step(params, src, pth, tgt, mask, labels, valid):
        tok, tok_s = params["token_embedding"], scale(params, "token_embedding")
        src_rows = table_gather(tok, tok_s, src,
                                int4_dim=int4_dim("token_embedding"))
        tgt_rows = table_gather(tok, tok_s, tgt,
                                int4_dim=int4_dim("token_embedding"))
        pth_rows = table_gather(params["path_embedding"],
                                scale(params, "path_embedding"), pth,
                                int4_dim=int4_dim("path_embedding"))
        # concat/cast/tanh-transform/attention exactly as
        # models/code2vec.py transform_gathered + encode (deterministic).
        # Hand-mirrored rather than routed through module.apply (the
        # flax param tree would have to bind int8 tables it never
        # reads); any drift from the canonical forward fails
        # test_release_fp32_forward_matches_facade in tests/test_quant.py.
        ctx = jnp.concatenate([src_rows, pth_rows, tgt_rows],
                              axis=-1).astype(compute_dtype)
        transformed = jnp.tanh(jnp.einsum(
            "bmc,cd->bmd", ctx, params["transform"].astype(compute_dtype),
            preferred_element_type=jnp.float32)).astype(compute_dtype)
        code_vectors, attention = masked_single_query_attention(
            transformed, params["attention"][:, 0], mask)
        code_vectors = code_vectors.astype(jnp.float32)
        target_s = scale(params, "target_embedding")
        out = blockwise_matmul_top_k(
            code_vectors, params["target_embedding"], k, block,
            scales=target_s, valid_rows=real_v, compute_dtype=compute_dtype,
            int4_dim=int4_dim("target_embedding"))
        label_logit = gathered_label_logits(
            code_vectors, params["target_embedding"], labels,
            scales=target_s, compute_dtype=compute_dtype,
            int4_dim=int4_dim("target_embedding"))
        loss_rows = valid & (labels > oov_floor)
        ce = (out.lse - label_logit) * loss_rows.astype(jnp.float32)
        return (out.values, out.indices.astype(jnp.int32), code_vectors,
                attention, jnp.sum(ce))

    return step


def _table_device_dtype(scheme: str):
    """Device dtype of the table params per scheme. fp8 payloads are
    bitcast from their on-disk uint8 patterns back to the fp8 dtype at
    load, so the step's astype decodes them; int4 stays packed uint8."""
    return {
        SCHEME_INT8: jnp.int8,
        SCHEME_FP8_E4M3: jnp.float8_e4m3fn,
        SCHEME_FP8_E5M2: jnp.float8_e5m2,
        SCHEME_INT4: jnp.uint8,
    }.get(scheme, jnp.float32)


def param_specs(meta: dict) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs of the artifact param tree (AOT export specs)."""
    dims = meta["dims"]
    scheme = meta["quantization"]["scheme"]
    quantized = scheme in QUANTIZED_SCHEMES
    d_tok, d_path = int(dims["token_dim"]), int(dims["path_dim"])
    code_dim = d_path + 2 * d_tok
    shapes = {
        "token_embedding": (int(dims["token_vocab_size"]), d_tok),
        "path_embedding": (int(dims["path_vocab_size"]), d_path),
        "target_embedding": (int(dims["target_vocab_size"]), code_dim),
    }
    if scheme == SCHEME_INT4:
        shapes = {name: (v, (d + 1) // 2)
                  for name, (v, d) in shapes.items()}
    table_dtype = _table_device_dtype(scheme)
    specs = {name: jax.ShapeDtypeStruct(shape, table_dtype)
             for name, shape in shapes.items()}
    if quantized:
        for name, shape in shapes.items():
            specs[f"{name}_scale"] = jax.ShapeDtypeStruct(
                (shape[0], 1), jnp.float32)
    specs["transform"] = jax.ShapeDtypeStruct((code_dim, code_dim),
                                              jnp.float32)
    specs["attention"] = jax.ShapeDtypeStruct((code_dim, 1), jnp.float32)
    return specs


def batch_specs(rows: int, m: int) -> Tuple[jax.ShapeDtypeStruct, ...]:
    return (jax.ShapeDtypeStruct((rows, m), jnp.int32),   # src
            jax.ShapeDtypeStruct((rows, m), jnp.int32),   # pth
            jax.ShapeDtypeStruct((rows, m), jnp.int32),   # tgt
            jax.ShapeDtypeStruct((rows, m), jnp.float32),  # mask
            jax.ShapeDtypeStruct((rows,), jnp.int32),     # labels
            jax.ShapeDtypeStruct((rows,), jnp.bool_))     # valid


def aot_export_serve_functions(out_dir: str, meta: dict, log=print) -> dict:
    """jax.export every (serve_batch_size, bucket) serve shape into
    `<out_dir>/aot/`; returns the meta["aot"] record. Lowerings are
    platform-tagged — a consumer on another backend jit-falls-back."""
    import os

    from jax import export as jax_export

    aot_dir = os.path.join(out_dir, "aot")
    os.makedirs(aot_dir, exist_ok=True)
    step = make_release_step(meta)
    specs = param_specs(meta)
    rows = int(meta["serve_batch_size"])
    entries = {}
    platforms = None
    t0 = time.perf_counter()
    for m in meta["buckets"]:
        exported = jax_export.export(jax.jit(step))(specs,
                                                    *batch_specs(rows, m))
        if platforms is None:
            platforms = list(exported.platforms)
        name = f"serve_r{rows}_m{m}.jaxexport"
        with open(os.path.join(aot_dir, name), "wb") as f:
            f.write(exported.serialize())
        entries[f"r{rows}_m{m}"] = f"aot/{name}"
    record = {
        "platform": jax.default_backend(),
        "platforms": platforms,
        "jax_version": jax.__version__,
        "entries": entries,
    }
    log(f"AOT-exported {len(entries)} serve shape(s) "
        f"(rows={rows}, buckets={list(meta['buckets'])}) for platform "
        f"{record['platform']} in {time.perf_counter() - t0:.2f}s")
    return record


class ReleaseModel(BucketedPredictMixin):
    """Serving/eval model over a release artifact — drop-in for the
    facade on the predict surface (PredictionServer, InteractivePredictor,
    offline predict, Evaluator via `eval_step`)."""

    def __init__(self, config, artifact: Optional[ReleaseArtifact] = None,
                 log=None):
        self.config = config
        self.log = log or config.log
        self.artifact = artifact or load_artifact(config.serve_artifact)
        meta = self.meta = self.artifact.meta
        self.mesh = None
        # The artifact is authoritative for everything that shapes the
        # compiled steps and the parse: a mismatched CLI override would
        # silently compile shapes the AOT store doesn't have (or parse
        # at the wrong context budget).
        config.max_contexts = int(meta["max_contexts"])
        config.separate_oov_and_pad = bool(meta["separate_oov_and_pad"])
        if config.top_k_words_considered_during_prediction != \
                int(meta["topk"]):
            # The serve step (and its AOT lowerings) are baked at the
            # export-time k; honoring a different serve-time --topk
            # would silently truncate predictions and mis-denominate
            # top-k metrics, so the artifact wins and the override is
            # visible in the log.
            self.log(
                f"topk {config.top_k_words_considered_during_prediction} "
                f"differs from the artifact's exported {meta['topk']}: "
                f"the artifact is authoritative (re-export to change k)")
            config.top_k_words_considered_during_prediction = \
                int(meta["topk"])
        self._context_buckets = tuple(int(b) for b in meta["buckets"])
        art_rows = int(meta["serve_batch_size"])
        if config.serve_batch_size != art_rows:
            fields = getattr(type(config), "__dataclass_fields__", {})
            default_rows = getattr(fields.get("serve_batch_size"),
                                   "default", None)
            explicit = "serve_batch_size" in getattr(
                config, "explicit_knobs", ())
            if config.serve_batch_size == default_rows and not explicit:
                # The consumer never asked for a batch size — it holds
                # the config default and the flag was not on the command
                # line (explicit_knobs). Adopting the artifact's exported
                # size keeps every serve shape on its AOT lowering;
                # leaving the default would silently trade the entire
                # trace-free cold start for nothing. An EXPLICIT
                # --serve_batch_size always wins, even when it equals
                # the default — the operator may be bounding per-request
                # latency/memory on a small replica.
                self.log(
                    f"adopting the artifact's AOT-exported "
                    f"serve_batch_size {art_rows} (config held the "
                    f"default {default_rows})")
                config.serve_batch_size = art_rows
            else:
                self.log(
                    f"serve_batch_size {config.serve_batch_size} differs "
                    f"from the artifact's AOT-exported {art_rows}: serve "
                    f"shapes will jit-compile instead of AOT-loading")
        self.vocabs = Code2VecVocabs.load(
            self.artifact.dictionaries_path,
            separate_oov_and_pad=config.separate_oov_and_pad)
        # Device-resident artifact params: quantized tables + f32 scales
        # (one transfer each; the mmap'd host copies are dropped after
        # this). fp8 payloads travel on disk as uint8 bit patterns
        # (numpy's npy mmap cannot represent ml_dtypes) and are viewed
        # back to their fp8 dtype here, so the step's astype decodes
        # them; int4 tables stay packed (unpacked per gathered row).
        import ml_dtypes
        fp8_np = {SCHEME_FP8_E4M3: ml_dtypes.float8_e4m3fn,
                  SCHEME_FP8_E5M2: ml_dtypes.float8_e5m2}.get(
            self.artifact.scheme)
        self.params = {}
        for name, arr in self.artifact.tables.items():
            if fp8_np is not None and not name.endswith(".scale") \
                    and arr.dtype == np.uint8:
                arr = np.asarray(arr).view(fp8_np)
            self.params[name.replace(".scale", "_scale")] = jnp.asarray(arr)
        self._step_fn = make_release_step(meta)
        self._predict_steps: Dict[Tuple[int, int], object] = {}
        self.aot_loads = {"aot": 0, "jit_fallback": 0, "jit_error": 0}
        self.log(
            f"Release model loaded from {self.artifact.path}: scheme="
            f"{self.artifact.scheme}, tables "
            f"{self.artifact.table_bytes() / 1e6:.1f} MB, buckets "
            f"{list(self._context_buckets)}, fingerprint "
            f"{self.artifact.fingerprint[:12]}, aot="
            f"{'none' if not meta.get('aot') else meta['aot']['platform']}"
            f"; {self.describe_devices()}")

    def describe_devices(self) -> str:
        return describe_devices(self.params)

    @property
    def context_buckets(self) -> Tuple[int, ...]:
        return self._context_buckets

    def _default_predict_batch_size(self) -> int:
        """Default predict chunks to the serve batch size (the
        artifact's AOT-exported rows unless --serve_batch_size
        overrode it): `--predict --artifact` and offline predict then
        cold-start from the shipped lowerings instead of tracing a
        (test_batch_size, bucket) shape the AOT store never saw."""
        return int(self.config.serve_batch_size)

    def model_fingerprint(self) -> str:
        return f"artifact:{self.artifact.fingerprint[:16]}"

    # ------------------------------------------------- predict plumbing

    def _make_predict_step(self, batch_rows: int, m: int):
        aot = self.meta.get("aot") or {}
        path = self.artifact.aot_path(batch_rows, m)
        if path is not None and _backend_matches(
                jax.default_backend(),
                aot.get("platforms") or [aot.get("platform")]):
            try:
                from jax import export as jax_export
                with open(path, "rb") as f:
                    exported = jax_export.deserialize(bytearray(f.read()))
                # jit around .call caches the (opaque-body) executable so
                # repeat calls skip the export calling-convention shim.
                step = jax.jit(exported.call)
                # Deserializing alone does not prove the lowering runs
                # here — version/platform skew can surface at first
                # execution, which happens inside the batcher dispatch
                # where nothing catches it. Run the step once now so a
                # stale lowering lands in this except and degrades to
                # jit instead of erroring every request on this bucket.
                jax.block_until_ready(
                    step(self.params, *self._dummy_batch(batch_rows, m)))
                self.aot_loads["aot"] += 1
                _aot_counter("aot").inc()
                return step
            except Exception as e:  # noqa: BLE001 — a stale lowering
                # must degrade to jit, never take the replica down
                self.aot_loads["jit_error"] += 1
                _aot_counter("jit_error").inc()
                self.log(f"AOT lowering {path} unusable "
                         f"({type(e).__name__}: {e}); jit fallback")
        else:
            self.aot_loads["jit_fallback"] += 1
            _aot_counter("jit_fallback").inc()
        return jax.jit(self._step_fn)

    @staticmethod
    def _dummy_batch(rows: int, m: int):
        """All-padding batch of one serve shape (AOT validation, warmup)."""
        return (jnp.zeros((rows, m), jnp.int32),
                jnp.zeros((rows, m), jnp.int32),
                jnp.zeros((rows, m), jnp.int32),
                jnp.ones((rows, m), jnp.float32),
                jnp.zeros((rows,), jnp.int32),
                jnp.ones((rows,), bool))

    def _call_predict_step(self, step, arrays):
        return EvalOutputs(*step(self.params, *arrays))

    # ------------------------------------------------------------- eval

    def eval_step(self, _params_unused, *arrays) -> EvalOutputs:
        """Evaluator-compatible signature: the standard Evaluator can
        score an artifact (quality-delta benches) — params come from the
        artifact, the first argument is accepted and ignored."""
        rows, m = arrays[0].shape
        step = self._get_bucketed_predict_step(rows, m)
        return self._call_predict_step(step, arrays)

    def eval_callable(self):
        """(eval_step, params) — the facade's surface for direct eval
        drivers (Evaluator, retrieval/embed_job.py). Params are the
        artifact's, bound inside `eval_step`, so the slot is None."""
        return self.eval_step, None

    def evaluate(self):
        """Score the artifact on config.test_data_path with the
        reference-definition metrics (the facade `--test` surface for a
        release bundle; `--artifact DIR --test data.c2v` in the CLI)."""
        from code2vec_tpu.evaluation.evaluator import Evaluator
        config = self.config
        config.num_test_examples = self._count_examples(
            config.test_data_path)
        evaluator = Evaluator(config, self.vocabs, self.eval_step,
                              mesh=None)
        return evaluator.evaluate(None, self._eval_batches())

    def warmup(self, rows: Optional[int] = None) -> float:
        """Build + run every (rows, bucket) serve shape once on a dummy
        batch; returns wall seconds. This is the replica cold-start the
        AOT store exists to shrink (measured in quant_bench)."""
        rows = int(rows or self.config.serve_batch_size)
        t0 = time.perf_counter()
        for m in self.context_buckets:
            step = self._get_bucketed_predict_step(rows, m)
            out = self._call_predict_step(step, self._dummy_batch(rows, m))
            jax.block_until_ready(out.topk_indices)
        return time.perf_counter() - t0
