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


def make_release_step(meta: dict, mips_topk=None):
    """Pure serve/eval function over artifact params:
    (params, src, pth, tgt, mask, labels, valid) ->
    (topk_values, topk_indices, code_vectors, attention, loss_sum).

    `mips_topk` (a retrieval/mips.py `MipsHead.topk_fn` closure)
    replaces the exact blockwise classifier head with the
    approximate-MIPS candidate search — serve/predict only, never the
    accuracy-eval path (config.verify rejects the combination); its
    steps report loss_sum = 0 (no logsumexp exists over a candidate
    subset, and no serving consumer reads it).

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
        if mips_topk is not None:
            values, indices = mips_topk(code_vectors)
            return (values, indices, code_vectors, attention,
                    jnp.zeros((), jnp.float32))
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
        mips_nprobe = int(getattr(config, "serve_mips_nprobe", 0) or 0)
        # Batch-shape-aware head dispatch (--serve_mips_crossover, the
        # PR-14 residue: MIPS wins 10-56x single-row but loses at bulk):
        # batches with <= mips_rows live rows route to the MIPS head,
        # bulk shapes to the exact blockwise head. -1 adopts the
        # crossover the export calibration recorded in the artifact
        # meta (mips_crossover) and falls back to legacy all-MIPS for
        # artifacts without one; 0 disables MIPS entirely (exact-only,
        # bit-for-bit the nprobe=0 path); a crossover at or above the
        # serve batch size IS all-MIPS (every batch is below it).
        crossover = int(getattr(config, "serve_mips_crossover", -1))
        self.mips_rows = 0          # hybrid threshold; 0 = no split
        self._mips_all = False
        if mips_nprobe > 0:
            if crossover == 0:
                mips_nprobe = 0
            elif crossover < 0:
                calibrated = int(meta.get("mips_crossover", 0) or 0)
                if calibrated > 0:
                    self.mips_rows = calibrated
                else:
                    self._mips_all = True
            else:
                self.mips_rows = crossover
            if self.mips_rows >= int(config.serve_batch_size):
                self._mips_all, self.mips_rows = True, 0
        self.params = {}
        for name, arr in self.artifact.tables.items():
            if self._mips_all and name.startswith("target_embedding"):
                # all-MIPS: the head (below) holds the list-reordered
                # copy and the exact head never runs, so transferring
                # the original-order table would double the dominant
                # table's device footprint. Hybrid dispatch keeps it —
                # the exact head serves every bulk batch.
                continue
            if fp8_np is not None and not name.endswith(".scale") \
                    and arr.dtype == np.uint8:
                arr = np.asarray(arr).view(fp8_np)
            self.params[name.replace(".scale", "_scale")] = jnp.asarray(arr)
        self._step_fn = make_release_step(meta)
        # Approximate-MIPS prediction head (--serve_mips_nprobe > 0):
        # built once from the artifact's (quantized) target table; the
        # predict steps then search nprobe coarse lists instead of
        # streaming the whole classifier. AOT lowerings bake the exact
        # head, so MIPS steps always jit (logged below); the exact
        # `_step_fn` remains the fallback/eval program.
        self.mips_head = None
        self._mips_step = None
        if mips_nprobe > 0:
            from code2vec_tpu.retrieval.mips import MipsHead
            dims = meta["dims"]
            int4_dim = (int(dims["path_dim"]) + 2 * int(dims["token_dim"])
                        if self.artifact.scheme == SCHEME_INT4 else None)
            # Build from the HOST-side artifact tables (fp8 viewed to
            # its ml_dtypes type, like the device-param load above) —
            # the head holds the list-reordered quantized rows on
            # device. All-MIPS skipped the original-order table in the
            # device-param loop above (the MIPS step never reads it)
            # so the dominant table is device-resident exactly once;
            # hybrid dispatch pays for both copies because the exact
            # head still serves every bulk batch.
            tgt = np.asarray(self.artifact.tables["target_embedding"])
            if fp8_np is not None and tgt.dtype == np.uint8:
                tgt = tgt.view(fp8_np)
            tgt_scale = self.artifact.tables.get("target_embedding.scale")
            self.mips_head = MipsHead.build(
                tgt,
                None if tgt_scale is None else np.asarray(tgt_scale),
                real_vocab=int(dims["real_target_vocab_size"]),
                nlist=int(getattr(config, "serve_mips_nlist", 0) or 0),
                nprobe=mips_nprobe, int4_dim=int4_dim,
                seed=int(getattr(config, "seed", 0)), log=self.log)
            k = min(int(meta["topk"]),
                    int(dims["real_target_vocab_size"]))
            self._mips_step = make_release_step(
                meta, mips_topk=self.mips_head.topk_fn(k, mips_nprobe))
            mode = ("all batches" if self._mips_all
                    else f"batches with <= {self.mips_rows} live rows "
                         f"(exact blockwise head above)")
            self.log(f"Approximate-MIPS head active for {mode}: nprobe "
                     f"{self.mips_head.nprobe}/{self.mips_head.nlist} "
                     f"lists per prediction (MIPS steps always jit — "
                     f"the AOT lowerings bake the exact head)")
        self._predict_steps: Dict[Tuple[int, int], object] = {}
        # MIPS steps cached apart from the exact `_predict_steps` so
        # compile-count surfaces (healthz, quant_bench) keep counting
        # exact serve shapes, and each head's compile budget stays
        # <= len(buckets) per rows shape.
        self._mips_predict_steps: Dict[Tuple[int, int], object] = {}
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
        if self._mips_all:
            return jax.jit(self._mips_step)
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

    def _get_mips_predict_step(self, rows: int, m: int):
        key = (rows, m)
        step = self._mips_predict_steps.get(key)
        if step is None:
            step = self._mips_predict_steps[key] = jax.jit(self._mips_step)
            self.log(f"Compiled MIPS predict step for shape "
                     f"(rows={rows}, contexts={m})")
        return step

    def _dispatch_predict_step(self, n: int, batch_rows: int, m: int):
        """Per-batch-shape head dispatch: batches whose LIVE row count
        is at or below the resolved crossover route to the MIPS head
        compiled at the crossover shape (small batches repad down, so
        a lone interactive row never pays the bulk shape's exact
        scan); everything else takes the exact blockwise head at the
        serve shape. All-MIPS and exact-only modes degenerate to the
        single-head behaviour."""
        if self._mips_all:
            return (self._get_bucketed_predict_step(batch_rows, m),
                    batch_rows, "mips")
        if 0 < n <= self.mips_rows:
            return (self._get_mips_predict_step(self.mips_rows, m),
                    self.mips_rows, "mips")
        return (self._get_bucketed_predict_step(batch_rows, m),
                batch_rows, "exact")

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
            if self.mips_rows > 0:
                # hybrid dispatch: small batches take the MIPS head at
                # the crossover shape — warm it too or the first
                # interactive request pays the jit it was routed to
                # avoid
                step = self._get_mips_predict_step(self.mips_rows, m)
                out = self._call_predict_step(
                    step, self._dummy_batch(self.mips_rows, m))
                jax.block_until_ready(out.topk_indices)
        return time.perf_counter() - t0


def calibrate_mips_crossover(artifact_dir: str, config, log=print):
    """Export-time head-crossover calibration: load the just-written
    artifact, time the exact blockwise head against the MIPS head on
    dummy batches over a small rows grid (one context bucket — the
    crossover is a rows property; per-context cost scales both heads
    alike), and return `(crossover, table)` where crossover is the
    largest row count at which MIPS still wins (0 if it never does,
    scanning stops at the first exact-head win so a noisy outlier deep
    in bulk territory cannot stretch the threshold). The exporter
    records the value as meta["mips_crossover"]; serving adopts it via
    --serve_mips_crossover -1. Timings are median-of-3 after a warmup
    execution, so jit/compile cost never pollutes the comparison."""
    import dataclasses

    nprobe = int(getattr(config, "serve_mips_nprobe", 0) or 0) or 8
    cfg = dataclasses.replace(
        config, serve_artifact=artifact_dir, serve_mips_nprobe=nprobe,
        serve_mips_crossover=1)  # hybrid: both heads live + both tables
    model = ReleaseModel(cfg, log=log)
    bs = int(cfg.serve_batch_size)
    grid = sorted({r for r in (1, 2, 4, 8, 16, bs) if 1 <= r <= bs})
    m = model.context_buckets[0]
    table, crossover = {}, 0
    for rows in grid:
        batch = model._dummy_batch(rows, m)
        timing = {}
        for head, step in (
                ("exact", model._get_bucketed_predict_step(rows, m)),
                ("mips", model._get_mips_predict_step(rows, m))):
            jax.block_until_ready(
                model._call_predict_step(step, batch).topk_indices)
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    model._call_predict_step(step, batch).topk_indices)
                samples.append(time.perf_counter() - t0)
            timing[head] = sorted(samples)[1]
        table[str(rows)] = {k: round(v * 1e6, 1) for k, v in timing.items()}
        if timing["mips"] < timing["exact"]:
            crossover = rows
        else:
            break
    log(f"MIPS crossover calibration (nprobe {nprobe}, bucket {m}): "
        f"crossover={crossover} over rows grid {grid} "
        f"(us medians: {table})")
    return crossover, table
