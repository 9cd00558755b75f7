"""Release-artifact writer/loader: the deployable inference bundle.

A release artifact is a directory that carries EVERYTHING a serving
replica needs — no checkpoint, no optimizer state, no training config:

    release_meta.json       format/quantization/dims/buckets/source/
                            fingerprint (see _build_meta)
    dictionaries.bin        the three vocabularies (reference sidecar
                            format, vocab.py)
    token_embedding.npy     int8 (V, D) / uint8 fp8 bit patterns (V, D)
                            / uint8 int4-packed (V, ceil(D/2)) — or f32
                            for --no_quantize (scheme in the meta)
    token_embedding.scale.npy   f32 (V, 1) per-row symmetric scales
    path_embedding[.scale].npy
    target_embedding[.scale].npy
    transform.npy           f32 (3d, d) — small dense params stay f32
    attention.npy           f32 (d, 1)
    aot/serve_r<rows>_m<m>.jaxexport   serialized jax.export lowerings,
                            one per (serve_batch_size, context bucket)

Quantization is per-row symmetric (ops/quant.py), scheme selectable at
export (`--release_scheme int8|fp8_e4m3|fp8_e5m2|int4`): int8 drops the
three tables ~3.9x at the flagship shape (1 byte/weight + 4 bytes/row),
fp8 keeps the byte count with a relative error profile, int4 packs two
weights per byte for another ~2x — which is both the artifact's
disk/RSS footprint and, because the hot ops move far more bytes than
they compute on, the serve step's HBM traffic. Quality deltas per
scheme are measured same-run vs fp32 in BENCH_QUANT.md.

Every load validates `kind`/`format`/table dtypes against the declared
scheme and raises ArtifactError naming the offending field; pointing
the fp32 checkpoint loader (--load) at an artifact is rejected up front
in model_facade with the same named-field treatment, so a quantized
bundle can never be silently misread as fp32 garbage.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Dict, Optional

import numpy as np

META_NAME = "release_meta.json"
DICT_NAME = "dictionaries.bin"
AOT_DIR = "aot"
ARTIFACT_FORMAT = 1
ARTIFACT_KIND = "code2vec_release_artifact"
SCHEME_INT8 = "int8_rowwise_symmetric"
SCHEME_FP8_E4M3 = "fp8_e4m3_rowwise"
SCHEME_FP8_E5M2 = "fp8_e5m2_rowwise"
SCHEME_INT4 = "int4_rowwise_packed"
SCHEME_FP32 = "float32"
# Every scheme the loader/runtime understand; the quantized ones carry
# per-row f32 scales. fp8/int4 payloads are stored as uint8 npy files
# (fp8 = bit patterns — numpy's mmap path cannot represent ml_dtypes;
# int4 = two nibbles per byte), decoded by the runtime (ops/quant.py).
QUANTIZED_SCHEMES = (SCHEME_INT8, SCHEME_FP8_E4M3, SCHEME_FP8_E5M2,
                     SCHEME_INT4)
ALL_SCHEMES = QUANTIZED_SCHEMES + (SCHEME_FP32,)
# --release_scheme CLI vocabulary -> on-disk scheme name.
SCHEME_BY_KNOB = {
    "int8": SCHEME_INT8,
    "fp8_e4m3": SCHEME_FP8_E4M3,
    "fp8_e5m2": SCHEME_FP8_E5M2,
    "int4": SCHEME_INT4,
    "float32": SCHEME_FP32,
}

_TABLES = ("token_embedding", "path_embedding", "target_embedding")
_DENSE = ("transform", "attention")


def _quantize_table(table: "np.ndarray", scheme: str):
    """(payload, scales-or-None) for one table under `scheme`."""
    from code2vec_tpu.ops import quant
    if scheme == SCHEME_INT8:
        return quant.quantize_rows(table)
    if scheme == SCHEME_FP8_E4M3:
        return quant.quantize_rows_fp8(table, "e4m3")
    if scheme == SCHEME_FP8_E5M2:
        return quant.quantize_rows_fp8(table, "e5m2")
    if scheme == SCHEME_INT4:
        return quant.quantize_rows_int4(table)
    assert scheme == SCHEME_FP32, scheme
    return table, None


class ArtifactError(ValueError):
    """Artifact rejected with the offending meta/table field named, so a
    bad deploy fails at load with a pointer instead of serving garbage."""

    def __init__(self, field: str, message: str):
        super().__init__(f"release artifact field `{field}`: {message}")
        self.field = field


@dataclasses.dataclass
class ReleaseArtifact:
    path: str
    meta: dict
    tables: Dict[str, np.ndarray]   # name -> array; int8 tables carry a
    #                                 sibling "<name>.scale" f32 entry

    @property
    def scheme(self) -> str:
        return self.meta["quantization"]["scheme"]

    @property
    def fingerprint(self) -> str:
        return self.meta["fingerprint"]

    @property
    def dictionaries_path(self) -> str:
        return os.path.join(self.path, DICT_NAME)

    def aot_path(self, rows: int, m: int) -> Optional[str]:
        entries = (self.meta.get("aot") or {}).get("entries", {})
        rel = entries.get(f"r{rows}_m{m}")
        if rel is None:
            return None
        p = os.path.join(self.path, rel)
        return p if os.path.isfile(p) else None

    def table_bytes(self) -> int:
        return sum(a.nbytes for a in self.tables.values())


def is_release_artifact(path: str) -> bool:
    return os.path.isfile(os.path.join(path, META_NAME))


def _content_fingerprint(payloads: Dict[str, np.ndarray], meta: dict) -> str:
    """sha256 over the table payloads + the identity-bearing meta core.
    Stable across re-serialization of the json (the hash covers values,
    not formatting) and across AOT re-export (lowerings are a cache of
    the tables + dims, not independent identity). Hashes the in-memory
    arrays the exporter just wrote — the loader never recomputes this,
    so re-reading a flagship-scale bundle off disk just to hash it
    would double the export I/O for nothing."""
    h = hashlib.sha256()
    core = {k: meta[k] for k in ("kind", "format", "quantization", "dims",
                                 "max_contexts", "compute_dtype")}
    h.update(json.dumps(core, sort_keys=True).encode())
    for name in sorted(payloads):
        arr = np.ascontiguousarray(payloads[name])
        h.update(f"{name}:{arr.dtype}:{arr.shape}".encode())
        h.update(arr.data)
    return h.hexdigest()


def export_artifact(model, out_dir: str, *, quantize: Optional[bool] = None,
                    aot: Optional[bool] = None,
                    scheme: Optional[str] = None, log=None) -> dict:
    """Write a release artifact from a live facade model. Returns the
    meta dict (with the content fingerprint filled in). `scheme` is an
    on-disk scheme name (ALL_SCHEMES); unset, it follows
    config.release_scheme with `quantize`/--no_quantize forcing fp32."""
    import jax

    config = model.config
    log = log or config.log
    quantize = config.release_quantize if quantize is None else quantize
    aot = config.release_aot if aot is None else aot
    if scheme is None:
        knob = getattr(config, "release_scheme", "int8")
        if knob not in SCHEME_BY_KNOB:
            raise ValueError(f"release_scheme must be one of "
                             f"{sorted(SCHEME_BY_KNOB)}, got {knob!r}")
        scheme = SCHEME_BY_KNOB[knob] if quantize else SCHEME_FP32
    if scheme not in ALL_SCHEMES:
        raise ValueError(f"unknown artifact scheme {scheme!r} "
                         f"(one of {ALL_SCHEMES})")
    os.makedirs(out_dir, exist_ok=True)

    params = {k: np.asarray(jax.device_get(v))
              for k, v in model.state.params.items()}
    fp32_bytes = sum(params[t].nbytes for t in _TABLES)
    written = 0
    payloads: Dict[str, np.ndarray] = {}
    for name in _TABLES:
        table = params[name].astype(np.float32)
        scale_path = os.path.join(out_dir, f"{name}.scale.npy")
        q, scales = _quantize_table(table, scheme)
        np.save(os.path.join(out_dir, f"{name}.npy"), q)
        written += q.nbytes
        payloads[name] = q
        if scales is not None:
            np.save(scale_path, scales)
            written += scales.nbytes
            payloads[f"{name}.scale"] = scales
        elif os.path.exists(scale_path):
            # A prior quantized export into the same dir leaves scale
            # files behind; the loader reads whatever scale files
            # exist, so stale ones must go with the tables they
            # described.
            os.remove(scale_path)
    for name in _DENSE:
        arr = params[name].astype(np.float32)
        np.save(os.path.join(out_dir, f"{name}.npy"), arr)
        payloads[name] = arr
    # Stale lowerings from a prior export must never ride along with
    # fresh tables (meta's aot entries are rewritten below either way;
    # this keeps the on-disk bundle == what the meta describes).
    stale_aot = os.path.join(out_dir, AOT_DIR)
    if os.path.isdir(stale_aot):
        import shutil
        shutil.rmtree(stale_aot)

    model.vocabs.save(os.path.join(out_dir, DICT_NAME))

    dims = model.dims
    meta = {
        "kind": ARTIFACT_KIND,
        "format": ARTIFACT_FORMAT,
        "quantization": {"scheme": scheme},
        "dims": {
            "token_vocab_size": dims.token_vocab_size,
            "path_vocab_size": dims.path_vocab_size,
            "target_vocab_size": dims.target_vocab_size,
            "real_target_vocab_size": dims.real_target_vocab_size,
            "token_dim": dims.token_dim,
            "path_dim": dims.path_dim,
            "target_oov_floor": dims.target_oov_floor,
        },
        "separate_oov_and_pad": config.separate_oov_and_pad,
        "compute_dtype": config.compute_dtype,
        "max_contexts": config.max_contexts,
        "topk": config.top_k_words_considered_during_prediction,
        "topk_block_size": config.topk_block_size,
        "serve_batch_size": config.serve_batch_size,
        "buckets": list(model.context_buckets),
        "source": {
            "checkpoint": (os.path.abspath(config.model_load_path)
                           if config.model_load_path else None),
            "step": int(jax.device_get(model.state.step)),
            "epoch": getattr(model, "initial_epoch", None),
        },
        "table_bytes": {"fp32": fp32_bytes, "artifact": written},
        "aot": None,
    }
    meta["fingerprint"] = _content_fingerprint(payloads, meta)

    if aot:
        from code2vec_tpu.release.runtime import aot_export_serve_functions
        meta["aot"] = aot_export_serve_functions(out_dir, meta, log=log)

    with open(os.path.join(out_dir, META_NAME), "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
        f.write("\n")

    log(f"Exported release artifact to {out_dir}: scheme={scheme}, "
        f"tables {fp32_bytes / 1e6:.1f} MB fp32 -> {written / 1e6:.1f} MB "
        f"({fp32_bytes / max(written, 1):.2f}x smaller), "
        f"aot={'on' if meta['aot'] else 'off'}, "
        f"fingerprint {meta['fingerprint'][:12]}")
    return meta


def _expected_dtype(scheme: str, name: str) -> np.dtype:
    if name.endswith(".scale") or name in _DENSE:
        return np.dtype(np.float32)
    if scheme == SCHEME_INT8:
        return np.dtype(np.int8)
    if scheme in (SCHEME_FP8_E4M3, SCHEME_FP8_E5M2, SCHEME_INT4):
        # fp8 bit patterns / packed nibbles both travel as uint8 bytes
        return np.dtype(np.uint8)
    return np.dtype(np.float32)


def table_dim(dims: dict, name: str) -> int:
    """Unpacked (model-side) column count of one embedding table."""
    d_tok, d_path = int(dims["token_dim"]), int(dims["path_dim"])
    return {"token_embedding": d_tok, "path_embedding": d_path,
            "target_embedding": d_path + 2 * d_tok}[name]


def _expected_shape(dims: dict, name: str,
                    scheme: str = SCHEME_FP32) -> tuple:
    """Declared shape of each payload per meta["dims"]. Shape drift must
    fail at load: a truncated table would otherwise serve silently-wrong
    rows (jnp.take clamps out-of-bounds ids under jit). int4-packed
    tables store two columns per byte."""
    d_tok, d_path = int(dims["token_dim"]), int(dims["path_dim"])
    code_dim = d_path + 2 * d_tok
    shape = {
        "token_embedding": (int(dims["token_vocab_size"]), d_tok),
        "path_embedding": (int(dims["path_vocab_size"]), d_path),
        "target_embedding": (int(dims["target_vocab_size"]), code_dim),
        "transform": (code_dim, code_dim),
        "attention": (code_dim, 1),
    }[name]
    if scheme == SCHEME_INT4 and name in _TABLES:
        return (shape[0], (shape[1] + 1) // 2)
    return shape


def load_artifact(path: str,
                  expect_scheme: Optional[str] = None) -> ReleaseArtifact:
    """Load + validate a release artifact. Tables are memory-mapped (the
    flagship int8 bundle is ~100 MB; serving moves it to device once).

    `expect_scheme` lets a caller that can only consume one flavor fail
    with a named-field error instead of misreading the payload — e.g.
    an fp32-only consumer handed an int8 bundle."""
    base = os.path.abspath(path)
    meta_path = os.path.join(base, META_NAME)
    if not os.path.isfile(meta_path):
        raise ArtifactError(
            "kind", f"{base} is not a release artifact ({META_NAME} "
            f"missing); checkpoints are served via --load, artifacts "
            f"are produced by the `export` subcommand")
    with open(meta_path) as f:
        try:
            meta = json.load(f)
        except json.JSONDecodeError as e:
            raise ArtifactError("kind", f"unparseable {META_NAME}: {e}")
    if meta.get("kind") != ARTIFACT_KIND:
        raise ArtifactError("kind", f"expected {ARTIFACT_KIND!r}, "
                                    f"got {meta.get('kind')!r}")
    if int(meta.get("format", -1)) > ARTIFACT_FORMAT:
        raise ArtifactError(
            "format", f"artifact format {meta.get('format')} is newer "
            f"than this build understands (<= {ARTIFACT_FORMAT})")
    scheme = (meta.get("quantization") or {}).get("scheme")
    if scheme not in ALL_SCHEMES:
        raise ArtifactError(
            "quantization.scheme",
            f"unknown scheme {scheme!r} (this build understands "
            f"{list(ALL_SCHEMES)})")
    if expect_scheme is not None and scheme != expect_scheme:
        raise ArtifactError(
            "quantization.scheme",
            f"artifact is quantized as {scheme!r} but the caller "
            f"requires {expect_scheme!r}; re-export with "
            f"{'--no_quantize' if expect_scheme == SCHEME_FP32 else 'the matching --release_scheme'} "
            f"or use a consumer that dequantizes")
    if "fingerprint" not in meta:
        raise ArtifactError("fingerprint", "missing (torn export?)")
    # Every meta field the runtime consumes (make_release_step,
    # ReleaseModel.__init__) must be present HERE: a torn or hand-edited
    # meta otherwise passes load and dies later with a bare KeyError,
    # breaking the named-field contract in the module docstring.
    for key in ("compute_dtype", "topk", "serve_batch_size",
                "max_contexts", "separate_oov_and_pad", "buckets"):
        if key not in meta:
            raise ArtifactError(
                key, f"missing from {META_NAME} (torn or hand-edited "
                     f"export?)")
    if not os.path.isfile(os.path.join(base, DICT_NAME)):
        raise ArtifactError("dictionaries", f"{DICT_NAME} missing")
    dims = meta.get("dims") or {}
    missing = {"token_vocab_size", "path_vocab_size", "target_vocab_size",
               "real_target_vocab_size", "target_oov_floor",
               "token_dim", "path_dim"} - dims.keys()
    if missing:
        raise ArtifactError("dims", f"missing field(s) {sorted(missing)}")

    tables: Dict[str, np.ndarray] = {}
    for name in _TABLES + _DENSE:
        p = os.path.join(base, f"{name}.npy")
        if not os.path.isfile(p):
            raise ArtifactError(name, "table file missing")
        arr = np.load(p, mmap_mode="r")
        want = _expected_dtype(scheme, name)
        if arr.dtype != want:
            raise ArtifactError(
                f"{name}.dtype",
                f"expected {want} under quantization.scheme={scheme}, "
                f"file holds {arr.dtype}")
        want_shape = _expected_shape(meta.get("dims") or {}, name, scheme)
        if tuple(arr.shape) != want_shape:
            raise ArtifactError(
                f"{name}.shape",
                f"expected {want_shape} per meta dims, file holds "
                f"{tuple(arr.shape)}")
        tables[name] = arr
        if scheme in QUANTIZED_SCHEMES and name in _TABLES:
            sp = os.path.join(base, f"{name}.scale.npy")
            if not os.path.isfile(sp):
                raise ArtifactError(f"{name}.scale", "scale file missing")
            scales = np.load(sp, mmap_mode="r")
            if scales.dtype != np.float32 or scales.shape != (arr.shape[0], 1):
                raise ArtifactError(
                    f"{name}.scale",
                    f"expected float32 ({arr.shape[0]}, 1), got "
                    f"{scales.dtype} {scales.shape}")
            tables[f"{name}.scale"] = scales
    return ReleaseArtifact(path=base, meta=meta, tables=tables)
