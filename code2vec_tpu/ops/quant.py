"""Per-row quantization schemes for the embedding tables: int8, fp8
(e4m3/e5m2) and sub-byte int4 (two weights per byte).

The flagship shape is 227-383M params dominated by three embedding
tables and the ~246K-name target classifier, and every hot op that
touches them moves far more bytes than it computes on: quantized
storage moves 1 byte (int8/fp8) or half a byte (int4) per weight instead
of four through HBM, with the dequant fused into the consuming op —
gathers multiply the gathered rows by their scales (ops below), the
classifier matmul dequants its block logits after f32 accumulation
(ops/topk.py blockwise_matmul_top_k).

Schemes (all per-row symmetric, no zero point — embedding rows are
~zero-centered by init and training, so dequant stays one fused
multiply; all-zero rows get scale 0 and reproduce exactly):

- **int8** (`quantize_rows`): s_r = max|w_r| / 127, q = round(w/s_r) in
  [-127, 127]. Worst-case round-trip error s_r/2 per element.
- **fp8 e4m3 / e5m2** (`quantize_rows_fp8`): s_r = max|w_r| / FP8_MAX,
  payload = (w/s_r) cast to the fp8 format. Same byte count as int8 but
  a RELATIVE error profile (~2^-3 of magnitude for e4m3, ~2^-2 for
  e5m2) instead of int8's absolute s_r/2: small-magnitude elements of a
  heavy-tailed row round proportionally instead of to a fixed grid.
  Stored on disk / moved through HBM as uint8 bit patterns (numpy's
  .npy mmap path cannot represent ml_dtypes; the bitcast is free).
- **int4 packed** (`quantize_rows_int4`): s_r = max|w_r| / 7, q =
  round(w/s_r) in [-7, 7], stored offset-binary (q+8, one nibble) two
  per uint8 byte — HALF the bytes of int8 (the ~2x the release
  artifact's int8 tables still leave on the table, BENCH_QUANT.md).
  Worst-case round-trip error s_r/2 with s_r 18x coarser than int8's;
  the end-to-end quality delta is measured same-run vs fp32 by
  experiments/quant_bench.py.

Error bounds are pinned in tests/test_quant.py; end-to-end quality
deltas live in BENCH_QUANT.md (same-run fp32 discipline).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

QMAX = 127
INT4_QMAX = 7
FP8_FORMATS = {
    "e4m3": ml_dtypes.float8_e4m3fn,
    "e5m2": ml_dtypes.float8_e5m2,
}
FP8_MAX = {fmt: float(ml_dtypes.finfo(dt).max)
           for fmt, dt in FP8_FORMATS.items()}


def quantize_rows(table: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side int8 quantizer: f32 (V, D) -> (int8 (V, D), f32 scales
    (V, 1)).

    Runs in numpy (export is an offline host job; the tables may be
    bigger than comfortable to round-trip through the device twice).
    """
    table = _check_2d(table)
    scales = _row_scales(table, QMAX)
    safe = np.where(scales > 0, scales, 1.0)
    q = np.clip(np.rint(table / safe), -QMAX, QMAX).astype(np.int8)
    return q, scales


def quantize_rows_fp8(table: np.ndarray, fmt: str = "e4m3"
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side fp8 quantizer: f32 (V, D) -> (uint8 bit patterns
    (V, D), f32 scales (V, 1)). The payload is the fp8 encoding of
    w / s_r viewed as uint8 (see module docstring for why bytes)."""
    if fmt not in FP8_FORMATS:
        raise ValueError(f"fp8 format must be one of "
                         f"{sorted(FP8_FORMATS)}, got {fmt!r}")
    table = _check_2d(table)
    scales = _row_scales(table, FP8_MAX[fmt])
    safe = np.where(scales > 0, scales, 1.0)
    q = (table / safe).astype(FP8_FORMATS[fmt])
    return q.view(np.uint8), scales


def quantize_rows_int4(table: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side packed-int4 quantizer: f32 (V, D) -> (uint8
    (V, ceil(D/2)), f32 scales (V, 1)). Nibble n of byte b holds column
    2b+n as offset-binary q+8 (q in [-7, 7]); an odd trailing column is
    padded with the encoding of 0 (decoded then sliced off by
    `unpack_int4`)."""
    table = _check_2d(table)
    scales = _row_scales(table, INT4_QMAX)
    safe = np.where(scales > 0, scales, 1.0)
    q = np.clip(np.rint(table / safe), -INT4_QMAX, INT4_QMAX)
    u = (q + 8).astype(np.uint8)
    if u.shape[1] % 2:
        u = np.concatenate(
            [u, np.full((u.shape[0], 1), 8, np.uint8)], axis=1)
    return (u[:, 0::2] | (u[:, 1::2] << 4)), scales


def _check_2d(table: np.ndarray) -> np.ndarray:
    table = np.asarray(table, np.float32)
    if table.ndim != 2:
        raise ValueError(f"row quantizers expect a 2-D table, "
                         f"got shape {table.shape}")
    return table


def _row_scales(table: np.ndarray, qmax: float) -> np.ndarray:
    absmax = np.abs(table).max(axis=1, keepdims=True)
    # 0-scale rows are exact zeros; consumers guard the divide.
    return (absmax / qmax).astype(np.float32)


def dequantize_rows(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Host-side inverse of quantize_rows (bench/analysis utility)."""
    return q.astype(np.float32) * np.asarray(scales, np.float32)


def dequantize_rows_fp8(q: np.ndarray, scales: np.ndarray,
                        fmt: str = "e4m3") -> np.ndarray:
    """Host-side inverse of quantize_rows_fp8 (uint8 bit patterns in)."""
    f = np.asarray(q).view(FP8_FORMATS[fmt]).astype(np.float32)
    return f * np.asarray(scales, np.float32)


def unpack_int4_host(packed: np.ndarray, dim: int) -> np.ndarray:
    """Host-side nibble unpack: uint8 (V, ceil(dim/2)) -> int8 (V, dim)
    in [-7, 7]."""
    packed = np.asarray(packed, np.uint8)
    lo = (packed & 0xF).astype(np.int8) - 8
    hi = (packed >> 4).astype(np.int8) - 8
    out = np.empty((packed.shape[0], packed.shape[1] * 2), np.int8)
    out[:, 0::2] = lo
    out[:, 1::2] = hi
    return out[:, :dim]


def dequantize_rows_int4(packed: np.ndarray, scales: np.ndarray,
                         dim: int) -> np.ndarray:
    """Host-side inverse of quantize_rows_int4."""
    return (unpack_int4_host(packed, dim).astype(np.float32)
            * np.asarray(scales, np.float32))


# ------------------------------------------------------- device (jax) side


def unpack_int4(packed: jax.Array, dim: int) -> jax.Array:
    """Nibble unpack inside a jitted consumer: uint8 (..., ceil(dim/2))
    -> f32 (..., dim). Runs on the gathered/sliced (batch- or
    block-sized) rows, never on the full table — the table moves
    through HBM packed."""
    lo = (packed & 0xF).astype(jnp.int32) - 8
    hi = (packed >> 4).astype(jnp.int32) - 8
    out = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (packed.shape[-1] * 2,))
    return out[..., :dim].astype(jnp.float32)


def dequant_gather(q_table: jax.Array, scales: jax.Array,
                   ids: jax.Array) -> jax.Array:
    """Gather rows of an int8 (or fp8-viewed) table by id with fused
    dequant: (..., D) f32. The gather moves quantized bytes; the
    per-row scale multiply happens on the gathered (batch-sized) rows,
    never on the full table."""
    rows = jnp.take(q_table, ids, axis=0).astype(jnp.float32)
    s = jnp.take(scales[:, 0], ids, axis=0)
    return rows * s[..., None]


def dequant_gather_int4(packed_table: jax.Array, scales: jax.Array,
                        ids: jax.Array, dim: int) -> jax.Array:
    """int4 flavor of `dequant_gather`: gather PACKED uint8 rows (half
    the HBM bytes of int8), unpack + scale on the gathered result."""
    rows = unpack_int4(jnp.take(packed_table, ids, axis=0), dim)
    s = jnp.take(scales[:, 0], ids, axis=0)
    return rows * s[..., None]


def table_gather(table: jax.Array, scales: Optional[jax.Array],
                 ids: jax.Array, *, int4_dim: Optional[int] = None
                 ) -> jax.Array:
    """Scheme-agnostic gather: f32 tables pass scales=None (plain take);
    int8/fp8 tables carry scales; int4-packed tables additionally pass
    their unpacked `int4_dim`. One call site serves every release
    artifact flavor (release/runtime.py)."""
    if scales is None:
        return jnp.take(table, ids, axis=0)
    if int4_dim is not None:
        return dequant_gather_int4(table, scales, ids, int4_dim)
    return dequant_gather(table, scales, ids)
