"""Blockwise top-k over the target-name classifier without materializing
the full logit row.

The code2vec prediction head is a (B, V) matmul against a ~246K-row
target table followed by top-k; at batch 1024 the logits alone are
~1 GB/batch of HBM traffic written once and read twice (top-k + CE).
By their shapes the hot ops move far more bytes than they compute on,
so never materializing that row is a direct lever (its effect is not
measured on the current machine). These kernels stream the
target table in row blocks, compute each block's (B, block) logit slice,
and fold it into a running `lax.top_k` merge (plus an optional running
logsumexp for the eval CE), so peak live logits are (B, block) instead
of (B, V).

Exactness: `lax.top_k` breaks ties toward the lower index. The merge
concatenates [running(k), block] with blocks visited in ascending-index
order, so among equal values the running entries (strictly lower global
indices, themselves tie-ordered ascending) occupy earlier positions —
position order equals global index order, and the merged result is
IDENTICAL (indices and values, bitwise) to `lax.top_k` over the full
logits. The one documented exception: rows whose finite-entry count is
below k may pick different -inf-valued indices (the init sentinel is
value -inf, index 0); callers clamp k to the real vocab size, so this
never happens in practice. Pinned in tests/test_quant.py.

The table blocks may be quantized with per-row symmetric scales
(ops/quant.py): int8 or fp8 blocks cast straight into the compute
dtype, int4-packed blocks (`int4_dim`) are nibble-unpacked AFTER the
block slice, and in every case the dequant is fused after the block
matmul (accumulation in the compute dtype, scales applied to the f32
block logits) — the table moves through HBM at one byte (int8/fp8) or
half a byte (int4) per weight.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class BlockTopKOutputs(NamedTuple):
    values: jax.Array   # (B, k) f32, sorted descending
    indices: jax.Array  # (B, k) i32 global target-vocab ids
    lse: jax.Array      # (B,) f32 logsumexp over all live logits


def _merge_top_k(vals: jax.Array, idx: jax.Array, block_vals: jax.Array,
                 block_idx: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Fold one block's (B, block) logits into the running (B, k) top-k.
    Concatenation order [running, block] is what makes ties resolve to
    the globally-lowest index (see module docstring)."""
    cat_v = jnp.concatenate([vals, block_vals], axis=1)
    cat_i = jnp.concatenate([idx, block_idx], axis=1)
    top_v, pos = jax.lax.top_k(cat_v, k)
    return top_v, jnp.take_along_axis(cat_i, pos, axis=1)


def _fold_lse(run_max: jax.Array, run_sum: jax.Array,
              block_logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One streaming-logsumexp step: rescale the running sum to the new
    max and add the block's sum-exp. -inf (masked) entries contribute 0;
    the isfinite guard keeps the first block's empty running term
    (max=-inf) from producing exp(-inf - -inf) = nan."""
    block_max = jnp.max(block_logits, axis=-1)
    new_max = jnp.maximum(run_max, block_max)
    safe_new = jnp.where(jnp.isfinite(new_max), new_max, 0.0)
    rescale = jnp.where(jnp.isfinite(run_max),
                        jnp.exp(run_max - safe_new), 0.0)
    run_sum = (run_sum * rescale
               + jnp.sum(jnp.exp(block_logits - safe_new[:, None]), axis=-1))
    return new_max, run_sum


@jax.named_scope("head_topk")
def blockwise_top_k_from_logits(logits: jax.Array, k: int,
                                block_cols: int
                                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k of precomputed (B, V) logits streamed in column blocks.

    Parity-test surface for the merge loop (the production paths below
    never hold full logits); returns exactly what
    `jax.lax.top_k(logits, k)` returns, per the tie argument in the
    module docstring.
    """
    b, v = logits.shape
    k = min(k, v)
    block_cols = max(1, min(int(block_cols), v))
    vals = jnp.full((b, k), -jnp.inf, logits.dtype)
    idx = jnp.zeros((b, k), jnp.int32)
    for start in range(0, v, block_cols):
        stop = min(start + block_cols, v)
        ids = jnp.arange(start, stop, dtype=jnp.int32)
        vals, idx = _merge_top_k(
            vals, idx, logits[:, start:stop],
            jnp.broadcast_to(ids[None, :], (b, stop - start)), k)
    return vals, idx


@jax.named_scope("head_topk")
def blockwise_matmul_top_k(
    code_vectors: jax.Array,          # (B, D) f32
    target_table: jax.Array,          # (V, D) f32 — or int8 with `scales`
    k: int,
    block_rows: int,
    *,
    scales: Optional[jax.Array] = None,   # (V, 1) f32 per-row dequant
    valid_rows: Optional[int] = None,     # ids >= this are padding (-inf)
    compute_dtype: jnp.dtype = jnp.float32,
    int4_dim: Optional[int] = None,       # table is int4-packed uint8
    #                                       (V, ceil(int4_dim/2))
) -> BlockTopKOutputs:
    """Streaming `top_k(code_vectors @ target_table.T, k)` + logsumexp.

    The (B, V) logit row is never materialized: a `fori_loop` slides a
    (block_rows, D) window over the table, computes the block's logits
    in `compute_dtype` (f32 accumulation), applies the fused per-row
    dequant when `scales` is given, and merges into the running top-k
    and running logsumexp. The last window is clamped to the table end
    and its already-visited prefix masked to -inf, so any (V, block)
    combination is exact — no table padding, no row read twice live.

    Per-element logit values are the same einsum contraction the full
    path runs (blocking the non-contracted axis does not change each
    output element's reduction over D), which is what makes the indices
    match the full path bitwise (pinned in tests/test_quant.py and
    re-verified on the accuracy-bench eval set by
    experiments/quant_bench.py).
    """
    b = code_vectors.shape[0]
    v = target_table.shape[0]
    k = min(k, v if valid_rows is None else valid_rows)
    block = max(1, min(int(block_rows), v))
    n_blocks = -(-v // block)
    cv = code_vectors.astype(compute_dtype)

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32),
            jnp.full((b,), -jnp.inf, jnp.float32),
            jnp.zeros((b,), jnp.float32))

    def body(i, carry):
        vals, idx, run_max, run_sum = carry
        start = jnp.minimum(i * block, v - block)
        tbl = jax.lax.dynamic_slice_in_dim(target_table, start, block, axis=0)
        if int4_dim is not None:
            # packed bytes through HBM; nibbles unpacked on the
            # block-sized slice only (ops/quant.py)
            from code2vec_tpu.ops.quant import unpack_int4
            tbl = unpack_int4(tbl, int4_dim)
        ids = start + jnp.arange(block, dtype=jnp.int32)
        logits = jnp.einsum("bd,vd->bv", cv, tbl.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
        if scales is not None:
            s = jax.lax.dynamic_slice_in_dim(scales, start, block, axis=0)
            logits = logits * s[:, 0][None, :]
        # Clamped-last-block overlap + padded classifier rows -> -inf
        # (never selected: k is clamped to the real vocab, and exp(-inf)
        # contributes 0 to the lse).
        live = ids >= i * block
        if valid_rows is not None:
            live &= ids < valid_rows
        logits = jnp.where(live[None, :], logits, -jnp.inf)
        vals, idx = _merge_top_k(
            vals, idx, logits, jnp.broadcast_to(ids[None, :], logits.shape), k)
        # The CE denominator gets the full eval path's nonfinite guard
        # (safe_logits = where(isfinite, logits, -1e30) in
        # training/step.py): a NaN/Inf logit from blown-up weights must
        # not poison the reported eval loss. Top-k above merges the RAW
        # logits — parity with `lax.top_k` over the full (unclamped)
        # logits is preserved; dead (-inf-masked) entries stay -inf and
        # keep contributing 0 to the lse.
        lse_in = jnp.where(live[None, :] & ~jnp.isfinite(logits),
                           -1e30, logits)
        run_max, run_sum = _fold_lse(run_max, run_sum, lse_in)
        return vals, idx, run_max, run_sum

    vals, idx, run_max, run_sum = jax.lax.fori_loop(0, n_blocks, body, init)
    lse = jnp.where(jnp.isfinite(run_max),
                    jnp.log(jnp.maximum(run_sum, 1e-30)) + run_max, run_max)
    return BlockTopKOutputs(vals, idx, lse)


def gathered_label_logits(code_vectors: jax.Array, target_table: jax.Array,
                          labels: jax.Array, *,
                          scales: Optional[jax.Array] = None,
                          compute_dtype: jnp.dtype = jnp.float32,
                          int4_dim: Optional[int] = None) -> jax.Array:
    """(B,) logit of each row's own label: a B-row gather + dot instead
    of a column of the full logit matrix. Same per-element contraction
    as the blockwise/full matmul, so CE = lse - label_logit matches the
    full path's cross-entropy — including its nonfinite guard: a
    NaN/Inf label logit is substituted with -1e30 exactly as the full
    path's safe_logits would have at that column."""
    rows = jnp.take(target_table, labels, axis=0)          # (B, D)
    if int4_dim is not None:
        from code2vec_tpu.ops.quant import unpack_int4
        rows = unpack_int4(rows, int4_dim)
    logits = jnp.einsum("bd,bd->b", code_vectors.astype(compute_dtype),
                        rows.astype(compute_dtype),
                        preferred_element_type=jnp.float32)
    if scales is not None:
        logits = logits * jnp.take(scales[:, 0], labels, axis=0)
    return jnp.where(jnp.isfinite(logits), logits, -1e30)
