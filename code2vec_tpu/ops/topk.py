"""Blockwise top-k over the target-name classifier without materializing
the full logit row.

The code2vec prediction head is a (B, V) matmul against the 261,245-row
java14m target table followed by top-k. These kernels stream the target
table in row blocks, compute each block's (B, block) logit slice, and
fold it into a running `lax.top_k` merge (plus an optional running
logsumexp for the eval CE), so peak live logits are (B, block) instead
of (B, V): 67 MB at the default block where the 1,024-row eval batch's
row would be 1.07 GB.

What the chip measured (TPU v5 lite; PERF.md section 6, PR 40; the head
alone, 64 rows x 384 against a bf16 table, k 10): a trip of the loop is
the block's matmul, mask and row max (6 us for 4,096 rows) and the
MERGE, and `lax.top_k` costs by the columns AND the rows it is given:
42 us for 64 rows of [running 10 | block 4,096], seven times the
matmul; 58 % of the served step was that sort. So the merge first
narrows the block EXACTLY (`_merge_top_k`): the maximum of each group
of `_GROUP` contiguous columns, the k groups of largest maximum, and a
sort over [running k | those k groups] alone, 1,290 columns whatever
the block's width. 3.65 ms -> 2.13 at 4,096-row blocks (groups of 128;
2.41 at 32, 2.87 at 64: a lane-wide group gathers cheapest), and the
merge no longer grows with the block, so wider blocks make fewer trips
of nearly the same cost: 1.27 ms at 8,192, 0.87 at 16,384 (the default
since), 0.54 at 65,536; all of (64, 261245) logits at once, 0.90. The
1,024-row eval head: 52 -> 20 -> 9.6 ms. Under 32 rows the plain sort
is cheaper than the prefilter's five small ops a trip (16 rows: 1.26 ms
plain, 1.61 filtered; 24: even; 32: 2.06 / 1.81), and at the token
models' one to sixteen rows against a float32 (151,936, 5,120) head the
loop is its table's bytes (4.60 / 5.23 ms plain, 4.67 / 5.44 filtered):
there the plain merge stays and the compiled head is, op for op, what
it was. Static shapes pick the form (`_prefilter_group`): also where
k x g is no small share of the block (a retrieval k of 100, a block
under k x g columns, a k clamped by a tiny vocabulary).

Exactness: `lax.top_k` breaks ties toward the lower index. The merge
concatenates [running(k), candidates] with blocks visited in
ascending-index order, so among equal values the running entries
(strictly lower global indices, themselves tie-ordered ascending) occupy
earlier positions — position order equals global index order, and the
merged result is IDENTICAL (indices and values, bitwise) to `lax.top_k`
over the full logits. The group prefilter keeps that: an element of the
block's true top-k cannot sit in a group that is not among the k best
by maximum (each of the k chosen groups holds an element that is
greater, or equal with a lower index: `lax.top_k` over the maxima sends
ties to the lower group, whose columns all precede the higher group's),
so the candidates hold the whole answer; and the chosen groups are
visited in ASCENDING group order, not in `lax.top_k`'s value order, so
that a candidate's position is again its global index order: left in
value order, a later group's element stands before an EQUAL element of
an earlier group whose maximum is smaller, and the answer names the
higher index first (the sparsely tied case of tests/test_quant.py). The
one documented exception: rows whose finite-entry count is below k may
pick different -inf-valued indices (the init sentinel is value -inf,
index 0); callers clamp k to the real vocab size, so this never happens
in practice. Pinned in tests/test_quant.py.

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


# The exact prefilter's group width and the fewest rows it pays at, both
# settled on the chip (module docstring).
_GROUP = 128
_MIN_ROWS = 32


def _prefilter_group(rows: int, width: int, k: int) -> int:
    """Columns a group for a (rows, width) block of logits, or 0 where
    the plain merge stays. Static shapes decide, no flag: the block must
    divide into at least k whole groups; the prefilter's two sorts (the
    group maxima, then [running k | k x g candidates]) must take at most
    half the columns the plain merge sorts; and there must be rows
    enough for a sort's cost to outweigh five small ops a trip. k 10
    over a 4,096 or 16,384 block at 64 or 1,024 rows filters; a
    retrieval k of 100, a block under k x g columns, a k clamped by a
    tiny vocabulary and the token models' one to sixteen rows do not
    (their compiled head is what it was)."""
    g = _GROUP
    if not g or rows < _MIN_ROWS or width % g or width // g < k:
        return 0
    return g if 2 * (width // g + k + k * g) <= width + k else 0


def sorted_columns(rows: int, width: int, k: int) -> int:
    """Columns that enter a sort in one merge of a (rows, width) block:
    what the gauge `head_topk_sorted_columns` says of a built step."""
    g = _prefilter_group(rows, width, k)
    return width // g + k + k * g if g else width + k


def _merge_top_k(vals: jax.Array, idx: jax.Array, block_vals: jax.Array,
                 start, k: int) -> Tuple[jax.Array, jax.Array]:
    """Fold one block's (B, block) logits, whose column 0 is global id
    `start`, into the running (B, k) top-k. Concatenation order
    [running, candidates in ascending id order] is what makes ties
    resolve to the globally-lowest index (see module docstring)."""
    b, width = block_vals.shape
    g = _prefilter_group(b, width, k)
    if g:
        grouped = block_vals.reshape(b, width // g, g)
        _, gids = jax.lax.top_k(jnp.max(grouped, axis=-1), k)
        # ascending group ids: candidate position order = global id order
        gids = jnp.sort(gids, axis=-1)[:, :, None]
        cand_v = jnp.take_along_axis(grouped, gids, axis=1).reshape(b, k * g)
        cand_i = (start + gids * g
                  + jnp.arange(g, dtype=jnp.int32)).reshape(b, k * g)
    else:
        cand_v = block_vals
        cand_i = jnp.broadcast_to(
            start + jnp.arange(width, dtype=jnp.int32)[None, :], (b, width))
    top_v, pos = jax.lax.top_k(jnp.concatenate([vals, cand_v], axis=1), k)
    return top_v, jnp.take_along_axis(
        jnp.concatenate([idx, cand_i], axis=1), pos, axis=1)


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
        vals, idx = _merge_top_k(vals, idx, logits[:, start:stop], start, k)
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
        vals, idx = _merge_top_k(vals, idx, logits, start, k)
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
