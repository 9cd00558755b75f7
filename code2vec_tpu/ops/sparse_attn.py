"""Grouped-query attention behind a learned selection of keys, over a
device-resident cache.

A token leaves behind, for later tokens, its keys and values (`hkv`
heads of `d`, the keys normalised and rotated) and ONE index key (`dI`
wide, shared by the indexer's heads). A query scores every key it may
see with the indexer,

    I[t, s] = sum_j a[t, j] relu(qI[t, j] . kI[s])        (float32)

keeps the `k` keys of largest score (all of them while it sees fewer;
ties go to the lower position) and attends those alone: softmax over
the kept keys of `q . k / sqrt(d)`, query head n reading key/value head
`n // (hq / hkv)`.

Three steps, each a scope of its own in a trace:

  index_scores  a row's index keys are read where they lie, block by
      block (`dynamic_slice` a row: a gather over the slot index pays
      by the ROW of the cache on this chip, PERF.md PR 31), and the
      scores land in one float32 buffer `[the slot's positions | the
      row's own tokens]`.
  select  the k-th largest score of every query is found EXACTLY by
      bisection over the bit pattern (float32 mapped to an unsigned
      integer of the same order: 32 counting passes over the buffer,
      where `lax.top_k` at k = 2,048 is a sort), then the keys above it
      and the first of those equal to it in position order are kept.
  attend  MASKED: scores against every visible key block by block with
      a running max and sum, keys that were not kept masked before the
      softmax. A GATHERED form (read only the kept keys and values) is
      the same mathematics; on this chip a gathered cache row costs as
      much as 75 ns, 2,048 of them a QUERY, where the whole slot of a
      row streams in 0.1 ms: no shape a served step has picks it, so it
      is not here (PERF.md section 6, PR 34, has the measurement;
      `tests/test_sparse_gqa_moe_lm.py` keeps a plain gathered form to
      hold this one against).

Operands bfloat16; index scores, their comparison, attention scores,
softmax, accumulators and rotary angles float32. Masked scores are a
large finite negative and a query that kept nothing (padding) reads
zeros: no NaN is made, so none can leak through a zero weight.

Rotary pairs are half-split, `(i, i + d/2)`; with `sections` the pairs
are dealt, in order, to three position streams (temporal, height,
width: multimodal rotary). Text gives the three streams equal, which is
plain rotary.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MASKED = -1e30


def rotate(x: jax.Array,            # (rows, l, ..., d), d even
           positions: jax.Array,    # (3, rows, l): t, h, w streams
           theta: float,
           sections: Optional[Sequence[int]] = None) -> jax.Array:
    """Pair (i, i + d/2) turned by `p_i * theta^(-2i/d)`, where p_i is
    the stream `sections` deals pair i to (the first `sections[0]` pairs
    the temporal one, ...; None: all temporal). Float32 out."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=F32) / half)
    if sections is None:
        at = positions[0][..., None]
    else:
        if sum(sections) != half:
            raise ValueError(f"sections {list(sections)} do not cover "
                             f"{half} rotary pairs")
        stream = np.repeat(np.arange(len(sections)), sections)
        at = jnp.moveaxis(positions[stream], 0, -1)     # (rows, l, half)
    angle = at.astype(F32) * inverse
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def visible_keys(rows: int, length: int, capacity: int,
                 cached_len: jax.Array, own_len: jax.Array) -> jax.Array:
    """(rows, l, capacity + l) bool over `[slot positions | own
    tokens]`: query i of a row sees the first `cached_len` positions of
    its slot and its own real tokens 0..i."""
    at = jnp.arange(capacity + length)
    own = at - capacity
    query = jnp.arange(length)[None, :, None]
    cached = at[None, None, :] < cached_len[:, None, None]
    mine = ((own >= 0)[None, None, :] & (own[None, None, :] <= query)
            & (own[None, None, :] < own_len[:, None, None]))
    return (cached & (at < capacity)[None, None, :]) | mine


def _row_blocks(cached: jax.Array, slot: jax.Array, start, step: int):
    """(rows, step, width): the block at `start` of each row's slot, one
    contiguous slice a row."""
    return jnp.concatenate([jax.lax.dynamic_slice(
        cached, (slot[r], start, 0), (1, step, cached.shape[2]))
        for r in range(slot.shape[0])])


def _block_step(capacity: int, block: int) -> int:
    step = min(block, capacity)
    if capacity % step:
        raise ValueError(f"a slot's {capacity} tokens are no multiple of "
                         f"the key block {step}")
    return step


@jax.named_scope("index_score")
def index_scores(q_i: jax.Array,        # (rows, l, hI, dI), rotated
                 a: jax.Array,          # (rows, l, hI) float32
                 own_k: jax.Array,      # (rows, l, dI)
                 cached_k: jax.Array,   # (slots, capacity, dI)
                 slot: jax.Array,       # (rows,) int32
                 cached_len: jax.Array,  # (rows,) int32
                 block: int = 512) -> jax.Array:
    """(rows, l, capacity + l) float32 over `[slot positions | own
    tokens]`. Blocks past the longest row's cached length are not
    visited and read 0; what a query may not see is `visible_keys`'
    to say, not this buffer's."""
    rows, length = q_i.shape[:2]
    capacity = cached_k.shape[1]
    step = _block_step(capacity, block)

    def score(keys):                    # (rows, n, dI) -> (rows, l, n)
        s = jnp.einsum("rlhd,rkd->rlhk", q_i, keys,
                       preferred_element_type=F32)
        return jnp.sum(a[..., None] * jax.nn.relu(s), axis=2)

    out = jnp.concatenate([jnp.zeros((rows, length, capacity), F32),
                           score(own_k)], axis=-1)

    def cached_block(j, out):
        got = score(_row_blocks(cached_k, slot, j * step, step))
        return jax.lax.dynamic_update_slice(out, got, (0, 0, j * step))
    blocks = (jnp.max(cached_len) + step - 1) // step
    return jax.lax.fori_loop(0, blocks, cached_block, out)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 of the same order (NaNs aside); no finite value
    and no infinity maps to 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def _exclusive_count(flags: jax.Array) -> jax.Array:
    """flags (..., n) bool -> (..., n) int32: how many flags lie before
    each position. Two triangular matmuls (within lanes of 128, then
    over the lanes' totals): a scan along the minor axis is slow on the
    chip. Exact: the counts stay far below 2^24."""
    n = flags.shape[-1]
    lanes = -(-n // 128)
    x = jnp.pad(flags, [(0, 0)] * (flags.ndim - 1) + [(0, lanes * 128 - n)])
    x = x.reshape(flags.shape[:-1] + (lanes, 128)).astype(jnp.bfloat16)
    before = jnp.triu(jnp.ones((128, 128), jnp.bfloat16), 1)
    inside = jnp.einsum("...ab,bc->...ac", x, before,
                        preferred_element_type=F32)
    totals = jnp.sum(x.astype(F32), axis=-1)            # (..., lanes)
    lanes_before = jnp.triu(jnp.ones((lanes, lanes), F32), 1)
    offset = jnp.einsum("...a,ab->...b", totals, lanes_before,
                        precision=jax.lax.Precision.HIGHEST)
    out = (inside + offset[..., None]).astype(jnp.int32)
    return out.reshape(flags.shape[:-1] + (lanes * 128,))[..., :n]


@jax.named_scope("index_select")
def select(scores: jax.Array,       # (rows, l, n) float32
           visible: jax.Array,      # (rows, l, n) bool
           k: int) -> jax.Array:
    """(rows, l, n) bool: of each query's visible keys the k of largest
    score, ties to the lower position; all of them where it sees at most
    k."""
    u = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))

    def narrow(i, prefix):
        # the k-th largest value bit by bit from the top: a bit stays
        # set if at least k values are no smaller than the candidate
        candidate = prefix | (jnp.uint32(1) << (jnp.uint32(31)
                                                - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= candidate[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, prefix)
    kth = jax.lax.fori_loop(0, 32, narrow,
                            jnp.zeros(u.shape[:-1], jnp.uint32))
    above = u > kth[..., None]
    equal = u == kth[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    kept = above | (equal & (_exclusive_count(equal) < room[..., None]))
    return kept & visible


def pack_bits(flags: jax.Array) -> jax.Array:
    """flags (..., n) bool -> (..., ceil(n / 32)) uint32: flag p is bit
    p % 32 of word p // 32."""
    n = flags.shape[-1]
    words = -(-n // 32)
    x = jnp.pad(flags, [(0, 0)] * (flags.ndim - 1) + [(0, words * 32 - n)])
    x = x.reshape(flags.shape[:-1] + (words, 32)).astype(jnp.uint32)
    return jnp.sum(x << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def unpack_bits(words: np.ndarray) -> np.ndarray:
    """The host's inverse of `pack_bits`: (..., words) uint32 -> the set
    positions of ONE packed row, ascending."""
    bits = np.unpackbits(np.ascontiguousarray(words, "<u4").view(np.uint8),
                         bitorder="little")
    return np.flatnonzero(bits)


@jax.named_scope("sparse_attend")
def attend(q: jax.Array,            # (rows, l, hq, d), rotated
           own_k: jax.Array,        # (rows, l, hkv, d), rotated
           own_v: jax.Array,        # (rows, l, hkv, d)
           cached_kv: jax.Array,    # (slots, capacity, 2 * hkv * d)
           slot: jax.Array,         # (rows,) int32
           cached_len: jax.Array,   # (rows,) int32
           selected: jax.Array,     # (rows, l, capacity + l) bool
           block: int = 512) -> jax.Array:
    """(rows, l, hq * d) bfloat16: each query's softmax over the keys
    `selected` kept for it. A slot's row is `[keys of every head |
    values of every head]`."""
    rows, length, hq, d = q.shape
    hkv = own_k.shape[2]
    capacity = cached_kv.shape[1]
    step = _block_step(capacity, block)
    bf16 = jnp.bfloat16
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(rows, length, hkv, hq // hkv, d)

    def fold(carry, k, v, keep):        # k, v (rows, n, hkv, d)
        top, total, acc = carry
        s = jnp.einsum("rlgmd,rkgd->rgmlk", qg, k,
                       preferred_element_type=F32) * scale
        keep = keep[:, None, None, :, :]
        s = jnp.where(keep, s, MASKED)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1))
        p = jnp.where(keep, jnp.exp(s - new_top[..., None]), 0.0)
        fade = jnp.exp(top - new_top)
        return (new_top, total * fade + jnp.sum(p, axis=-1),
                acc * fade[..., None] + jnp.einsum(
                    "rgmlk,rkgd->rgmld", p.astype(bf16), v,
                    preferred_element_type=F32))

    shape = (rows, hkv, hq // hkv, length)
    carry = (jnp.full(shape, MASKED, F32), jnp.zeros(shape, F32),
             jnp.zeros(shape + (d,), F32))
    carry = fold(carry, own_k, own_v, selected[..., capacity:])

    def cached_block(j, carry):
        blk = _row_blocks(cached_kv, slot, j * step, step)
        blk = blk.reshape(rows, step, 2, hkv, d)
        keep = jax.lax.dynamic_slice_in_dim(selected, j * step, step, axis=2)
        return fold(carry, blk[:, :, 0], blk[:, :, 1], keep)
    blocks = (jnp.max(cached_len) + step - 1) // step
    _, total, acc = jax.lax.fori_loop(0, blocks, cached_block, carry)
    out = acc / jnp.maximum(total, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        rows, length, hq * d).astype(bf16)
