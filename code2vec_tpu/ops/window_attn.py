"""Grouped-query attention over the two kinds of state a context leaves
in a model whose layers mix WINDOW and FULL attention.

A window layer keeps, a context, a RING of the last `W` tokens' keys
and values: position `p` lives in column `p mod W` of the context's ring
slot, so a ring that holds `n` tokens holds positions `max(0, n - W) ..
n - 1`, each in its own column, whatever `n`. A query at position `i`
sees the keys `j` with `i - W < j <= i`. A full layer keeps every token:
page `g` of a context's PAGE LIST holds its positions `[g P, (g + 1)
P)`, the pages lying anywhere in one pool; a query sees every `j <= i`.

Both arrays are TOKEN-MINOR, `(slots or pages, 2 * hkv * d, tokens)`: a
token is a column, `[keys | values]` down it. Both products of the fold
contract or spread over the tokens, and laid token-major (a row a token)
the chip's compiler re-laid the WHOLE array for them in every step of
two rows or more (671 MB a full layer at the published size, found by
compiling for the chip; a one-row step it left alone).

Both are one fold: scores of a block of keys against the rows' queries
with a running max and sum, `softmax_j(q_i . k_j / sqrt(d)) v_j`, query
head n reading key/value head `n // (hq / hkv)`; a key is visible by its
POSITION alone (`_visible`), so a ring needs no unrolling and a
page no place in the sequence but its index in the list.

  window_attend  the rows' own tokens, then each row's ring slot read
      where it lies, block by block (one `dynamic_slice` a row).
  full_attend  the rows' own tokens, then each row's pages in list
      order, one `dynamic_slice` a (row, block) on the PAGE id; the
      loop's trip count is the most cached tokens any row of the step
      holds, not the pool's size and not the list's: a step of short
      rows walks a page or two. Rows with fewer pages ride the longer
      rows' trips masked (`lm_facade.py` counts what that costs).

Operands bfloat16; scores, softmax and accumulators float32. Masked
scores are a large finite negative and a query that sees nothing
(padding) reads zeros: no NaN is made.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30


def _visible(q_pos: jax.Array,      # (rows, l) int32
             k_pos: jax.Array,      # (rows, n) int32
             k_real: jax.Array,     # (rows, n) bool
             window: Optional[int]) -> jax.Array:
    """(rows, l, n) bool: key j is real, not ahead of query i and, with
    a window, less than `window` positions behind it."""
    behind = q_pos[:, :, None] - k_pos[:, None, :]
    seen = k_real[:, None, :] & (behind >= 0)
    return seen if window is None else seen & (behind < window)


def _fold(carry, qg, k, v, keep, minor: bool = False):
    """One block of keys into the running (max, sum, weighted values).
    qg (rows, l, hkv, m, d); k, v (rows, n, hkv, d), or token-minor
    (rows, hkv, d, n) as the caches hold them; keep (rows, l, n)."""
    top, total, acc = carry
    scale = 1.0 / (qg.shape[-1] ** 0.5)
    laid = "rgdk" if minor else "rkgd"
    s = jnp.einsum(f"rlgmd,{laid}->rgmlk", qg, k,
                   preferred_element_type=F32) * scale
    keep = keep[:, None, None, :, :]
    s = jnp.where(keep, s, MASKED)
    new_top = jnp.maximum(top, jnp.max(s, axis=-1))
    p = jnp.where(keep, jnp.exp(s - new_top[..., None]), 0.0)
    fade = jnp.exp(top - new_top)
    return (new_top, total * fade + jnp.sum(p, axis=-1),
            acc * fade[..., None] + jnp.einsum(
                f"rgmlk,{laid}->rgmld", p.astype(jnp.bfloat16), v,
                preferred_element_type=F32))


def _key_step(keys: int, queries: int, most: int = 1 << 20) -> int:
    """Keys a block: all of `keys` while a block's scores (a query head)
    stay under `most` (query, key) pairs, else halved until they do or
    128 is reached."""
    step = keys
    while step * queries > most and step % 2 == 0 and step > 128:
        step //= 2
    return step


class _Rows:
    """What both forms share: the queries grouped by key/value head, the
    positions, the fold over the rows' own tokens, and the way out."""

    def __init__(self, q, own_k, own_v, cached_len, own_len,
                 window: Optional[int]):
        self.rows, self.length, self.hq, self.d = q.shape
        self.hkv = own_k.shape[2]
        self.window = window
        self.qg = q.reshape(self.rows, self.length, self.hkv,
                            self.hq // self.hkv, self.d)
        at = jnp.arange(self.length, dtype=jnp.int32)[None, :]
        self.q_pos = cached_len[:, None] + at
        shape = (self.rows, self.hkv, self.hq // self.hkv, self.length)
        carry = (jnp.full(shape, MASKED, F32), jnp.zeros(shape, F32),
                 jnp.zeros(shape + (self.d,), F32))
        # the rows' own tokens, in blocks where a chunk is long
        step = _key_step(self.length, self.rows * self.length)
        if self.length % step:
            step = self.length
        real = at < own_len[:, None]
        for start in range(0, self.length, step):
            cut = slice(start, start + step)
            carry = _fold(carry, self.qg, own_k[:, cut], own_v[:, cut],
                          self.visible(self.q_pos[:, cut], real[:, cut]))
        self.carry = carry

    def visible(self, k_pos, k_real):
        return _visible(self.q_pos, k_pos, k_real, self.window)

    def fold_cached(self, carry, blocks, k_pos, k_real):
        """One block of cached keys a row into the carry: `blocks[r]` (1,
        2 * hkv * d, n) as it lies in the cache, `k_pos` and `k_real`
        (rows, n). Row by row, each slice multiplied where it was read
        (the rows' slices are never copied side by side)."""
        keep = self.visible(k_pos, k_real)
        half = self.hkv * self.d
        rows = []
        for r, block in enumerate(blocks):
            one = slice(r, r + 1)
            k, v = (part.reshape(1, self.hkv, self.d, -1)
                    for part in (block[:, :half], block[:, half:]))
            rows.append(_fold(tuple(c[one] for c in carry), self.qg[one],
                              k, v, keep[one], minor=True))
        return tuple(jnp.concatenate(parts) for parts in zip(*rows))

    def out(self, carry) -> jax.Array:
        _, total, acc = carry
        o = acc / jnp.maximum(total, 1e-30)[..., None]
        return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(
            self.rows, self.length, self.hq * self.d).astype(jnp.bfloat16)


@jax.named_scope("window_attend")
def window_attend(q: jax.Array,         # (rows, l, hq, d), rotated
                  own_k: jax.Array,     # (rows, l, hkv, d), rotated
                  own_v: jax.Array,     # (rows, l, hkv, d)
                  ring: jax.Array,      # (ring slots, 2 * hkv * d, W)
                  slot: jax.Array,      # (rows,) int32
                  cached_len: jax.Array,    # (rows,) int32
                  own_len: jax.Array,   # (rows,) int32
                  ) -> jax.Array:
    """(rows, l, hq * d) bfloat16: each query over the keys less than `W`
    positions behind it, `W` the ring's columns. Column t of a slot that
    holds `n` tokens holds the LAST position below `n` that is `t mod
    W`."""
    window = ring.shape[2]
    rows = _Rows(q, own_k, own_v, cached_len, own_len, window)
    step = _key_step(window, rows.rows * rows.length)

    def ring_block(j, carry):
        blocks = [jax.lax.dynamic_slice(
            ring, (slot[r], 0, j * step), (1, ring.shape[1], step))
            for r in range(rows.rows)]
        t = j * step + jnp.arange(step, dtype=jnp.int32)[None, :]
        newest = cached_len[:, None] - 1
        k_pos = newest - jnp.mod(newest - t, window)
        return rows.fold_cached(carry, blocks, k_pos, k_pos >= 0)
    return rows.out(jax.lax.fori_loop(0, window // step, ring_block,
                                      rows.carry))


@jax.named_scope("full_attend")
def full_attend(q: jax.Array,           # (rows, l, hq, d)
                own_k: jax.Array,       # (rows, l, hkv, d)
                own_v: jax.Array,       # (rows, l, hkv, d)
                pool: jax.Array,        # (pages, 2 * hkv * d, P)
                pages: jax.Array,       # (rows, most pages a context) int32
                cached_len: jax.Array,  # (rows,) int32
                own_len: jax.Array,     # (rows,) int32
                ) -> jax.Array:
    """(rows, l, hq * d) bfloat16: each query over every cached token of
    its row's pages and its own tokens up to itself. Entry g of a row's
    list names the page that holds its positions `[g P, (g + 1) P)`;
    entries past the row's cached tokens are never read as keys."""
    page = pool.shape[2]
    rows = _Rows(q, own_k, own_v, cached_len, own_len, None)
    step = _key_step(page, rows.rows * rows.length)
    per_page = page // step

    def page_block(j, carry):
        which = jax.lax.dynamic_slice_in_dim(pages, j // per_page, 1,
                                             axis=1)[:, 0]
        inside = (j % per_page) * step
        blocks = [jax.lax.dynamic_slice(
            pool, (which[r], 0, inside), (1, pool.shape[1], step))
            for r in range(rows.rows)]
        k_pos = jnp.broadcast_to(
            j * step + jnp.arange(step, dtype=jnp.int32)[None, :],
            (rows.rows, step))
        return rows.fold_cached(carry, blocks, k_pos,
                                k_pos < cached_len[:, None])
    blocks = (jnp.max(cached_len) + step - 1) // step
    return rows.out(jax.lax.fori_loop(0, blocks, page_block, rows.carry))
