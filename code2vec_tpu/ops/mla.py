"""Multi-head latent attention (MLA) over a latent cache.

A token leaves two things behind for later tokens to attend to, and the
cache holds only those: `c_kv`, the normalised low-rank key/value latent
(`kv_rank` wide), and `k_r`, ONE rotary key shared by all heads
(`d_rope` wide), already rotated to the token's position. Keys and
values of every head are functions of the latent:

    [k_n | v](head h) = c_kv W_kvb[:, h]       (d_nope + d_v a head)
    score(q, token)   = (q_n . k_n + q_r . k_r) / sqrt(d_nope + d_rope)

`project` makes a token's queries and its latent. `attend` lets query
rows read their own latents (causal) and one slot of cached latents
each (all of it visible, up to the row's cached length). It has two forms
over the same weights and picks one from the shapes it is given:

  expanded  rebuild `k_n` and `v` of every head from each cached block,
            then ordinary multi-head attention: `kv_rank * heads *
            (d_nope + d_v)` multiply-adds a KEY, whatever the queries.
  absorbed  fold `W_uk` into the query (`q' = q_n W_uk^T`, `kv_rank`
            wide) and `W_uv` into the output, so a score is `q' . c_kv +
            q_r . k_r` and the weighted sum is over `c_kv` itself: the
            cache is read as it lies, but a (query, key) pair costs
            `2 kv_rank + d_rope` a head where the expanded one costs
            `d_nope + d_rope + d_v`.

Few queries a row (a question against a long context) favour the
absorbed form, many (a registration chunk) the expanded one; the
crossover `reads_absorbed` is where the two costs meet, and the cached
length cancels out of it.

Both run block by block over the keys with a running max and sum; the
queries' own block comes first, so every real query has a finite max
before any block it cannot see. Masked scores are a large finite
negative, not -inf: padding rows then read garbage, never NaN, and a
NaN could not stay in its row (0 x NaN in the weighted sum).

Operands bfloat16, scores, softmax, accumulators and rotary angles
float32. Rotary pairs are interleaved, (2i, 2i + 1), the family's
convention.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30


def rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of the last axis (even width d): pair (2i, 2i+1)
    turned by `position * theta^(-2i/d)`. `positions` has x's shape
    without the last axis (or broadcasts to it). Float32 out."""
    d = x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions[..., None].astype(F32) * inverse
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(F32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _rms(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(F32))


def _dot(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


@jax.named_scope("mla_proj")
def project(u: jax.Array,           # (rows, l, hidden)
            positions: jax.Array,   # (rows, l) absolute positions
            q_a: jax.Array, q_norm: jax.Array, q_b: jax.Array,
            kv_a: jax.Array, kv_norm: jax.Array,
            heads: int, d_rope: int, theta: float, eps: float
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (q_n (rows, l, heads, d_nope), q_r (rows, l, heads, d_rope)
    rotated, latent (rows, l, kv_rank + d_rope) = [rms(c_kv) | rotated
    k_r]), all bfloat16: what `attend` takes and the cache holds."""
    rows, length, _ = u.shape
    bf16 = jnp.bfloat16
    c_q = _rms(_dot(u, q_a), q_norm, eps)
    q = _dot(c_q, q_b).reshape(rows, length, heads, -1)
    d_nope = q.shape[-1] - d_rope
    q_r = rotate(q[..., d_nope:], positions[..., None], theta)
    kv = _dot(u, kv_a)
    kv_rank = kv.shape[-1] - d_rope
    latent = jnp.concatenate(
        [_rms(kv[..., :kv_rank], kv_norm, eps),
         rotate(kv[..., kv_rank:], positions, theta)], axis=-1)
    return (q[..., :d_nope].astype(bf16), q_r.astype(bf16),
            latent.astype(bf16))


def reads_absorbed(queries: int, heads: int, d_nope: int, d_rope: int,
                   d_v: int, kv_rank: int) -> bool:
    """Whether `queries` query positions a row are too few to pay for
    expanding a cached key: multiply-adds a key, absorbed against
    expanded. The number of keys multiplies both sides."""
    absorbed = queries * heads * (2 * kv_rank + d_rope)
    expanded = (kv_rank * heads * (d_nope + d_v)
                + queries * heads * (d_nope + d_rope + d_v))
    return absorbed < expanded


@jax.named_scope("mla_attend")
def attend(q_n: jax.Array,          # (rows, l, heads, d_nope)
           q_r: jax.Array,          # (rows, l, heads, d_rope), rotated
           own: jax.Array,          # (rows, l, kv_rank + d_rope)
           cached: jax.Array,       # (slots, capacity, kv_rank + d_rope)
           slot: jax.Array,         # (rows,) int32: each row's slot
           cached_len: jax.Array,   # (rows,) int32: cached tokens to read
           own_len: jax.Array,      # (rows,) int32: real query tokens
           kv_b: jax.Array,         # (kv_rank, heads * (d_nope + d_v))
           block: int = 512) -> jax.Array:
    """(rows, l, heads, d_v) bfloat16: query i of a row attends the
    first `cached_len` tokens of the row's slot and its own tokens 0..i.
    The slots are read block by block where they lie: no row's slot is
    copied whole."""
    rows, length, heads, d_nope = q_n.shape
    d_rope = q_r.shape[-1]
    rank = own.shape[-1] - d_rope
    w = kv_b.reshape(rank, heads, -1)
    d_v = w.shape[-1] - d_nope
    bf16 = jnp.bfloat16
    scale = 1.0 / ((d_nope + d_rope) ** 0.5)
    absorbed = reads_absorbed(length, heads, d_nope, d_rope, d_v, rank)
    if absorbed:
        # scores and sums stay (rows, l, heads, .): every head of a row
        # reads the same block, so heads and queries are one matmul side
        q = jnp.concatenate([jnp.einsum(
            "rlhn,chn->rlhc", q_n, w[..., :d_nope],
            preferred_element_type=F32).astype(bf16), q_r], axis=-1)
        acc_shape = (rows, length, heads, rank)

        def visit(blk):
            s = jnp.einsum("rlhc,rkc->rlhk", q, blk,
                           preferred_element_type=F32)
            return s, lambda p: jnp.einsum(
                "rlhk,rkc->rlhc", p, blk[..., :rank],
                preferred_element_type=F32)

        def to_heads(valid):                # (rows, l, k) -> s's shape
            return valid[:, :, None, :]
    else:
        q = jnp.concatenate([q_n, q_r], axis=-1)
        acc_shape = (rows, heads, length, d_v)

        def visit(blk):
            kv = jnp.einsum("rkc,chm->rkhm", blk[..., :rank], w,
                            preferred_element_type=F32).astype(bf16)
            k_r = jnp.broadcast_to(blk[:, :, None, rank:],
                                   kv.shape[:3] + (d_rope,))
            k = jnp.concatenate([kv[..., :d_nope], k_r], axis=-1)
            s = jnp.einsum("rlhd,rkhd->rhlk", q, k,
                           preferred_element_type=F32)
            return s, lambda p: jnp.einsum(
                "rhlk,rkhv->rhlv", p, kv[..., d_nope:],
                preferred_element_type=F32)

        def to_heads(valid):
            return valid[:, None, :, :]

    def fold(carry, blk, valid):
        top, total, acc = carry
        s, weigh = visit(blk)
        s = jnp.where(to_heads(valid), s * scale, MASKED)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1))
        p = jnp.exp(s - new_top[..., None])
        fade = jnp.exp(top - new_top)
        return (new_top, total * fade + jnp.sum(p, axis=-1),
                acc * fade[..., None] + weigh(p.astype(bf16)))

    carry = (jnp.full(acc_shape[:-1], MASKED, F32),
             jnp.zeros(acc_shape[:-1], F32), jnp.zeros(acc_shape, F32))
    # the queries' own tokens, causal; block 0 holds key 0, which every
    # real query sees
    query_at = jnp.arange(length)
    for start in range(0, length, block):
        key_at = jnp.arange(start, min(start + block, length))
        valid = ((key_at[None, None, :] <= query_at[None, :, None])
                 & (key_at[None, None, :] < own_len[:, None, None]))
        carry = fold(carry, own[:, start:start + block], valid)
    # then the cached tokens, as many blocks as the longest row needs
    capacity = cached.shape[1]
    step = min(block, capacity)
    if capacity % step:
        raise ValueError(f"a slot's {capacity} tokens are no multiple of "
                         f"the key block {step}")

    def cached_block(j, carry):
        # one contiguous slice a row: a gather over the slot index pays by
        # the ROW of the cache on this chip (PERF.md, PR 31)
        blk = jnp.concatenate([jax.lax.dynamic_slice(
            cached, (slot[r], j * step, 0), (1, step, cached.shape[2]))
            for r in range(rows)])
        key_at = j * step + jnp.arange(step)
        valid = jnp.broadcast_to(
            key_at[None, None, :] < cached_len[:, None, None],
            (rows, length, step))
        return fold(carry, blk, valid)
    blocks = (jnp.max(cached_len) + step - 1) // step
    _, total, acc = jax.lax.fori_loop(0, blocks, cached_block, carry)
    out = (acc / total[..., None]).astype(bf16)
    if absorbed:
        return jnp.einsum("rlhc,chv->rlhv", out, w[..., d_nope:],
                          preferred_element_type=F32).astype(bf16)
    return jnp.transpose(out, (0, 2, 1, 3))
