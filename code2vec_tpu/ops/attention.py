"""Masked single-query attention over the bag of path-contexts.

This is the core of code2vec: a single trainable query vector scores every
context, invalid (padding) contexts get -inf via an additive log-mask, and
the code vector is the attention-weighted sum. Exact math from the
reference (tensorflow_model.py:253-262 / keras_attention_layer.py:52-63):

    w      = tanh(ctx @ W) @ a            # (B, M)
    w     += log(mask)                    # -inf on invalid contexts
    attn   = softmax(w, axis=contexts)
    codev  = sum(attn * tanh(ctx @ W), axis=contexts)

Kept as a standalone op so the context axis can be sharded: with contexts
split over a mesh axis the softmax combines per-shard (max, sum-exp)
partials with collectives — the degenerate single-query form of ring
attention (SURVEY.md §5 long-context plan). `axis_name=None` is the
single-shard path used under plain jit/GSPMD.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def masked_softmax(scores: jax.Array, context_valid_mask: jax.Array,
                   axis_name: Optional[str] = None) -> jax.Array:
    """Softmax over the contexts of float32 `(B, M_local)` scores, the
    invalid ones at weight exactly 0 (a row with none: all 0)."""
    # Additive log-mask (reference: tensorflow_model.py:256-258). Where the
    # mask is 0 this is -inf; jnp.where keeps the gradient clean.
    neg_inf = jnp.asarray(-jnp.inf, dtype=scores.dtype)
    scores = jnp.where(context_valid_mask > 0, scores, neg_inf)

    # The max shift is numerical stabilization only; its gradient cancels
    # exactly in softmax, so stop_gradient (also: pmax has no AD rule).
    local_max = jax.lax.stop_gradient(jnp.max(scores, axis=1, keepdims=True))
    if axis_name is not None:
        local_max = jax.lax.pmax(local_max, axis_name)
    # Guard all-invalid rows (padded eval examples): exp(-inf - -inf) = nan,
    # so pin the max to 0 there; the row's weights become 0/sum=0 -> handled
    # by the caller's example_valid mask.
    safe_max = jnp.where(jnp.isfinite(local_max), local_max, 0.0)
    unnorm = jnp.exp(scores - safe_max)                      # (B, M)
    denom = jnp.sum(unnorm, axis=1, keepdims=True)           # (B, 1)
    if axis_name is not None:
        denom = jax.lax.psum(denom, axis_name)
    return unnorm / jnp.maximum(denom, 1e-30)                # (B, M)


@jax.named_scope("attention")
def masked_single_query_attention(
    transformed: jax.Array,       # (B, M_local, D) already tanh(ctx @ W)
    attention_param: jax.Array,   # (D,)
    context_valid_mask: jax.Array,  # (B, M_local) float {0,1}
    axis_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (code_vectors (B, D), attention_weights (B, M_local)).

    Softmax runs in float32 regardless of the compute dtype. When
    `axis_name` names a mesh axis over which the context dimension is
    sharded, the max/sum-exp/weighted-sum reductions are combined across
    shards with pmax/psum so the result equals the unsharded computation.
    """
    scores = jnp.einsum(
        "bmd,d->bm", transformed, attention_param.astype(transformed.dtype),
        preferred_element_type=jnp.float32)           # (B, M)
    attention = masked_softmax(scores, context_valid_mask, axis_name)

    code_vectors = jnp.einsum(
        "bm,bmd->bd", attention.astype(transformed.dtype), transformed,
        preferred_element_type=jnp.float32)                  # (B, D)
    if axis_name is not None:
        code_vectors = jax.lax.psum(code_vectors, axis_name)
    return code_vectors, attention


@jax.named_scope("attn")
def causal_gqa_attention(q: jax.Array,      # (b, l, q_heads, d)
                         k: jax.Array,      # (b, l, kv_heads, d)
                         v: jax.Array,      # (b, l, kv_heads, d)
                         block: int = 512) -> jax.Array:
    """Causal softmax(q k^T / sqrt(d)) v with grouped queries (query head
    n reads key/value head n // (q_heads / kv_heads)), float32 out.

    Computed block by block with a running max and sum, so the (l, l)
    scores never exist whole: a query block visits only the key blocks
    at or before it. Operands keep their dtype (bfloat16 on the MXU),
    scores, softmax and accumulator are float32. Right padding is safe:
    a position never reads one after it.
    """
    bsz, length, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    block = min(block, length)
    pad = (-length) % block
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    nb = (length + pad) // block
    q = q.reshape(bsz, nb, block, hkv, rep, d)
    k = k.reshape(bsz, nb, block, hkv, d)
    v = v.reshape(bsz, nb, block, hkv, d)
    scale = 1.0 / (d ** 0.5)
    within = jnp.arange(block)
    f32 = jnp.float32

    def query_block(i):
        qi = q[:, i]                                     # (b, q, h, r, d)

        def key_block(j, carry):
            top, total, acc = carry
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qi, k[:, j],
                           preferred_element_type=f32) * scale
            seen = (i * block + within)[:, None] >= (j * block + within)
            s = jnp.where(seen, s, -jnp.inf)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            p = jnp.exp(s - new_top[..., None])
            fade = jnp.exp(top - new_top)
            total = total * fade + jnp.sum(p, axis=-1)
            acc = acc * fade[..., None] + jnp.einsum(
                "bhrqk,bkhd->bhrqd", p.astype(v.dtype), v[:, j],
                preferred_element_type=f32)
            return new_top, total, acc
        init = (jnp.full((bsz, hkv, rep, block), -jnp.inf, f32),
                jnp.zeros((bsz, hkv, rep, block), f32),
                jnp.zeros((bsz, hkv, rep, block, d), f32))
        # the diagonal block comes first, so the running max is finite
        # from the first step on; then the blocks before it
        top, total, acc = key_block(i, init)
        top, total, acc = jax.lax.fori_loop(0, i, key_block,
                                            (top, total, acc))
        return acc / total[..., None]                    # (b, h, r, q, d)
    out = jax.lax.map(query_block, jnp.arange(nb))       # (nb, b, h, r, q, d)
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5))         # (b, nb, q, h, r, d)
    return out.reshape(bsz, nb * block, hq, d)[:, :length]


def causal_gqa_attention_plain(q, k, v) -> jax.Array:
    """The same attention with the whole (l, l) score matrix, float32,
    "highest": what the blockwise form is tested against."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    bsz, length, hq, d = q.shape
    rep = hq // k.shape[2]
    k = jnp.repeat(k.astype(f32), rep, axis=2)
    v = jnp.repeat(v.astype(f32), rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k,
                   precision=hi) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)
