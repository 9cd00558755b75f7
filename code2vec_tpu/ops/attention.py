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
    attention = unnorm / jnp.maximum(denom, 1e-30)           # (B, M)

    code_vectors = jnp.einsum(
        "bm,bmd->bd", attention.astype(transformed.dtype), transformed,
        preferred_element_type=jnp.float32)                  # (B, D)
    if axis_name is not None:
        code_vectors = jax.lax.psum(code_vectors, axis_name)
    return code_vectors, attention
