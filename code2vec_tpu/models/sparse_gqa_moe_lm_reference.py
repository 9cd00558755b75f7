"""The plain reference of models/sparse_gqa_moe_lm.py: the same layer
equations in straightforward `jax.numpy`, float32, every product at
"highest", one sequence at a time.

No cache and no chunks (one forward pass over the whole sequence), the
index scores of the whole causal row and `lax.top_k` over them (which
keeps the lower position of equal scores), the whole causal attention
matrix with the keys that were not kept masked, no grouped matmul (the
experts are a loop with a dense mask), no blockwise head (all logits of
the slice). It is given the same share as the program: the leading
`layers`, the experts `[expert_first, expert_first + experts_held)` and
the first `vocab_rows` rows.

Not in the published config, set by the family's convention here as in
the program, and listed under `assumed` in the benchmark's
configuration file:
  - RMSNorm with a weight over each head's `head_dim` on q and k, before
    the rotary;
  - the index key goes through a LayerNorm (weight and bias), and index
    queries and key are rotated over all `indexer_head_dim` dimensions
    with the attention head's `rope_theta` and its sections scaled to
    the index head's width;
  - the scale `indexer_num_heads^-1/2 * indexer_head_dim^-1/2` is folded
    into the head weights;
  - rotary pairs are half-split, `(i, i + d/2)`;
  - `q_chunk_size` / `kv_chunk_size` tile the published code's index
    scores and fix no value: nothing here reads them.
Departure: a text token's three rotary positions are equal, so the
reference rotates by ONE position; the program's three streams are held
against it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import layer_params
from code2vec_tpu.models.sparse_gqa_moe_lm import LMConfig
from code2vec_tpu.ops import moe

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def _rotate(x, theta):
    """x (l, ..., d) at positions 0..l-1: pair (i, i + d/2) turned by
    position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[0], dtype=F32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inverse
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def selection(cfg: LMConfig, p: Dict, u: jax.Array) -> jax.Array:
    """(l, l) bool: the keys each query keeps."""
    length = u.shape[0]
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    q_i = _rotate(_mm(u, p["idx_q"]).reshape(length, hi, di),
                  cfg.rope_theta)
    k = _mm(u, p["idx_k"])
    mean = jnp.mean(k, -1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), -1, keepdims=True) + cfg.norm_eps)
        * p["idx_k_norm"] + p["idx_k_bias"])
    k_i = _rotate(k, cfg.rope_theta)
    a = _mm(u, p["idx_w"]) * ((hi * di) ** -0.5)
    score = jnp.einsum("qh,qhk->qk", a, jax.nn.relu(jnp.einsum(
        "qhd,kd->qhk", q_i, k_i, precision=HI)), precision=HI)
    at = jnp.arange(length)
    causal = at[:, None] >= at[None, :]
    _, kept = jax.lax.top_k(jnp.where(causal, score, -jnp.inf),
                            min(cfg.topk, length))
    chosen = jnp.zeros((length, length), bool).at[at[:, None], kept].set(
        True)
    return chosen & causal


def attention(cfg: LMConfig, p: Dict, u: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """u (l, hidden) float32, positions 0..l-1 -> (the block's output,
    the selection (l, l))."""
    length = u.shape[0]
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = _rotate(_rms(_mm(u, p["wq"]).reshape(length, hq, d), p["q_norm"],
                     cfg.norm_eps), cfg.rope_theta)
    k = _rotate(_rms(_mm(u, p["wk"]).reshape(length, hkv, d), p["k_norm"],
                     cfg.norm_eps), cfg.rope_theta)
    v = _mm(u, p["wv"]).reshape(length, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    kept = selection(cfg, p, u)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (d ** 0.5)
    pr = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
    return _mm(o.reshape(length, hq * d), p["wo"]), kept


def experts(cfg: LMConfig, p: Dict, u: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (the layer's output (l, hidden), the router's choice (l, k))."""
    prob = jax.nn.softmax(_mm(u, p["router"]), axis=-1)
    picked, chosen = jax.lax.top_k(prob, cfg.num_experts_per_tok)
    routed = moe.Routed(chosen.astype(jnp.int32),
                        picked / jnp.sum(picked, -1, keepdims=True))
    return (moe.experts_loop(u, routed, p["w_up"], p["w_down"],
                             cfg.expert_first, w_gate=p["w_gate"]),
            routed.experts)


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One sequence `ids` (l,) -> (next-token logits at its last position
    over the rows held (vocab_rows,), the router's choices (layers, l,
    k), the keys the LAST query kept (layers, l) bool)."""
    h = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    chosen, kept_last = [], []
    for i in range(cfg.layers):
        p = layer_params(params, i)
        out, kept = attention(cfg, p, _rms(h, p["attn_norm"], cfg.norm_eps))
        h = h + out
        kept_last.append(kept[-1])
        out, choice = experts(cfg, p, _rms(h, p["mlp_norm"], cfg.norm_eps))
        h = h + out
        chosen.append(choice)
    last = _rms(h[-1], params["final_norm"], cfg.norm_eps)
    return (_mm(params["head"].astype(F32), last), jnp.stack(chosen),
            jnp.stack(kept_last))
