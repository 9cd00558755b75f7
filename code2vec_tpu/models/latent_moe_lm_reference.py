"""The plain reference of models/latent_moe_lm.py: the same layer
equations in straightforward `jax.numpy`, float32, every product at
"highest", one sequence at a time.

No cache and no chunks (one forward pass over the whole sequence, the
whole causal score matrix), attention expanded (keys and values of
every head rebuilt from the latent, the rotary key copied to each
head), no grouped matmul (the experts are a loop with a dense mask), no
blockwise head (all logits of the slice). It is given the same share as
the program: the leading `layers`, the experts `[expert_first,
expert_first + experts_held)` and the first `vocab_rows` rows.

Assumption, also stated in the benchmark's configuration file: rotary
pairs are interleaved, (2i, 2i + 1), over all `qk_rope_head_dim`
dimensions (`partial_rotary_factor` 1).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import layer_params
from code2vec_tpu.models.latent_moe_lm import LMConfig
from code2vec_tpu.ops import moe

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def _rotate(x, positions, theta):
    """x (l, ..., d), positions (l,): pair (2i, 2i+1) turned by
    position * theta^(-2i/d)."""
    d = x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * inverse
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                     a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)
    return out.reshape(x.shape)


def _gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def attention(cfg: LMConfig, p: Dict, u: jax.Array) -> jax.Array:
    """u (l, hidden) float32, positions 0..l-1."""
    length = u.shape[0]
    heads, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    rank = cfg.kv_lora_rank
    at = jnp.arange(length)
    q = _mm(_rms(_mm(u, p["q_a"]), p["q_norm"], cfg.norm_eps),
            p["q_b"]).reshape(length, heads, dn + dr)
    q_n, q_r = q[..., :dn], _rotate(q[..., dn:], at, cfg.rope_theta)
    kv = _mm(u, p["kv_a"])
    c_kv = _rms(kv[:, :rank], p["kv_norm"], cfg.norm_eps)
    k_r = _rotate(kv[:, rank:], at, cfg.rope_theta)         # (l, dr)
    expanded = _mm(c_kv, p["kv_b"]).reshape(length, heads, dn + dv)
    k_n, v = expanded[..., :dn], expanded[..., dn:]
    s = (jnp.einsum("qhd,khd->hqk", q_n, k_n, precision=HI)
         + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=HI)
         ) / ((dn + dr) ** 0.5)
    causal = at[:, None] >= at[None, :]
    pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
    return _mm(o.reshape(length, heads * dv), p["o"])


def experts(cfg: LMConfig, p: Dict, u: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (the layer's output (l, hidden), the router's choice (l, k))."""
    routed = moe.route(u, p["router"], p["router_bias"],
                       cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    r = moe.experts_loop(u, routed, p["w_up"], p["w_down"],
                         cfg.expert_first, w_gate=p["w_gate"])
    return (r + _gated(u, p["shared_gate"], p["shared_up"],
                       p["shared_down"]), routed.experts)


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids
           ) -> Tuple[jax.Array, jax.Array]:
    """One sequence `ids` (l,) -> (next-token logits at its last position
    over the rows held (vocab_rows,), the router's choices (expert
    layers, l, k))."""
    h = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    chosen = []
    for i, kind in enumerate(cfg.pattern):
        p = layer_params(params, i)
        h = h + attention(cfg, p, _rms(h, p["attn_norm"], cfg.norm_eps))
        u = _rms(h, p["mlp_norm"], cfg.norm_eps)
        if kind == "D":
            h = h + _gated(u, p["gate"], p["up"], p["down"])
        else:
            out, choice = experts(cfg, p, u)
            h = h + out
            chosen.append(choice)
    last = _rms(h[-1], params["final_norm"], cfg.norm_eps)
    k = cfg.num_experts_per_tok
    return (_mm(params["head"].astype(F32), last),
            jnp.stack(chosen) if chosen
            else jnp.zeros((0, len(ids), k), jnp.int32))
