"""The plain reference of models/retention_lm.py: the same layer
equations in straightforward `jax.numpy`, float32, every product at
"highest", one sequence at a time.

The QUADRATIC form: every weight `a_ij = (q_i . k_j / sqrt(d))^2 *
exp(sum_{l=j+1..i} log g_l)`, `j <= i`, of the whole sequence, and `y_i
= sum_j a_ij v_j / (sum_j a_ij + eps)`. No state, no feature map, no
chunks, no cache, no blockwise head (all logits of the slice). It is
given the same share as the program: the leading `layers` and the first
`vocab_rows` rows.

Not in the published config, set by the family's own description here
as in the program, and listed under `assumed` in the benchmark's
configuration file: the power 2; one gate a key/value head and token,
`log sigmoid(W_g u + b_g)`; an RMSNorm with a weight over each head's
`head_dim` on q and k, before the rotary; rotary over the whole head,
half-split pairs `(i, i + d/2)`; the scale `1 / sqrt(d)`; eps 1e-6.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import layer_params
from code2vec_tpu.models.retention_lm import LMConfig
from code2vec_tpu.ops.power_retention import EPS

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


def retention(cfg: LMConfig, p: Dict, u: jax.Array) -> jax.Array:
    """u (l, hidden) float32, positions 0..l-1 -> the block's output."""
    length = u.shape[0]
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = _rotate(_rms(_mm(u, p["wq"]).reshape(length, hq, d), p["q_norm"],
                     cfg.norm_eps), cfg.rope_theta)
    k = _rotate(_rms(_mm(u, p["wk"]).reshape(length, hkv, d), p["k_norm"],
                     cfg.norm_eps), cfg.rope_theta)
    v = _mm(u, p["wv"]).reshape(length, hkv, d)
    log_g = jax.nn.log_sigmoid(_mm(u, p["wg"]) + p["bg"])    # (l, hkv)
    k, v, log_g = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v, log_g))
    cum = jnp.cumsum(log_g, axis=0).T                        # (hq, l)
    at = jnp.arange(length)
    causal = at[:, None] >= at[None, :]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (d ** 0.5)
    a = jnp.where(causal, jnp.square(s) * jnp.exp(jnp.where(
        causal, cum[:, :, None] - cum[:, None, :], 0.0)), 0.0)
    y = jnp.einsum("hqk,khd->qhd", a, v, precision=HI)
    y = y / (jnp.sum(a, axis=-1).T[..., None] + EPS)
    return _mm(y.reshape(length, hq * d), p["wo"])


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids) -> jax.Array:
    """One sequence `ids` (l,) -> next-token logits at its last position
    over the rows held (vocab_rows,)."""
    h = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    for i in range(cfg.layers):
        p = layer_params(params, i)
        h = h + retention(cfg, p, _rms(h, p["attn_norm"], cfg.norm_eps))
        r = _rms(h, p["mlp_norm"], cfg.norm_eps)
        h = h + _mm(jax.nn.silu(_mm(r, p["gate"])) * _mm(r, p["up"]),
                    p["down"])
    last = _rms(h[-1], params["final_norm"], cfg.norm_eps)
    return _mm(params["head"].astype(F32), last)
