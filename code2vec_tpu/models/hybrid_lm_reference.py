"""The plain reference of models/hybrid_lm.py: the same layer equations
in straightforward `jax.numpy`, float32, every product at "highest",
one sequence at a time.

No chunks (the state-space layer is the recurrence over `t`), no
grouped matmul (the experts are a loop with a dense mask), no blocks in
the attention (the whole causal score matrix), no blockwise head (all
logits of the slice). It is given the same share as the program: the
experts `[expert_first, expert_first + experts_held)` and the first
`vocab_rows` rows of the vocabulary.

Assumptions, each also stated in the benchmark's configuration file: no
positional encoding in the attention layers (the `nemotron_h` family
relies on the Mamba layers for position; the published `rope_theta` is
unused there); no `time_step_limit` clip on `dt`.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.hybrid_lm import LMConfig, layer_params
from code2vec_tpu.ops import moe
from code2vec_tpu.ops.attention import causal_gqa_attention_plain
from code2vec_tpu.ops.ssd import ssd_recurrence

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def mamba(cfg: LMConfig, p: Dict, u: jax.Array) -> jax.Array:
    """u (l, hidden) float32."""
    length = u.shape[0]
    nh, hd, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                    cfg.ssm_state_size)
    di = cfg.d_inner
    zxbcdt = _mm(u, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.conv_dim], axis=-1)
    k = cfg.conv_kernel
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(F32) + sum(
        padded[j:j + length] * p["conv_w"][:, j].astype(F32)
        for j in range(k))
    xbc = jax.nn.silu(conv)
    x, b_in, c_in = jnp.split(xbc, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    y = ssd_recurrence(x.reshape(1, length, nh, hd), dt[None],
                       -jnp.exp(p["a_log"].astype(F32)),
                       b_in.reshape(1, length, g, n),
                       c_in.reshape(1, length, g, n), p["d"])[0]
    y = y.reshape(length, di) * jax.nn.silu(z)
    y = _rms(y.reshape(length, g, di // g),
             p["gate_norm"].astype(F32).reshape(g, di // g), cfg.norm_eps)
    return _mm(y.reshape(length, di), p["out_proj"])


def attention(cfg: LMConfig, p: Dict, u: jax.Array) -> jax.Array:
    length = u.shape[0]
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = _mm(u, p["wq"]).reshape(1, length, hq, d)
    k = _mm(u, p["wk"]).reshape(1, length, hkv, d)
    v = _mm(u, p["wv"]).reshape(1, length, hkv, d)
    o = causal_gqa_attention_plain(q, k, v)[0]
    return _mm(o.reshape(length, hq * d), p["wo"])


def experts(cfg: LMConfig, p: Dict, u: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (the layer's output (l, hidden), the router's choice (l, k))."""
    routed = moe.route(u, p["router"], p["router_bias"],
                       cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    latent = _mm(u, p["down"])
    r = moe.experts_loop(latent, routed, p["w1"], p["w2"], cfg.expert_first)
    shared = _mm(moe.relu2(_mm(u, p["shared_w1"])), p["shared_w2"])
    return _mm(r, p["up"]) + shared, routed.experts


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids
           ) -> Tuple[jax.Array, jax.Array]:
    """One sequence `ids` (l,) -> (next-token logits at its last position
    over the rows held (vocab_rows,), the router's choices (expert
    layers, l, k))."""
    h = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    chosen = []
    for i, kind in enumerate(cfg.pattern):
        p = layer_params(params, i)
        u = _rms(h, p["norm"].astype(F32), cfg.norm_eps)
        if kind == "M":
            h = h + mamba(cfg, p, u)
        elif kind == "*":
            h = h + attention(cfg, p, u)
        else:
            out, choice = experts(cfg, p, u)
            h = h + out
            chosen.append(choice)
    last = _rms(h[-1], params["final_norm"].astype(F32), cfg.norm_eps)
    k = cfg.num_experts_per_tok
    return (_mm(params["head"].astype(F32), last),
            jnp.stack(chosen) if chosen
            else jnp.zeros((0, len(ids), k), jnp.int32))
