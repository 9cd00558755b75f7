"""The plain reference of models/window_moe_lm.py: the same layer
equations in straightforward `jax.numpy`, float32, every product at
"highest", one sequence at a time.

No cache, no ring, no pages and no chunks: one forward pass over the
whole sequence, the window as a MASK over the whole causal square, the
experts a loop with a dense mask, all logits of the slice. It is given
the same share as the program: the leading `layers`, the experts
`[expert_first, expert_first + experts_held)` and the first `vocab_rows`
rows.

    h0 = E[ids] * sqrt(hidden_size)                       (mup_enabled)
    u  = rms(h; w_in)
    q  = rms_d(W_q u; w_qn),  k = rms_d(W_k u; w_kn),  v = W_v u
    window layer:  q, k = rot(q), rot(k);  i - sliding_window < j <= i
    full layer:    no rotary;  j <= i
    y  = softmax_j(q_i . k_j / sqrt(head_dim)) v_j * sigmoid(W_g u)
    h' = h + rms(W_o y; w_post_attn)
    r  = rms(h'; w_pre_mlp)
    dense:    z = W_down(silu(W_gate r) * W_up r)
    experts:  s = sigmoid(W_r r);  S = top-k of (s + b);
              g_e = route_scale * s_e / (sum_S s + 1e-20)
              z = shared(r) + sum_{e in S} g_e * expert_e(r)
    h'' = h' + rms(z; w_post_mlp)

Not in the published config, set by the family's published
implementation here as in the program, and listed under `assumed` in the
benchmark's configuration file: the sigmoid gate on the attention's
output (one a head and dimension, from the layer's normed input), the
RMSNorm with a weight over each head's `head_dim` on q and k, rotary
(half-split pairs over all of `head_dim`) in the window layers ONLY, the
four norms a layer, the embedding's `sqrt(hidden_size)`, and the
`1e-20`.

`window` and the FAULTS are for the tests that show a departure READS:
`window` another window than the configuration's; `fault` one of
`rotary_everywhere` (the full layers rotated too), `no_attn_gate`,
`no_post_norms`, `no_route_scale`, `no_router_bias`.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import layer_params
from code2vec_tpu.models.window_moe_lm import LMConfig
from code2vec_tpu.ops import moe

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FAULTS = ("rotary_everywhere", "no_attn_gate", "no_post_norms",
          "no_route_scale", "no_router_bias")


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def _rotate(x, theta):
    """x (l, heads, d) at positions 0..l-1: pair (i, i + d/2) turned by
    position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inverse
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def attention(cfg: LMConfig, p: Dict, kind: str, u: jax.Array,
              window: int, fault: Optional[str]) -> jax.Array:
    """u (l, hidden) float32, positions 0..l-1 -> the block's output
    before its post-norm."""
    length = u.shape[0]
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = _rms(_mm(u, p["wq"]).reshape(length, hq, d), p["q_norm"],
             cfg.norm_eps)
    k = _rms(_mm(u, p["wk"]).reshape(length, hkv, d), p["k_norm"],
             cfg.norm_eps)
    v = _mm(u, p["wv"]).reshape(length, hkv, d)
    at = jnp.arange(length)
    seen = at[:, None] >= at[None, :]
    if kind == "w":
        seen = seen & (at[:, None] - at[None, :] < window)
    if kind == "w" or fault == "rotary_everywhere":
        q, k = _rotate(q, cfg.rope_theta), _rotate(k, cfg.rope_theta)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (d ** 0.5)
    pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    y = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(
        length, hq * d)
    if fault != "no_attn_gate":
        y = y * jax.nn.sigmoid(_mm(u, p["w_attn_gate"]))
    return _mm(y, p["wo"])


def _gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def experts(cfg: LMConfig, p: Dict, r: jax.Array, fault: Optional[str]
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (the layer's output (l, hidden), the router's choice (l, k))."""
    s = jax.nn.sigmoid(_mm(r, p["router"]))
    steer = s if fault == "no_router_bias" else s + p["router_bias"]
    _, chosen = jax.lax.top_k(steer, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    scale = 1.0 if fault == "no_route_scale" else cfg.route_scale
    routed = moe.Routed(
        chosen.astype(jnp.int32),
        scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20))
    out = moe.experts_loop(r, routed, p["w_up"], p["w_down"],
                           cfg.expert_first, w_gate=p["w_gate"])
    return (out + _gated(r, p["shared_gate"], p["shared_up"],
                         p["shared_down"]), routed.experts)


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids,
           window: Optional[int] = None, fault: Optional[str] = None
           ) -> Tuple[jax.Array, jax.Array]:
    """One sequence `ids` (l,) -> (next-token logits at its last position
    over the rows held (vocab_rows,), the router's choices (expert
    layers, l, k))."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {', '.join(FAULTS)}")
    window = cfg.sliding_window if window is None else window
    h = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    if cfg.mup_enabled:
        h = h * (cfg.hidden_size ** 0.5)
    eps, chosen = cfg.norm_eps, []

    def post(x, w):
        return x if fault == "no_post_norms" else _rms(x, w, eps)
    for i, (kind, mlp) in enumerate(cfg.kinds):
        p = layer_params(params, i)
        h = h + post(attention(cfg, p, kind, _rms(h, p["attn_norm"], eps),
                               window, fault), p["post_attn_norm"])
        r = _rms(h, p["mlp_norm"], eps)
        if mlp == "D":
            out = _gated(r, p["gate"], p["up"], p["down"])
        else:
            out, choice = experts(cfg, p, r, fault)
            chosen.append(choice)
        h = h + post(out, p["post_mlp_norm"])
    last = _rms(h[-1], params["final_norm"], eps)
    return (_mm(params["head"].astype(F32), last),
            jnp.stack(chosen) if chosen else jnp.zeros(
                (0, len(ids), cfg.num_experts_per_tok), jnp.int32))
