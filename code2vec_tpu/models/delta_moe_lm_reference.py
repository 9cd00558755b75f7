"""The plain reference of models/delta_moe_lm.py: the same layer
equations in straightforward `jax.numpy`, float32, every product at
"highest", one sequence at a time.

No cache, no state carried, no pages and no chunks: ONE forward pass over
the whole sequence (a session as registered ++ every kept turn ++ the
turn asked about), the delta rule as the recurrence over `t`, the conv
over the whole sequence, attention as a mask over the whole causal
square, the experts a loop with a dense mask, all logits of the slice.
It is given the same share as the program: the leading `layers`, the
experts `[expert_first, expert_first + experts_held)` and the first
`vocab_rows` rows.

    h0 = E[ids]
    u  = rms(h; w_in)
    G: q, k, v = W_q u, W_k u, W_v u;  j <= i;  no positions
       y = softmax_j(q_i . k_j / sqrt(d)) v_j * sigmoid(W_g u);  m = W_o y
    K: c(x)_t = silu(sum_{i=0..3} w_i x_{t-3+i}),  x before token 0 is 0
       q = l2(c(W_q u)) / sqrt(d),  k = l2(c(W_k u)),  v = c(W_v u)
       g = -exp(A_log) * softplus(W_fb (W_fa u) + dt_bias)
       b = 2 sigmoid(W_b u)
       Z = diag(exp(g_t)) S_{t-1};  S_t = Z + b_t k_t (v_t - Z^T k_t)^T
       o_t = S_t^T q_t
       y = rms_d(o; w_on) * sigmoid(W_gb (W_ga u));  m = W_o y
    h' = h + m;  r = rms(h'; w_mlp)
    s = sigmoid(W_r r);  S = top-k of (s + bias);  w_e = scale s_e / sum_S s
    h''= h' + shared(r) + sum_{e in S, held} w_e expert_e(r)

The FAULTS are for the tests that show a departure READS. Five change an
equation: `b_not_doubled`, `no_erase` (`S_t = Z + b k v^T`),
`no_qk_norm`, `no_gqa_gate`, `head_decay` (the decay one a HEAD, its
channels' mean). Four are what a cache of a state and pages can get
wrong, seen from the LAST turn (`starts`: where each kept turn begins,
the last of them the turn asked about): `state_not_written` (the turn
before the last was scored but the K layers' state and conv inputs
behind it were not kept: the last turn's K layers see the sequence with
that turn cut out), `tails_zeroed` (the conv inputs before every turn's
start read as zeros), `page_start` (each earlier turn's keys and values
were written from its first page's START, not from where the session
stood: the last turn's queries see them there, and zeros where they
should have been), `foreign_state` (the last turn's K layers continue
ANOTHER session, `other`: its tokens in place of everything before the
turn, while the G layers read the session's own keys).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.delta_moe_lm import L2_EPS, LMConfig
from code2vec_tpu.models.lm_common import layer_params
from code2vec_tpu.ops import moe
from code2vec_tpu.ops.delta_rule import delta_recurrence

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_not_written", "tails_zeroed", "page_start",
          "foreign_state", "b_not_doubled", "no_erase", "no_qk_norm",
          "no_gqa_gate", "head_decay")


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def _gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def attention(cfg: LMConfig, p: Dict, u: jax.Array, fault: Optional[str],
              starts: Sequence[int], page: int) -> jax.Array:
    """u (l, hidden) float32 -> the G mixer's output."""
    length = u.shape[0]
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = _mm(u, p["wq"]).reshape(length, hq, d)
    k = _mm(u, p["wk"]).reshape(length, hkv, d)
    v = _mm(u, p["wv"]).reshape(length, hkv, d)
    at = jnp.arange(length)
    seen = at[:, None] >= at[None, :]

    def attend(k, v):
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (d ** 0.5)
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(
            length, hq * d)
    y = attend(k, v)
    if fault == "page_start" and len(starts) > 1:
        # what the pages hold once every EARLIER turn wrote from its
        # first page's start; the last turn reads its own tokens
        last = starts[-1]
        seen_k, seen_v = k, v
        for s, e in zip(starts[:-1], starts[1:]):
            first = s // page * page
            n = e - s
            gap = slice(max(s, first + n), e)
            seen_k, seen_v = (t.at[gap].set(0.0).at[first:first + n].set(
                own[s:e]) for t, own in ((seen_k, k), (seen_v, v)))
        seen_k, seen_v = (jnp.concatenate([t[:last], own[last:]])
                          for t, own in ((seen_k, k), (seen_v, v)))
        y = jnp.concatenate([y[:last], attend(seen_k, seen_v)[last:]])
    if cfg.use_gqa_gate and fault != "no_gqa_gate":
        y = y * jax.nn.sigmoid(_mm(u, p["w_attn_gate"]))
    return _mm(y, p["wo"])


def delta_mixer(cfg: LMConfig, p: Dict, u: jax.Array, fault: Optional[str],
                fresh_at: Sequence[int] = ()) -> jax.Array:
    """u (l, hidden) float32 -> the K mixer's output. `fresh_at`: the
    positions whose conv reads zeros for what lies before them (the
    fault `tails_zeroed`)."""
    length = u.shape[0]
    n, d = cfg.linear_num_heads, cfg.linear_head_dim
    x = jnp.concatenate([_mm(u, p[w]) for w in ("wq", "wk", "wv")], -1)
    taps = p["conv_w"].shape[1]
    at = jnp.arange(length)
    # the start of the stretch each position's conv may read back into
    floor = jnp.zeros((length,), jnp.int32)
    for s in fresh_at:
        floor = jnp.where(at >= s, s, floor)
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    mixed = 0.0
    for j in range(taps):
        source = at - (taps - 1) + j
        term = padded[j:j + length] * p["conv_w"][:, j].astype(F32)
        mixed = mixed + jnp.where((source >= floor)[:, None], term, 0.0)
    mixed = jax.nn.silu(mixed).reshape(length, 3, n, d)

    def unit(x):
        if fault == "no_qk_norm":
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    q, k, v = unit(mixed[:, 0]) * (d ** -0.5), unit(mixed[:, 1]), mixed[:, 2]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (_mm(_mm(u, p["w_fa"]), p["w_fb"]) + p["dt_bias"]).reshape(
            length, n, d))
    if fault == "head_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    b = jax.nn.sigmoid(_mm(u, p["w_b"]))
    if cfg.kda_allow_neg_eigval and fault != "b_not_doubled":
        b = 2.0 * b
    state = jnp.zeros((1, n, d, d), F32)
    if fault == "no_erase":
        def token(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            s = jnp.exp(g_t)[..., None] * s + jnp.einsum(
                "hk,hv->hkv", k_t, b_t[..., None] * v_t, precision=HI)
            return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)
        _, o = jax.lax.scan(token, state[0], (q, k, v, g, b))
    else:
        o = delta_recurrence(q[None], k[None], v[None], g[None], b[None],
                             state)[0][0]
    gate = jax.nn.sigmoid(_mm(_mm(u, p["w_ga"]), p["w_gb"]))
    y = _rms(o, p["out_norm"], cfg.norm_eps).reshape(length, n * d) * gate
    return _mm(y, p["wo"])


def experts(cfg: LMConfig, p: Dict, r: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (the layer's output (l, hidden), the router's choice (l, k))."""
    s = jax.nn.sigmoid(_mm(r, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    routed = moe.Routed(
        chosen.astype(jnp.int32), cfg.routed_scaling_factor * picked
        / jnp.sum(picked, -1, keepdims=True))
    out = moe.experts_loop(r, routed, p["w_up"], p["w_down"],
                           cfg.expert_first, w_gate=p["w_gate"])
    return (out + _gated(r, p["shared_gate"], p["shared_up"],
                         p["shared_down"]), routed.experts)


def logits(cfg: LMConfig, params: Dict[str, jax.Array], ids,
           fault: Optional[str] = None, starts: Sequence[int] = (),
           other=None, page: int = 1) -> Tuple[jax.Array, jax.Array]:
    """One sequence `ids` (l,) -> (next-token logits at its last position
    over the rows held (vocab_rows,), the router's choices (expert
    layers, l, k)). `starts`, `other` and `page` (a page's tokens) are
    what the cache faults read (module docstring)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {', '.join(FAULTS)}")
    ids = jnp.asarray(ids)
    eps, chosen = cfg.norm_eps, []
    h = jnp.take(params["embed"], ids, axis=0).astype(F32)
    last = starts[-1] if starts else 0
    # a second stream for the faults under which the last turn's K layers
    # see ANOTHER sequence before them: the session with the turn before
    # cut out, or another session's tokens
    shadow, cut = None, 0
    if fault == "state_not_written" and len(starts) > 1:
        cut = starts[-2]
        shadow = jnp.concatenate([ids[:cut], ids[last:]])
    elif fault == "foreign_state":
        cut = len(other)
        shadow = jnp.concatenate([jnp.asarray(other), ids[last:]])
    if shadow is not None:
        # the stream's own hidden states before the turn; the turn's are
        # the main sequence's (its G layers read the session's own keys)
        hs = jnp.take(params["embed"], shadow, axis=0).astype(F32)
    for i, kind in enumerate(cfg.kinds):
        p = layer_params(params, i)
        u = _rms(h, p["attn_norm"], eps)
        if kind == "G":
            mixed = attention(cfg, p, u, fault, starts, page)
        else:
            mixed = delta_mixer(
                cfg, p, u, fault,
                starts if fault == "tails_zeroed" else ())
        if shadow is not None:
            us = jnp.concatenate([_rms(hs[:cut], p["attn_norm"], eps),
                                  u[last:]])
            if kind == "G":
                ms = attention(cfg, p, us, None, (), page)
            else:
                ms = delta_mixer(cfg, p, us, None)
                mixed = jnp.concatenate([mixed[:last], ms[cut:]])
            hs = hs + jnp.concatenate([ms[:cut], mixed[last:]])
        h = h + mixed
        out, choice = experts(cfg, p, _rms(h, p["mlp_norm"], eps))
        chosen.append(choice)
        h = h + out
        if shadow is not None:
            hs = jnp.concatenate([hs[:cut], h[last:]])
            outs, _ = experts(cfg, p, _rms(hs[:cut], p["mlp_norm"], eps))
            hs = jnp.concatenate([hs[:cut] + outs, h[last:]])
    final = _rms(h[-1], params["final_norm"], eps)
    return _mm(params["head"].astype(F32), final), jnp.stack(chosen)
