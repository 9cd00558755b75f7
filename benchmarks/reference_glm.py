"""The plain reference of the latent-attention / gated-expert language
model (configuration `glm47-flash-pp8`): ONE forward pass over a whole
sequence (a context and the question behind it) in straightforward
`jax.numpy`, float32, every product at "highest", LAYER BY LAYER, so
that one layer's weights are all that is resident.

It imports nothing of the program and takes nothing the program made:
no cache, no chunks, no slot. The weights are a pure function of
(`--seed`, leaf name, element index) through the counter hash of
`reference.py`, as `reference_lm.py` makes them (its `_words` and
`_unit`), rounded to bfloat16, the type the configuration states for
parameters: the program is handed the same values (`make_leaf`), the
reference reads them in float32. The deviation of each leaf is the
configuration file's `init_std` (0.02; `q_b` 0.2, so that attention is
peaked and a context matters; the correction bias 0.01).

Every layer is two pre-norm residual blocks, eps 1e-5, weights on the
norms:

  attention  `c_q = rms(x W_qa)`; `[q_n | q_r] = c_q W_qb` a head;
     `[c_kv | k_r] = x W_kva`, `c_kv = rms(c_kv)`; `q_r` and `k_r` turned
     by position (pairs (2i, 2i+1), theta 1e6), `k_r` one key for all
     heads; `[k_n | v] = c_kv W_kvb` a head (EXPANDED: keys and values
     of every head rebuilt from the latent); scores `(q_n . k_n + q_r .
     k_r) / sqrt(d_nope + d_rope)`, causal softmax, `(P v) W_o`; in
     query blocks only so that the scores fit.
  MLP  layer 0: `down(silu(gate x) * up x)`. The others: `s = sigmoid(x
     W_r)`; the k largest of `s + b`; `w_i = scale * s_i / sum of the
     chosen s`; `sum_i w_i expert_i(x)` as a loop over the experts with
     a dense mask, plus one shared expert; every expert the same gated
     unit.
  head: final RMSNorm, `logits = W_head h_last`.

`lower=True` is the CONTROL, the same pass in the nearest precision
below the configuration's: matmul operands rounded to int8 (per-tensor
absmax), router and logits bfloat16, and the latent a token leaves for
later ones (`c_kv` after its norm, `k_r` after its rotation: what a
cache would hold) rounded to 3 mantissa bits, 4 fewer than bfloat16
stores. It has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_lm import _int8, _unit, _words

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD_TO = 2048       # a sequence is padded to a multiple (few programs)


def padded_length(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


# ------------------------------------------------------------- the leaves

def layer_kind(c: Dict, index: int) -> str:
    return "D" if index < c["first_k_dense_replace"] else "E"


def layer_leaves(c: Dict, kind: str) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    out = [("attn_norm", (h,), "float32", "ones"),
           ("q_a", (h, rq), "bfloat16", "normal"),
           ("q_norm", (rq,), "float32", "ones"),
           ("q_b", (rq, heads * (dn + dr)), "bfloat16", "normal"),
           ("kv_a", (h, rkv + dr), "bfloat16", "normal"),
           ("kv_norm", (rkv,), "float32", "ones"),
           ("kv_b", (rkv, heads * (dn + dv)), "bfloat16", "normal"),
           ("o", (heads * dv, h), "bfloat16", "normal"),
           ("mlp_norm", (h,), "float32", "ones")]
    if kind == "D":
        w = c["intermediate_size"]
        return out + [("gate", (h, w), "bfloat16", "normal"),
                      ("up", (h, w), "bfloat16", "normal"),
                      ("down", (w, h), "bfloat16", "normal")]
    w, e = c["moe_intermediate_size"], c["experts_held"]
    sw = c["n_shared_experts"] * w
    return out + [
        ("router", (h, c["n_routed_experts"]), "bfloat16", "normal"),
        ("router_bias", (c["n_routed_experts"],), "float32", "normal"),
        ("w_gate", (e, h, w), "bfloat16", "normal"),
        ("w_up", (e, h, w), "bfloat16", "normal"),
        ("w_down", (e, w, h), "bfloat16", "normal"),
        ("shared_gate", (h, sw), "bfloat16", "normal"),
        ("shared_up", (h, sw), "bfloat16", "normal"),
        ("shared_down", (sw, h), "bfloat16", "normal")]


def layer_name(index: int, leaf: str) -> str:
    return f"layers.{index:02d}.{leaf}"


def all_leaves(c: Dict) -> List[Tuple[str, tuple, str, str]]:
    h, v = c["hidden_size"], c["vocab_rows"]
    out = [("embed", (v, h), "bfloat16", "normal")]
    for i in range(c["layers"]):
        out += [(layer_name(i, n), s, d, k)
                for n, s, d, k in layer_leaves(c, layer_kind(c, i))]
    return out + [("final_norm", (h,), "float32", "ones"),
                  ("head", (v, h), "bfloat16", "normal")]


def num_params(c: Dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _, _ in all_leaves(c))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _leaf(words_a, words_b, shape: tuple, dtype: str, init: str,
          first_row: int, std: float) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, jnp.dtype(dtype))
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    cols = shape[-1]
    u1 = _unit(words_a, first_row, rows, cols)
    u2 = _unit(words_b, first_row, rows, cols)
    z = jnp.sqrt(-2.0 * jnp.log(1.0 - u1)) * jnp.cos(2.0 * math.pi * u2)
    return (std * z).reshape(shape).astype(jnp.dtype(dtype))


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated type.
    The experts' leaves start at the first expert HELD, so that each
    share of a layer draws its own experts of one whole layer."""
    first = 0
    if name.rsplit(".", 1)[-1] in ("w_gate", "w_up", "w_down"):
        first = int(c.get("expert_first", 0)) * shape[1]
    std = c["init_std"].get(name.rsplit(".", 1)[-1],
                            c["init_std"]["default"])
    return _leaf(jnp.asarray(_words(seed, name, 1)),
                 jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                 init, first, float(std))


def make_layer(seed: int, c: Dict, index: int) -> Dict[str, jax.Array]:
    return {n: make_leaf(seed, c, layer_name(index, n), s, d, k)
            for n, s, d, k in layer_leaves(c, layer_kind(c, index))}


# ------------------------------------------------------------ the products

def _mm(x, w, lower: bool):
    x, w = x.astype(F32), w.astype(F32)
    if lower:
        x, w = _int8(x), _int8(w)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, positions, theta):
    """x (l, ..., d): pair (2i, 2i+1) turned by position * theta^(-2i/d)."""
    d = x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inverse
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      a * jnp.sin(angle) + b * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def _gated(x, gate, up, down, lower: bool):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


# -------------------------------------------------------------- the layers

def _attention(c: Dict, p: Dict, u, lower: bool, block: int = 512):
    length = u.shape[0]
    heads = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rank, theta, eps = c["kv_lora_rank"], float(c["rope_theta"]), \
        c["norm_eps"]
    at = jnp.arange(length)
    q = _mm(_rms(_mm(u, p["q_a"], lower), p["q_norm"], eps), p["q_b"],
            lower).reshape(length, heads, dn + dr)
    q_n, q_r = q[..., :dn], _rotate(q[..., dn:], at, theta)
    kv = _mm(u, p["kv_a"], lower)
    c_kv = _rms(kv[:, :rank], p["kv_norm"], eps)
    k_r = _rotate(kv[:, rank:], at, theta)
    if lower:       # what a cache one precision below bfloat16 would hold
        c_kv, k_r = (jax.lax.reduce_precision(t, 8, 3) for t in (c_kv, k_r))
    expanded = _mm(c_kv, p["kv_b"], lower).reshape(length, heads, dn + dv)
    k_n, v = expanded[..., :dn], expanded[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def query_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, axis=0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision=HI)
             + jnp.einsum("qhd,kd->hqk", qr, k_r, precision=HI)) * scale
        seen = (start + jnp.arange(block))[:, None] >= at[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
    o = jax.lax.map(query_block, jnp.arange(0, length, block))
    return _mm(o.reshape(length, heads * dv), p["o"], lower)


def _experts(c: Dict, p: Dict, u, lower: bool):
    k, first = c["num_experts_per_tok"], int(c.get("expert_first", 0))
    router_type = jnp.bfloat16 if lower else F32
    s = jax.nn.sigmoid(jnp.dot(
        u.astype(router_type), p["router"].astype(router_type),
        precision=HI, preferred_element_type=router_type)).astype(F32)
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = (float(c["routed_scaling_factor"]) * picked
               / jnp.sum(picked, axis=-1, keepdims=True))

    def one_expert(acc, inputs):
        e, gate, up, down = inputs
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return acc + w[:, None] * _gated(u, gate, up, down, lower), None
    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                             (jnp.arange(held), p["w_gate"], p["w_up"],
                              p["w_down"]))
    shared = _gated(u, p["shared_gate"], p["shared_up"], p["shared_down"],
                    lower)
    return routed + shared, chosen


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(cfg_items: tuple, kind: str, p: Dict, h, lower: bool):
    c = dict(cfg_items)
    h = h + _attention(c, p, _rms(h, p["attn_norm"], c["norm_eps"]), lower)
    u = _rms(h, p["mlp_norm"], c["norm_eps"])
    if kind == "D":
        return h + _gated(u, p["gate"], p["up"], p["down"], lower), None
    out, chosen = _experts(c, p, u, lower)
    return h + out, chosen


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(norm_w, head_w, h_last, eps: float, lower: bool):
    last = _rms(h_last, norm_w, eps)
    if lower:
        return jnp.dot(head_w.astype(jnp.bfloat16),
                       last.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16).astype(F32)
    return jnp.dot(head_w.astype(F32), last, precision=HI)


def _static(c: Dict) -> tuple:
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rope_theta",
            "num_experts_per_tok", "routed_scaling_factor", "expert_first")
    return tuple((k, c.get(k, 0)) for k in keys) + (
        ("norm_eps", c.get("norm_eps", c["rms_norm_eps"])),)


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            lower: bool = False) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer. Returns
    `logits` (N, vocab_rows) float32 at each sequence's last position
    and `chosen_last` (N, expert layers, k): the router's choice there.
    Hidden states wait on the host between layers; a sequence is padded
    on the right to a multiple of 2,048 (causal: nothing before the
    padding changes)."""
    static = _static(c)
    eps = dict(static)["norm_eps"]
    lengths = [len(s) for s in sequences]
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"], c["hidden_size"]),
                      "bfloat16", "normal")
    hidden = []
    for s in sequences:
        ids = np.zeros((padded_length(len(s)),), np.int32)
        ids[:len(s)] = s
        hidden.append(np.asarray(
            jnp.take(embed, jnp.asarray(ids), axis=0).astype(F32)))
    embed.delete()
    chosen_last: List[List[np.ndarray]] = [[] for _ in sequences]
    for i in range(c["layers"]):
        p = make_layer(seed, c, i)
        for n, h in enumerate(hidden):
            out, chosen = _layer(static, layer_kind(c, i), p,
                                 jnp.asarray(h), lower)
            hidden[n] = np.asarray(out)
            if chosen is not None:
                chosen_last[n].append(np.asarray(chosen[lengths[n] - 1]))
        for leaf in p.values():
            leaf.delete()
    norm_w = make_leaf(seed, c, "final_norm", (c["hidden_size"],),
                       "float32", "ones")
    head_w = make_leaf(seed, c, "head", (c["vocab_rows"], c["hidden_size"]),
                       "bfloat16", "normal")
    logits = np.stack([np.asarray(_head(
        norm_w, head_w, jnp.asarray(h[n_last - 1]), float(eps), lower))
        for h, n_last in zip(hidden, lengths)])
    return {"logits": logits,
            "chosen_last": np.asarray(chosen_last, np.int32)}
