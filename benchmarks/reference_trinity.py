"""The plain reference of the window / full attention expert language
model (configuration `trinity-mini-pp4`): ONE forward pass over a whole
sequence (a context and the question behind it) in straightforward
`jax.numpy`, float32, every product at "highest", LAYER BY LAYER, so that
one layer's weights are all that is resident.

It imports nothing of the program and takes nothing the program made:
no cache, no ring, no pages, no chunks; the window is a MASK over the
whole sequence. The weights are a pure function of (`--seed`, leaf name,
element index) through the counter hash of `reference.py`, as
`reference_lm.py` makes them, rounded to bfloat16, the type the
configuration states for parameters: the program is handed the same
values (`make_leaf`), the reference reads them in float32. The deviation
of each leaf is the configuration file's `init_std` (0.02; the routed
experts' `w_down` and the router's bias 0.01), a norm weight its
`init_gain` (1; `q_norm` 2).

`h0 = E[ids] * sqrt(2048)` (`mup_enabled`). Layer l, attention kind
`layer_types[l]`, MLP dense while `l < num_dense_layers`; every `rms`
with a weight, eps 1e-5:

    u  = rms(h; w_in)
    q  = rms_128(W_q u; w_qn) (32 heads),  k = rms_128(W_k u; w_kn),
    v  = W_v u (4 heads), no biases; query head n reads head n // 8
    window layer:  q, k = rot(q), rot(k)   all 128 dimensions, half-split
                   pairs (i, i + 64), theta 10,000, by position;
                   query i sees keys j with  i - 2048 < j <= i
    full layer:    NO rotary;  query i sees every j <= i
    y  = softmax_j(q_i . k_j / sqrt(128)) v_j * sigmoid(W_g u)
    h' = h + rms(W_o y; w_post_attn)
    r  = rms(h'; w_pre_mlp)
    dense:    z = W_down(silu(W_gate r) * W_up r)
    experts:  s = sigmoid(W_r r) over 128;  S = top-8 of (s + b);
              g_e = 2.826 * s_e / (sum_S s + 1e-20)
              z = shared(r) + sum_{e in S} g_e * expert_e(r)
              (a loop over the experts with a dense mask)
    h'' = h' + rms(z; w_post_mlp)

then `rms(h; w_final)` and `logits = W_head h_last`. Attention in query
blocks only so that a block's scores fit (66 K keys x 32 heads x 128
queries of float32 are 1.1 GB), in four stretches of queries, each
against the keys up to its own end (causal: what lies behind is never
read).

`lower=True` is the CONTROL, the same pass in the nearest precision
below the configuration's: matmul operands rounded to int8 (per-tensor
absmax); router and logits bfloat16; and what a token leaves for later
ones (keys after norm and rotation, values: what the ring and the pages
would hold) rounded to 3 mantissa bits, 4 fewer than bfloat16 stores. It
has to come out as not correct.

FAULTS a cache of two geometries and this model's layers can have, for
the readings the limits are set from (`control_trinity.py`), `context`
giving each sequence's context tokens: `foreign_pages` (the question's
queries read, in the FULL layers, the keys and values of ANOTHER
context, `other`: the sequence with that context's tokens in place of
its own, while the window layers read their own ring), `ring_first` (in
the WINDOW layers the question's queries see the context's FIRST 2,048
tokens, a ring written without `p mod 2048`, in place of its last),
`rotary_everywhere` (the full layers rotated too), `no_attn_gate`,
`no_post_norms`, `no_route_scale`. A slot read one token short is the
reference over the context less its last token, which needs no switch
here.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_keye import _leaf
from benchmarks.reference_lm import _int8, _words

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD_TO = 4096       # a sequence is padded to a multiple (few programs)
QUERY_BLOCK = 128   # 32 heads x 128 x 69,632 float32 scores: 1.14 GB
SEGMENTS = 4        # stretches of queries, each against the keys up to
#                     its own end: 10/16 of the whole square's work
FAULTS = ("foreign_pages", "ring_first", "rotary_everywhere",
          "no_attn_gate", "no_post_norms", "no_route_scale")
# the one kind of layer a fault changes (the others change every layer):
# a layer of another kind runs, and was compiled, sound
FAULT_OF_KIND = {"foreign_pages": "f", "rotary_everywhere": "f",
                 "ring_first": "w", "no_route_scale": "E"}
KINDS = {"sliding_attention": "w", "full_attention": "f"}


def padded_length(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


# ------------------------------------------------------------- the leaves

def layer_kind(c: Dict, index: int) -> Tuple[str, str]:
    """(attention, MLP) of layer `index`: `w` window or `f` full, `D` a
    dense MLP or `E` experts."""
    return (KINDS[c["layer_types"][index]],
            "D" if index < c["num_dense_layers"] else "E")


def pattern(c: Dict) -> str:
    return " ".join("".join(layer_kind(c, i)) for i in range(c["layers"]))


def layer_leaves(c: Dict, mlp: str) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    out = [("attn_norm", (h,), "float32", "ones"),
           ("wq", (h, q), "bfloat16", "normal"),
           ("wk", (h, kv), "bfloat16", "normal"),
           ("wv", (h, kv), "bfloat16", "normal"),
           ("q_norm", (d,), "float32", "ones"),
           ("k_norm", (d,), "float32", "ones"),
           ("w_attn_gate", (h, q), "bfloat16", "normal"),
           ("wo", (q, h), "bfloat16", "normal"),
           ("post_attn_norm", (h,), "float32", "ones"),
           ("mlp_norm", (h,), "float32", "ones"),
           ("post_mlp_norm", (h,), "float32", "ones")]
    if mlp == "D":
        w = c["intermediate_size"]
        return out + [("gate", (h, w), "bfloat16", "normal"),
                      ("up", (h, w), "bfloat16", "normal"),
                      ("down", (w, h), "bfloat16", "normal")]
    w, e = c["moe_intermediate_size"], c["experts_held"]
    sw = c["num_shared_experts"] * w
    return out + [("router", (h, c["num_experts"]), "bfloat16", "normal"),
                  ("router_bias", (c["num_experts"],), "float32", "bias"),
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
        out += [(layer_name(i, n), s, d, k) for n, s, d, k in
                layer_leaves(c, layer_kind(c, i)[1])]
    return out + [("final_norm", (h,), "float32", "ones"),
                  ("head", (v, h), "bfloat16", "normal")]


def num_params(c: Dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _, _ in all_leaves(c))


def cache_bytes(c: Dict) -> Dict[str, int]:
    """What the configuration's ring slots and page pool hold: keys and
    values of every key/value head, bfloat16, `sliding_window` tokens a
    ring slot or page, a layer of its kind."""
    held = c["serve"]["context_cache"]
    kinds = [layer_kind(c, i)[0] for i in range(c["layers"])]
    unit = (held["register_chunk"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * 2)
    return {"rings": held["slots"] * kinds.count("w") * unit,
            "pages": held["pages"] * kinds.count("f") * unit}


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated type.
    The experts' leaves start at the first expert HELD, so that each
    share of a layer draws its own experts of one whole layer."""
    leaf = name.rsplit(".", 1)[-1]
    first = 0
    if leaf in ("w_gate", "w_up", "w_down"):
        first = int(c.get("expert_first", 0)) * shape[1]
    if init == "ones":
        scale = c.get("init_gain", {}).get(leaf, 1.0)
    else:       # `normal`, and the router's `bias`: a small normal
        scale, init = c["init_std"].get(leaf, c["init_std"]["default"]), \
            "normal"
    return _leaf(jnp.asarray(_words(seed, name, 1)),
                 jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                 init, first, float(scale))


def make_layer(seed: int, c: Dict, index: int) -> Dict[str, jax.Array]:
    return {n: make_leaf(seed, c, layer_name(index, n), s, d, k)
            for n, s, d, k in layer_leaves(c, layer_kind(c, index)[1])}


# ------------------------------------------------------------ the products

def _mm(x, w, lower: bool):
    x, w = x.astype(F32), w.astype(F32)
    if lower:
        x, w = _int8(x), _int8(w)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, theta):
    """x (l, heads, d) at positions 0..l-1: pair (i, i + d/2) turned by
    position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inverse
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def _stored(x, lower: bool):
    """What a cache one precision below bfloat16 would hold."""
    return jax.lax.reduce_precision(x, 8, 3) if lower else x


def _gated(x, gate, up, down, lower: bool):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


# -------------------------------------------------------------- the layers

def _attention(c: Dict, p: Dict, kind: str, u, u_other, context,
               lower: bool, fault: Optional[str]):
    """u (l, hidden) -> the block's output before its post-norm.
    `u_other`: the layer input of the sequence whose context's pages a
    `foreign_pages` fault reads; `context`: the sequence's context
    tokens (the question's queries stand at `context` and behind)."""
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    theta, eps, window = (float(c["rope_theta"]), c["rms_norm_eps"],
                          c["sliding_window"])
    at = jnp.arange(length)
    rotary = kind == "w" or fault == "rotary_everywhere"

    def keys_values(x):
        k = _rms(_mm(x, p["wk"], lower).reshape(length, hkv, d),
                 p["k_norm"], eps)
        k = _rotate(k, theta) if rotary else k
        return (_stored(k, lower),
                _stored(_mm(x, p["wv"], lower).reshape(length, hkv, d),
                        lower))
    q = _rms(_mm(u, p["wq"], lower).reshape(length, hq, d), p["q_norm"],
             eps)
    q = _rotate(q, theta) if rotary else q
    k, v = keys_values(u)
    if fault == "foreign_pages" and kind == "f":
        theirs = (at < context)[:, None, None]
        k_other, v_other = keys_values(u_other)
        k, v = jnp.where(theirs, k_other, k), jnp.where(theirs, v_other, v)
    block = QUERY_BLOCK

    def query_block(start, keys):
        """Queries [start, start + block) against keys [0, keys)."""
        rows = start + jnp.arange(block)
        behind = rows[:, None] - at[None, :keys]
        seen = behind >= 0
        if kind == "w":
            seen = seen & (behind < window)
            if fault == "ring_first":
                asks = (rows >= context)[:, None]
                theirs = (at[None, :keys] < context)
                first = theirs & (at[None, :keys] < window)
                seen = jnp.where(asks, (seen & ~theirs) | first, seen)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0).reshape(
            block, hkv, hq // hkv, d)
        s = jnp.einsum("qgmd,kgd->gmqk", qb, k[:keys],
                       precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gmqk,kgd->qgmd", pr, v[:keys],
                          precision=HI).reshape(block, hq * d)
    # causal: a stretch of queries reads no key behind its own end
    stretch = length // SEGMENTS if length % (SEGMENTS * block) == 0 \
        else length
    y = jnp.concatenate([jax.lax.map(
        functools.partial(query_block, keys=begin + stretch),
        jnp.arange(begin, begin + stretch, block)).reshape(stretch, hq * d)
        for begin in range(0, length, stretch)])
    if fault != "no_attn_gate":
        y = y * jax.nn.sigmoid(_mm(u, p["w_attn_gate"], lower))
    return _mm(y, p["wo"], lower)


def _experts(c: Dict, p: Dict, r, lower: bool, fault: Optional[str]):
    k, first = c["num_experts_per_tok"], int(c.get("expert_first", 0))
    router_type = jnp.bfloat16 if lower else F32
    s = jax.nn.sigmoid(jnp.dot(
        r.astype(router_type), p["router"].astype(router_type),
        precision=HI, preferred_element_type=router_type)).astype(F32)
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    scale = 1.0 if fault == "no_route_scale" else float(c["route_scale"])
    weights = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + 1e-20)

    def one_expert(acc, inputs):
        e, gate, up, down = inputs
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return acc + w[:, None] * _gated(r, gate, up, down, lower), None
    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(r),
                             (jnp.arange(held), p["w_gate"], p["w_up"],
                              p["w_down"]))
    return routed + _gated(r, p["shared_gate"], p["shared_up"],
                           p["shared_down"], lower), chosen


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7))
def _layer(cfg_items: tuple, kinds: Tuple[str, str], p: Dict, h, h_other,
           context, lower: bool, fault: Optional[str]):
    c = dict(cfg_items)
    eps = c["rms_norm_eps"]

    def post(x, w):
        return x if fault == "no_post_norms" else _rms(x, w, eps)
    h = h + post(_attention(
        c, p, kinds[0], _rms(h, p["attn_norm"], eps),
        _rms(h_other, p["attn_norm"], eps), context, lower, fault),
        p["post_attn_norm"])
    r = _rms(h, p["mlp_norm"], eps)
    if kinds[1] == "D":
        out, chosen = _gated(r, p["gate"], p["up"], p["down"], lower), None
    else:
        out, chosen = _experts(c, p, r, lower, fault)
    return h + post(out, p["post_mlp_norm"]), chosen


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(norm_w, head_w, h_last, eps: float, lower: bool):
    last = _rms(h_last, norm_w, eps)
    if lower:
        return jnp.dot(head_w.astype(jnp.bfloat16),
                       last.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16).astype(F32)
    return jnp.dot(head_w.astype(F32), last, precision=HI)


def _static(c: Dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "sliding_window", "num_experts_per_tok",
            "route_scale", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys) + (
        ("expert_first", c.get("expert_first", 0)),)


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            lower: bool = False, fault: Optional[str] = None,
            context: Optional[List[int]] = None,
            other: Optional[List[np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer. Returns
    `logits` (N, vocab_rows) float32 at each sequence's last position and
    `chosen_last` (N, expert layers, k): the router's choice there.
    Hidden states wait on the host between layers; a sequence is padded
    on the right to a multiple of 4,096 (causal: nothing before the
    padding changes). `context[n]`: the context tokens of sequence n
    (the faults that tell a question from its context need it). With
    `fault="foreign_pages"`, `other[n]` is sequence n with another
    context's tokens in place of its own (the same length): it runs
    beside the sequence, sound, and the sequence's full layers read ITS
    keys and values for the context's positions."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {', '.join(FAULTS)}")
    static = _static(c)
    eps = c["rms_norm_eps"]
    lengths = [len(s) for s in sequences]
    context = [0] * len(sequences) if context is None else context
    foreign = fault == "foreign_pages"
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"], c["hidden_size"]),
                      "bfloat16", "normal")
    scale = math.sqrt(c["hidden_size"]) if c.get("mup_enabled") else 1.0

    def embedded(tokens):
        ids = np.zeros((padded_length(len(tokens)),), np.int32)
        ids[:len(tokens)] = tokens
        return np.asarray(jnp.take(embed, jnp.asarray(ids), axis=0
                                   ).astype(F32) * scale)
    hidden = [embedded(s) for s in sequences]
    beside = [embedded(s) for s in other] if foreign else hidden
    embed.delete()
    chosen_last: List[List[np.ndarray]] = [[] for _ in sequences]
    for i in range(c["layers"]):
        p = make_layer(seed, c, i)
        kinds = layer_kind(c, i)
        for n, h in enumerate(hidden):
            at = np.int32(context[n])
            if foreign:
                # the other sequence's own sound layer first: its input
                # is what the foreign keys and values are made of
                moved, _ = _layer(static, kinds, p, jnp.asarray(beside[n]),
                                  jnp.asarray(beside[n]), at, lower, None)
            out, chosen = _layer(
                static, kinds, p, jnp.asarray(h), jnp.asarray(beside[n]),
                at, lower,
                fault if FAULT_OF_KIND.get(fault, kinds[0]) in kinds
                else None)
            hidden[n] = np.asarray(out)
            if foreign:
                beside[n] = np.asarray(moved)
            if chosen is not None:
                chosen_last[n].append(np.asarray(chosen[lengths[n] - 1]))
        if not foreign:
            beside = hidden
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
