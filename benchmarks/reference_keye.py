"""The plain reference of the grouped-query / selected-key / softmax-
expert language model (configuration `keye-vl2-pp8`): ONE forward pass
over a whole sequence (a context and the question behind it) in
straightforward `jax.numpy`, float32, every product at "highest", LAYER
BY LAYER, so that one layer's weights are all that is resident.

It imports nothing of the program and takes nothing the program made:
no cache, no chunks, no slot, no bisection. The weights are a pure
function of (`--seed`, leaf name, element index) through the counter
hash of `reference.py`, as `reference_lm.py` makes them (its `_words`
and `_unit`), rounded to bfloat16, the type the configuration states
for parameters: the program is handed the same values (`make_leaf`),
the reference reads them in float32. The deviation of each leaf is the
configuration file's `init_std` (0.02; the experts' `w_down` 0.01), a
norm weight its `init_gain` (1; `q_norm` 2, so that attention is peaked
and WHICH keys were kept shows).

Every layer is the same two pre-norm residual blocks, eps 1e-6, weights
on the norms; with `u = rms(h)`:

  attention  `q = rot(rms_128(W_q u))` for 32 heads of 128, `k =
     rot(rms_128(W_k u))` and `v = W_v u` for 4 heads; query head n reads
     key/value head n // 8; rotary over all 128 dimensions, half-split
     pairs (i, i + 64), theta 1e7 (a text token's three multimodal
     positions are equal: ONE position here). The indexer: `qI = rot(W_Iq
     u)` for 16 heads of 64, one index key `kI = rot(layernorm(W_Ik u))`,
     head weights `a = W_Iw u / sqrt(16 * 64)`; `I[t, s] = sum_j a[t, j]
     relu(qI[t, j] . kI[s])` over the WHOLE causal row; `lax.top_k` keeps
     the 2,048 largest (the lower position of equal scores; every key
     while t < 2,048); softmax of `q . k / sqrt(128)` over the kept keys
     alone, `(P v) W_o`. In query blocks only so that a block's scores
     fit, and in four stretches of queries, each against the keys up to
     its own end (causal: what lies behind is never read).
  experts  `p = softmax(u W_r)` over all 128; the 8 largest; `w_e = p_e
     / sum of the chosen p`; `sum_e w_e W_down^e (silu(W_gate^e u) *
     W_up^e u)` as a loop over the experts with a dense mask. No shared
     expert.
  head: final RMSNorm, `logits = W_head h_last`.

Assumed sizes and conventions (the head norms, the index key's
LayerNorm, the index rotary and its scale, the tie rule) are the
configuration file's `assumed`; `q_chunk_size` / `kv_chunk_size` tile
the published code's index scores and fix no value: nothing here reads
them.

`lower=True` is the CONTROL, the same pass in the nearest precision
below the configuration's: matmul operands rounded to int8 (per-tensor
absmax); router, logits and index scores bfloat16; and what a token
leaves for later ones (keys after norm and rotation, values, the index
key: what the caches would hold) rounded to 3 mantissa bits, 4 fewer
than bfloat16 stores. It has to come out as not correct.

FAULTS a selecting cache can have, for the readings the limits are set
from (`control_keye.py`): `fault="dense"` ignores the selection (every
visible key attended); `fault="foreign_index"` selects by the index
keys of ANOTHER context (`other`: the context's tokens replaced) while
attending the right keys and values.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_lm import _int8, _unit, _words

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD_TO = 4096       # a sequence is padded to a multiple (few programs)
QUERY_BLOCK = 128   # 32 heads x 128 x 45,056 float32 scores: 0.74 GB
SEGMENTS = 4        # stretches of queries, each against the keys up to
#                     its own end: 10/16 of the whole square's work


def padded_length(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


# ------------------------------------------------------------- the leaves

def layer_leaves(c: Dict) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    w, e = c["moe_intermediate_size"], c["experts_held"]
    return [("attn_norm", (h,), "float32", "ones"),
            ("wq", (h, q), "bfloat16", "normal"),
            ("wk", (h, kv), "bfloat16", "normal"),
            ("wv", (h, kv), "bfloat16", "normal"),
            ("q_norm", (d,), "float32", "ones"),
            ("k_norm", (d,), "float32", "ones"),
            ("wo", (q, h), "bfloat16", "normal"),
            ("idx_q", (h, hi * di), "bfloat16", "normal"),
            ("idx_k", (h, di), "bfloat16", "normal"),
            ("idx_w", (h, hi), "bfloat16", "normal"),
            ("idx_k_norm", (di,), "float32", "ones"),
            ("idx_k_bias", (di,), "float32", "zeros"),
            ("mlp_norm", (h,), "float32", "ones"),
            ("router", (h, c["num_experts"]), "bfloat16", "normal"),
            ("w_gate", (e, h, w), "bfloat16", "normal"),
            ("w_up", (e, h, w), "bfloat16", "normal"),
            ("w_down", (e, w, h), "bfloat16", "normal")]


def layer_name(index: int, leaf: str) -> str:
    return f"layers.{index:02d}.{leaf}"


def all_leaves(c: Dict) -> List[Tuple[str, tuple, str, str]]:
    h, v = c["hidden_size"], c["vocab_rows"]
    out = [("embed", (v, h), "bfloat16", "normal")]
    for i in range(c["layers"]):
        out += [(layer_name(i, n), s, d, k) for n, s, d, k in
                layer_leaves(c)]
    return out + [("final_norm", (h,), "float32", "ones"),
                  ("head", (v, h), "bfloat16", "normal")]


def num_params(c: Dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _, _ in all_leaves(c))


def cache_bytes(c: Dict) -> int:
    """What the configuration's slots hold: keys and values of every
    key/value head and one index key, bfloat16, a token and layer."""
    held = c["serve"]["context_cache"]
    token = 2 * (2 * c["num_key_value_heads"] * c["head_dim"]
                 + c["sa_config"]["indexer_head_dim"])
    return held["slots"] * held["tokens_per_slot"] * c["layers"] * token


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _leaf(words_a, words_b, shape: tuple, dtype: str, init: str,
          first_row: int, scale: float) -> jax.Array:
    if init in ("ones", "zeros"):
        return jnp.full(shape, scale if init == "ones" else 0.0,
                        jnp.dtype(dtype))
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    cols = shape[-1]
    u1 = _unit(words_a, first_row, rows, cols)
    u2 = _unit(words_b, first_row, rows, cols)
    z = jnp.sqrt(-2.0 * jnp.log(1.0 - u1)) * jnp.cos(2.0 * math.pi * u2)
    return (scale * z).reshape(shape).astype(jnp.dtype(dtype))


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated type.
    The experts' leaves start at the first expert HELD, so that each
    share of a layer draws its own experts of one whole layer."""
    leaf = name.rsplit(".", 1)[-1]
    first = 0
    if leaf in ("w_gate", "w_up", "w_down"):
        first = int(c.get("expert_first", 0)) * shape[1]
    if init == "normal":
        scale = c["init_std"].get(leaf, c["init_std"]["default"])
    else:
        scale = c.get("init_gain", {}).get(leaf, 1.0)
    return _leaf(jnp.asarray(_words(seed, name, 1)),
                 jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                 init, first, float(scale))


def make_layer(seed: int, c: Dict, index: int) -> Dict[str, jax.Array]:
    return {n: make_leaf(seed, c, layer_name(index, n), s, d, k)
            for n, s, d, k in layer_leaves(c)}


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
    """x (l, ..., d) at positions 0..l-1: pair (i, i + d/2) turned by
    position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[0], dtype=F32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inverse
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def _stored(x, lower: bool):
    """What a cache one precision below bfloat16 would hold."""
    return jax.lax.reduce_precision(x, 8, 3) if lower else x


# -------------------------------------------------------------- the layers

def _index(c: Dict, p: Dict, u, lower: bool):
    """-> (index queries (l, hI, dI), the index key (l, dI), the head
    weights (l, hI))."""
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, eps = float(c["rope_theta"]), c["rms_norm_eps"]
    q_i = _rotate(_mm(u, p["idx_q"], lower).reshape(-1, hi, di), theta)
    k = _mm(u, p["idx_k"], lower)
    mean = jnp.mean(k, -1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), -1, keepdims=True) + eps)
        * p["idx_k_norm"] + p["idx_k_bias"])
    return (q_i, _stored(_rotate(k, theta), lower),
            _mm(u, p["idx_w"], lower) * ((hi * di) ** -0.5))


def _attention(c: Dict, p: Dict, u, u_index, lower: bool, dense: bool,
               last: int):
    """u (l, hidden) -> (the block's output, the keys query `last` kept
    (l,) bool). `u_index`: the layer input the INDEX KEYS are made of
    (`u` itself; another context's for the foreign-index fault)."""
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    theta, eps, topk = (float(c["rope_theta"]), c["rms_norm_eps"],
                        c["sa_config"]["topk"])
    at = jnp.arange(length)
    q = _rotate(_rms(_mm(u, p["wq"], lower).reshape(length, hq, d),
                     p["q_norm"], eps), theta)
    k = _stored(_rotate(_rms(_mm(u, p["wk"], lower).reshape(
        length, hkv, d), p["k_norm"], eps), theta), lower)
    v = _stored(_mm(u, p["wv"], lower).reshape(length, hkv, d), lower)
    q_i, _, a = _index(c, p, u, lower)
    _, k_i, _ = _index(c, p, u_index, lower)
    score_type = jnp.bfloat16 if lower else F32
    block = QUERY_BLOCK

    def query_block(start, keys):
        """Queries [start, start + block) against keys [0, keys)."""
        take = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=start, slice_size=block, axis=0)
        seen = (start + jnp.arange(block))[:, None] >= at[None, :keys]
        dots = jnp.einsum("qhd,kd->qhk", take(q_i).astype(score_type),
                          k_i[:keys].astype(score_type), precision=HI,
                          preferred_element_type=score_type)
        index = jnp.einsum("qh,qhk->qk", take(a).astype(score_type),
                           jax.nn.relu(dots), precision=HI,
                           preferred_element_type=score_type).astype(F32)
        _, kept = jax.lax.top_k(jnp.where(seen, index, -jnp.inf),
                                min(topk, keys))
        chosen = jnp.zeros((block, keys), bool).at[
            jnp.arange(block)[:, None], kept].set(True) & seen
        if dense:
            chosen = seen
        qb = take(q).reshape(block, hkv, hq // hkv, d)
        s = jnp.einsum("qgmd,kgd->gmqk", qb, k[:keys],
                       precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gmqk,kgd->qgmd", pr, v[:keys], precision=HI)
        mine = chosen[jnp.clip(last - start, 0, block - 1)]
        return o.reshape(block, hq * d), jnp.pad(mine, (0, length - keys))
    # causal: a stretch of queries reads no key behind its own end, so
    # the row of scores (and its top_k) is only as long as that
    stretch = length // SEGMENTS if length % (SEGMENTS * block) == 0 \
        else length
    outs, kepts = [], []
    for begin in range(0, length, stretch):
        o, kept = jax.lax.map(
            functools.partial(query_block, keys=begin + stretch),
            jnp.arange(begin, begin + stretch, block))
        outs.append(o.reshape(stretch, hq * d))
        kepts.append(kept)
    kept = jnp.concatenate(kepts)[last // block]
    return _mm(jnp.concatenate(outs), p["wo"], lower), kept


def _experts(c: Dict, p: Dict, u, lower: bool):
    k, first = c["num_experts_per_tok"], int(c.get("expert_first", 0))
    router_type = jnp.bfloat16 if lower else F32
    prob = jax.nn.softmax(jnp.dot(
        u.astype(router_type), p["router"].astype(router_type),
        precision=HI, preferred_element_type=router_type).astype(F32),
        axis=-1)
    picked, chosen = jax.lax.top_k(prob, k)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def one_expert(acc, inputs):
        e, gate, up, down = inputs
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        out = _mm(jax.nn.silu(_mm(u, gate, lower)) * _mm(u, up, lower),
                  down, lower)
        return acc + w[:, None] * out, None
    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                             (jnp.arange(held), p["w_gate"], p["w_up"],
                              p["w_down"]))
    return routed, chosen


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(cfg_items: tuple, p: Dict, h, h_index, lower: bool, dense: bool,
           last):
    c = dict(cfg_items)
    c["sa_config"] = dict(c["sa_config"])
    eps = c["rms_norm_eps"]
    out, kept = _attention(c, p, _rms(h, p["attn_norm"], eps),
                           _rms(h_index, p["attn_norm"], eps), lower, dense,
                           last)
    h = h + out
    out, chosen = _experts(c, p, _rms(h, p["mlp_norm"], eps), lower)
    return h + out, chosen[last], kept


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
            "rope_theta", "num_experts_per_tok", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys) + (
        ("expert_first", c.get("expert_first", 0)),
        ("sa_config", tuple(sorted(c["sa_config"].items()))))


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            lower: bool = False, fault: Optional[str] = None,
            other: Optional[List[np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer. Returns
    `logits` (N, vocab_rows) float32 at each sequence's last position,
    `chosen_last` (N, layers, k): the router's choice there, and
    `selected_last`: a list of (layers, l) bool arrays, the keys the
    last position kept. Hidden states wait on the host between layers;
    a sequence is padded on the right to a multiple of 4,096 (causal:
    nothing before the padding changes). With `fault="foreign_index"`,
    `other[n]` is sequence n with another context's tokens in place of
    its own (the same length): it runs beside the sequence, and the
    sequence's index keys are made of ITS hidden states."""
    static = _static(c)
    eps = c["rms_norm_eps"]
    lengths = [len(s) for s in sequences]
    foreign = fault == "foreign_index"
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"], c["hidden_size"]),
                      "bfloat16", "normal")

    def embedded(tokens):
        ids = np.zeros((padded_length(len(tokens)),), np.int32)
        ids[:len(tokens)] = tokens
        return np.asarray(jnp.take(embed, jnp.asarray(ids), axis=0
                                   ).astype(F32))
    hidden = [embedded(s) for s in sequences]
    beside = [embedded(s) for s in other] if foreign else hidden
    embed.delete()
    chosen_last: List[List[np.ndarray]] = [[] for _ in sequences]
    kept_last: List[List[np.ndarray]] = [[] for _ in sequences]
    for i in range(c["layers"]):
        p = make_layer(seed, c, i)
        for n, h in enumerate(hidden):
            last = np.int32(lengths[n] - 1)
            if foreign:
                # the other sequence's own sound layer first: its input
                # is what the foreign index keys are made of
                moved, _, _ = _layer(static, p, jnp.asarray(beside[n]),
                                     jnp.asarray(beside[n]), lower, False,
                                     last)
            out, chosen, kept = _layer(
                static, p, jnp.asarray(h), jnp.asarray(beside[n]), lower,
                fault == "dense", last)
            hidden[n] = np.asarray(out)
            if foreign:
                beside[n] = np.asarray(moved)
            chosen_last[n].append(np.asarray(chosen))
            kept_last[n].append(np.asarray(kept)[:lengths[n]])
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
            "chosen_last": np.asarray(chosen_last, np.int32),
            "selected_last": [np.stack(k) for k in kept_last]}


def selected_overlap(served: List[List[List[int]]],
                     kept: List[np.ndarray]) -> float:
    """Share of the reference's kept keys (each sequence's last position,
    a layer) that the served answer kept too."""
    same = total = 0
    for mine, theirs in zip(served, kept):
        for positions, mask in zip(mine, theirs):
            want = set(np.flatnonzero(mask).tolist())
            same += len(want & set(positions))
            total += len(want)
    return same / max(total, 1)
