"""The plain reference of the power-retention language model
(configuration `brumby-14b-pp8`): ONE forward pass over a whole sequence
(a context and the question behind it) in straightforward `jax.numpy`,
float32, every product at "highest", LAYER BY LAYER, so that one layer's
weights are all that is resident.

It imports nothing of the program and takes nothing the program made:
no state, no feature map, no chunks, no cache, no slot. The weights are a
pure function of (`--seed`, leaf name, element index) through the
counter hash of `reference.py`, as `reference_lm.py` makes them (its
`_words` and `_unit`), rounded to bfloat16, the type the configuration
states for parameters: the program is handed the same values
(`make_leaf`), the reference reads them in float32. The deviation of
each leaf is the configuration file's `init_std` (0.02), norm weights
1, and the gate's bias `log(m - 1)` for memories `m` log-spaced over the
key/value heads between the file's `gate_memory_tokens` (`assumed`
says why).

Every layer is the same two pre-norm residual blocks, eps 1e-6, weights
on the norms; with `u = rms(h)`, d = 128, 40 query and 8 key/value
heads, query head n in group n // 5:

  retention  `q = rot(rms_128(W_q u))`, `k = rot(rms_128(W_k u))`, `v =
     W_v u`; rotary over all 128 dimensions, half-split pairs (i, i +
     64), theta 1e6; `log g_t = log sigmoid(W_g u_t + b_g)`, one gate a
     key/value head and token;
         a_ij = (q_i . k_j / sqrt(128))^2 * exp(sum_{l=j+1..i} log g_l)
         y_i  = sum_{j<=i} a_ij v_j / (sum_{j<=i} a_ij + 1e-6)
     the QUADRATIC form: every weight of the causal triangle, in blocks
     of queries only so that a block's weights fit, and in four
     stretches of queries, each against the keys up to its own end
     (causal: what lies behind is never read); `y W_o`.
  MLP  `W_down (silu(W_gate r) * W_up r)`, `r = rms(x')`.
  head: final RMSNorm, `logits = W_head h_last`.

`lower=True` is the CONTROL, the same mathematics in the nearest
precision below the configuration's: matmul operands rounded to int8
(per-tensor absmax), logits bfloat16, and the retention as the
RECURRENCE in chunks of 256 whose state and normaliser are HELD IN
BFLOAT16 from chunk to chunk (what a cache of bfloat16 states would
hold; the feature map here is the whole outer product `x x^T`, 16,384
products, which needs no weights and no gather). It has to come out as
not correct.

FAULTS a state cache and this layer can have, for the readings the
limits are set from (`control_brumby.py`): `gates_ignored` (g = 1),
`normaliser_dropped`, `power_one` (p = 1), and `first_key[n]`: the keys
before that position of sequence n count for nothing (a slot that holds
the state of the LAST registration chunk alone, nothing carried). A
wrong slot and a context one token short are other SEQUENCES.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_lm import _int8, _unit, _words

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD_TO = 2048       # a sequence is padded to a multiple (few programs)
QUERY_BLOCK = 128   # 40 heads x 128 x 34,816 float32 weights: 0.71 GB
SEGMENTS = 4        # stretches of queries, each against the keys up to
#                     its own end: 10/16 of the whole square's work
LOWER_CHUNK = 256   # the control's recurrence: tokens a chunk
EPS = 1e-6
FAULTS = ("gates_ignored", "normaliser_dropped", "power_one")


def padded_length(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


# ------------------------------------------------------------- the leaves

def layer_leaves(c: Dict) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h, d, w = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    hkv = c["num_key_value_heads"]
    q, kv = c["num_attention_heads"] * d, hkv * d
    return [("attn_norm", (h,), "float32", "ones"),
            ("wq", (h, q), "bfloat16", "normal"),
            ("wk", (h, kv), "bfloat16", "normal"),
            ("wv", (h, kv), "bfloat16", "normal"),
            ("q_norm", (d,), "float32", "ones"),
            ("k_norm", (d,), "float32", "ones"),
            ("wg", (h, hkv), "bfloat16", "normal"),
            ("bg", (hkv,), "float32", "gate_bias"),
            ("wo", (q, h), "bfloat16", "normal"),
            ("mlp_norm", (h,), "float32", "ones"),
            ("gate", (h, w), "bfloat16", "normal"),
            ("up", (h, w), "bfloat16", "normal"),
            ("down", (w, h), "bfloat16", "normal")]


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


def state_bytes(c: Dict) -> int:
    """What ONE context holds, every layer: `[S | z]` of each key/value
    head, float32, whatever the context's length."""
    d = c["head_dim"]
    return (c["layers"] * c["num_key_value_heads"] * (d + 1)
            * (d * (d + 1) // 2) * 4)


def cache_bytes(c: Dict) -> int:
    """The states of all the configuration's slots."""
    return c["serve"]["context_cache"]["slots"] * state_bytes(c)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _normal(words_a, words_b, shape: tuple, dtype: str, scale: float
            ) -> jax.Array:
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    cols = shape[-1]
    u1 = _unit(words_a, 0, rows, cols)
    u2 = _unit(words_b, 0, rows, cols)
    z = jnp.sqrt(-2.0 * jnp.log(1.0 - u1)) * jnp.cos(2.0 * math.pi * u2)
    return (scale * z).reshape(shape).astype(jnp.dtype(dtype))


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated type."""
    leaf = name.rsplit(".", 1)[-1]
    if init == "ones":
        return jnp.ones(shape, jnp.dtype(dtype))
    if init == "gate_bias":
        low, high = (float(t) for t in c["gate_memory_tokens"])
        memory = np.exp(np.linspace(np.log(low), np.log(high), shape[0]))
        return jnp.asarray(np.log(memory - 1.0), jnp.dtype(dtype))
    if init != "normal":
        raise ValueError(init)
    scale = c["init_std"].get(leaf, c["init_std"]["default"])
    return _normal(jnp.asarray(_words(seed, name, 1)),
                   jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                   float(scale))


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


# -------------------------------------------------------------- the layers

def _projections(c: Dict, p: Dict, u, lower: bool, fault: Optional[str]):
    """-> (q (l, hkv, r, d), k (l, hkv, d), v (l, hkv, d), cum (hkv, l):
    the sum of log g over 0..i)."""
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    theta, eps = float(c["rope_theta"]), c["rms_norm_eps"]
    q = _rotate(_rms(_mm(u, p["wq"], lower).reshape(length, hq, d),
                     p["q_norm"], eps), theta)
    k = _rotate(_rms(_mm(u, p["wk"], lower).reshape(length, hkv, d),
                     p["k_norm"], eps), theta)
    v = _mm(u, p["wv"], lower).reshape(length, hkv, d)
    log_g = jax.nn.log_sigmoid(_mm(u, p["wg"], lower) + p["bg"])
    if fault == "gates_ignored":
        log_g = jnp.zeros_like(log_g)
    return (q.reshape(length, hkv, hq // hkv, d), k, v,
            jnp.cumsum(log_g, axis=0).T)


def _retention(c: Dict, p: Dict, u, fault: Optional[str], first_key):
    """The quadratic form. u (l, hidden) -> the block's output."""
    length = u.shape[0]
    hq, d = c["num_attention_heads"], c["head_dim"]
    q, k, v, cum = _projections(c, p, u, False, fault)
    at = jnp.arange(length)
    block = QUERY_BLOCK

    def query_block(start, keys):
        """Queries [start, start + block) against keys [0, keys)."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        mine = start + jnp.arange(block)
        seen = ((mine[:, None] >= at[None, :keys])
                & (at[None, :keys] >= first_key))
        s = jnp.einsum("qgmd,kgd->gmqk", qb, k[:keys],
                       precision=HI) / math.sqrt(d)
        cum_q = jax.lax.dynamic_slice_in_dim(cum, start, block, axis=1)
        decay = jnp.exp(jnp.where(
            seen, cum_q[:, :, None] - cum[:, None, :keys], 0.0))
        power = s if fault == "power_one" else jnp.square(s)
        a = jnp.where(seen, power * decay[:, None], 0.0)
        y = jnp.einsum("gmqk,kgd->qgmd", a, v[:keys], precision=HI)
        if fault != "normaliser_dropped":
            y = y / (jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)[..., None]
                     + EPS)
        return y.reshape(block, hq * d)
    # causal: a stretch of queries reads no key behind its own end
    stretch = length // SEGMENTS if length % (SEGMENTS * block) == 0 \
        else length
    outs = []
    for begin in range(0, length, stretch):
        y = jax.lax.map(
            functools.partial(query_block, keys=begin + stretch),
            jnp.arange(begin, begin + stretch, block))
        outs.append(y.reshape(stretch, hq * d))
    return _mm(jnp.concatenate(outs), p["wo"], False)


def _retention_lower(c: Dict, p: Dict, u):
    """The CONTROL's retention: the recurrence in chunks, int8 matmul
    operands in the projections, the state and the normaliser rounded
    to bfloat16 at every chunk's end (held in bfloat16 between chunks).
    The feature map is the whole outer product: `vec(q q^T) . vec(k
    k^T) = (q . k)^2`, no weights."""
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q, k, v, cum = _projections(c, p, u, True, None)
    scale = d ** -0.25
    n = length // LOWER_CHUNK
    v1 = jnp.concatenate([v, jnp.ones((length, hkv, 1), F32)], axis=-1)

    def chunks(t, axis=0):
        return jnp.moveaxis(t.reshape(
            t.shape[:axis] + (n, LOWER_CHUNK) + t.shape[axis + 1:]), axis, 0)

    def outer(x):
        return (x[..., :, None] * x[..., None, :]).reshape(
            x.shape[:-1] + (d * d,))
    causal = jnp.tril(jnp.ones((LOWER_CHUNK, LOWER_CHUNK), bool))

    def step(state, inputs):
        q_c, k_c, v_c, cum_c = inputs       # cum_c (hkv, chunk), running
        base = state["cum"]                 # (hkv,): the sum before it
        rel = cum_c - base[:, None]
        s = jnp.einsum("qgmd,kgd->gmqk", q_c, k_c, precision=HI) \
            / math.sqrt(d)
        decay = jnp.exp(jnp.where(causal, rel[:, :, None] - rel[:, None, :],
                                  0.0))
        a = jnp.where(causal, jnp.square(s) * decay[:, None], 0.0)
        num = jnp.einsum("gmqk,kge->qgme", a, v_c, precision=HI)
        held = state["s"].astype(F32)
        read = jnp.einsum("qgmf,gfe->qgme", outer(q_c * scale), held,
                          precision=HI)
        num = num + read * jnp.exp(rel).T[:, :, None, None]
        to_end = jnp.exp(rel[:, -1:] - rel).T       # (chunk, hkv)
        own = jnp.einsum("kgf,kge->gfe", outer(k_c * scale),
                         v_c * to_end[..., None], precision=HI)
        new = held * jnp.exp(rel[:, -1])[:, None, None] + own
        y = num[..., :d] / (num[..., d:] + EPS)
        return ({"s": new.astype(jnp.bfloat16), "cum": cum_c[:, -1]},
                y.reshape(LOWER_CHUNK, hq * d))
    start = {"s": jnp.zeros((hkv, d * d, d + 1), jnp.bfloat16),
             "cum": jnp.zeros((hkv,), F32)}
    _, y = jax.lax.scan(step, start, (chunks(q), chunks(k), chunks(v1),
                                      chunks(cum, axis=1)))
    return _mm(y.reshape(length, hq * d), p["wo"], True)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(cfg_items: tuple, p: Dict, h, lower: bool, fault, first_key):
    c = dict(cfg_items)
    eps = c["rms_norm_eps"]
    u = _rms(h, p["attn_norm"], eps)
    h = h + (_retention_lower(c, p, u) if lower
             else _retention(c, p, u, fault, first_key))
    r = _rms(h, p["mlp_norm"], eps)

    def mlp(block):
        return _mm(jax.nn.silu(_mm(block, p["gate"], lower))
                   * _mm(block, p["up"], lower), p["down"], lower)
    # in blocks of tokens, so that a block's two 17,408-wide products fit
    out = jax.lax.map(mlp, r.reshape(-1, min(PAD_TO, r.shape[0]),
                                     r.shape[1]))
    return h + out.reshape(h.shape)


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
            "rope_theta", "rms_norm_eps")
    return tuple((k, c[k]) for k in keys)


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            lower: bool = False, fault: Optional[str] = None,
            first_key: Optional[Sequence[int]] = None
            ) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer. Returns
    `logits` (N, vocab_rows) float32 at each sequence's last position.
    Hidden states wait on the host between layers; a sequence is padded
    on the right to a multiple of 2,048 (causal: nothing before the
    padding changes). `first_key[n]`: keys of sequence n before that
    position count for nothing (module docstring)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    static = _static(c)
    eps = c["rms_norm_eps"]
    lengths = [len(s) for s in sequences]
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"], c["hidden_size"]),
                      "bfloat16", "normal")
    hidden = []
    for s in sequences:
        ids = np.zeros((padded_length(len(s)),), np.int32)
        ids[:len(s)] = s
        hidden.append(np.asarray(jnp.take(embed, jnp.asarray(ids), axis=0
                                          ).astype(F32)))
    embed.delete()
    for i in range(c["layers"]):
        p = make_layer(seed, c, i)
        for n, h in enumerate(hidden):
            hidden[n] = np.asarray(_layer(
                static, p, jnp.asarray(h), lower, fault,
                np.int32(0 if first_key is None else first_key[n])))
        for leaf in p.values():
            leaf.delete()
    norm_w = make_leaf(seed, c, "final_norm", (c["hidden_size"],),
                       "float32", "ones")
    head_w = make_leaf(seed, c, "head", (c["vocab_rows"], c["hidden_size"]),
                       "bfloat16", "normal")
    logits = np.stack([np.asarray(_head(
        norm_w, head_w, jnp.asarray(h[n_last - 1]), float(eps), lower))
        for h, n_last in zip(hidden, lengths)])
    return {"logits": logits}
