"""The plain reference of the hybrid state-space / latent-expert language
model (configuration `nemotron3-super-ep4`): the forward pass in
straightforward `jax.numpy`, float32, every product at "highest", one
sequence at a time, LAYER BY LAYER, so that one layer's weights are all
that is resident beside the program's.

It imports nothing of the program and takes nothing the program made.
The weights are a pure function of (`--seed`, leaf name, element index)
through the counter hash of `reference.py` (`hash_uniform`; a normal is
two of its uniforms through Box-Muller), rounded to bfloat16, the type
the configuration states for parameters: the program is handed the same
values (`make_leaf`), the reference reads them in float32.

Each block is `h <- h + mix(RMSNorm(h))`, eps 1e-5, weight on the norm:

  M  `[z | xBC | dt] = W_in u`; `xBC <- silu(causal depthwise conv1d(xBC),
     kernel 4, bias)`; split `x`, `B`, `C`; `dt = softplus(dt + dt_bias)`,
     `A = -exp(A_log)`; head n of group n // (heads / groups):
     `S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t`, `y_t = S_t C_t + D x_t`
     as the plain recurrence over t (a `lax.scan`, no chunks);
     `y <- RMSNorm over each group (y * silu(z))`; `W_out y`.
  *  causal softmax(q k^T / sqrt(d)) v, grouped queries, no bias, NO
     positional encoding (assumed: the family's Mamba layers carry
     position); query blocks only so that the scores fit.
  E  `s = sigmoid(W_r u)`; the k largest of `s + b`; `w_i = scale * s_i /
     sum of the chosen s`; `l = W_down u`; `r = sum over the chosen experts
     HELD of w_i W2_i relu(W1_i l)^2` (a loop over the experts held with a
     dense mask); `W_up r + W2_s relu(W1_s u)^2`.
  head: final RMSNorm, `logits = W_head[rows held] h_last`.

The share is the configuration's: experts `[expert_first, expert_first +
experts_held)`, vocabulary rows `[0, vocab_rows)`.

`lower=True` is the CONTROL, the same pass in the nearest precision
below the configuration's: matmul operands rounded to int8 (per-tensor
absmax), and bfloat16 where the configuration says float32 (router,
state, logits). It has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import hash_uniform

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SHORTEST = 512      # a sequence is padded to 512 * 2^j (few programs)


def padded_length(n: int) -> int:
    out = SHORTEST
    while out < n:
        out *= 2
    return out


# ------------------------------------------------------------- the leaves

def layer_leaves(c: Dict, kind: str) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h = c["hidden_size"]
    nh, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    di = nh * hd
    conv = di + 2 * c["n_groups"] * c["ssm_state_size"]
    out = [("norm", (h,), "float32", "ones")]
    if kind == "M":
        out += [("in_proj", (h, di + conv + nh), "bfloat16", "normal"),
                ("conv_w", (conv, c["conv_kernel"]), "float32", "conv"),
                ("conv_b", (conv,), "float32", "zeros"),
                ("dt_bias", (nh,), "float32", "dt_bias"),
                ("a_log", (nh,), "float32", "a_log"),
                ("d", (nh,), "float32", "ones"),
                ("gate_norm", (di,), "float32", "ones"),
                ("out_proj", (di, h), "bfloat16", "normal")]
    elif kind == "*":
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        out += [("wq", (h, q), "bfloat16", "normal"),
                ("wk", (h, kv), "bfloat16", "normal"),
                ("wv", (h, kv), "bfloat16", "normal"),
                ("wo", (q, h), "bfloat16", "normal")]
    elif kind == "E":
        lat, w = c["moe_latent_size"], c["moe_intermediate_size"]
        sw = c["moe_shared_expert_intermediate_size"]
        e = c["experts_held"]
        out += [("router", (h, c["n_routed_experts"]), "bfloat16", "normal"),
                ("router_bias", (c["n_routed_experts"],), "float32", "bias"),
                ("down", (h, lat), "bfloat16", "normal"),
                ("up", (lat, h), "bfloat16", "normal"),
                ("w1", (e, lat, w), "bfloat16", "normal"),
                ("w2", (e, w, lat), "bfloat16", "normal"),
                ("shared_w1", (h, sw), "bfloat16", "normal"),
                ("shared_w2", (sw, h), "bfloat16", "normal")]
    else:
        raise ValueError(f"layer kind {kind!r}")
    return out


def layer_name(index: int, leaf: str) -> str:
    return f"layers.{index:02d}.{leaf}"


def all_leaves(c: Dict) -> List[Tuple[str, tuple, str, str]]:
    h, v = c["hidden_size"], c["vocab_rows"]
    out = [("embed", (v, h), "bfloat16", "normal")]
    for i, kind in enumerate(c["pattern"]):
        out += [(layer_name(i, n), s, d, k)
                for n, s, d, k in layer_leaves(c, kind)]
    return out + [("final_norm", (h,), "float32", "ones"),
                  ("head", (v, h), "bfloat16", "normal")]


def _words(seed: int, name: str, stream: int) -> np.ndarray:
    tag = zlib.crc32(f"{name}#{stream}".encode())
    seed = int(seed)
    return np.array([(seed ^ (tag * 0x9E3779B1)) & 0xFFFFFFFF,
                     ((seed >> 32) + 0x7F4A7C15 * (tag + 1)) & 0xFFFFFFFF],
                    dtype=np.uint32)


def _unit(words, first_row: int, rows: int, cols: int) -> jax.Array:
    """(rows, cols) uniform in [0, 1), element (r, c) of the WHOLE leaf a
    function of (words, (first_row + r) * cols + c)."""
    x = hash_uniform(words, jnp.arange(first_row, first_row + rows,
                                       dtype=jnp.uint32), cols, 1.0)
    return (x + 1.0) * 0.5


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _leaf(words_a, words_b, shape: tuple, dtype: str, init: str,
          first_row: int, lo: float, hi: float) -> jax.Array:
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    cols = shape[-1]
    if init in ("ones", "zeros"):
        return jnp.full(shape, 1.0 if init == "ones" else 0.0,
                        jnp.dtype(dtype))
    u1 = _unit(words_a, first_row, rows, cols)
    if init in ("normal", "bias"):
        u2 = _unit(words_b, first_row, rows, cols)
        z = jnp.sqrt(-2.0 * jnp.log(1.0 - u1)) * jnp.cos(2.0 * math.pi * u2)
        out = (0.02 if init == "normal" else 0.01) * z
    elif init == "conv":
        out = (2.0 * u1 - 1.0) * 0.5
    elif init == "a_log":
        out = jnp.log(1.0 + 15.0 * u1)
    elif init == "dt_bias":
        dt = jnp.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u1)
        dt = jnp.maximum(dt, 1e-4)
        out = dt + jnp.log(-jnp.expm1(-dt))         # inverse softplus
    else:
        raise ValueError(init)
    return out.reshape(shape).astype(jnp.dtype(dtype))


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated
    type. The experts' leaves start at the first expert HELD, so that
    each share of a group draws its own experts of one whole layer."""
    first = 0
    if name.endswith((".w1", ".w2")):
        first = int(c.get("expert_first", 0)) * shape[1]
    return _leaf(jnp.asarray(_words(seed, name, 1)),
                 jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                 init, first, float(c["time_step_min"]),
                 float(c["time_step_max"]))


def make_layer(seed: int, c: Dict, index: int) -> Dict[str, jax.Array]:
    return {n: make_leaf(seed, c, layer_name(index, n), s, d, k)
            for n, s, d, k in layer_leaves(c, c["pattern"][index])}


# ------------------------------------------------------------ the products

def _int8(x):
    """x rounded to the 255 levels of a per-tensor absmax int8 scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, lower: bool):
    x, w = x.astype(F32), w.astype(F32)
    if lower:
        x, w = _int8(x), _int8(w)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# -------------------------------------------------------------- the layers

def _mamba(c: Dict, p: Dict, u, lower: bool):
    length = u.shape[0]
    nh, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, k = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    di, r = nh * hd, nh // g
    conv_dim = di + 2 * g * n
    zxbcdt = _mm(u, p["in_proj"], lower)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(F32) + sum(
        padded[j:j + length] * p["conv_w"][:, j].astype(F32)
        for j in range(k))
    xbc = jax.nn.silu(conv)
    x, b_in, c_in = jnp.split(xbc, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    x = x.reshape(length, nh, hd)
    state_type = jnp.bfloat16 if lower else F32

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        b_h = jnp.repeat(b_t.reshape(g, n), r, axis=0)      # (heads, n)
        c_h = jnp.repeat(c_t.reshape(g, n), r, axis=0)
        s = (jnp.exp(dt_t * a)[:, None, None] * s.astype(F32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", s, c_h, precision=HI)
        return s.astype(state_type), y_t
    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n), state_type),
                        (x, dt, b_in, c_in))
    y = y + p["d"].astype(F32)[None, :, None] * x
    y = y.reshape(length, di) * jax.nn.silu(z)
    y = _rms(y.reshape(length, g, di // g),
             p["gate_norm"].reshape(g, di // g), c["norm_eps"])
    return _mm(y.reshape(length, di), p["out_proj"], lower)


def _attention(c: Dict, p: Dict, u, lower: bool, block: int = 512):
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    rep = hq // hkv
    q = _mm(u, p["wq"], lower).reshape(length, hkv, rep, d)
    k = _mm(u, p["wk"], lower).reshape(length, hkv, d)
    v = _mm(u, p["wv"], lower).reshape(length, hkv, d)
    block = min(block, length)
    position = jnp.arange(length)

    def query_block(start):
        qi = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhrd,khd->hrqk", qi, k, precision=HI) / math.sqrt(d)
        seen = (start + jnp.arange(block))[:, None] >= position[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hrqk,khd->qhrd", pr, v, precision=HI)
    o = jax.lax.map(query_block, jnp.arange(0, length, block))
    return _mm(o.reshape(length, hq * d), p["wo"], lower)


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
    latent = _mm(u, p["down"], lower)

    def one_expert(acc, inputs):
        e, w1, w2 = inputs
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = _mm(_relu2(_mm(latent, w1, lower)), w2, lower)
        return acc + w[:, None] * y, None
    held = p["w1"].shape[0]
    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                        (jnp.arange(held), p["w1"], p["w2"]))
    shared = _mm(_relu2(_mm(u, p["shared_w1"], lower)), p["shared_w2"],
                 lower)
    return _mm(r, p["up"], lower) + shared, chosen


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(cfg_items: tuple, kind: str, p: Dict, h, lower: bool):
    c = dict(cfg_items)
    u = _rms(h, p["norm"], c["norm_eps"])
    if kind == "M":
        return h + _mamba(c, p, u, lower), None
    if kind == "*":
        return h + _attention(c, p, u, lower), None
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
    keys = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "routed_scaling_factor", "norm_eps", "expert_first")
    return tuple((k, c.get(k, 0)) for k in keys)


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            lower: bool = False) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer. Returns
    `logits` (N, vocab_rows) float32 at each sequence's last position
    and `chosen_last` (N, expert layers, k): the router's choice there.
    Hidden states wait on the host between layers; a sequence is padded
    on the right to 512 * 2^j (causal: nothing before the padding
    changes)."""
    static = _static(c)
    lengths = [len(s) for s in sequences]
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"],
                                         c["hidden_size"]),
                      "bfloat16", "normal")
    hidden = []
    for s in sequences:
        ids = np.zeros((padded_length(len(s)),), np.int32)
        ids[:len(s)] = s
        hidden.append(np.asarray(
            jnp.take(embed, jnp.asarray(ids), axis=0).astype(F32)))
    del embed
    chosen_last: List[List[np.ndarray]] = [[] for _ in sequences]
    for i, kind in enumerate(c["pattern"]):
        p = make_layer(seed, c, i)
        for n, h in enumerate(hidden):
            out, chosen = _layer(static, kind, p, jnp.asarray(h), lower)
            hidden[n] = np.asarray(out)
            if chosen is not None:
                chosen_last[n].append(np.asarray(
                    chosen[lengths[n] - 1]))
        for leaf in p.values():
            leaf.delete()
    norm_w = make_leaf(seed, c, "final_norm", (c["hidden_size"],),
                       "float32", "ones")
    head_w = make_leaf(seed, c, "head", (c["vocab_rows"],
                                         c["hidden_size"]),
                       "bfloat16", "normal")
    logits = np.stack([np.asarray(_head(
        norm_w, head_w, jnp.asarray(h[n_last - 1]), float(c["norm_eps"]),
        lower)) for h, n_last in zip(hidden, lengths)])
    return {"logits": logits,
            "chosen_last": np.asarray(chosen_last, np.int32)}


# ---------------------------------------------------------- the comparison

def served_gap(ref_logits: np.ndarray, served_ids: np.ndarray,
               served_logits: np.ndarray) -> Dict[str, float]:
    """`served_ids`, `served_logits` (N, K): the answers, best first.
    `top_gap`: the widest gap by which a served top token's reference
    logit lies below the reference's best. `score_gap`: the widest
    difference between a served logit difference (token k against the
    top token) and the reference's for the same two tokens;
    `score_gap_median`: the median over the sequences of each sequence's
    widest such difference (an expert chosen otherwise on a near-tie
    moves one sequence's logits by a step, so the widest swings from run
    to run and the median does not). All over the spread (best - mean)
    of the sequence's reference logits."""
    best = ref_logits.max(axis=1)
    spread = np.maximum(best - ref_logits.mean(axis=1), 1e-30)
    picked = np.take_along_axis(ref_logits, served_ids, axis=1)
    top = (best - picked[:, 0]) / spread
    own = served_logits - served_logits[:, :1]
    score = (np.abs(own - (picked - picked[:, :1]))
             / spread[:, None]).max(axis=1)
    return {"top_gap": float(top.max()), "score_gap": float(score.max()),
            "score_gap_median": float(np.median(score))}


def own_answers(logits: np.ndarray, k: int):
    """(ids, logits) (N, k) that `logits` themselves would answer with:
    the control's answers, from its own logits."""
    ids = np.argsort(-logits, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(logits, ids, axis=1)


def same_expert_sets(a: np.ndarray, b: np.ndarray) -> float:
    """Share of (sequence, expert layer) choices that are the same set."""
    return float((np.sort(a, -1) == np.sort(b, -1)).all(-1).mean())
