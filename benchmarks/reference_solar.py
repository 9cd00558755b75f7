"""The plain reference of the delta-rule / grouped-query expert language
model (configuration `solar-open2-ep8`): ONE forward pass over a whole
sequence (a session as registered ++ every kept turn up to and
including the one asked about) in straightforward `jax.numpy`, float32,
every product at "highest", LAYER BY LAYER, so that one layer's weights
are all that is resident beside the sequences' hidden states.

It imports nothing of the program and takes nothing the program made:
no cache, no state carried from a turn to the next, no pages, no chunks
of 64: the delta rule is the recurrence over `t`, the conv runs over the
whole sequence, attention is a causal mask. (It walks a long sequence in
BLOCKS so that a block's temporaries fit: the K mixer 4,096 tokens at a
time, the three inputs before a block and the state carried exactly as
a longer array would hold them; attention in blocks of queries, each
against the keys up to its stretch's end.) Because the model is causal,
one pass over a session's final sequence answers EVERY turn checked on
it: `read_at` names the positions whose next-token logits are wanted.

The weights are a pure function of (`--seed`, leaf name, element index)
through the counter hash of `reference.py`, as `reference_lm.py` makes
them, rounded to bfloat16, the type the configuration states for
parameters: the program is handed the same values (`make_leaf`), the
reference reads them in float32. A leaf's deviation is the configuration
file's `init_std`; `A_log` and `dt_bias` are SPREAD, not drawn
(`init_spread`): `A = exp(A_log)` log-spaced over the heads, `dt =
softplus(dt_bias)` log-spaced over a head's channels.

`h0 = E[ids]`. Layer l, mixer G if l in `gqa_layers` else K; every `rms`
with a weight, eps 1e-5; no positional encoding anywhere:

    u  = rms(h; w_in)
    G: q = W_q u (64 heads x 128),  k = W_k u,  v = W_v u (8 heads x 128)
       y_i = sum_{j<=i} softmax_j(q_i . k_j / sqrt(128)) v_j   query head n
             reads key/value head n // 8
       y  = y * sigmoid(W_g u);   m = W_o y
    K: x_q, x_k, x_v = W_q u, W_k u, W_v u        64 heads x 128 each
       c(x)_t = silu(sum_{i=0..3} w_i * x_{t-3+i})     x before token 0 is 0
       q = l2(c(x_q)) / sqrt(128),  k = l2(c(x_k)),  v = c(x_v)
       g = -exp(A_log[n]) * softplus(W_fb (W_fa u) + dt_bias)
       b = 2 * sigmoid(W_b u)
       Z = diag(exp(g_t)) S_{t-1};  S_t = Z + b_t k_t (v_t - Z^T k_t)^T
       o_t = S_t^T q_t                            S_0 = 0
       y  = rms_128(o; w_on) * sigmoid(W_gb (W_ga u));   m = W_o y
    h' = h + m;   r = rms(h'; w_mlp)
    s  = sigmoid(W_r r) over 320;  S = top-8 of (s + bias);
    w_e = 1.0 * s_e / sum_S s
    z  = shared(r) + sum_{e in S, e held} w_e * expert_e(r)   a loop over
         the 40 held, a dense mask
    h''= h' + z

then `rms(h; w_final)` and `logits = W_head h`.

`lower=True` is the CONTROL, the same pass in the nearest precision
below the configuration's: matmul operands rounded to int8 (per-tensor
absmax); router and logits bfloat16; the STATE held in bfloat16 from
token to token; the G layer's keys and values (what the pages would
hold) rounded to 3 mantissa bits. It has to come out as not correct.

FAULTS, for the readings the limits are set from (`control_solar.py`).
Five change an equation: `b_not_doubled`, `no_erase` (`S_t = Z + b k
v^T`), `no_qk_norm`, `no_gqa_gate`, `head_decay` (the decay one a HEAD,
its channels' mean). Four are what a cache of a state and pages can get
wrong, seen from the LAST turn of a sequence (`starts[n]`: where each
kept turn of sequence n begins, the last of them the turn asked about;
one read a sequence, at its end): `state_not_written` (the turn before
the last was scored but the K layers' state and conv inputs behind it
were not kept: the last turn's K layers see the sequence with that turn
cut out), `tails_zeroed` (the conv inputs before every turn's start read
as zeros), `page_start` (each earlier turn's keys and values were
written from its first page's START: the last turn's queries see them
there, and zeros where they should have been), `foreign_state` (the last
turn's K layers continue ANOTHER session, `others[n]`, while the G
layers read the session's own keys).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_keye import _leaf
from benchmarks.reference_lm import _int8, _words

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD_TO = 16384      # a sequence is padded to a multiple (few programs)
TOKEN_BLOCK = 4096  # tokens of the K mixer at a time: (4096, 24576) float32
QUERY_BLOCK = 32    # 64 heads x 32 x 147,456 float32 scores: 1.2 GB
SEGMENTS = 8        # stretches of queries, each against the keys up to
#                     its own end: 9/16 of the whole square's work
L2_EPS = 1e-6
FAULTS = ("state_not_written", "tails_zeroed", "page_start",
          "foreign_state", "b_not_doubled", "no_erase", "no_qk_norm",
          "no_gqa_gate", "head_decay")
# the one kind of layer a fault changes: a layer of the other kind runs,
# and was compiled, sound
FAULT_OF_KIND = {"page_start": "G", "no_gqa_gate": "G"}
FAULT_OF_KIND.update({f: "K" for f in FAULTS if f not in FAULT_OF_KIND})
SHADOWED = ("state_not_written", "foreign_state")


def padded_length(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


# ------------------------------------------------------------- the leaves

def layer_kind(c: Dict, index: int) -> str:
    return "G" if index in c["gqa_layers"] else "K"


def pattern(c: Dict) -> str:
    return " ".join(layer_kind(c, i) for i in range(c["layers"]))


def layer_leaves(c: Dict, kind: str) -> List[Tuple[str, tuple, str, str]]:
    """(name, shape, dtype, initializer) of one layer's leaves, from the
    configuration file's own numbers."""
    h = c["hidden_size"]
    out = [("attn_norm", (h,), "float32", "ones")]
    if kind == "G":
        d = c["head_dim"]
        q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
        out += [("wq", (h, q), "bfloat16", "normal"),
                ("wk", (h, kv), "bfloat16", "normal"),
                ("wv", (h, kv), "bfloat16", "normal"),
                ("w_attn_gate", (h, q), "bfloat16", "normal"),
                ("wo", (q, h), "bfloat16", "normal")]
    else:
        lin = c["linear_attn_config"]
        n, d = lin["num_heads"], lin["head_dim"]
        q = n * d
        out += [("wq", (h, q), "bfloat16", "normal"),
                ("wk", (h, q), "bfloat16", "normal"),
                ("wv", (h, q), "bfloat16", "normal"),
                ("conv_w", (3 * q, lin["short_conv_kernel_size"]),
                 "float32", "normal"),
                ("a_log", (n,), "float32", "a_spread"),
                ("dt_bias", (q,), "float32", "dt_spread"),
                ("w_fa", (h, d), "bfloat16", "normal"),
                ("w_fb", (d, q), "bfloat16", "normal"),
                ("w_ga", (h, d), "bfloat16", "normal"),
                ("w_gb", (d, q), "bfloat16", "normal"),
                ("w_b", (h, n), "bfloat16", "normal"),
                ("out_norm", (d,), "float32", "ones"),
                ("wo", (q, h), "bfloat16", "normal")]
    w, e = c["moe_intermediate_size"], c["experts_held"]
    sw = c["n_shared_experts"] * w
    return out + [("mlp_norm", (h,), "float32", "ones"),
                  ("router", (h, c["n_routed_experts"]), "bfloat16",
                   "normal"),
                  ("router_bias", (c["n_routed_experts"],), "float32",
                   "normal"),
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
                layer_leaves(c, layer_kind(c, i))]
    return out + [("final_norm", (h,), "float32", "ones"),
                  ("head", (v, h), "bfloat16", "normal")]


def num_params(c: Dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _, _ in all_leaves(c))


def cache_bytes(c: Dict) -> Dict[str, int]:
    """What the configuration's state slots and page pool hold (the
    arrays keep one spare zero state more): a K layer a context the
    state of every head, float32, and the conv's last inputs, bfloat16;
    the G layer a page the keys and values of every key/value head,
    bfloat16."""
    held = c["serve"]["context_cache"]
    lin = c["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    kinds = [layer_kind(c, i) for i in range(c["layers"])]
    state = n * d * d * 4 + (lin["short_conv_kernel_size"] - 1) * 3 * n * d * 2
    page = (held["register_chunk"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * 2)
    return {"states": held["slots"] * kinds.count("K") * state,
            "pages": held["pages"] * kinds.count("G") * page}


def make_leaf(seed: int, c: Dict, name: str, shape: tuple, dtype: str,
              init: str) -> jax.Array:
    """One leaf of the seed's weights, on the device, in its stated type.
    The experts' leaves start at the first expert HELD, so that each
    share of a layer draws its own experts of one whole layer."""
    leaf = name.rsplit(".", 1)[-1]
    if init in ("a_spread", "dt_spread"):
        lo, hi = (float(x) for x in c["init_spread"][leaf])
        lin = c["linear_attn_config"]
        n, d = lin["num_heads"], lin["head_dim"]
        if init == "a_spread":      # A = exp(a_log), a head
            return jnp.linspace(math.log(lo), math.log(hi), n, dtype=F32)
        dt = jnp.exp(jnp.linspace(math.log(lo), math.log(hi), d, dtype=F32))
        # the inverse softplus, a head's channels, the same every head
        return jnp.tile(dt + jnp.log(-jnp.expm1(-dt)), n)
    first = 0
    if leaf in ("w_gate", "w_up", "w_down"):
        first = int(c.get("expert_first", 0)) * shape[1]
    scale = 1.0 if init == "ones" else c["init_std"].get(
        leaf, c["init_std"]["default"])
    return _leaf(jnp.asarray(_words(seed, name, 1)),
                 jnp.asarray(_words(seed, name, 2)), tuple(shape), dtype,
                 init, first, float(scale))


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


def _stored(x, lower: bool):
    """What pages one precision below bfloat16 would hold."""
    return jax.lax.reduce_precision(x, 8, 3) if lower else x


def _gated(x, gate, up, down, lower: bool):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


# -------------------------------------------------------------- the layers

def _attention(c: Dict, p: Dict, u, turns, page: int, lower: bool,
               fault: Optional[str]):
    """u (l, hidden) -> the G mixer's output. `turns` (T + 1,) int32: the
    starts of the sequence's kept turns and, last, its real length (the
    fault `page_start` reads them)."""
    length = u.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    at = jnp.arange(length)
    k = _stored(_mm(u, p["wk"], lower).reshape(length, hkv, d), lower)
    v = _stored(_mm(u, p["wv"], lower).reshape(length, hkv, d), lower)
    asks = None
    if fault == "page_start":
        # what the pages hold once every EARLIER turn wrote from its
        # first page's start: position x of the pages holds token
        # `source[x]` (or nothing); the last turn reads its own tokens
        last = turns[-2]
        source = at
        for j in range(turns.shape[0] - 2):
            s, e = turns[j], turns[j + 1]
            first = s // page * page
            moved = (at >= first) & (at < first + e - s)
            emptied = (at >= jnp.maximum(s, first + e - s)) & (at < e)
            source = jnp.where(moved, at - first + s,
                               jnp.where(emptied, -1, source))
        source = jnp.where(at >= last, at, source)

        def as_held(x):
            return jnp.where((source >= 0)[:, None, None],
                             jnp.take(x, jnp.maximum(source, 0), axis=0),
                             0.0)
        k_held, v_held = as_held(k), as_held(v)
        asks = at >= last
    block = QUERY_BLOCK

    def query_block(start, keys):
        """Queries [start, start + block) against keys [0, keys)."""
        rows = start + jnp.arange(block)
        seen = rows[:, None] >= at[None, :keys]
        ub = jax.lax.dynamic_slice_in_dim(u, start, block, axis=0)
        qb = _mm(ub, p["wq"], lower).reshape(block, hkv, hq // hkv, d)

        def attend(k, v):
            s = jnp.einsum("qgmd,kgd->gmqk", qb, k[:keys],
                           precision=HI) / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("gmqk,kgd->qgmd", pr, v[:keys],
                              precision=HI).reshape(block, hq * d)
        y = attend(k, v)
        if asks is not None:
            mine = jax.lax.dynamic_slice_in_dim(asks, start, block)
            y = jnp.where(mine[:, None], attend(k_held, v_held), y)
        if fault != "no_gqa_gate":
            y = y * jax.nn.sigmoid(_mm(ub, p["w_attn_gate"], lower))
        return _mm(y, p["wo"], lower)
    # causal: a stretch of queries reads no key behind its own end
    stretch = length // SEGMENTS if length % (SEGMENTS * block) == 0 \
        else length
    hidden = c["hidden_size"]
    return jnp.concatenate([jax.lax.map(
        functools.partial(query_block, keys=begin + stretch),
        jnp.arange(begin, begin + stretch, block)).reshape(stretch, hidden)
        for begin in range(0, length, stretch)])


def _delta_mixer(c: Dict, p: Dict, u, turns, lower: bool,
                 fault: Optional[str]):
    """u (l, hidden) -> the K mixer's output: blocks of TOKEN_BLOCK
    tokens, the conv's three inputs before a block and the state carried
    from block to block; inside a block the recurrence token by token."""
    length = u.shape[0]
    lin = c["linear_attn_config"]
    n, d, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    eps = c["rms_norm_eps"]
    block = TOKEN_BLOCK if length % TOKEN_BLOCK == 0 else length
    conv_w = p["conv_w"].astype(F32)
    a = jnp.exp(p["a_log"].astype(F32))
    state_type = jnp.bfloat16 if lower else F32

    def unit(x):
        if fault == "no_qk_norm":
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        z = jnp.exp(g_t)[..., None] * s.astype(F32)
        if fault == "no_erase":
            wrote = b_t[..., None] * v_t
        else:
            wrote = b_t[..., None] * (v_t - jnp.einsum(
                "hkv,hk->hv", z, k_t, precision=HI))
        s = z + jnp.einsum("hk,hv->hkv", k_t, wrote, precision=HI)
        s = s.astype(state_type)
        return s, jnp.einsum("hkv,hk->hv", s.astype(F32), q_t, precision=HI)

    def one_block(carry, start):
        state, tail = carry
        ub = jax.lax.dynamic_slice_in_dim(u, start, block, axis=0)
        x = jnp.concatenate([_mm(ub, p[w], lower)
                             for w in ("wq", "wk", "wv")], -1)
        xp = jnp.concatenate([tail, x])
        at = start + jnp.arange(block)
        # the start of the stretch a position's conv may read back into
        floor = jnp.zeros((block,), jnp.int32)
        if fault == "tails_zeroed":
            for j in range(turns.shape[0] - 1):
                floor = jnp.where(at >= turns[j], turns[j], floor)
        mixed = 0.0
        for j in range(taps):
            source = at - (taps - 1) + j
            mixed = mixed + jnp.where(
                (source >= floor)[:, None], xp[j:j + block] * conv_w[:, j],
                0.0)
        mixed = jax.nn.silu(mixed).reshape(block, 3, n, d)
        q = unit(mixed[:, 0]) * (d ** -0.5)
        k, v = unit(mixed[:, 1]), mixed[:, 2]
        g = -a[:, None] * jax.nn.softplus(
            (_mm(_mm(ub, p["w_fa"], lower), p["w_fb"], lower)
             + p["dt_bias"].astype(F32)).reshape(block, n, d))
        if fault == "head_decay":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        b = jax.nn.sigmoid(_mm(ub, p["w_b"], lower))
        if c["kda_allow_neg_eigval"] and fault != "b_not_doubled":
            b = 2.0 * b
        state, o = jax.lax.scan(token, state, (q, k, v, g, b))
        gate = jax.nn.sigmoid(_mm(_mm(ub, p["w_ga"], lower), p["w_gb"],
                                  lower))
        y = _rms(o, p["out_norm"], eps).reshape(block, n * d) * gate
        return (state, xp[block:]), _mm(y, p["wo"], lower)
    start = (jnp.zeros((n, d, d), state_type),
             jnp.zeros((taps - 1, 3 * n * d), F32))
    _, out = jax.lax.scan(one_block, start, jnp.arange(0, length, block))
    return out.reshape(length, c["hidden_size"])


def _experts(c: Dict, p: Dict, r, lower: bool):
    k, first = c["num_experts_per_tok"], int(c.get("expert_first", 0))
    router_type = jnp.bfloat16 if lower else F32
    s = jax.nn.sigmoid(jnp.dot(
        r.astype(router_type), p["router"].astype(router_type),
        precision=HI, preferred_element_type=router_type)).astype(F32)
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = float(c["routed_scaling_factor"]) * picked / jnp.sum(
        picked, axis=-1, keepdims=True)

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


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6, 7))
def _mixer(cfg_items: tuple, kind: str, p: Dict, h, turns, page: int,
           lower: bool, fault: Optional[str]):
    c = dict(cfg_items)
    c["linear_attn_config"] = dict(c["linear_attn_config"])
    u = _rms(h, p["attn_norm"], c["rms_norm_eps"])
    if kind == "G":
        return _attention(c, p, u, turns, page, lower, fault)
    return _delta_mixer(c, p, u, turns, lower, fault)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _mlp(cfg_items: tuple, p: Dict, h, lower: bool):
    c = dict(cfg_items)
    out, chosen = _experts(c, p, _rms(h, p["mlp_norm"], c["rms_norm_eps"]),
                           lower)
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
    lin = c["linear_attn_config"]
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts_per_tok", "routed_scaling_factor",
            "rms_norm_eps", "kda_allow_neg_eigval")
    return tuple((k, c[k]) for k in keys) + (
        ("expert_first", c.get("expert_first", 0)),
        ("linear_attn_config", tuple(sorted(
            (k, v) for k, v in lin.items() if v is not None))))


def _padded(x, length: int):
    return jnp.pad(x, ((0, length - x.shape[0]), (0, 0)))


def forward(seed: int, c: Dict, sequences: List[np.ndarray],
            read_at: Optional[List[Sequence[int]]] = None,
            lower: bool = False, fault: Optional[str] = None,
            starts: Optional[List[Sequence[int]]] = None,
            others: Optional[List[np.ndarray]] = None
            ) -> Dict[str, np.ndarray]:
    """Every sequence through the model, layer by layer; hidden states
    wait on the host between layers. `read_at[n]`: the positions of
    sequence n whose next-token logits are wanted (default: its last).
    Returns `logits` (reads, vocab_rows) float32 and `chosen_last`
    (reads, layers, k), the router's choice at each read position, the
    reads in the order of (sequence, position). A sequence is padded on
    the right to a multiple of 16,384 (causal: nothing before the
    padding changes). The cache FAULTS read `starts[n]` (and
    `foreign_state` `others[n]`) and take one read a sequence, at its
    end (module docstring)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {', '.join(FAULTS)}")
    static = _static(c)
    eps = c["rms_norm_eps"]
    page = int(c["serve"]["context_cache"]["register_chunk"])
    lengths = [len(s) for s in sequences]
    if read_at is None:
        read_at = [[n - 1] for n in lengths]
    if fault is not None and any(list(r) != [n - 1]
                                 for r, n in zip(read_at, lengths)):
        raise ValueError("a fault is read at a sequence's end alone")
    starts = [list(s) for s in starts] if starts else [[0]] * len(sequences)
    turns = [jnp.asarray(list(s) + [n], jnp.int32)
             for s, n in zip(starts, lengths)]
    shadowed = fault in SHADOWED
    embed = make_leaf(seed, c, "embed", (c["vocab_rows"], c["hidden_size"]),
                      "bfloat16", "normal")

    def embedded(tokens):
        ids = np.zeros((padded_length(len(tokens)),), np.int32)
        ids[:len(tokens)] = tokens
        return np.asarray(jnp.take(embed, jnp.asarray(ids), axis=0
                                   ).astype(F32))
    hidden = [embedded(s) for s in sequences]
    # what stands before the last turn in the eyes of its K layers, under
    # a fault that shows them ANOTHER sequence there: (its hidden states,
    # how many of them)
    before: List = []
    if fault == "foreign_state":
        before = [(embedded(o), len(o)) for o in others]
    embed.delete()
    chosen_at: List[List[np.ndarray]] = [[] for _ in sequences]
    none = jnp.zeros((2,), jnp.int32)
    for i in range(c["layers"]):
        p = make_layer(seed, c, i)
        kind = layer_kind(c, i)
        here = fault if FAULT_OF_KIND.get(fault) == kind else None
        if shadowed:
            here = None     # the main stream runs sound
        for n, h in enumerate(hidden):
            h = jnp.asarray(h)
            mixed = _mixer(
                static, kind, p, h,
                turns[n] if here in ("page_start", "tails_zeroed")
                else none, page, lower, here)
            if fault == "foreign_state" or (shadowed
                                            and len(starts[n]) > 1):
                last, real = starts[n][-1], lengths[n]
                if fault == "foreign_state":
                    prefix, cut = before[n]
                    prefix = jnp.asarray(prefix)
                    moved = _mixer(static, kind, p, prefix, none, page,
                                   lower, None)
                else:
                    prefix, cut = h, starts[n][-2]
                if kind == "K":
                    seen = jnp.concatenate([prefix[:cut], h[last:real]])
                    mixed_turn = _mixer(
                        static, kind, p,
                        _padded(seen, padded_length(seen.shape[0])), none,
                        page, lower, None)[cut:cut + real - last]
                    mixed = jnp.concatenate(
                        [mixed[:last], mixed_turn, mixed[real:]])
                if fault == "foreign_state":
                    before[n] = (np.asarray(
                        _mlp(static, p, prefix + moved, lower)[0]), cut)
            out, chosen = _mlp(static, p, h + mixed, lower)
            hidden[n] = np.asarray(out)
            picks = np.asarray(chosen)
            chosen_at[n].append(picks[list(read_at[n])])
        for leaf in p.values():
            leaf.delete()
    norm_w = make_leaf(seed, c, "final_norm", (c["hidden_size"],),
                       "float32", "ones")
    head_w = make_leaf(seed, c, "head", (c["vocab_rows"], c["hidden_size"]),
                       "bfloat16", "normal")
    logits = np.stack([np.asarray(_head(norm_w, head_w, jnp.asarray(h[at]),
                                        float(eps), lower))
                       for h, reads in zip(hidden, read_at) for at in reads])
    chosen_last = np.concatenate([np.stack(layers, axis=1)
                                  for layers in chosen_at])
    return {"logits": logits, "chosen_last": chosen_last.astype(np.int32)}
