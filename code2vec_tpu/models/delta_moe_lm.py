"""A routed-expert language model whose layers mix tokens by a gated
DELTA RULE in three layers of four and by softmax grouped-query
attention in the fourth (the `solar_open2` layout), served for scoring
against SESSIONS THAT GROW: a context keeps a recurrent STATE in its
delta-rule layers and PAGES of keys and values in its attention layers,
and a turn that is scored can be KEPT.

`h0 = E[ids]`. Layer `l`, mixer G if `l` in `gqa_layers` else K; every
`rms` with a weight, eps `rms_norm_eps`; no positional encoding anywhere
(`use_rope` false: nothing reads `rope_theta` or
`partial_rotary_factor`), `d = 128`:

    u  = rms(h; w_in)
    G: q = W_q u (heads x d),  k = W_k u,  v = W_v u (key/value heads x d),
       no biases, no head norms, no rotary
       y_i = sum_{j<=i} softmax_j(q_i . k_j / sqrt(d)) v_j    query head n
                          reads key/value head n // (heads / key/value
                          heads); float32
       y  = y * sigmoid(W_g u)              a gate a head and dimension
                                            (use_gqa_gate)
       m  = W_o y
    K: x_q, x_k, x_v = W_q u, W_k u, W_v u   linear heads x d each
       c(x)_t = silu(sum_{i=0..3} w_i * x_{t-3+i})   depthwise, one 4-tap
                          filter a channel, causal; x before the
                          context's first token is 0
       q = l2(c(x_q)) / sqrt(d),  k = l2(c(x_k))  (each head's d to unit
                          length, eps 1e-6),  v = c(x_v)
       g = -exp(A_log[n]) * softplus(W_fb (W_fa u) + dt_bias)   float32,
                          one a head AND channel (< 0); the pair low-rank
                          (kda_use_full_proj false)
       b = 2 * sigmoid(W_b u)               one a head, in (0, 2)
                                            (kda_allow_neg_eigval)
       a head's state S (d key x d value channels, float32), S_0 = 0:
           Z   = diag(exp(g_t)) S_{t-1}
           S_t = Z + b_t k_t (v_t - Z^T k_t)^T
           o_t = S_t^T q_t
       y  = rms_d(o; w_on) * sigmoid(W_gb (W_ga u))
       m  = W_o y
    h' = h + m
    r  = rms(h'; w_mlp)
    s  = sigmoid(W_r r) float32 over all routed experts;  S = top-k of
         (s + bias);  w_e = routed_scaling_factor * s_e / sum_S s
    z  = shared(r) + sum_{e in S, e held here} w_e * expert_e(r)   gated
    h''= h' + z

then `rms(h; w_final)` and the untied head. The delta rule runs in
chunks of 64 (`ops/delta_rule.py`: the chunked form is derived there and
tested against the recurrence above; nothing of it is approximated). The
G layer's attention and gate, the router, the experts, the shared expert
and the head are the code the other token models run
(`ops/window_attn.py full_attend`, `ops/moe.py`, `ops/topk.py`).
DEPARTURES from the published implementation of the family: `q`, `k`
and `v` stay float32 from the conv to the delta rule (it rounds them to
bfloat16 first) and the chunk's products are float32 "highest"
throughout (it takes bfloat16 operands for those that do not read the
state): both are the more exact side.

A context leaves TWO kinds of state (`CACHE_KIND = "state+pages"`): a K
layer keeps `S` of every head behind the context's last token and the
last three inputs of its conv (`(slots + 1, heads, d, d)` float32 and
`(slots + 1, 3, 3 * heads * d)` bfloat16; the spare last entry is the
zero state a context's first tokens and a row with no context start
from), the same bytes whatever the context's length; a G layer keeps
`[keys | values]` of every token in the context's PAGES, token-minor
`(pages, 2 * key/value heads * d, page tokens)` (`ops/window_attn.py`
says why). SCORING (`lm_score_step`) runs rows from their contexts'
states and pages and writes nothing. A KEPT TURN (`ctx_extend_step`, the
cache donated) runs the same rows and then writes, a row: the state and
the conv inputs behind its last real token into its slot, and its
tokens' keys and values into its pages from position `held` on, which
may lie in the MIDDLE of a page; it answers like scoring. The rows are
written ONE AFTER THE OTHER by a loop: unrolled, a step of two rows'
updates of the donated pool and states stalled the chip. Registration
is that step on an empty context, chunk by chunk.

The share held here is `layers` of `num_hidden_layers` (the leading
ones: a pipeline stage), experts `[expert_first, expert_first +
experts_held)` and vocabulary rows `[0, vocab_rows)`. NOT here:
generation, training, forking a session, a prefix held once.

Precision: parameters, matmul operands, activations and pages bfloat16,
accumulation float32; the state, the decays and their sums, the solve,
the conv, q / k / v of the delta rule, the router, softmax, the gates'
sigmoids, norms and logits float32.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import (
    Leaf, ScoreOutputs, StepStats, _matmul, layer_params, layer_prefix,
    rms_norm,
)
from code2vec_tpu.models.window_moe_lm import expert_block
from code2vec_tpu.ops import delta_rule, window_attn
from code2vec_tpu.ops.topk import blockwise_matmul_top_k

F32 = jnp.float32
CACHE_KIND = "state+pages"
CHUNK = 64          # tokens a chunk of the delta rule
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them (the linear
    layers' from its `linear_attn_config` group), and the share held
    here."""
    hidden_size: int
    num_hidden_layers: int
    layers: int
    gqa_layers: Tuple[int, ...]
    vocab_size: int
    vocab_rows: int
    max_position_embeddings: int
    # grouped-query attention (the G layers)
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    use_gqa_gate: bool
    # the delta rule (the K layers)
    linear_num_heads: int
    linear_head_dim: int
    short_conv_kernel_size: int
    kda_allow_neg_eigval: bool
    # the expert MLP of every layer
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: int
    expert_first: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_eps: float

    # what the shared expert block (models/window_moe_lm.py) reads
    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    # what the program's own initializers read (models/lm_common.py)
    conv_kernel = property(lambda self: self.short_conv_kernel_size)
    time_step_min, time_step_max, time_step_floor = 1e-3, 1e-1, 1e-4

    def __post_init__(self):
        if not 0 < self.layers <= self.num_hidden_layers:
            raise ValueError("layers must lie in (0, num_hidden_layers]")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.n_routed_experts):
            raise ValueError("the experts held lie outside the router's "
                             "width")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")
        if any(not 0 <= i < self.num_hidden_layers for i in self.gqa_layers):
            raise ValueError("gqa_layers names a layer the model has not")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys; `layers`
        (the leading layers held here), `experts_held`, `expert_first`
        and `vocab_rows` state the share and default to the whole
        model. What the module does not run is refused."""
        raw = dict(raw)
        linear = dict(raw.get("linear_attn_config") or {})
        raw.setdefault("layers", raw.get("num_hidden_layers"))
        raw.setdefault("experts_held", raw.get("n_routed_experts"))
        raw.setdefault("expert_first", 0)
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("rms_norm_eps", 1e-5))
        raw.setdefault("linear_num_heads", linear.get("num_heads"))
        raw.setdefault("linear_head_dim", linear.get("head_dim"))
        raw.setdefault("short_conv_kernel_size",
                       linear.get("short_conv_kernel_size"))
        refused = {
            "use_rope true (the model has no positional encoding)":
                bool(raw.get("use_rope")),
            "kda_use_full_proj true (the gates are low-rank pairs)":
                bool(raw.get("kda_use_full_proj")),
            "first_k_dense_replace other than 0 (every layer's MLP is "
            "experts)": raw.get("first_k_dense_replace", 0) != 0,
            "tie_word_embeddings": bool(raw.get("tie_word_embeddings")),
            "linear_attn_config.num_kv_heads other than null":
                linear.get("num_kv_heads") is not None,
            "norm_topk_prob false": not raw.get("norm_topk_prob", True),
        }
        for what, found in refused.items():
            if found:
                raise ValueError(f"{where}: {what} is not supported")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if raw.get(n) is None]
        if missing:
            raise ValueError(f"{where}: no {', '.join(missing)}")
        raw["gqa_layers"] = tuple(int(i) for i in raw["gqa_layers"])
        return cls(**{n: raw[n] for n in names})

    @classmethod
    def from_file(cls, path: str) -> "LMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), path)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of each layer held: `G` softmax grouped-query
        attention, `K` the delta rule."""
        return tuple("G" if i in self.gqa_layers else "K"
                     for i in range(self.layers))

    @property
    def pattern(self) -> str:
        return " ".join(self.kinds)

    @property
    def state_layers(self) -> int:
        return self.kinds.count("K")

    @property
    def full_layers(self) -> int:
        return self.kinds.count("G")

    @property
    def cache_width(self) -> int:
        """Values a token leaves in a G layer's pages."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        """x_q | x_k | x_v of a K layer, side by side."""
        return 3 * self.linear_num_heads * self.linear_head_dim


def layer_leaf_specs(cfg: LMConfig, kind: str) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix."""
    h = cfg.hidden_size
    out = [Leaf("attn_norm", (h,), "float32", "ones")]
    if kind == "G":
        d = cfg.head_dim
        q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        out += [Leaf("wq", (h, q), "bfloat16", "normal"),
                Leaf("wk", (h, kv), "bfloat16", "normal"),
                Leaf("wv", (h, kv), "bfloat16", "normal")]
        if cfg.use_gqa_gate:
            out.append(Leaf("w_attn_gate", (h, q), "bfloat16", "normal"))
        out.append(Leaf("wo", (q, h), "bfloat16", "normal"))
    else:
        n, d = cfg.linear_num_heads, cfg.linear_head_dim
        q = n * d
        out += [
            Leaf("wq", (h, q), "bfloat16", "normal"),
            Leaf("wk", (h, q), "bfloat16", "normal"),
            Leaf("wv", (h, q), "bfloat16", "normal"),
            Leaf("conv_w", (3 * q, cfg.short_conv_kernel_size), "float32",
                 "conv"),
            Leaf("a_log", (n,), "float32", "a_log"),
            Leaf("dt_bias", (q,), "float32", "dt_bias"),
            Leaf("w_fa", (h, d), "bfloat16", "normal"),
            Leaf("w_fb", (d, q), "bfloat16", "normal"),
            Leaf("w_ga", (h, d), "bfloat16", "normal"),
            Leaf("w_gb", (d, q), "bfloat16", "normal"),
            Leaf("w_b", (h, n), "bfloat16", "normal"),
            Leaf("out_norm", (d,), "float32", "ones"),
            Leaf("wo", (q, h), "bfloat16", "normal"),
        ]
    w, held = cfg.moe_intermediate_size, cfg.experts_held
    sw = cfg.n_shared_experts * w
    return out + [
        Leaf("mlp_norm", (h,), "float32", "ones"),
        Leaf("router", (h, cfg.n_routed_experts), "bfloat16", "normal"),
        Leaf("router_bias", (cfg.n_routed_experts,), "float32", "bias"),
        Leaf("w_gate", (held, h, w), "bfloat16", "normal"),
        Leaf("w_up", (held, h, w), "bfloat16", "normal"),
        Leaf("w_down", (held, w, h), "bfloat16", "normal"),
        Leaf("shared_gate", (h, sw), "bfloat16", "normal"),
        Leaf("shared_up", (h, sw), "bfloat16", "normal"),
        Leaf("shared_down", (sw, h), "bfloat16", "normal"),
    ]


def leaf_specs(cfg: LMConfig) -> List[Leaf]:
    """Every leaf of the model, in forward order."""
    h = cfg.hidden_size
    out = [Leaf("embed", (cfg.vocab_rows, h), "bfloat16", "normal")]
    for i, kind in enumerate(cfg.kinds):
        out += [leaf._replace(name=layer_prefix(i) + leaf.name)
                for leaf in layer_leaf_specs(cfg, kind)]
    out += [Leaf("final_norm", (h,), "float32", "ones"),
            Leaf("head", (cfg.vocab_rows, h), "bfloat16", "normal")]
    return out


# ----------------------------------------------------------------- the cache

# a layer's entry: a G layer's pool (pages, cache_width, page tokens)
# bfloat16; a K layer's (states (slots + 1, heads, d, d) float32, conv
# inputs (slots + 1, K - 1, conv_channels) bfloat16)
Cache = Tuple[object, ...]


def init_cache(cfg: LMConfig, slots: int, pages: int, page_tokens: int
               ) -> Cache:
    """One entry MORE than `slots` in a K layer's arrays, the last,
    always zero: what a context's first tokens and a row with no context
    start from, read like any other slot."""
    n, d = cfg.linear_num_heads, cfg.linear_head_dim
    out = []
    for kind in cfg.kinds:
        if kind == "G":
            out.append(jnp.zeros((pages, cfg.cache_width, page_tokens),
                                 jnp.bfloat16))
        else:
            out.append((
                jnp.zeros((slots + 1, n, d, d), F32),
                jnp.zeros((slots + 1, cfg.short_conv_kernel_size - 1,
                           cfg.conv_channels), jnp.bfloat16)))
    return tuple(out)


def _slot_rows(array: jax.Array, slot: jax.Array, used: jax.Array
               ) -> jax.Array:
    """(rows,) + array.shape[1:]: each row's slot where it lies, one
    contiguous slice a row; the spare zero entry for a row that reads
    none (`used` false)."""
    at = jnp.where(used, slot, array.shape[0] - 1)
    return jnp.concatenate([jax.lax.dynamic_slice(
        array, (at[r],) + (0,) * (array.ndim - 1), (1,) + array.shape[1:])
        for r in range(slot.shape[0])])


# ---------------------------------------------------------------- the layers

def gqa_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
              pool: jax.Array, pages: jax.Array, cached_len: jax.Array,
              lengths: jax.Array):
    """u (rows, l, hidden) bfloat16 -> (the mixer's output, the tokens'
    `[keys | values]` (rows, l, cache_width)), both bfloat16."""
    rows, length, _ = u.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope("gqa_proj"):
        q = _matmul(u, p["wq"]).reshape(rows, length, hq, d)
        k = _matmul(u, p["wk"]).reshape(rows, length, hkv, d)
        v = _matmul(u, p["wv"]).reshape(rows, length, hkv, d)
    y = window_attn.full_attend(q, k, v, pool, pages, cached_len, lengths)
    if cfg.use_gqa_gate:
        with jax.named_scope("attn_gate"):
            y = (y.astype(F32) * jax.nn.sigmoid(
                _matmul(u, p["w_attn_gate"], F32))).astype(jnp.bfloat16)
    with jax.named_scope("gqa_proj"):
        left = jnp.concatenate([k.reshape(rows, length, -1),
                                v.reshape(rows, length, -1)], axis=-1)
        return _matmul(y, p["wo"]), left


def kda_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
              state_in: jax.Array, tail_in: jax.Array, lengths: jax.Array):
    """u (rows, l, hidden) bfloat16; `state_in` (rows, heads, d, d)
    float32 and `tail_in` (rows, K - 1, conv_channels) bfloat16: what the
    rows' contexts left. -> (the mixer's output bfloat16, (the state and
    the conv inputs behind each row's last real token))."""
    rows, length, _ = u.shape
    n, d = cfg.linear_num_heads, cfg.linear_head_dim
    with jax.named_scope("kda_proj"):
        x = jnp.concatenate([_matmul(u, p[w]) for w in ("wq", "wk", "wv")],
                            axis=-1)
    with jax.named_scope("kda_conv"):
        mixed, tail = delta_rule.conv_carried(x, p["conv_w"], tail_in,
                                              lengths=lengths)
        mixed = jax.nn.silu(mixed).reshape(rows, length, 3, n, d)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
        q = unit(mixed[:, :, 0]) * (d ** -0.5)
        k = unit(mixed[:, :, 1])
        v = mixed[:, :, 2]
    with jax.named_scope("kda_gates"):
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
            (_matmul(_matmul(u, p["w_fa"]), p["w_fb"], F32) + p["dt_bias"]
             ).reshape(rows, length, n, d))
        b = jax.nn.sigmoid(_matmul(u, p["w_b"], F32))
        if cfg.kda_allow_neg_eigval:
            b = 2.0 * b
        gate = jax.nn.sigmoid(_matmul(_matmul(u, p["w_ga"]), p["w_gb"], F32))
    o, state = delta_rule.delta_chunked(q, k, v, g, b, state_in, lengths,
                                        CHUNK)
    with jax.named_scope("kda_out"):
        y = (rms_norm(o, p["out_norm"], cfg.norm_eps).reshape(
            rows, length, n * d) * gate).astype(jnp.bfloat16)
        return _matmul(y, p["wo"]), (state, tail)


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  cache: Sequence, ids: jax.Array, lengths: jax.Array,
                  slot: jax.Array, pages: jax.Array, cached_len: jax.Array):
    """ids (rows, l) int32 padded on the right, lengths (rows,) real
    tokens; row r continues the `cached_len[r]` tokens whose state slot
    `slot[r]` and pages `pages[r]` hold (`cached_len[r] == 0`: from
    zeros, whatever the slot holds). -> (hidden states (rows, l, hidden)
    bfloat16, what each layer's tokens leave (a G layer: `[keys |
    values]` (rows, l, cache_width); a K layer: (state, conv inputs)
    behind each row's last real token), StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    last = jnp.maximum(lengths - 1, 0)
    bf16 = jnp.bfloat16
    used = cached_len > 0
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    left, loads, unserved, chosen = [], [], [], []
    for i, kind in enumerate(cfg.kinds):
        p = layer_params(params, i)
        u = rms_norm(h, p["attn_norm"], cfg.norm_eps).astype(bf16)
        if kind == "G":
            mixed, state = gqa_block(cfg, p, u, cache[i], pages, cached_len,
                                     lengths)
        else:
            with jax.named_scope("kda_chunk"):     # the state's read
                held = [_slot_rows(a, slot, used) for a in cache[i]]
            mixed, state = kda_block(cfg, p, u, *held, lengths)
        left.append(state)
        h = h + mixed
        mixed, stats, choice = expert_block(
            cfg, p, rms_norm(h, p["mlp_norm"], cfg.norm_eps), token_real)
        loads.append(stats.load)
        unserved.append(stats.unserved_tokens)
        chosen.append(jnp.take_along_axis(
            choice, last[:, None, None], axis=1)[:, 0])
        h = h + mixed.astype(bf16)
    stats = StepStats(
        load=jnp.stack(loads), unserved_tokens=jnp.stack(unserved),
        real_tokens=jnp.sum(token_real).astype(jnp.int32),
        chosen_last=jnp.stack(chosen, axis=1))
    return h, left, stats


def _head(cfg: LMConfig, top_k: int, block_rows: int,
          params: Dict[str, jax.Array], h: jax.Array, lengths: jax.Array,
          stats: StepStats) -> ScoreOutputs:
    with jax.named_scope("lm_head"):
        last = jnp.maximum(lengths - 1, 0)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        h_last = rms_norm(h_last, params["final_norm"], cfg.norm_eps)
        with jax.default_matmul_precision("highest"):
            top = blockwise_matmul_top_k(h_last, params["head"], top_k,
                                         block_rows,
                                         compute_dtype=jnp.float32)
    return ScoreOutputs(top.values, top.indices, top.lse, stats)


def _no_cache(cfg: LMConfig, rows: int):
    """What a step without a cache reads: the spare zero state alone and
    one page that no row holds a token of."""
    return (init_cache(cfg, 0, 1, 128), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows, 1), jnp.int32))


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array, cache: Optional[Sequence] = None,
                  slot: Optional[jax.Array] = None,
                  cached_len: Optional[jax.Array] = None,
                  pages: Optional[jax.Array] = None) -> ScoreOutputs:
    """One batch of question rows, each after the `cached_len` tokens
    whose state its slot and whose keys and values its pages `(rows,
    most pages a context)` hold: the forward pass, then the blockwise
    float32 head at each row's last real position. The cache is read,
    not written."""
    if cache is None:
        cache, slot, cached_len, pages = _no_cache(cfg, ids.shape[0])
    h, _, stats = hidden_states(cfg, params, cache, ids, lengths, slot,
                                pages, cached_len)
    return _head(cfg, top_k, block_rows, params, h, lengths, stats)


def write_pages(pool: jax.Array,        # (pages, cache_width, P)
                new: jax.Array,         # (l, cache_width): a row's tokens
                length: jax.Array,      # () real tokens of them
                pages: jax.Array,       # (most pages a context,) the row's
                start: jax.Array,       # () tokens the row's pages hold
                ) -> jax.Array:
    """The row's `length` tokens into its pages from position `start`
    on: column `p mod P` of page `pages[p // P]` for each position `p`
    in `[start, start + length)`, which may begin and end in the MIDDLE
    of a page; every other column keeps what it held."""
    page = pool.shape[2]
    tokens = new.shape[0]
    # a token a column, a page's width of zeros on either side
    wide = jnp.pad(jnp.swapaxes(new, 0, 1), ((0, 0), (page, page)))
    first = start // page
    for j in range(-(-tokens // page) + 1):     # the pages it may touch
        g = first + j
        # column c of page g holds position g P + c: token g P + c - start
        shift = g * page - start
        at = jnp.take(pages, jnp.minimum(g, pages.shape[0] - 1))
        fresh = jax.lax.dynamic_slice(
            wide, (0, shift + page), (wide.shape[0], page))
        token = shift + jnp.arange(page)
        mine = ((token >= 0) & (token < length)
                & (g < pages.shape[0]))[None, None, :]
        old = jax.lax.dynamic_slice(pool, (at, 0, 0), (1,) + pool.shape[1:])
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(mine, fresh[None], old), (at, 0, 0))
    return pool


def ctx_extend_step(cfg: LMConfig, top_k: int, block_rows: int,
                    params: Dict[str, jax.Array], cache: Sequence,
                    ids: jax.Array, lengths: jax.Array, slot: jax.Array,
                    cached_len: jax.Array, pages: jax.Array
                    ) -> Tuple[Cache, ScoreOutputs]:
    """`lm_score_step`'s rows and answer, and the rows KEPT: each row's
    context is extended by its `lengths[r]` real tokens. Every layer
    reads what the cache held; then, a row, the state and the conv
    inputs behind its last real token take its slot's place in every K
    layer and its tokens' keys and values go into its pages from
    position `cached_len[r]` on (`pages[r]` must already list the pages
    those positions need). A row of no real token writes back what it
    read. No two rows may name one slot. Returns (the cache (donate it:
    the update is in place), the answer)."""
    h, left, stats = hidden_states(cfg, params, cache, ids, lengths, slot,
                                   pages, cached_len)

    def row_of(x, r):
        return jax.lax.dynamic_index_in_dim(x, r, 0, keepdims=False)

    def write_row(r, held):
        """Row r's share of the write-back, every layer. The rows go ONE
        AFTER THE OTHER through a loop, never side by side: unrolled, a
        step of two rows' updates of the donated pool and states stalled
        the chip (PERF.md, PR 45), where a one-row step never did."""
        wrote = []
        for kind, entry, new in zip(cfg.kinds, held, left):
            if kind == "G":
                with jax.named_scope("page_write"):
                    wrote.append(write_pages(
                        entry, row_of(new, r), lengths[r], pages[r],
                        cached_len[r]))
                continue
            with jax.named_scope("state_write"):
                # a row of no token keeps its slot as it is (a context's
                # first chunk READ the spare zero state, not its slot)
                arrays = []
                for array, fresh in zip(entry, new):
                    at = (slot[r],) + (0,) * (array.ndim - 1)
                    old = jax.lax.dynamic_slice(array, at,
                                                (1,) + array.shape[1:])
                    arrays.append(jax.lax.dynamic_update_slice(
                        array, jnp.where(
                            lengths[r] > 0,
                            row_of(fresh, r)[None].astype(array.dtype),
                            old), at))
                wrote.append(tuple(arrays))
        return tuple(wrote)
    out = jax.lax.fori_loop(0, ids.shape[0], write_row, tuple(cache))
    return out, _head(cfg, top_k, block_rows, params, h, lengths,
                             stats)
