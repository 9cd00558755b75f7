"""A grouped-query-attention / softmax-routed-expert language model whose
attention attends a LEARNED SELECTION of keys (the `KeyeVL2` family's
language decoder), served for scoring against contexts whose keys,
values and index keys stay on the device.

Every layer is the same two pre-norm residual blocks, RMSNorm before
each; with `u = rms(h)`:

  attention  `q = rot(rms_d(W_q u))` for `num_attention_heads` heads of
             `head_dim`, `k = rot(rms_d(W_k u))` and `v = W_v u` for
             `num_key_value_heads`, no biases; rotary over the whole
             head, half-split pairs, three position streams dealt to the
             pairs by `rope_scaling.mrope_section` (a text token's three
             positions are equal: plain rotary). The indexer
             (`sa_config`): `qI = rot(W_Iq u)` for `indexer_num_heads`
             heads of `indexer_head_dim`, ONE index key `kI =
             rot(layernorm(W_Ik u))`, head weights `a = W_Iw u /
             sqrt(heads * head_dim)`; a query keeps the `topk` keys of
             largest `sum_j a_j relu(qI_j . kI)` among those it may see
             and attends them alone (ops/sparse_attn.py).
  experts    softmax over ALL `num_experts` in float32, the
             `num_experts_per_tok` largest, renormalised over the chosen
             (`norm_topk_prob`); gated experts of width
             `moe_intermediate_size` (ops/moe.py, grouped matmuls over
             the experts HELD here). No shared expert, no dense layer
             (`decoder_sparse_step` 1, `mlp_only_layers` []).

then a final RMSNorm and an untied head.

A token leaves TWO kinds of state a layer for later tokens: its keys
and values (`2 * num_key_value_heads * head_dim` values) and its index
key (`indexer_head_dim`). A cache slot holds both, as two arrays a layer
(`LayerCache`), read by different steps of the attention at different
rates. One forward pass serves both uses of a slot, as in
models/latent_moe_lm.py: REGISTRATION runs a chunk of a context behind
what the slot holds and writes the chunk's state in place
(`ctx_register_step`, the cache donated); SCORING runs question rows,
each against its own slot and itself (`lm_score_step`), and writes
nothing.

The share held here is `layers` of `num_hidden_layers` (the leading
ones: a pipeline stage), experts `[expert_first, expert_first +
experts_held)` and vocabulary rows `[0, vocab_rows)`. NOT here: the
vision tower (image positions would only set the three rotary streams
apart), generation, training.

Precision: parameters, matmul operands, activations and both caches
bfloat16, accumulation float32; router, attention softmax, norms,
rotary angles, logits and the index scores (their relu, head weights,
sum over heads and the comparison that selects) float32.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import (
    Leaf, ScoreOutputs, StepStats, _matmul, layer_params, layer_prefix,
    rms_norm,
)
from code2vec_tpu.ops import moe, sparse_attn
from code2vec_tpu.ops.topk import blockwise_matmul_top_k

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them (the
    indexer's under `sa_config`, the rotary sections under
    `rope_scaling`), and the share held here."""
    hidden_size: int
    num_hidden_layers: int
    layers: int
    vocab_size: int
    vocab_rows: int
    # grouped-query attention
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    mrope_section: Tuple[int, ...]
    # the indexer
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    # experts
    moe_intermediate_size: int
    num_experts: int
    experts_held: int
    expert_first: int
    num_experts_per_tok: int
    norm_eps: float

    def __post_init__(self):
        if not 0 < self.layers <= self.num_hidden_layers:
            raise ValueError("layers must lie in (0, num_hidden_layers]")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.num_experts):
            raise ValueError("the experts held lie outside the router's "
                             "width")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")
        if self.head_dim % 2 or self.indexer_head_dim % 2:
            raise ValueError("head_dim and indexer_head_dim must be even")
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError("mrope_section must cover head_dim / 2 "
                             "rotary pairs")
        by = self.head_dim // self.indexer_head_dim
        if (self.head_dim % self.indexer_head_dim
                or any(s % by for s in self.mrope_section)):
            raise ValueError("the index head's rotary sections are the "
                             "attention head's scaled to its width: "
                             "indexer_head_dim must divide head_dim, and "
                             "their ratio every mrope_section")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys; `layers`
        (the leading layers held here), `experts_held`, `expert_first`
        and `vocab_rows` state the share and default to the whole
        model."""
        raw = dict(raw)
        raw.setdefault("layers", raw.get("num_hidden_layers"))
        raw.setdefault("experts_held", raw.get("num_experts"))
        raw.setdefault("expert_first", 0)
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("rms_norm_eps", 1e-6))
        for key, value in (raw.get("sa_config") or {}).items():
            raw.setdefault(key, value)
        sections = (raw.get("rope_scaling") or {}).get("mrope_section")
        if sections is None and raw.get("head_dim"):
            sections = [raw["head_dim"] // 2, 0, 0]     # plain rotary
        raw.setdefault("mrope_section", sections)
        if raw.get("mlp_only_layers") or raw.get(
                "decoder_sparse_step", 1) != 1:
            raise ValueError(f"{where}: dense layers among the expert "
                             f"layers (mlp_only_layers, "
                             f"decoder_sparse_step) are not supported")
        if not raw.get("norm_topk_prob", True):
            raise ValueError(f"{where}: norm_topk_prob false is not "
                             f"supported")
        if raw.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError(f"{where}: more than one index key a token "
                             f"is not supported")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if raw.get(n) is None]
        if missing:
            raise ValueError(f"{where}: no {', '.join(missing)}")
        raw["mrope_section"] = tuple(int(s) for s in raw["mrope_section"])
        return cls(**{n: raw[n] for n in names})

    @classmethod
    def from_file(cls, path: str) -> "LMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), path)

    @property
    def pattern(self) -> str:
        """The layers held: every one an expert layer."""
        return "E" * self.layers

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def kv_width(self) -> int:
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def cache_width(self) -> int:
        """Values a token and layer leaves in the cache, both arrays."""
        return self.kv_width + self.indexer_head_dim

    @property
    def index_sections(self) -> Tuple[int, ...]:
        """The rotary sections of the index head: the attention head's,
        scaled to its width."""
        by = self.head_dim // self.indexer_head_dim
        return tuple(s // by for s in self.mrope_section)


def layer_leaf_specs(cfg: LMConfig) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix."""
    h, d = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    w, held = cfg.moe_intermediate_size, cfg.experts_held
    return [
        Leaf("attn_norm", (h,), "float32", "ones"),
        Leaf("wq", (h, q), "bfloat16", "normal"),
        Leaf("wk", (h, kv), "bfloat16", "normal"),
        Leaf("wv", (h, kv), "bfloat16", "normal"),
        Leaf("q_norm", (d,), "float32", "ones"),
        Leaf("k_norm", (d,), "float32", "ones"),
        Leaf("wo", (q, h), "bfloat16", "normal"),
        Leaf("idx_q", (h, hi * di), "bfloat16", "normal"),
        Leaf("idx_k", (h, di), "bfloat16", "normal"),
        Leaf("idx_w", (h, hi), "bfloat16", "normal"),
        Leaf("idx_k_norm", (di,), "float32", "ones"),
        Leaf("idx_k_bias", (di,), "float32", "zeros"),
        Leaf("mlp_norm", (h,), "float32", "ones"),
        Leaf("router", (h, cfg.num_experts), "bfloat16", "normal"),
        Leaf("w_gate", (held, h, w), "bfloat16", "normal"),
        Leaf("w_up", (held, h, w), "bfloat16", "normal"),
        Leaf("w_down", (held, w, h), "bfloat16", "normal"),
    ]


def leaf_specs(cfg: LMConfig) -> List[Leaf]:
    """Every leaf of the model, in forward order."""
    h = cfg.hidden_size
    out = [Leaf("embed", (cfg.vocab_rows, h), "bfloat16", "normal")]
    for i in range(cfg.layers):
        out += [leaf._replace(name=layer_prefix(i) + leaf.name)
                for leaf in layer_leaf_specs(cfg)]
    out += [Leaf("final_norm", (h,), "float32", "ones"),
            Leaf("head", (cfg.vocab_rows, h), "bfloat16", "normal")]
    return out


# ----------------------------------------------------------------- the cache

class LayerCache(NamedTuple):
    """What a layer's slots hold, a token: `[keys | values]` of every
    key/value head, and the one index key."""
    kv: jax.Array       # (slots, capacity, 2 * hkv * head_dim)
    index: jax.Array    # (slots, capacity, indexer_head_dim)


Cache = Tuple[LayerCache, ...]


def init_cache(cfg: LMConfig, slots: int, capacity: int) -> Cache:
    return tuple(LayerCache(
        jnp.zeros((slots, capacity, cfg.kv_width), jnp.bfloat16),
        jnp.zeros((slots, capacity, cfg.indexer_head_dim), jnp.bfloat16))
        for _ in range(cfg.layers))


ATTEND_FORM = "masked"      # the one form ops/sparse_attn.py has


# ---------------------------------------------------------------- the layers

def _layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
                eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def project_heads(u: jax.Array, w: jax.Array, norm: jax.Array, heads: int,
                  head_dim: int, positions: jax.Array, theta: float,
                  sections, eps: float) -> jax.Array:
    """u (rows, l, hidden) -> (rows, l, heads, head_dim) float32: the
    projection, an RMSNorm with a weight over each head, the rotary."""
    rows, length, _ = u.shape
    x = _matmul(u, w, F32).reshape(rows, length, heads, head_dim)
    return sparse_attn.rotate(rms_norm(x, norm, eps), positions, theta,
                              sections)


def attention_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                    positions: jax.Array, cached: LayerCache,
                    slot: jax.Array, cached_len: jax.Array,
                    lengths: jax.Array):
    """u (rows, l, hidden) bfloat16, positions (3, rows, l) -> (the
    block's output bfloat16, the tokens' state as a LayerCache of (rows,
    l, .) arrays, the selection (rows, l, capacity + l) bool)."""
    rows, length, _ = u.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    bf16 = jnp.bfloat16
    with jax.named_scope("gqa_proj"):
        def heads(w, norm, n):
            return project_heads(u, w, norm, n, d, positions,
                                 cfg.rope_theta, cfg.mrope_section,
                                 cfg.norm_eps).astype(bf16)
        q = heads(p["wq"], p["q_norm"], hq)
        k = heads(p["wk"], p["k_norm"], hkv)
        v = _matmul(u, p["wv"]).reshape(rows, length, hkv, d)
        q_i = sparse_attn.rotate(
            _matmul(u, p["idx_q"], F32).reshape(rows, length, hi, di),
            positions, cfg.rope_theta, cfg.index_sections).astype(bf16)
        k_i = sparse_attn.rotate(
            _layer_norm(_matmul(u, p["idx_k"], F32), p["idx_k_norm"],
                        p["idx_k_bias"], cfg.norm_eps),
            positions, cfg.rope_theta, cfg.index_sections).astype(bf16)
        a = _matmul(u, p["idx_w"], F32) * ((hi * di) ** -0.5)
    scores = sparse_attn.index_scores(q_i, a, k_i, cached.index, slot,
                                      cached_len)
    selected = sparse_attn.select(
        scores, sparse_attn.visible_keys(rows, length, cached.kv.shape[1],
                                         cached_len, lengths), cfg.topk)
    o = sparse_attn.attend(q, k, v, cached.kv, slot, cached_len, selected)
    with jax.named_scope("gqa_proj"):
        left = LayerCache(
            jnp.concatenate([k.reshape(rows, length, -1),
                             v.reshape(rows, length, -1)], axis=-1), k_i)
        return _matmul(o, p["wo"]), left, selected


def expert_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                 token_real: jax.Array):
    """u (rows, l, hidden) float32 -> ((rows, l, hidden) bfloat16, stats,
    the router's choice (rows, l, k)). The router reads the float32
    input; the matmuls take it as bfloat16."""
    rows, length, hidden = u.shape
    flat32 = u.reshape(rows * length, hidden)
    routed = moe.route(flat32, p["router"], None, cfg.num_experts_per_tok,
                       softmax=True)
    out, stats = moe.experts_grouped(
        flat32.astype(jnp.bfloat16), routed, p["w_up"], p["w_down"],
        cfg.expert_first, token_real.reshape(-1), w_gate=p["w_gate"])
    return (out.astype(jnp.bfloat16).reshape(rows, length, hidden), stats,
            routed.experts.reshape(rows, length, -1))


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  cache: Sequence[LayerCache], ids: jax.Array,
                  lengths: jax.Array, slot: jax.Array,
                  cached_len: jax.Array):
    """ids (rows, l) int32 padded on the right, lengths (rows,) real
    tokens; row r reads `cached_len[r]` tokens of slot `slot[r]` and
    stands at positions `cached_len[r] + 0..l` (text: the three rotary
    streams equal). -> (hidden states (rows, l, hidden) bfloat16, the
    tokens' state a layer, StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    at = cached_len[:, None] + jnp.arange(length)[None, :]
    positions = jnp.broadcast_to(at[None], (3, rows, length))
    last = jnp.maximum(lengths - 1, 0)
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    left, loads, unserved, chosen, kept_last, kept = [], [], [], [], [], []
    for i in range(cfg.layers):
        p = layer_params(params, i)
        u = rms_norm(h, p["attn_norm"], cfg.norm_eps).astype(jnp.bfloat16)
        mixed, state, selected = attention_block(
            cfg, p, u, positions, cache[i], slot, cached_len, lengths)
        left.append(state)
        with jax.named_scope("index_select"):
            kept_last.append(sparse_attn.pack_bits(jnp.take_along_axis(
                selected, last[:, None, None], axis=1)[:, 0]))
            kept.append(jnp.sum(selected & token_real[:, :, None],
                                dtype=jnp.int32))
        h = h + mixed
        mixed, stats, choice = expert_block(
            cfg, p, rms_norm(h, p["mlp_norm"], cfg.norm_eps), token_real)
        loads.append(stats.load)
        unserved.append(stats.unserved_tokens)
        chosen.append(jnp.take_along_axis(
            choice, last[:, None, None], axis=1)[:, 0])
        h = h + mixed
    stats = StepStats(
        load=jnp.stack(loads), unserved_tokens=jnp.stack(unserved),
        real_tokens=jnp.sum(token_real).astype(jnp.int32),
        chosen_last=jnp.stack(chosen, axis=1),
        selected_last=jnp.stack(kept_last, axis=1),
        selected_keys=jnp.stack(kept))
    return h, left, stats


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array, cache: Sequence[LayerCache],
                  slot: jax.Array, cached_len: jax.Array) -> ScoreOutputs:
    """One batch of question rows, each after its slot's `cached_len`
    tokens: the forward pass, then the blockwise float32 head at each
    row's last real position. The cache is read, not written."""
    h, _, stats = hidden_states(cfg, params, cache, ids, lengths, slot,
                                cached_len)
    with jax.named_scope("lm_head"):
        last = jnp.maximum(lengths - 1, 0)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        h_last = rms_norm(h_last, params["final_norm"], cfg.norm_eps)
        with jax.default_matmul_precision("highest"):
            top = blockwise_matmul_top_k(h_last, params["head"], top_k,
                                         block_rows,
                                         compute_dtype=jnp.float32)
    return ScoreOutputs(top.values, top.indices, top.lse, stats)


def ctx_register_step(cfg: LMConfig, params: Dict[str, jax.Array],
                      cache: Sequence[LayerCache], ids: jax.Array,
                      length: jax.Array, slot: jax.Array,
                      start: jax.Array) -> Cache:
    """One chunk `ids` (l,) of a context, `length` of them real, behind
    the `start` tokens slot `slot` already holds: the chunk's keys,
    values and index keys land at `[start, start + l)` of the slot in
    every layer. Returns the cache (donate it: the update is in
    place)."""
    _, left, _ = hidden_states(
        cfg, params, cache, ids[None, :], length[None], slot[None],
        start[None])
    with jax.named_scope("cache_write"):
        return tuple(LayerCache(*(
            jax.lax.dynamic_update_slice(held, new, (slot, start, 0))
            for held, new in zip(layer, state)))
            for layer, state in zip(cache, left))
