"""A latent-attention / gated-expert language model (the `glm4_moe_lite`
family's layout), served for scoring against contexts whose latent
key/value cache stays on the device.

Every layer is two pre-norm residual blocks, RMSNorm before each:

  attention  multi-head latent attention (ops/mla.py): low-rank query
             and key/value projections, a rotary key shared by the
             heads, keys and values rebuilt from (or folded around) the
             latent. A token's `[rms(c_kv) | rotated k_r]` is all a
             later token needs of it: that is what a cache slot holds,
             `kv_lora_rank + qk_rope_head_dim` values a token and layer.
  MLP        the first `first_k_dense_replace` layers: one gated MLP,
             `down(silu(gate x) * up x)`. The others: a sigmoid router
             over all experts, the k largest of score + correction bias
             (the bias steers the choice only), weights normalised over
             the chosen and scaled; gated experts (ops/moe.py, grouped
             matmuls over the experts HELD here) plus one shared gated
             expert every token passes.

then a final RMSNorm and an untied head. `n_group` = `topk_group` = 1:
no group-limited routing.

One forward pass serves both uses of a cache slot. REGISTRATION runs a
chunk of a context's tokens (one row) against what the slot holds so
far and writes the chunk's latents behind it, layer by layer, in place
(`ctx_register_step`, the cache donated). SCORING runs question rows,
each against its own slot's tokens and itself, positions continuing
from the slot's length, and answers with the top-k of the next token
(`lm_score_step`); it writes nothing. A row names its slot by index and
the step reads each row's slot where it lies, block by block.

The share held here is `layers` of `num_hidden_layers` (the leading
ones: a pipeline stage), experts `[expert_first, expert_first +
experts_held)` and vocabulary rows `[0, vocab_rows)`, as models/
hybrid_lm.py has them. NOT here: generation, the multi-token-prediction
module, training.

Precision: parameters, matmul operands and activations bfloat16,
accumulation float32; router, softmax, norms, rotary angles and logits
float32.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import (
    Leaf, ScoreOutputs, StepStats, _matmul, layer_params, layer_prefix,
    rms_norm,
)
from code2vec_tpu.ops import mla, moe
from code2vec_tpu.ops.topk import blockwise_matmul_top_k


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them, and the
    share held here."""
    hidden_size: int
    num_hidden_layers: int
    layers: int
    first_k_dense_replace: int
    vocab_size: int
    vocab_rows: int
    # latent attention
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    # MLPs
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: int
    expert_first: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_eps: float

    def __post_init__(self):
        if not 0 < self.layers <= self.num_hidden_layers:
            raise ValueError("layers must lie in (0, num_hidden_layers]")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.n_routed_experts):
            raise ValueError("the experts held lie outside the router's "
                             "width")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys; `layers`
        (the leading layers held here), `experts_held`, `expert_first`
        and `vocab_rows` state the share and default to the whole
        model."""
        raw = dict(raw)
        raw.setdefault("layers", raw.get("num_hidden_layers"))
        raw.setdefault("experts_held", raw.get("n_routed_experts"))
        raw.setdefault("expert_first", 0)
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("rms_norm_eps", 1e-5))
        if (raw.get("n_group", 1), raw.get("topk_group", 1)) != (1, 1):
            raise ValueError(f"{where}: group-limited routing (n_group, "
                             f"topk_group other than 1) is not supported")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if raw.get(n) is None]
        if missing:
            raise ValueError(f"{where}: no {', '.join(missing)}")
        return cls(**{n: raw[n] for n in names})

    @classmethod
    def from_file(cls, path: str) -> "LMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), path)

    @property
    def pattern(self) -> str:
        """The layers held: `D` a dense MLP, `E` experts."""
        dense = min(self.first_k_dense_replace, self.layers)
        return "D" * dense + "E" * (self.layers - dense)

    @property
    def cache_width(self) -> int:
        """Values a token and layer leaves in the cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def layer_leaf_specs(cfg: LMConfig, kind: str) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    out = [
        Leaf("attn_norm", (h,), "float32", "ones"),
        Leaf("q_a", (h, cfg.q_lora_rank), "bfloat16", "normal"),
        Leaf("q_norm", (cfg.q_lora_rank,), "float32", "ones"),
        Leaf("q_b", (cfg.q_lora_rank, heads * qk), "bfloat16", "normal"),
        Leaf("kv_a", (h, cfg.cache_width), "bfloat16", "normal"),
        Leaf("kv_norm", (cfg.kv_lora_rank,), "float32", "ones"),
        Leaf("kv_b", (cfg.kv_lora_rank, heads * (cfg.qk_nope_head_dim
                                                 + cfg.v_head_dim)),
             "bfloat16", "normal"),
        Leaf("o", (heads * cfg.v_head_dim, h), "bfloat16", "normal"),
        Leaf("mlp_norm", (h,), "float32", "ones"),
    ]
    if kind == "D":
        w = cfg.intermediate_size
        return out + [Leaf("gate", (h, w), "bfloat16", "normal"),
                      Leaf("up", (h, w), "bfloat16", "normal"),
                      Leaf("down", (w, h), "bfloat16", "normal")]
    w, held = cfg.moe_intermediate_size, cfg.experts_held
    sw = cfg.n_shared_experts * w
    return out + [
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
    for i, kind in enumerate(cfg.pattern):
        out += [leaf._replace(name=layer_prefix(i) + leaf.name)
                for leaf in layer_leaf_specs(cfg, kind)]
    out += [Leaf("final_norm", (h,), "float32", "ones"),
            Leaf("head", (cfg.vocab_rows, h), "bfloat16", "normal")]
    return out


# ----------------------------------------------------------------- the cache

Cache = Tuple[jax.Array, ...]       # a layer: (slots, capacity, cache_width)


def init_cache(cfg: LMConfig, slots: int, capacity: int) -> Cache:
    return tuple(jnp.zeros((slots, capacity, cfg.cache_width), jnp.bfloat16)
                 for _ in range(cfg.layers))


# ---------------------------------------------------------------- the layers

def attention_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                    positions: jax.Array, cached: jax.Array,
                    slot: jax.Array, cached_len: jax.Array,
                    lengths: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """u (rows, l, hidden) bfloat16 -> (the block's output, the tokens'
    latents (rows, l, cache_width)), both bfloat16."""
    rows, length, _ = u.shape
    q_n, q_r, latent = mla.project(
        u, positions, p["q_a"], p["q_norm"], p["q_b"], p["kv_a"],
        p["kv_norm"], cfg.num_attention_heads, cfg.qk_rope_head_dim,
        cfg.rope_theta, cfg.norm_eps)
    o = mla.attend(q_n, q_r, latent, cached, slot, cached_len, lengths,
                   p["kv_b"])
    with jax.named_scope("mla_proj"):
        return _matmul(o.reshape(rows, length, -1), p["o"]), latent


def expert_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                 token_real: jax.Array):
    """u (rows, l, hidden) float32 -> ((rows, l, hidden) bfloat16, stats,
    the router's choice (rows, l, k)). The router reads the float32
    input; the matmuls take it as bfloat16."""
    rows, length, hidden = u.shape
    flat32 = u.reshape(rows * length, hidden)
    routed = moe.route(flat32, p["router"], p["router_bias"],
                       cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    flat = flat32.astype(jnp.bfloat16)
    out, stats = moe.experts_grouped(
        flat, routed, p["w_up"], p["w_down"], cfg.expert_first,
        token_real.reshape(-1), w_gate=p["w_gate"])
    with jax.named_scope("moe_shared"):
        out = out + moe.gated_mlp(flat, p["shared_gate"], p["shared_up"],
                                  p["shared_down"])
    return (out.astype(jnp.bfloat16).reshape(rows, length, hidden), stats,
            routed.experts.reshape(rows, length, -1))


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  cache: Sequence[jax.Array], ids: jax.Array,
                  lengths: jax.Array, slot: jax.Array,
                  cached_len: jax.Array):
    """ids (rows, l) int32 padded on the right, lengths (rows,) real
    tokens; row r reads `cached_len[r]` tokens of slot `slot[r]` and
    stands at positions `cached_len[r] + 0..l`. -> (hidden states (rows,
    l, hidden) bfloat16, the tokens' latents a layer, StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    positions = cached_len[:, None] + jnp.arange(length)[None, :]
    last = jnp.maximum(lengths - 1, 0)
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    latents, loads, unserved, chosen = [], [], [], []
    for i, kind in enumerate(cfg.pattern):
        p = layer_params(params, i)
        u = rms_norm(h, p["attn_norm"], cfg.norm_eps).astype(jnp.bfloat16)
        mixed, latent = attention_block(cfg, p, u, positions, cache[i],
                                        slot, cached_len, lengths)
        latents.append(latent)
        h = h + mixed
        u = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        if kind == "D":
            with jax.named_scope("dense_mlp"):
                mixed = moe.gated_mlp(u, p["gate"], p["up"], p["down"],
                                      jnp.bfloat16)
        else:
            mixed, stats, choice = expert_block(cfg, p, u, token_real)
            loads.append(stats.load)
            unserved.append(stats.unserved_tokens)
            chosen.append(jnp.take_along_axis(
                choice, last[:, None, None], axis=1)[:, 0])
        h = h + mixed
    k = cfg.num_experts_per_tok
    stats = StepStats(
        load=(jnp.stack(loads) if loads
              else jnp.zeros((0, cfg.experts_held), jnp.int32)),
        unserved_tokens=(jnp.stack(unserved) if unserved
                         else jnp.zeros((0,), jnp.int32)),
        real_tokens=jnp.sum(token_real).astype(jnp.int32),
        chosen_last=(jnp.stack(chosen, axis=1) if chosen
                     else jnp.zeros((rows, 0, k), jnp.int32)))
    return h, latents, stats


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array, cache: Sequence[jax.Array],
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
                      cache: Sequence[jax.Array], ids: jax.Array,
                      length: jax.Array, slot: jax.Array,
                      start: jax.Array) -> Cache:
    """One chunk `ids` (l,) of a context, `length` of them real, behind
    the `start` tokens slot `slot` already holds: the chunk's latents
    land at `[start, start + l)` of the slot in every layer. Returns the
    cache (donate it: the update is in place)."""
    _, latents, _ = hidden_states(
        cfg, params, cache, ids[None, :], length[None], slot[None],
        start[None])
    with jax.named_scope("cache_write"):
        return tuple(jax.lax.dynamic_update_slice(
            layer, latent, (slot, start, 0))
            for layer, latent in zip(cache, latents))
