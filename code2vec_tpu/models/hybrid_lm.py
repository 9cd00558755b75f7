"""A hybrid state-space / latent-expert language model, served for
scoring: one forward pass over a token sequence, the next-token logits
at its last position.

A stack of pre-norm residual blocks, `h <- h + mix(RMSNorm(h))`, whose
kinds a pattern string chooses (the `nemotron_h` family's layout):

  `M`  Mamba-2: `[z | xBC | dt] = W_in u`; `xBC <- silu(causal depthwise
       conv1d(xBC))`, split into `x`, `B`, `C`; `dt = softplus(dt +
       dt_bias)`, `A = -exp(A_log)`; the selective recurrence (ops/ssd.py,
       in chunks); `y <- RMSNorm over each group (y * silu(z))`;
       `W_out y`.
  `*`  causal grouped-query attention, no bias, no positional encoding
       (the Mamba layers carry position): ops/attention.py.
  `E`  latent experts: a sigmoid router over all experts, the k largest
       of score + correction bias, weights normalised over the chosen
       and scaled; experts act in a latent space (`W_down`, then
       `W2_i relu(W1_i l)^2`, then `W_up`), beside a shared expert on
       the full width: ops/moe.py.

then a final RMSNorm and an untied head.

The model can be told it holds a SHARE of a layer group that several
chips divide: experts `[expert_first, expert_first + experts_held)` of
`n_routed_experts` and rows `[0, vocab_rows)` of the vocabulary. The
router keeps its whole width; what absent experts would add is left
out (the partial sum an expert-parallel chip brings to the combine),
and embedding, head, logits and top-k are over the slice.

NOT here: generation (no recurrent-state or key/value cache, no decode
step), the multi-token-prediction module, training (no backward pass of
the expert layer, no optimizer share), an expert axis on a mesh.

Precision: parameters, matmul operands and activations bfloat16,
accumulation float32; router, softplus, decays, states, norms and
logits float32.

Parameters are a flat dict of arrays, `layers.<nn>.<leaf>`; `leaf_specs`
is the one list of their names, shapes and types.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List

import jax
import jax.numpy as jnp

from code2vec_tpu.models.lm_common import (  # noqa: F401  (re-exported)
    Leaf, ScoreOutputs, StepStats, _matmul, abstract_leaves, count_leaves,
    init_leaf, init_leaves, layer_params, layer_prefix, rms_norm,
)
from code2vec_tpu.ops import moe, ssd
from code2vec_tpu.ops.attention import causal_gqa_attention
from code2vec_tpu.ops.delta_rule import conv_carried
from code2vec_tpu.ops.topk import blockwise_matmul_top_k

KINDS = "M*E"


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them, and the
    share held here; `from_file` reads a model-configuration file."""
    hidden_size: int
    pattern: str
    vocab_size: int
    vocab_rows: int
    # Mamba-2
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    # attention
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # latent experts
    n_routed_experts: int
    experts_held: int
    expert_first: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_eps: float

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: layer kinds are "
                             f"{', '.join(KINDS)}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must be a multiple of n_groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.n_routed_experts):
            raise ValueError("the experts held lie outside the router's "
                             "width")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys;
        `pattern` (the layers held here), `experts_held`, `expert_first`
        and `vocab_rows` state the share and default to the whole
        model."""
        raw = dict(raw)
        raw.setdefault("pattern", raw.get("hybrid_override_pattern"))
        raw.setdefault("experts_held", raw.get("n_routed_experts"))
        raw.setdefault("expert_first", 0)
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("layer_norm_epsilon", 1e-5))
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
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


def layer_leaf_specs(cfg: LMConfig, kind: str) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix."""
    h = cfg.hidden_size
    out = [Leaf("norm", (h,), "float32", "ones")]
    if kind == "M":
        nh = cfg.mamba_num_heads
        out += [
            Leaf("in_proj", (h, cfg.d_inner + cfg.conv_dim + nh),
                 "bfloat16", "normal"),
            Leaf("conv_w", (cfg.conv_dim, cfg.conv_kernel), "float32",
                 "conv"),
            Leaf("conv_b", (cfg.conv_dim,), "float32", "zeros"),
            Leaf("dt_bias", (nh,), "float32", "dt_bias"),
            Leaf("a_log", (nh,), "float32", "a_log"),
            Leaf("d", (nh,), "float32", "ones"),
            Leaf("gate_norm", (cfg.d_inner,), "float32", "ones"),
            Leaf("out_proj", (cfg.d_inner, h), "bfloat16", "normal"),
        ]
    elif kind == "*":
        q = cfg.num_attention_heads * cfg.head_dim
        kv = cfg.num_key_value_heads * cfg.head_dim
        out += [Leaf("wq", (h, q), "bfloat16", "normal"),
                Leaf("wk", (h, kv), "bfloat16", "normal"),
                Leaf("wv", (h, kv), "bfloat16", "normal"),
                Leaf("wo", (q, h), "bfloat16", "normal")]
    else:
        lat, w = cfg.moe_latent_size, cfg.moe_intermediate_size
        sw = cfg.moe_shared_expert_intermediate_size
        out += [
            Leaf("router", (h, cfg.n_routed_experts), "bfloat16", "normal"),
            Leaf("router_bias", (cfg.n_routed_experts,), "float32", "bias"),
            Leaf("down", (h, lat), "bfloat16", "normal"),
            Leaf("up", (lat, h), "bfloat16", "normal"),
            Leaf("w1", (cfg.experts_held, lat, w), "bfloat16", "normal"),
            Leaf("w2", (cfg.experts_held, w, lat), "bfloat16", "normal"),
            Leaf("shared_w1", (h, sw), "bfloat16", "normal"),
            Leaf("shared_w2", (sw, h), "bfloat16", "normal"),
        ]
    return out


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


def num_params(cfg: LMConfig) -> int:
    return count_leaves(leaf_specs(cfg))


def init_params(cfg: LMConfig, seed: int) -> Dict[str, jax.Array]:
    return init_leaves(cfg, leaf_specs(cfg), seed)


def abstract_params(cfg: LMConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    return abstract_leaves(leaf_specs(cfg))


# ---------------------------------------------------------------- the layers

def mamba_mixer(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array
                ) -> jax.Array:
    """u (b, l, hidden) bfloat16 -> (b, l, hidden) bfloat16."""
    bsz, length, _ = u.shape
    nh, hd, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                    cfg.ssm_state_size)
    di = cfg.d_inner
    with jax.named_scope("mamba_proj"):
        zxbcdt = _matmul(u, p["in_proj"], jnp.float32)
        z = zxbcdt[..., :di].astype(jnp.bfloat16)
        xbc = zxbcdt[..., di:di + cfg.conv_dim].astype(jnp.bfloat16)
        dt = jax.nn.softplus(zxbcdt[..., di + cfg.conv_dim:]
                             + p["dt_bias"])                # float32
        xbc = jax.nn.silu(conv_carried(xbc, p["conv_w"], bias=p["conv_b"])[0]
                          ).astype(jnp.bfloat16)
    x = xbc[..., :di].reshape(bsz, length, nh, hd)
    b_in = xbc[..., di:di + g * n].reshape(bsz, length, g, n)
    c_in = xbc[..., di + g * n:].reshape(bsz, length, g, n)
    y = ssd.ssd_chunked(x, dt, -jnp.exp(p["a_log"]), b_in, c_in, p["d"],
                        chunk=cfg.chunk_size)
    with jax.named_scope("mamba_proj"):
        y = y.reshape(bsz, length, di) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y.reshape(bsz, length, g, di // g),
                     p["gate_norm"].reshape(g, di // g), cfg.norm_eps)
        return _matmul(y.reshape(bsz, length, di), p["out_proj"])


def attention_mixer(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array
                    ) -> jax.Array:
    bsz, length, _ = u.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope("attn"):
        q = _matmul(u, p["wq"]).reshape(bsz, length, hq, d)
        k = _matmul(u, p["wk"]).reshape(bsz, length, hkv, d)
        v = _matmul(u, p["wv"]).reshape(bsz, length, hkv, d)
    o = causal_gqa_attention(q, k, v)
    with jax.named_scope("attn"):
        return _matmul(o.reshape(bsz, length, hq * d), p["wo"])


def expert_mixer(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                 token_real: jax.Array):
    """u (b, l, hidden) float32 -> ((b, l, hidden) bfloat16, stats, the
    router's choice (b, l, k)). The router reads the float32 input; the
    matmuls take it as bfloat16."""
    bsz, length, hidden = u.shape
    routed = moe.route(u.reshape(bsz * length, hidden), p["router"],
                       p["router_bias"], cfg.num_experts_per_tok,
                       cfg.routed_scaling_factor)
    flat = u.reshape(bsz * length, hidden).astype(jnp.bfloat16)
    with jax.named_scope("moe_experts"):
        latent = _matmul(flat, p["down"])
    r, stats = moe.experts_grouped(latent, routed, p["w1"], p["w2"],
                                   cfg.expert_first,
                                   token_real.reshape(-1))
    with jax.named_scope("moe_experts"):
        out = _matmul(r, p["up"], jnp.float32)
    with jax.named_scope("moe_shared"):
        shared = _matmul(moe.relu2(_matmul(flat, p["shared_w1"],
                                           jnp.float32)),
                         p["shared_w2"], jnp.float32)
    out = (out + shared).astype(jnp.bfloat16).reshape(bsz, length, hidden)
    return out, stats, routed.experts.reshape(bsz, length, -1)


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  ids: jax.Array, lengths: jax.Array):
    """ids (rows, length) int32 padded on the right, lengths (rows,) ->
    (the normalised hidden state at each row's last real position
    (rows, hidden) float32, StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    last = jnp.maximum(lengths - 1, 0)
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    loads, unserved, chosen = [], [], []
    real = jnp.sum(token_real).astype(jnp.int32)
    for i, kind in enumerate(cfg.pattern):
        p = layer_params(params, i)
        u = rms_norm(h, p["norm"], cfg.norm_eps)
        if kind == "M":
            mixed = mamba_mixer(cfg, p, u.astype(jnp.bfloat16))
        elif kind == "*":
            mixed = attention_mixer(cfg, p, u.astype(jnp.bfloat16))
        else:
            mixed, stats, choice = expert_mixer(cfg, p, u, token_real)
            loads.append(stats.load)
            unserved.append(stats.unserved_tokens)
            chosen.append(jnp.take_along_axis(
                choice, last[:, None, None], axis=1)[:, 0])
        h = h + mixed
    with jax.named_scope("lm_head"):
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        h_last = rms_norm(h_last, params["final_norm"], cfg.norm_eps)
    k = cfg.num_experts_per_tok
    stats = StepStats(
        load=(jnp.stack(loads) if loads
              else jnp.zeros((0, cfg.experts_held), jnp.int32)),
        unserved_tokens=(jnp.stack(unserved) if unserved
                         else jnp.zeros((0,), jnp.int32)),
        real_tokens=real,
        chosen_last=(jnp.stack(chosen, axis=1) if chosen
                     else jnp.zeros((rows, 0, k), jnp.int32)))
    return h_last, stats


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array) -> ScoreOutputs:
    """One batch: the forward pass, then the blockwise head (ops/topk.py)
    in float32 over the vocabulary rows held."""
    h_last, stats = hidden_states(cfg, params, ids, lengths)
    with jax.named_scope("lm_head"), jax.default_matmul_precision("highest"):
        top = blockwise_matmul_top_k(h_last, params["head"], top_k,
                                     block_rows,
                                     compute_dtype=jnp.float32)
    return ScoreOutputs(top.values, top.indices, top.lse, stats)
