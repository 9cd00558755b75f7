"""A dense language model whose layers mix tokens by POWER RETENTION in
the place of softmax attention (the `brumby` family), served for scoring
against contexts held as retention STATE: a state of fixed size a
context, whatever its length.

Every layer is the same two pre-norm residual blocks. With `u` the
layer's input after its RMSNorm, `d = head_dim`, `hq` query and `hkv`
key/value heads, query head `n` in group `n // (hq / hkv)`:

    q = rotate(rmsnorm_head(W_q u)), k = rotate(rmsnorm_head(W_k u)),
    v = W_v u                                  (rotary over all of d)
    log g_t = log sigmoid(W_g u_t + b_g)       one gate a key/value head
                                               and token, float32
    a_ij = (q_i . k_j / sqrt(d))^p * exp(sum_{l=j+1..i} log g_l)
                                               j <= i, p = 2
    y_i = sum_j a_ij v_j / (sum_j a_ij + eps)
    x' = x + W_o y ;  x'' = x' + W_down(silu(W_gate r) * W_up r),
                                               r = rmsnorm(x')

and the same as a recurrence, with `phi(x)` the symmetric square of x
(the `d (d + 1) / 2` products `x_a x_b`, `a <= b`, the off-diagonal ones
times sqrt 2, so that `phi(q) . phi(k) = (q . k)^2`):

    S_t = g_t S_{t-1} + phi(k_t / d^(1/4)) v_t^T
    z_t = g_t z_{t-1} + phi(k_t / d^(1/4))
    y_t = S_t^T phi(q_t / d^(1/4)) / (z_t . phi(q_t / d^(1/4)) + eps)

(ops/power_retention.py computes both in chunks.) Then a final RMSNorm
and an untied head.

A context leaves ONE thing a layer for later tokens: `[S | z]` of every
key/value head behind its last token, `hkv x (d + 1) x d (d + 1) / 2`
float32 values, the same for 8 thousand tokens and for 32 thousand
(`CACHE_KIND = "state"`: a cache slot is a state, not a row a token).
REGISTRATION runs a chunk of a context from the state the slot holds
(zeros for the context's first chunk, so a reused slot never leaks what
it held) and writes the state behind the chunk back (`ctx_register_step`,
the cache donated). SCORING runs question rows, each from its own slot's
state, positions continuing at the context's length, and writes nothing
(`lm_score_step`); a row that names no context starts from zeros.

The share held here is `layers` of `num_hidden_layers` (the leading
ones: a pipeline stage) and vocabulary rows `[0, vocab_rows)`. NOT here:
generation, training, extending a registered state in place.

Not in the published config and set here by the family's own description
(the benchmark's configuration file lists each under `assumed`): the
power 2; the gate's projection, bias and log-sigmoid; the per-head
RMSNorms on q and k and the rotary, kept from the model the family was
retrained from; the score's scale `1 / sqrt(d)`; eps; the state float32.

Precision: parameters, matmul operands and activations bfloat16,
accumulation float32; gates, decays, the state, the normaliser, norms,
rotary angles and logits float32.
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
from code2vec_tpu.models.sparse_gqa_moe_lm import project_heads
from code2vec_tpu.ops import moe, power_retention
from code2vec_tpu.ops.topk import blockwise_matmul_top_k

F32 = jnp.float32
CACHE_KIND = "state"    # a slot holds a state of fixed size, not tokens


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them, and the
    share held here."""
    hidden_size: int
    num_hidden_layers: int
    layers: int
    vocab_size: int
    vocab_rows: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    max_position_embeddings: int
    intermediate_size: int
    norm_eps: float
    retention_chunk: int
    gate_memory_tokens: Tuple[float, float]

    def __post_init__(self):
        if not 0 < self.layers <= self.num_hidden_layers:
            raise ValueError("layers must lie in (0, num_hidden_layers]")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        low, high = self.gate_memory_tokens
        if not 1.0 < low <= high:
            raise ValueError("gate_memory_tokens must be (low, high) "
                             "tokens of memory, 1 < low <= high")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys; `layers`
        (the leading layers held here) and `vocab_rows` state the share
        and default to the whole model."""
        raw = dict(raw)
        raw.setdefault("layers", raw.get("num_hidden_layers"))
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("rms_norm_eps", 1e-6))
        if raw.get("retention_power", 2) != 2:
            raise ValueError(f"{where}: only the power 2 has a feature map "
                             f"here")
        raw.setdefault("retention_chunk", 256)
        raw.setdefault("gate_memory_tokens", (1000.0, 10000.0))
        if raw.get("rope_scaling") or raw.get("use_sliding_window"):
            raise ValueError(f"{where}: rope_scaling and sliding windows "
                             f"are not supported")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if raw.get(n) is None]
        if missing:
            raise ValueError(f"{where}: no {', '.join(missing)}")
        raw["gate_memory_tokens"] = tuple(
            float(t) for t in raw["gate_memory_tokens"])
        return cls(**{n: raw[n] for n in names})

    @classmethod
    def from_file(cls, path: str) -> "LMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), path)

    @property
    def pattern(self) -> str:
        """The layers held: every one a retention layer, none an expert
        layer."""
        return "R" * self.layers

    @property
    def state_features(self) -> int:
        return power_retention.state_features(self.head_dim)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One slot of one layer: `[S | z]` of every key/value head."""
        return (self.num_key_value_heads, self.head_dim + 1,
                self.state_features)


def layer_leaf_specs(cfg: LMConfig) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix."""
    h, d, w = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    hkv = cfg.num_key_value_heads
    q, kv = cfg.num_attention_heads * d, hkv * d
    return [
        Leaf("attn_norm", (h,), "float32", "ones"),
        Leaf("wq", (h, q), "bfloat16", "normal"),
        Leaf("wk", (h, kv), "bfloat16", "normal"),
        Leaf("wv", (h, kv), "bfloat16", "normal"),
        Leaf("q_norm", (d,), "float32", "ones"),
        Leaf("k_norm", (d,), "float32", "ones"),
        Leaf("wg", (h, hkv), "bfloat16", "normal"),
        Leaf("bg", (hkv,), "float32", "gate_bias"),
        Leaf("wo", (q, h), "bfloat16", "normal"),
        Leaf("mlp_norm", (h,), "float32", "ones"),
        Leaf("gate", (h, w), "bfloat16", "normal"),
        Leaf("up", (h, w), "bfloat16", "normal"),
        Leaf("down", (w, h), "bfloat16", "normal"),
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

Cache = Tuple[jax.Array, ...]   # a layer: (slots + 1,) + cfg.state_shape, float32


def init_cache(cfg: LMConfig, slots: int, capacity: int) -> Cache:
    """`capacity` sizes nothing: a slot is one state, whatever the
    tokens behind it (the facade admits contexts up to it). One state
    MORE than `slots`, the last, always zero: what a row with no context
    and a context's first registration chunk start from, read like any
    other slot (no mask over a state, no second program)."""
    del capacity
    return tuple(jnp.zeros((slots + 1,) + cfg.state_shape, F32)
                 for _ in range(cfg.layers))


def slot_states(layer: jax.Array, slot: jax.Array, used: jax.Array
                ) -> List[jax.Array]:
    """One state_shape array a row: the row's slot where it lies, one
    contiguous slice a row (a gather over the slot index pays by the
    cache row on this chip, PERF.md PR 31); the zero state for a row
    that reads none (`used` false)."""
    at = jnp.where(used, slot, layer.shape[0] - 1)
    return [jax.lax.dynamic_slice(
        layer, (at[r], 0, 0, 0), (1,) + layer.shape[1:])[0]
        for r in range(slot.shape[0])]


# ---------------------------------------------------------------- the layers

def retention_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                    positions: jax.Array, token_real: jax.Array,
                    state_in: Optional[jax.Array], chunk: int,
                    want_state: bool):
    """u (rows, l, hidden) bfloat16 -> (the block's output bfloat16, the
    state behind each row's last real token or None)."""
    rows, length, _ = u.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope("retention_proj"):
        def heads(w, norm, n):
            return project_heads(u, w, norm, n, d, positions,
                                 cfg.rope_theta, None, cfg.norm_eps)
        q = heads(p["wq"], p["q_norm"], hq)
        # a padded position takes no part in the state: phi(0) = 0 and
        # a gate of one
        k = jnp.where(token_real[..., None, None],
                      heads(p["wk"], p["k_norm"], hkv), 0.0)
        v = _matmul(u, p["wv"]).reshape(rows, length, hkv, d)
        log_g = jnp.where(token_real[..., None], jax.nn.log_sigmoid(
            _matmul(u, p["wg"], F32) + p["bg"]), 0.0)
    y, state = power_retention.retain(q, k, v, log_g, state_in, chunk,
                                      want_state)
    with jax.named_scope("retention_proj"):
        return _matmul(y.reshape(rows, length, hq * d), p["wo"]), state


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  states: Optional[Sequence[jax.Array]], ids: jax.Array,
                  lengths: jax.Array, start: jax.Array, chunk: int,
                  want_state: bool):
    """ids (rows, l) int32 padded on the right, lengths (rows,) real
    tokens; row r starts from `states[layer][r]` (None: zeros) and
    stands at positions `start[r] + 0..l`. -> (hidden states (rows, l,
    hidden) bfloat16, the state a layer behind each row's last real
    token (`want_state`), StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    positions = (start[:, None] + jnp.arange(length)[None, :])[None]
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    left = []
    for i in range(cfg.layers):
        p = layer_params(params, i)
        u = rms_norm(h, p["attn_norm"], cfg.norm_eps).astype(jnp.bfloat16)
        mixed, state = retention_block(
            cfg, p, u, positions, token_real,
            None if states is None else states[i], chunk, want_state)
        left.append(state)
        h = h + mixed
        with jax.named_scope("dense_mlp"):
            h = h + moe.gated_mlp(
                rms_norm(h, p["mlp_norm"], cfg.norm_eps), p["gate"],
                p["up"], p["down"], jnp.bfloat16)
    # a dense model: no expert layer, so nothing of a router to report
    stats = StepStats(
        load=jnp.zeros((0, 0), jnp.int32),
        unserved_tokens=jnp.zeros((0,), jnp.int32),
        real_tokens=jnp.sum(token_real).astype(jnp.int32),
        chosen_last=jnp.zeros((rows, 0, 0), jnp.int32))
    return h, left, stats


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array,
                  cache: Optional[Sequence[jax.Array]] = None,
                  slot: Optional[jax.Array] = None,
                  held: Optional[jax.Array] = None) -> ScoreOutputs:
    """One batch of question rows: the forward pass, then the blockwise
    float32 head at each row's last real position. With a cache, row r
    continues the context of `held[r]` tokens whose state slot `slot[r]`
    holds (`held[r] == 0`: no context, zeros); the cache is read, not
    written. A row is ONE chunk of the retention."""
    rows, length = ids.shape
    states = None
    if cache is not None:
        states = [slot_states(layer, slot, held > 0) for layer in cache]
    else:
        held = jnp.zeros((rows,), jnp.int32)
    h, _, stats = hidden_states(cfg, params, states, ids, lengths, held,
                                length, want_state=False)
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
    the `start` tokens whose state slot `slot` holds (`start == 0`: from
    zeros, whatever the slot held): the state behind the chunk takes the
    slot's place in every layer. Returns the cache (donate it: the
    update is in place)."""
    states = [slot_states(layer, slot[None], (start > 0)[None])
              for layer in cache]
    _, left, _ = hidden_states(
        cfg, params, states, ids[None, :], length[None], start[None],
        cfg.retention_chunk, want_state=True)
    with jax.named_scope("cache_write"):
        return tuple(jax.lax.dynamic_update_slice(
            layer, state, (slot, 0, 0, 0))
            for layer, state in zip(cache, left))
