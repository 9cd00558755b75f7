"""A grouped-query / sigmoid-routed-expert language model whose layers
mix WINDOW and FULL attention (the `afmoe` family's layout), served for
scoring against contexts that keep a RING in its window layers and
PAGES in its full layers.

`h0 = E[ids] * sqrt(hidden_size)` (`mup_enabled`). Layer `l` has an
attention kind `a_l = layer_types[l]` and an MLP kind `m_l = dense if l
< num_dense_layers else experts`; every `rms` has a weight and eps
`rms_norm_eps`:

    u  = rms(h; w_in)
    q  = rms_d(W_q u; w_qn)  (heads x head_dim),  k = rms_d(W_k u; w_kn),
    v  = W_v u  (key/value heads x head_dim), no biases
    window layer:  q, k = rot(q), rot(k)   rotary over all head_dim
                   dimensions, half-split pairs, `rope_theta`, by position;
                   query i sees keys j with  i - sliding_window < j <= i
    full layer:    NO rotary;  query i sees every j <= i
    y  = softmax_j(q_i . k_j / sqrt(head_dim)) v_j    (query head n reads
         key/value head n // (heads / key/value heads)), float32 softmax
    y  = y * sigmoid(W_g u)                 one gate a head and dimension
    h' = h + rms(W_o y; w_post_attn)
    r  = rms(h'; w_pre_mlp)
    dense:    z = W_down(silu(W_gate r) * W_up r)
    experts:  s = sigmoid(W_r r) float32 over all experts;  S = top-k of
              (s + b);  g_e = route_scale * s_e / sum_S s
              z = shared(r) + sum_{e in S} g_e * expert_e(r)     gated
    h'' = h' + rms(z; w_post_mlp)

then `rms(h; w_final)` and an untied head. DEPARTURE from the published
implementation: its `g_e` divides by `sum_S s + 1e-20`; beside a sum of
`k` sigmoids the 1e-20 is below float32's last place, so the shared
router (`ops/moe.py route`) is used as it is.

A token leaves ONE kind of state a layer, `[keys | values]` of every
key/value head (the keys normalised and, in a window layer, rotated),
but a layer keeps it in one of two GEOMETRIES (`ops/window_attn.py`): a
window layer in column `p mod W` of its context's ring slot (`W =
sliding_window` tokens a context whatever its length), a full layer in
the context's pages of `W` tokens each (both full layers use the same
page ids); both arrays token-minor, `(slots or pages, cache_width, W)`.
`CACHE_KIND = "paged"` tells the facade to give a step the rows' page
lists beside their ring slots. REGISTRATION runs one chunk of `W` tokens
behind what the context holds and then writes the chunk's state: its own
columns of the ring (AFTER every window layer has read what the ring
held) and one page a full layer (`ctx_register_step`, the cache donated);
columns of the chunk past its real tokens keep what they held.
SCORING runs question rows, each against its own ring slot, its own
pages and itself, and writes nothing.

The share held here is `layers` of `num_hidden_layers` (the leading
ones: a pipeline stage), experts `[expert_first, expert_first +
experts_held)` and vocabulary rows `[0, vocab_rows)`. NOT here:
generation, training.

Precision: parameters, matmul operands, activations and both caches
bfloat16, accumulation float32; router, attention softmax, the output
gate's sigmoid, norms, rotary angles and logits float32.
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
from code2vec_tpu.ops import moe, window_attn
from code2vec_tpu.ops.sparse_attn import rotate
from code2vec_tpu.ops.topk import blockwise_matmul_top_k

F32 = jnp.float32
CACHE_KIND = "paged"
ATTENTION_KINDS = {"sliding_attention": "w", "full_attention": "f"}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The widths as the published `config.json` names them, and the
    share held here."""
    hidden_size: int
    num_hidden_layers: int
    layers: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    num_dense_layers: int
    vocab_size: int
    vocab_rows: int
    max_position_embeddings: int
    # grouped-query attention
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    # MLPs
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    experts_held: int
    expert_first: int
    num_experts_per_tok: int
    num_shared_experts: int
    route_scale: float
    mup_enabled: bool
    norm_eps: float

    def __post_init__(self):
        if not 0 < self.layers <= self.num_hidden_layers:
            raise ValueError("layers must lie in (0, num_hidden_layers]")
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in ATTENTION_KINDS for t in self.layer_types):
            raise ValueError(
                f"layer_types must name one of "
                f"{', '.join(ATTENTION_KINDS)} for each of the "
                f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.num_experts):
            raise ValueError("the experts held lie outside the router's "
                             "width")
        if not 0 < self.vocab_rows <= self.vocab_size:
            raise ValueError("vocab_rows must lie in (0, vocab_size]")
        if self.head_dim % 2 or self.sliding_window < 1:
            raise ValueError("head_dim must be even and sliding_window "
                             "positive")

    @classmethod
    def from_dict(cls, raw: Dict, where: str = "the configuration"
                  ) -> "LMConfig":
        """A model-configuration object with the published keys; `layers`
        (the leading layers held here), `experts_held`, `expert_first`
        and `vocab_rows` state the share and default to the whole
        model. What the module does not run is refused."""
        raw = dict(raw)
        raw.setdefault("layers", raw.get("num_hidden_layers"))
        raw.setdefault("experts_held", raw.get("num_experts"))
        raw.setdefault("expert_first", 0)
        raw.setdefault("vocab_rows", raw.get("vocab_size"))
        raw.setdefault("norm_eps", raw.get("rms_norm_eps", 1e-5))
        raw.setdefault("mup_enabled", False)
        refused = {
            "group-limited routing (n_group, topk_group other than 1)":
                (raw.get("n_group", 1), raw.get("topk_group", 1)) != (1, 1),
            "rope_scaling": raw.get("rope_scaling") is not None,
            f"score_func {raw.get('score_func')!r} (only sigmoid)":
                raw.get("score_func", "sigmoid") != "sigmoid",
            "route_norm false": not raw.get("route_norm", True),
            "tie_word_embeddings": bool(raw.get("tie_word_embeddings")),
        }
        for what, found in refused.items():
            if found:
                raise ValueError(f"{where}: {what} is not supported")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if raw.get(n) is None]
        if missing:
            raise ValueError(f"{where}: no {', '.join(missing)}")
        raw["layer_types"] = tuple(raw["layer_types"])
        return cls(**{n: raw[n] for n in names})

    @classmethod
    def from_file(cls, path: str) -> "LMConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f), path)

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(attention, MLP) of each layer held: `w` window or `f` full;
        `D` a dense MLP or `E` experts."""
        return tuple((ATTENTION_KINDS[self.layer_types[i]],
                      "D" if i < self.num_dense_layers else "E")
                     for i in range(self.layers))

    @property
    def pattern(self) -> str:
        return " ".join(a + m for a, m in self.kinds)

    @property
    def window_layers(self) -> int:
        return sum(a == "w" for a, _ in self.kinds)

    @property
    def full_layers(self) -> int:
        return sum(a == "f" for a, _ in self.kinds)

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def cache_width(self) -> int:
        """Values a token and layer leaves in the cache."""
        return 2 * self.num_key_value_heads * self.head_dim


def layer_leaf_specs(cfg: LMConfig, mlp: str) -> List[Leaf]:
    """One layer's leaves, names without the `layers.<nn>.` prefix (the
    attention's are the same in both kinds of layer)."""
    h, d = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    out = [
        Leaf("attn_norm", (h,), "float32", "ones"),
        Leaf("wq", (h, q), "bfloat16", "normal"),
        Leaf("wk", (h, kv), "bfloat16", "normal"),
        Leaf("wv", (h, kv), "bfloat16", "normal"),
        Leaf("q_norm", (d,), "float32", "ones"),
        Leaf("k_norm", (d,), "float32", "ones"),
        Leaf("w_attn_gate", (h, q), "bfloat16", "normal"),
        Leaf("wo", (q, h), "bfloat16", "normal"),
        Leaf("post_attn_norm", (h,), "float32", "ones"),
        Leaf("mlp_norm", (h,), "float32", "ones"),
        Leaf("post_mlp_norm", (h,), "float32", "ones"),
    ]
    if mlp == "D":
        w = cfg.intermediate_size
        return out + [Leaf("gate", (h, w), "bfloat16", "normal"),
                      Leaf("up", (h, w), "bfloat16", "normal"),
                      Leaf("down", (w, h), "bfloat16", "normal")]
    w, held = cfg.moe_intermediate_size, cfg.experts_held
    sw = cfg.num_shared_experts * w
    return out + [
        Leaf("router", (h, cfg.num_experts), "bfloat16", "normal"),
        Leaf("router_bias", (cfg.num_experts,), "float32", "bias"),
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
    for i, (_, mlp) in enumerate(cfg.kinds):
        out += [leaf._replace(name=layer_prefix(i) + leaf.name)
                for leaf in layer_leaf_specs(cfg, mlp)]
    out += [Leaf("final_norm", (h,), "float32", "ones"),
            Leaf("head", (cfg.vocab_rows, h), "bfloat16", "normal")]
    return out


# ----------------------------------------------------------------- the cache

# a layer's entry: a window layer's rings (ring slots, cache_width, W), a
# full layer's pool (pages, cache_width, W): a token is a COLUMN
# (ops/window_attn.py says why)
Cache = Tuple[jax.Array, ...]


def init_cache(cfg: LMConfig, ring_slots: int, pages: int,
               page_tokens: int) -> Cache:
    if page_tokens != cfg.sliding_window:
        raise ValueError(
            f"a page and a registration chunk are the window "
            f"({cfg.sliding_window} tokens), not {page_tokens}: a chunk "
            f"then writes one page a full layer and its own rows of the "
            f"ring")
    return tuple(jnp.zeros(
        (ring_slots if attention == "w" else pages, cfg.cache_width,
         cfg.sliding_window), jnp.bfloat16) for attention, _ in cfg.kinds)


# ---------------------------------------------------------------- the layers

def attention_block(cfg: LMConfig, p: Dict[str, jax.Array], kind: str,
                    u: jax.Array, positions: jax.Array, cached: jax.Array,
                    slot: jax.Array, pages: jax.Array,
                    cached_len: jax.Array, lengths: jax.Array):
    """u (rows, l, hidden) bfloat16 -> (the block's output before its
    post-norm, the tokens' `[keys | values]` (rows, l, cache_width)),
    both bfloat16."""
    rows, length, _ = u.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    bf16 = jnp.bfloat16
    with jax.named_scope("gqa_proj"):
        def heads(w, norm, n):
            x = rms_norm(_matmul(u, w, F32).reshape(rows, length, n, d),
                         norm, cfg.norm_eps)
            if kind == "w":
                x = rotate(x, positions[None], cfg.rope_theta)
            return x.astype(bf16)
        q = heads(p["wq"], p["q_norm"], hq)
        k = heads(p["wk"], p["k_norm"], hkv)
        v = _matmul(u, p["wv"]).reshape(rows, length, hkv, d)
    if kind == "w":
        y = window_attn.window_attend(q, k, v, cached, slot, cached_len,
                                      lengths)
    else:
        y = window_attn.full_attend(q, k, v, cached, pages, cached_len,
                                    lengths)
    with jax.named_scope("attn_gate"):
        y = (y.astype(F32) * jax.nn.sigmoid(
            _matmul(u, p["w_attn_gate"], F32))).astype(bf16)
    with jax.named_scope("gqa_proj"):
        left = jnp.concatenate([k.reshape(rows, length, -1),
                                v.reshape(rows, length, -1)], axis=-1)
        return _matmul(y, p["wo"]), left


def expert_block(cfg: LMConfig, p: Dict[str, jax.Array], u: jax.Array,
                 token_real: jax.Array):
    """u (rows, l, hidden) float32 -> ((rows, l, hidden) float32, stats,
    the router's choice (rows, l, k)). The router reads the float32
    input; the matmuls take it as bfloat16."""
    rows, length, hidden = u.shape
    flat32 = u.reshape(rows * length, hidden)
    routed = moe.route(flat32, p["router"], p["router_bias"],
                       cfg.num_experts_per_tok, cfg.route_scale)
    flat = flat32.astype(jnp.bfloat16)
    out, stats = moe.experts_grouped(
        flat, routed, p["w_up"], p["w_down"], cfg.expert_first,
        token_real.reshape(-1), w_gate=p["w_gate"])
    with jax.named_scope("moe_shared"):
        out = out + moe.gated_mlp(flat, p["shared_gate"], p["shared_up"],
                                  p["shared_down"])
    return (out.reshape(rows, length, hidden), stats,
            routed.experts.reshape(rows, length, -1))


def hidden_states(cfg: LMConfig, params: Dict[str, jax.Array],
                  cache: Sequence[jax.Array], ids: jax.Array,
                  lengths: jax.Array, slot: jax.Array, pages: jax.Array,
                  cached_len: jax.Array):
    """ids (rows, l) int32 padded on the right, lengths (rows,) real
    tokens; row r reads `cached_len[r]` tokens of ring slot `slot[r]`
    and of the pages `pages[r]`, and stands at positions `cached_len[r]
    + 0..l`. -> (hidden states (rows, l, hidden) bfloat16, the tokens'
    `[keys | values]` a layer, StepStats)."""
    rows, length = ids.shape
    token_real = jnp.arange(length)[None, :] < lengths[:, None]
    positions = cached_len[:, None] + jnp.arange(length)[None, :]
    last = jnp.maximum(lengths - 1, 0)
    bf16 = jnp.bfloat16
    h = jnp.take(params["embed"], ids, axis=0)          # bfloat16
    if cfg.mup_enabled:
        h = (h.astype(F32) * (cfg.hidden_size ** 0.5)).astype(bf16)
    left, loads, unserved, chosen = [], [], [], []
    for i, (attention, mlp) in enumerate(cfg.kinds):
        p = layer_params(params, i)
        u = rms_norm(h, p["attn_norm"], cfg.norm_eps).astype(bf16)
        mixed, state = attention_block(cfg, p, attention, u, positions,
                                       cache[i], slot, pages, cached_len,
                                       lengths)
        left.append(state)
        h = h + rms_norm(mixed, p["post_attn_norm"],
                         cfg.norm_eps).astype(bf16)
        u = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        if mlp == "D":
            with jax.named_scope("dense_mlp"):
                mixed = moe.gated_mlp(u, p["gate"], p["up"], p["down"])
        else:
            mixed, stats, choice = expert_block(cfg, p, u, token_real)
            loads.append(stats.load)
            unserved.append(stats.unserved_tokens)
            chosen.append(jnp.take_along_axis(
                choice, last[:, None, None], axis=1)[:, 0])
        h = h + rms_norm(mixed, p["post_mlp_norm"],
                         cfg.norm_eps).astype(bf16)
    k = cfg.num_experts_per_tok
    stats = StepStats(
        load=(jnp.stack(loads) if loads
              else jnp.zeros((0, cfg.experts_held), jnp.int32)),
        unserved_tokens=(jnp.stack(unserved) if unserved
                         else jnp.zeros((0,), jnp.int32)),
        real_tokens=jnp.sum(token_real).astype(jnp.int32),
        chosen_last=(jnp.stack(chosen, axis=1) if chosen
                     else jnp.zeros((rows, 0, k), jnp.int32)))
    return h, left, stats


def lm_score_step(cfg: LMConfig, top_k: int, block_rows: int,
                  params: Dict[str, jax.Array], ids: jax.Array,
                  lengths: jax.Array, cache: Sequence[jax.Array],
                  slot: jax.Array, cached_len: jax.Array,
                  pages: jax.Array) -> ScoreOutputs:
    """One batch of question rows, each after the `cached_len` tokens of
    its ring slot and its pages `(rows, most pages a context)`: the
    forward pass, then the blockwise float32 head at each row's last
    real position. The cache is read, not written."""
    h, _, stats = hidden_states(cfg, params, cache, ids, lengths, slot,
                                pages, cached_len)
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
                      start: jax.Array, pages: jax.Array) -> Cache:
    """One chunk `ids` (W,) of a context, `length` of them real, behind
    the `start` tokens (a multiple of W) that ring slot `slot` and the
    first `start / W` of `pages` (most pages a context,) hold. The
    chunk's real tokens land in columns `0..length` of the ring slot of
    every window layer, every one of which has read the ring by then,
    and in page `pages[start / W]` of every full layer; columns past
    `length` keep what they held (the ring's are still inside the
    window). Returns the cache (donate it: the update is in place)."""
    _, left, _ = hidden_states(
        cfg, params, cache, ids[None, :], length[None], slot[None],
        pages[None, :], start[None])
    with jax.named_scope("cache_write"):
        real = (jnp.arange(ids.shape[0]) < length)[None, None, :]
        page = jnp.take(pages, start // cfg.sliding_window)
        out = []
        for (attention, _), held, new in zip(cfg.kinds, cache, left):
            new = jnp.swapaxes(new, 1, 2)       # a token a column
            at = (slot if attention == "w" else page, 0, 0)
            old = jax.lax.dynamic_slice(held, at, new.shape)
            out.append(jax.lax.dynamic_update_slice(
                held, jnp.where(real, new, old), at))
        return tuple(out)
