"""What every token model of `models/` is made of, whatever its layers:
the description of a parameter leaf, the flat `layers.<nn>.<leaf>`
naming, RMSNorm, the bfloat16 matmul with float32 accumulation, and the
two tuples a scoring step answers with. The facade (`lm_facade.py`)
reads a step's answer through these and nothing model-specific.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    name: str
    shape: Tuple[int, ...]
    dtype: str          # "bfloat16" for matrices, "float32" for the rest
    init: str           # normal | ones | zeros | a_log | dt_bias | bias |
    #                     gate_bias


def layer_prefix(index: int) -> str:
    return f"layers.{index:02d}."


def layer_params(params: Dict[str, jax.Array], index: int
                 ) -> Dict[str, jax.Array]:
    prefix = layer_prefix(index)
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def count_leaves(specs: List[Leaf]) -> int:
    n = 0
    for leaf in specs:
        size = 1
        for s in leaf.shape:
            size *= s
        n += size
    return n


def init_leaf(cfg, leaf: Leaf, key) -> jax.Array:
    """The program's own initializer of one leaf: normal(0, 0.02) for
    projections and embeddings, `A` in [1, 16], `dt` log-uniform in
    [time_step_min, time_step_max] (floor time_step_floor) through the
    inverse softplus, ones for norms and `D`, a small non-zero
    correction bias, a retention gate's bias by the memory it gives."""
    dtype = jnp.dtype(leaf.dtype)
    if leaf.init == "normal":
        return (0.02 * jax.random.normal(key, leaf.shape, jnp.float32)
                ).astype(dtype)
    if leaf.init == "conv":
        bound = 1.0 / (cfg.conv_kernel ** 0.5)
        return jax.random.uniform(key, leaf.shape, jnp.float32,
                                  -bound, bound)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "a_log":
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32,
                                          1.0, 16.0))
    if leaf.init == "dt_bias":
        lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, leaf.shape, jnp.float32,
                                        lo, hi))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    if leaf.init == "bias":
        return 0.01 * jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.init == "gate_bias":
        return gate_bias(leaf.shape[0], *cfg.gate_memory_tokens)
    raise ValueError(f"unknown initializer {leaf.init!r}")


def gate_bias(heads: int, low: float, high: float) -> jax.Array:
    """The bias of a retention gate a key/value head: `sigmoid(b) = 1 -
    1 / m` for memories `m` of `low` to `high` tokens, log-spaced over
    the heads. At zero a gate would be one half and the state forget in
    twenty tokens, every context answering alike; trained retention
    heads remember thousands."""
    memory = jnp.exp(jnp.linspace(jnp.log(low), jnp.log(high), heads))
    return jnp.log(memory - 1.0).astype(jnp.float32)


def init_leaves(cfg, specs: List[Leaf], seed: int) -> Dict[str, jax.Array]:
    """Leaf by leaf on the device, so that nothing larger than the
    largest leaf exists beside the parameters. A leaf's name is no part
    of its initializer, so leaves of one shape share one compiled
    program."""
    root = jax.random.PRNGKey(seed)
    make = jax.jit(init_leaf, static_argnums=(0, 1))
    return {leaf.name: make(cfg, leaf._replace(name=""),
                            jax.random.fold_in(root, i))
            for i, leaf in enumerate(specs)}


def abstract_leaves(specs: List[Leaf]) -> Dict[str, jax.ShapeDtypeStruct]:
    return {leaf.name: jax.ShapeDtypeStruct(leaf.shape,
                                            jnp.dtype(leaf.dtype))
            for leaf in specs}


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Float32 in and out; the caller casts."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(jnp.float32))


def _matmul(x: jax.Array, w: jax.Array, out_dtype=jnp.bfloat16) -> jax.Array:
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


class StepStats(NamedTuple):
    """What the router did in one step, per expert layer; and, for a
    model whose attention selects its keys, what it selected."""
    load: jax.Array             # (expert layers, held) int32
    unserved_tokens: jax.Array  # (expert layers,) int32
    real_tokens: jax.Array      # () int32
    chosen_last: jax.Array      # (rows, expert layers, k) int32: each
    #                             row's choice at its last real position
    selected_last: Optional[jax.Array] = None   # (rows, layers, words)
    #   uint32: the keys each row's last real query attended, one bit a
    #   key position, bit p % 32 of word p // 32 (ops/sparse_attn.py
    #   `pack_bits`); positions count the slot's tokens, then the row's
    selected_keys: Optional[jax.Array] = None   # (layers,) int32: keys
    #   selected, summed over the real queries of the step


class ScoreOutputs(NamedTuple):
    topk_values: jax.Array      # (rows, k) float32 logits
    topk_indices: jax.Array     # (rows, k) int32, ids over the rows held
    lse: jax.Array              # (rows,) float32 logsumexp over the slice
    stats: StepStats
