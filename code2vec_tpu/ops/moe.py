"""A routed expert layer that is told which experts it holds.

The router scores every token against ALL experts of the model, in one
of the two published forms: sigmoid scores, a correction bias that only
steers the choice, the `k` largest chosen, weights normalised over the
chosen and scaled; or a softmax over all experts, its `k` largest,
renormalised over the chosen. This chip holds experts `[first, first +
held)`: it computes, for every token, the sum over the chosen experts IT
HOLDS and leaves out what the absent ones would add. With expert
parallelism that partial sum is what each chip brings to the combine; on
one chip the layer runs without its exchange, and nothing stands in for
the absent chips.

Dispatch drops no token: every (token, chosen expert) pair whose expert
is held becomes one row of a buffer sorted by expert; a grouped matmul
multiplies each group by its expert's weights and visits only the tiles
that hold rows. On the TPU that is the megablox kernel of
`jax.experimental.pallas.ops.tpu` with row tiles of 256: a group takes
at least one tile whatever its size, and at a few dozen rows an expert
`lax.ragged_dot`'s own kernel (row tiles of 512) took three times as
long on the chip (PERF.md, PR 26); elsewhere it is `lax.ragged_dot`.
The buffer is `tokens * k` rows, the most the router can send here, so
its shape is static; rows past the last group cost no matmul time.

An expert is either the two-matrix `W2 relu(W1 x)^2` or, given a gate
matrix, the gated `W2 (silu(Wg x) * W1 x)`: a third grouped matmul over
the same sorted rows. `gated_mlp` is the same gated unit as one plain
MLP (a shared expert, a dense layer).

`experts_loop` is the plain form, one expert after the other with a
dense mask, float32: what the grouped form is tested against.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Routed(NamedTuple):
    experts: jax.Array      # (T, k) int32, ids over ALL experts
    weights: jax.Array      # (T, k) float32


class ExpertStats(NamedTuple):
    load: jax.Array             # (held,) int32: tokens sent to each expert
    unserved_tokens: jax.Array  # () int32: real tokens with no held expert
    real_tokens: jax.Array      # () int32


@jax.named_scope("moe_route")
def route(u: jax.Array,             # (T, hidden)
          router: jax.Array,        # (hidden, experts)
          bias: Optional[jax.Array],    # (experts,) float32, or None
          k: int, scaling: float = 1.0, softmax: bool = False) -> Routed:
    """The router in float32, the matmul too. Sigmoid form: s =
    sigmoid(u W_r); the k largest of s + bias (the correction bias
    steers the choice only); w_i = scaling * s_i / sum over the chosen.
    Softmax form: s = softmax(u W_r) over ALL experts, the k largest of
    it (ties to the lower expert), the same renormalisation."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.softmax(logits, axis=-1) if softmax
         else jax.nn.sigmoid(logits))
    steer = s if bias is None else s + bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(steer, k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return Routed(chosen.astype(jnp.int32), weights)


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _tile(dim: int, cap: int = 1024) -> int:
    """The largest multiple of 128 that divides `dim` and is at most
    `cap`; 0 where there is none."""
    return max((t for t in range(128, min(dim, cap) + 1, 128)
                if dim % t == 0), default=0)


def grouped_matmul(x: jax.Array,        # (rows, k), sorted by group
                   w: jax.Array,        # (groups, k, n)
                   sizes: jax.Array,    # (groups,) int32 rows a group
                   out_dtype) -> jax.Array:
    """x[group g's rows] @ w[g] for every group; rows past the last group
    hold nothing meaningful."""
    rows, k = x.shape
    tiles = (256 if rows % 256 == 0 else 128, _tile(k), _tile(w.shape[2]))
    if jax.default_backend() == "tpu" and rows % 128 == 0 and all(tiles):
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(x, w, sizes, preferred_element_type=out_dtype,
                   tiling=tiles)
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=out_dtype)


@jax.named_scope("moe_experts")
def experts_grouped(latent: jax.Array,      # (T, latent_dim)
                    routed: Routed,
                    w1: jax.Array,          # (held, latent_dim, width)
                    w2: jax.Array,          # (held, width, latent_dim)
                    first: int,
                    token_real: jax.Array,  # (T,) bool: not padding
                    w_gate: Optional[jax.Array] = None,  # as w1: gated
                    ) -> Tuple[jax.Array, ExpertStats]:
    """(T, latent_dim) float32: sum_i w_i W2_i relu(W1_i l)^2 (with
    `w_gate`: sum_i w_i W2_i (silu(Wg_i l) * W1_i l)) over the chosen
    experts held here; and the router's load on them."""
    tokens, k = routed.experts.shape
    held = w1.shape[0]
    local = routed.experts - first
    mine = (local >= 0) & (local < held) & token_real[:, None]
    # sort the (token, expert) pairs by held expert; the rest go last.
    # Sorts and row gathers only: a scatter runs one update at a time
    key = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    back = jnp.argsort(order)                   # the inverse permutation
    edges = jnp.searchsorted(key[order], jnp.arange(held + 1), side="left")
    load = (edges[1:] - edges[:-1]).astype(jnp.int32)
    x = jnp.take(latent, order // k, axis=0)            # (T * k, latent)
    if w_gate is None:
        hidden = grouped_matmul(x, w1, load, latent.dtype)
        hidden = relu2(hidden.astype(jnp.float32)).astype(latent.dtype)
    else:
        gate = grouped_matmul(x, w_gate, load, jnp.float32)
        hidden = (jax.nn.silu(gate) * grouped_matmul(
            x, w1, load, jnp.float32)).astype(latent.dtype)
    y = grouped_matmul(hidden, w2, load, jnp.float32)
    # rows past the last group are whatever the kernel left there: a
    # zero weight does not undo a NaN, so they are masked outright
    in_group = (jnp.arange(tokens * k) < edges[-1])[:, None]
    weight = jnp.where(mine, routed.weights, 0.0).reshape(-1)[order]
    y = jnp.where(in_group, y * weight[:, None], 0.0)
    out = jnp.take(y, back, axis=0).reshape(tokens, k, -1).sum(axis=1)
    served = jnp.any(mine, axis=-1)
    stats = ExpertStats(
        load=load,
        unserved_tokens=jnp.sum(token_real & ~served).astype(jnp.int32),
        real_tokens=jnp.sum(token_real).astype(jnp.int32))
    return out, stats


def gated_mlp(x: jax.Array, gate: jax.Array, up: jax.Array,
              down: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """down(silu(gate x) * up x): operands in the weights' type,
    accumulation and the gate's product float32."""
    x = x.astype(gate.dtype)
    f32 = jnp.float32
    hidden = (jax.nn.silu(jnp.dot(x, gate, preferred_element_type=f32))
              * jnp.dot(x, up, preferred_element_type=f32))
    return jnp.dot(hidden.astype(down.dtype), down,
                   preferred_element_type=f32).astype(out_dtype)


def experts_loop(latent, routed: Routed, w1, w2, first: int,
                 w_gate=None) -> jax.Array:
    """One held expert after the other over every token, masked by the
    router's choice; float32, "highest"."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    latent = latent.astype(f32)
    out = jnp.zeros(latent.shape, f32)
    for e in range(w1.shape[0]):
        w = jnp.sum(jnp.where(routed.experts == first + e,
                              routed.weights, 0.0), axis=-1)   # (T,)
        h = jnp.dot(latent, w1[e].astype(f32), precision=hi)
        h = (relu2(h) if w_gate is None else h * jax.nn.silu(
            jnp.dot(latent, w_gate[e].astype(f32), precision=hi)))
        out = out + w[:, None] * jnp.dot(h, w2[e].astype(f32), precision=hi)
    return out
