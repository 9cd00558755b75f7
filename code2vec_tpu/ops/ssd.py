"""The selective state-space recurrence of a Mamba-2 layer, computed in
chunks (the "state-space duality" form; Dao & Gu, "Transformers are
SSMs", 2024, section 6).

For head `n` (of group `n // heads_per_group`), state `S` in
R^(head_dim x state):

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

`ssd_chunked` cuts the sequence into chunks of `chunk` positions. Inside
a chunk the recurrence unrolls into a masked matrix product
(`(C B^T) * decay) @ (dt x)`); between chunks only the state at each
chunk's end is passed on, by a short `lax.scan` over the chunks. Decays,
their cumulative sums and the states are float32; the matrix products
take `operand_dtype` operands (bfloat16 on the MXU, as the published
kernels do: they round the state to the operand type for the product
and keep it in float32 between chunks) and accumulate in float32.

`ssd_recurrence` is the same mathematics as the plain recurrence over
`t`, one position a step, everything float32: what the chunked form is
tested against.

Right padding is safe: the recurrence is causal, so positions after a
row's last real one change nothing before it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _segsum(a: jax.Array) -> jax.Array:
    """(..., l) -> (..., l, l): entry (i, j) is sum(a[j+1..i]) for
    j <= i, -inf above the diagonal (so that exp() is the decay from
    position j to position i, and 0 where j lies in the future)."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    keep = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(keep, diff, -jnp.inf)


@jax.named_scope("ssd_scan")
def ssd_chunked(x: jax.Array,       # (b, l, h, p)
                dt: jax.Array,      # (b, l, h) float32, after softplus
                a: jax.Array,       # (h,) float32, negative
                b_in: jax.Array,    # (b, l, g, n)
                c_in: jax.Array,    # (b, l, g, n)
                d: jax.Array,       # (h,) float32
                chunk: int = 128,
                operand_dtype=jnp.bfloat16) -> jax.Array:
    """y (b, l, h, p) float32. A length that is no multiple of `chunk`
    is padded on the right (dt = 0 there: the state neither decays nor
    takes input) and the padding cut off again."""
    bsz, length, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    r = h // g                       # heads per group
    pad = (-length) % chunk
    if pad:
        x, dt, b_in, c_in = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (
            t.ndim - 2)) for t in (x, dt, b_in, c_in))
    nc = (length + pad) // chunk
    f32 = jnp.float32
    od = operand_dtype
    dt = dt.astype(f32)
    xs = x.reshape(bsz, nc, chunk, g, r, p)
    dts = dt.reshape(bsz, nc, chunk, g, r)
    bs = b_in.reshape(bsz, nc, chunk, g, n).astype(od)
    cs = c_in.reshape(bsz, nc, chunk, g, n).astype(od)
    da = dts * a.reshape(g, r).astype(f32)              # (b, c, l, g, r)
    da = jnp.moveaxis(da, 2, -1)                        # (b, c, g, r, l)
    cum = jnp.cumsum(da, axis=-1)                       # (b, c, g, r, l)
    xdt = (xs.astype(f32) * dts[..., None]).astype(od)  # (b, c, l, g, r, p)

    # inside a chunk: (C B^T * decay) @ (dt x)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cs, bs,
                    preferred_element_type=f32)         # (b, c, g, l, s)
    decay = jnp.exp(_segsum(da))                        # (b, c, g, r, l, s)
    scores = (cb[:, :, :, None] * decay).astype(od)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, xdt,
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)               # (b, c, g, r, l)
    xdt_end = (xs.astype(f32) * (dts * jnp.moveaxis(to_end, -1, 2))[..., None]
               ).astype(od)
    states = jnp.einsum("bclgn,bclgrp->bcgrpn", bs, xdt_end,
                        preferred_element_type=f32)     # (b, c, g, r, p, n)

    # between chunks: the state that ENTERS each chunk
    chunk_decay = jnp.exp(cum[..., -1])                 # (b, c, g, r)

    def carry_state(s, inputs):
        own, dec = inputs
        return s * dec[..., None, None] + own, s
    _, entering = jax.lax.scan(
        carry_state, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (b, c, g, r, p, n)

    # what the entering state adds at each position of the chunk
    from_start = jnp.exp(cum)                           # (b, c, g, r, l)
    y_state = jnp.einsum("bclgn,bcgrpn->bclgrp", cs, entering.astype(od),
                         preferred_element_type=f32)
    y = y + y_state * jnp.moveaxis(from_start, -1, 2)[..., None]
    y = y + xs.astype(f32) * d.reshape(g, r).astype(f32)[..., None]
    return y.reshape(bsz, nc * chunk, h, p)[:, :length]


def ssd_recurrence(x, dt, a, b_in, c_in, d) -> jax.Array:
    """The recurrence itself, one position a step, float32: the plain
    form `ssd_chunked` is held against."""
    f32 = jnp.float32
    bsz, length, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    r = h // g
    x, dt, b_in, c_in = (t.astype(f32) for t in (x, dt, b_in, c_in))
    a, d = a.astype(f32), d.astype(f32)

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs        # (b,h,p) (b,h) (b,g,n) (b,g,n)
        b_h = jnp.repeat(b_t, r, axis=1)    # (b, h, n)
        c_h = jnp.repeat(c_t, r, axis=1)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", s, c_h,
                         precision=jax.lax.Precision.HIGHEST)
        return s, y_t + d[None, :, None] * x_t
    _, ys = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), f32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b_in, c_in)))
    return jnp.moveaxis(ys, 0, 1)
