"""The gated DELTA RULE with a decay a key CHANNEL (the linear attention
of the `kda_*` family), and the short causal conv in front of it whose
first inputs are CARRIED from a cache.

A head keeps a state `S` (key channels x value channels, float32). A
token first decays what the state holds, channel by channel, then ERASES
what it holds along its key, then writes:

    Z   = diag(exp(g_t)) S_{t-1}                g_t < 0, one a key channel
    S_t = Z + b_t k_t (v_t - Z^T k_t)^T         b_t one a head, in (0, 2)
    o_t = S_t^T q_t

`delta_recurrence` is exactly that, a scan over `t`: what the chunked
form is tested against. `delta_chunked` computes the same in chunks of
`C` tokens (the builder's derivation from the recurrence; nothing of it
is approximated). With `G_t` the chunk's running sum of `g` (inclusive)
and `u_t = b_t (v_t - Z^T k_t)`, the written pseudo-values,

    S_t = diag(exp(G_t)) S_0 + sum_{i<=t} diag(exp(G_t - G_i)) k_i u_i^T

so that the `u` of a chunk solve one unit lower-triangular system

    (I + diag(b) strictly_lower(A_kk)) U = diag(b) (V - K~ S_0)
    A_xk[t, i] = sum_d x_t[d] k_i[d] exp(G_t[d] - G_i[d])    (i <= t)
    K~_t = k_t * exp(G_t)

and, with `[W | U_0] = (I + diag(b) L)^-1 diag(b) [K~ | V]`, three
products a chunk read the state that enters it:

    U   = U_0 - W S_0
    O   = Q~ S_0 + A_qk U                       Q~_t = q_t * exp(G_t)
    S_C = diag(exp(G_C)) S_0 + K^^T U           K^_i = k_i * exp(G_C - G_i)

No exponent above is ever positive, so nothing overflows whatever the
decay (none is clamped): `A_xk` is computed in SUB-BLOCKS of 16 tokens,
a diagonal block element by element (`exp(G_t - G_i)` only where `i <=
t`), a block below the diagonal as a product of `x_t exp(G_t - r)` and
`k_i exp(r - G_i)` with `r` the running sum at the END of the block
before `t`'s, which lies between the two.

A row's real `lengths` are respected: a padded position has `b = 0` and
`g = 0`: it writes nothing, decays nothing, and the state behind the row
is the state behind its last real token.

Everything here is float32 with every product at "highest": the state,
the decays, their sums and the solve have to be, and the chunk's other
products are small beside a layer's projections (30 GFLOP a step of
1,024 tokens over three layers at the published size).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB = 16        # tokens a sub-block of the chunk's (token, token) matrices


def conv_carried(x: jax.Array,              # (rows, l, channels)
                 w: jax.Array,              # (channels, K)
                 tail: Optional[jax.Array] = None,  # (rows, K - 1, channels)
                 bias: Optional[jax.Array] = None,  # (channels,)
                 lengths: Optional[jax.Array] = None,   # (rows,) int32
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise and causal, one K-tap filter a channel: `y[t] = sum_j
    w[:, j] x[t - (K - 1) + j] (+ bias)`, float32. The `K - 1` inputs
    before `x[0]` are `tail` (what the cache kept of the tokens before;
    None: zeros, a sequence's start). -> (y, the last `K - 1` inputs
    behind each row's `lengths` real ones, in x's type: the next call's
    `tail`; all of `x` is real where `lengths` is None)."""
    rows, length, _ = x.shape
    k = w.shape[1]
    if tail is None:
        xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([tail.astype(F32), x.astype(F32)], axis=1)
    y = None if bias is None else bias.astype(F32)
    for j in range(k):
        term = xp[:, j:j + length] * w[:, j].astype(F32)
        y = term if y is None else y + term
    if lengths is None:
        lengths = jnp.full((rows,), length, jnp.int32)
    at = lengths[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    kept = jnp.take_along_axis(xp, at[:, :, None], axis=1)
    return y, kept.astype(x.dtype)


def _real(b: jax.Array, g: jax.Array, lengths: Optional[jax.Array]):
    """`b` and `g` with padded positions at zero."""
    if lengths is None:
        return b.astype(F32), g.astype(F32)
    real = jnp.arange(b.shape[1])[None, :] < lengths[:, None]
    return (jnp.where(real[..., None], b.astype(F32), 0.0),
            jnp.where(real[..., None, None], g.astype(F32), 0.0))


def delta_recurrence(q: jax.Array,          # (rows, l, heads, dk)
                     k: jax.Array,          # (rows, l, heads, dk)
                     v: jax.Array,          # (rows, l, heads, dv)
                     g: jax.Array,          # (rows, l, heads, dk) float32
                     b: jax.Array,          # (rows, l, heads) float32
                     state_in: jax.Array,   # (rows, heads, dk, dv) float32
                     lengths: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """The module docstring's three lines, token by token. -> (o (rows,
    l, heads, dv) float32, the state behind each row's last real
    token)."""
    b, g = _real(b, g, lengths)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        z = jnp.exp(g_t)[..., None] * s
        read = jnp.einsum("rhkv,rhk->rhv", z, k_t, precision=HI)
        s = z + jnp.einsum("rhk,rhv->rhkv", k_t,
                           b_t[..., None] * (v_t - read), precision=HI)
        return s, jnp.einsum("rhkv,rhk->rhv", s, q_t, precision=HI)

    def by_time(x):
        return jnp.moveaxis(x.astype(F32), 1, 0)
    state, o = jax.lax.scan(token, state_in.astype(F32),
                            tuple(by_time(x) for x in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), state


def _pair_sums(x: jax.Array,    # (..., 2, C, dk): [q, k] of a chunk
               k: jax.Array,    # (..., C, dk)
               big_g: jax.Array,    # (..., C, dk) the chunk's running sums
               ) -> jax.Array:
    """(..., 2, C, C): `A_xk[t, i] = sum_d x_t[d] k_i[d] exp(G_t[d] -
    G_i[d])` for `i <= t`, zero above the diagonal; sub-block by
    sub-block (module docstring), no exponent positive."""
    chunk, dk = k.shape[-2:]
    sub = SUB if chunk % SUB == 0 else chunk
    m = chunk // sub
    lead = k.shape[:-2]
    xs = x.reshape(lead + (2, m, sub, dk))
    ks = k.reshape(lead + (m, sub, dk))
    gs = big_g.reshape(lead + (m, sub, dk))
    # the diagonal blocks, element by element
    below = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    fade = jnp.exp(jnp.where(
        below, gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf))
    # a product and a sum, not an einsum: the compiler then computes the
    # (token, token, channel) factors inside the reduction and never
    # holds them (1.07 GB a layer at 2,048 tokens of 64 heads)
    faded = (ks[..., None, :, :] * fade)[..., None, :, :, :, :]
    diagonal = jnp.sum(xs[..., :, None, :] * faded, axis=-1)
    out = []
    for j in range(m):
        parts = []
        if j:
            ref = gs[..., j - 1, sub - 1, :]            # (..., dk)
            mine = xs[..., j, :, :] * jnp.exp(
                gs[..., j, :, :] - ref[..., None, :])[..., None, :, :]
            theirs = (ks[..., :j, :, :] * jnp.exp(
                ref[..., None, None, :] - gs[..., :j, :, :])
            ).reshape(lead + (j * sub, dk))
            parts.append(jnp.einsum("...xtd,...id->...xti", mine, theirs,
                                    precision=HI))
        parts.append(diagonal[..., j, :, :])
        if j < m - 1:
            parts.append(jnp.zeros(lead + (2, sub, (m - 1 - j) * sub), F32))
        out.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(out, axis=-2)


@jax.named_scope("kda_chunk")
def delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  b: jax.Array, state_in: jax.Array,
                  lengths: Optional[jax.Array] = None, chunk: int = 64,
                  ) -> Tuple[jax.Array, jax.Array]:
    """`delta_recurrence`'s arguments and answer, in chunks of `chunk`
    tokens (module docstring): ONE scan over the chunks, a chunk's
    (token, token) matrices and its solve inside the step, so that what
    is held at once is one chunk's (all chunks' sub-block factors at
    once were 1.07 GB a layer at 2,048 tokens of 64 heads, found by
    compiling for the chip)."""
    rows, length, heads, dk = q.shape
    dv = v.shape[-1]
    b, g = _real(b, g, lengths)
    pad = -length % chunk
    n = (length + pad) // chunk

    def chunks(x):
        """(rows, l, heads, ...) -> (n, rows, heads, C, ...), float32,
        padded with zeros: b = 0 and g = 0 there."""
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2))
        x = x.reshape((rows, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def one(s, x):
        q_c, k_c, v_c, g_c, b_c = x                 # (rows, h, C, ...)
        big_g = jnp.cumsum(g_c, axis=-2)
        a = _pair_sums(jnp.stack([q_c, k_c], axis=2), k_c, big_g)
        a_qk, a_kk = a[:, :, 0], a[:, :, 1]
        system = jnp.eye(chunk, dtype=F32) + jnp.where(
            strict, b_c[..., None] * a_kk, 0.0)
        grown = jnp.exp(big_g)
        solved = solve_triangular(
            system,
            b_c[..., None] * jnp.concatenate([k_c * grown, v_c], axis=-1),
            lower=True, unit_diagonal=True)
        w, u0 = solved[..., :dk], solved[..., dk:]
        last = big_g[..., -1:, :]                   # (rows, h, 1, dk)
        u = u0 - jnp.einsum("rhtk,rhkv->rhtv", w, s, precision=HI)
        o = (jnp.einsum("rhtk,rhkv->rhtv", q_c * grown, s, precision=HI)
             + jnp.einsum("rhti,rhiv->rhtv", a_qk, u, precision=HI))
        s = jnp.exp(last[..., 0, :])[..., None] * s + jnp.einsum(
            "rhtk,rhtv->rhkv", k_c * jnp.exp(last - big_g), u, precision=HI)
        return s, o
    state, o = jax.lax.scan(one, state_in.astype(F32),
                            tuple(chunks(x) for x in (q, k, v, g, b)))
    # (n, rows, h, C, dv) -> (rows, l, h, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        rows, n * chunk, heads, dv)
    return o[:, :length], state
