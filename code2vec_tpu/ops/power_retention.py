"""Power retention: attention whose weights are a POWER of the query-key
product in place of its exponential, times a learned decay, normalised
by their own sum (Buckman, Gelada & Zhang, "Scaling context requires
rethinking attention", 2025; the `brumby` family's layers).

For key/value head `h` (query head `n` reads head `n // (hq / hkv)`),
with `q` and `k` already normalised and rotated, `d` their width, `p`
the power (2 here) and `log g_t <= 0` the gate of token t:

    a_ij = (q_i . k_j / sqrt(d))^p * exp(sum_{l=j+1..i} log g_l)    j <= i
    y_i  = sum_j a_ij v_j / (sum_j a_ij + eps)

`(q . k)^2 = phi(q) . phi(k)` with `phi(x)` the symmetric square of x:
the `d (d + 1) / 2` products `x_a x_b`, `a <= b`, the off-diagonal ones
times sqrt 2 (`phi`, `state_features`). So the same layer is a linear
recurrence over a state of FIXED size, whatever the tokens before:

    S_t = g_t S_{t-1} + phi(k_t / d^(1/4)) [v_t | 1]^T
    y_t = S_t^T phi(q_t / d^(1/4)),   its last entry the normaliser

`retain` computes it in chunks: inside a chunk the masked product
`((Q K^T)^2 * decay) @ [V | 1]` (scope `retention_intra`), from the
state that ENTERS the chunk `phi(Q) @ state` times the decay from the
chunk's start (`retention_state_read`), and the state that leaves it,
`decay_to_end * [V | 1]^T @ phi(K)` on top of the decayed entering one
(`retention_state_update`); a `lax.scan` over the chunks carries the
state. A call of ONE chunk is what a scoring step makes (`state_in` a
slot's state, `state_out` dropped: nothing of the update is computed);
registration makes several and writes `state_out` back.

The state of one key/value head is `(d_v + 1, features)` float32: row e
< d_v is S's column e, the last row the normaliser's `z`. Features are
the MINOR dimension because the chip tiles the two minor dimensions of
a float32 array by (8, 128): `(features, d_v + 1)` would pad 129 lanes
to 256, twice the bytes.

Precision: matmul operands bfloat16 (q, k, `phi`, the masked scores,
`[V | 1]` and the entering state as read), accumulation float32; decays,
cumulative sums, the carried state and the normaliser float32.

`phi` is two 0/1 selection matmuls (feature f takes `x[first[f]] *
x[second[f]]`) and a product: exact for bfloat16 inputs, all on aligned
tiles; a gather along the minor dimension pays by the element here.

Right padding is safe for `y` (causal). A padded position must not
reach `state_out`: the CALLER zeroes its `k` and `log_g` (`phi(0) = 0`,
no decay), as `retain` itself does for the positions it pads up to a
whole chunk.

`retain_quadratic` (the `a_ij` above, float32, no state inside) and
`retain_recurrence` (one position a step) are the plain forms the
chunked one is tested against.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def state_features(d: int) -> int:
    """Products `x_a x_b`, `a <= b`, of a `d`-wide head."""
    return d * (d + 1) // 2


@functools.lru_cache(maxsize=None)
def _selectors(d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(first, second) `(d, features)` 0/1 matrices and the features'
    weights: 1 on the diagonal, sqrt 2 off it."""
    a, b = np.triu_indices(d)
    at = np.arange(a.size)
    first = np.zeros((d, a.size), np.float32)
    second = np.zeros((d, a.size), np.float32)
    first[a, at] = 1.0
    second[b, at] = 1.0
    return first, second, np.where(a == b, 1.0, np.sqrt(2.0)).astype(
        np.float32)


def phi(x: jax.Array, out_dtype=F32) -> jax.Array:
    """(..., d) -> (..., d (d + 1) / 2): the symmetric square, so that
    `phi(q) . phi(k) == (q . k)^2`. Float32 inputs are selected at
    "highest" (exact); bfloat16 ones in one pass (exact too)."""
    first, second, weight = _selectors(x.shape[-1])
    exact = {} if x.dtype == BF16 else {"precision": HI}
    one = jnp.dot(x, jnp.asarray(first, x.dtype), **exact)
    two = jnp.dot(x, jnp.asarray(second, x.dtype), **exact)
    return (one.astype(F32) * two.astype(F32) * weight).astype(out_dtype)


def _cumulative(log_g: jax.Array) -> jax.Array:
    """(rows, c, hkv) -> (rows, hkv, c): sum of log g over 0..i."""
    return jnp.cumsum(jnp.moveaxis(log_g.astype(F32), 1, 2), axis=-1)


def _read_state(phi_q: jax.Array, state) -> jax.Array:
    """phi_q (rows, c, hkv, r, features) bfloat16 against each row's
    state -> (rows, c, hkv, r, e) float32. `state` is one array (rows,
    hkv, e, features), or a sequence of one (hkv, e, features) array a
    row: a row's product then reads its state where it lies (a slice of
    the cache) and rounds it to bfloat16 on the way; no float32 copy of
    all the rows' states is made (at sixteen rows that copy is 0.55 GB a
    layer, and the compiler hoists every layer's to the step's start)."""
    if isinstance(state, (list, tuple)):
        return jnp.stack([jnp.einsum(
            "ihgf,hef->ihge", phi_q[r], one.astype(BF16),
            preferred_element_type=F32) for r, one in enumerate(state)])
    return jnp.einsum("rihgf,rhef->rihge", phi_q, state.astype(BF16),
                      preferred_element_type=F32)


def _one_chunk(q, k, v1, log_g, state, want_state: bool):
    """One chunk of every row. q (rows, c, hkv, r, d) and k (rows, c,
    hkv, d) bfloat16, scaled by d^(-1/4); v1 = [V | 1] (rows, c, hkv,
    e) bfloat16; log_g (rows, c, hkv) float32; state (rows, hkv, e,
    features) float32, or a row's a piece (`_read_state`) -> (num (rows,
    c, hkv, r, e) float32: weighted values and, last, the normaliser;
    the state behind the chunk)."""
    c = q.shape[1]
    cum = _cumulative(log_g)                            # (rows, hkv, c)
    with jax.named_scope("retention_intra"):
        dots = jnp.einsum("rihgd,rjhd->rhgij", q, k,
                          preferred_element_type=F32)
        causal = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.where(causal, jnp.exp(jnp.where(
            causal, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        weights = (jnp.square(dots) * decay[:, :, None]).astype(BF16)
        num = jnp.einsum("rhgij,rjhe->rihge", weights, v1,
                         preferred_element_type=F32)
    with jax.named_scope("retention_state_read"):
        read = _read_state(phi(q, BF16), state)
        from_start = jnp.moveaxis(jnp.exp(cum), 1, 2)   # (rows, c, hkv)
        num = num + read * from_start[..., None, None]
    if not want_state:
        return num, None
    with jax.named_scope("retention_state_update"):
        to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), 1, 2)
        own = jnp.einsum(
            "rjhe,rjhf->rhef",
            (v1.astype(F32) * to_end[..., None]).astype(BF16),
            phi(k, BF16), preferred_element_type=F32)
        if isinstance(state, (list, tuple)):
            state = jnp.stack(state)
        state = state * jnp.exp(cum[..., -1])[..., None, None] + own
    return num, state


def retain(q: jax.Array,            # (rows, l, hq, d), normalised, rotated
           k: jax.Array,            # (rows, l, hkv, d)
           v: jax.Array,            # (rows, l, hkv, dv)
           log_g: jax.Array,        # (rows, l, hkv) float32, <= 0
           state_in,    # (rows, hkv, dv + 1, features), or a row's a piece
           chunk: int,
           want_state: bool = True,
           eps: float = EPS) -> Tuple[jax.Array, Optional[jax.Array]]:
    """-> (y (rows, l, hq, dv) float32, the state behind position l - 1,
    float32; None with `want_state` false). `state_in` None: zeros; a
    sequence of one state a row is read row by row (`_read_state`)."""
    rows, length, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    r = hq // hkv
    if state_in is None:
        state_in = jnp.zeros((rows, hkv, dv + 1, state_features(d)), F32)
    scale = d ** -0.25
    q = (q.astype(F32) * scale).astype(BF16).reshape(rows, length, hkv, r, d)
    k = (k.astype(F32) * scale).astype(BF16)
    v1 = jnp.concatenate([v.astype(BF16),
                          jnp.ones((rows, length, hkv, 1), BF16)], axis=-1)
    log_g = log_g.astype(F32)
    chunk = min(chunk, length)
    pad = (-length) % chunk
    if pad:
        # k = 0 and log g = 0 there: the state neither takes nor decays
        q, k, v1, log_g = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (
            t.ndim - 2)) for t in (q, k, v1, log_g))
    n = (length + pad) // chunk
    if n == 1:
        num, state = _one_chunk(q, k, v1, log_g, state_in, want_state)
    else:
        def chunks_first(t):
            return jnp.moveaxis(t.reshape((rows, n, chunk) + t.shape[2:]),
                                1, 0)

        def step(state, inputs):
            num, state = _one_chunk(*inputs, state, True)
            return state, num
        if isinstance(state_in, (list, tuple)):
            state_in = jnp.stack(state_in)
        state, num = jax.lax.scan(
            step, state_in.astype(F32),
            tuple(chunks_first(t) for t in (q, k, v1, log_g)))
        num = jnp.moveaxis(num, 0, 1).reshape(
            (rows, n * chunk) + num.shape[3:])
        if not want_state:
            state = None
    num = num[:, :length]
    y = num[..., :dv] / (num[..., dv:] + eps)
    return y.reshape(rows, length, hq, dv), state


# ------------------------------------------------------- the plain forms

def retain_quadratic(q, k, v, log_g, power: int = 2, gated: bool = True,
                     normalised: bool = True, eps: float = EPS
                     ) -> jax.Array:
    """The definition: every (i, j <= i) weight of a whole sequence in
    float32 at "highest", no state. `power`, `gated` and `normalised`
    are there for the FAULTS a test holds the layer against (p = 1, g =
    1, the normaliser dropped)."""
    rows, length, hq, d = q.shape
    hkv = k.shape[2]
    q = q.astype(F32).reshape(rows, length, hkv, hq // hkv, d)
    k, v, log_g = k.astype(F32), v.astype(F32), log_g.astype(F32)
    dots = jnp.einsum("rihgd,rjhd->rhgij", q, k, precision=HI) / (d ** 0.5)
    cum = _cumulative(log_g if gated else jnp.zeros_like(log_g))
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    a = dots ** power * decay[:, :, None]
    y = jnp.einsum("rhgij,rjhe->rihge", a, v, precision=HI)
    if normalised:
        y = y / (jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)[..., None] + eps)
    return y.reshape(rows, length, hq, v.shape[-1])


def retain_recurrence(q, k, v, log_g, state_in=None, eps: float = EPS):
    """The recurrence itself, one position a step, float32: -> (y, the
    state behind the last position)."""
    rows, length, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    scale = d ** -0.25
    q = q.astype(F32).reshape(rows, length, hkv, hq // hkv, d) * scale
    k = k.astype(F32) * scale
    v1 = jnp.concatenate([v.astype(F32),
                          jnp.ones((rows, length, hkv, 1), F32)], axis=-1)
    if state_in is None:
        state_in = jnp.zeros((rows, hkv, dv + 1, state_features(d)), F32)

    def step(state, inputs):
        q_t, k_t, v_t, g_t = inputs
        state = (state * jnp.exp(g_t)[..., None, None]
                 + v_t[..., :, None] * phi(k_t)[..., None, :])
        num = jnp.einsum("rhgf,rhef->rhge", phi(q_t), state, precision=HI)
        return state, num[..., :dv] / (num[..., dv:] + eps)
    state, y = jax.lax.scan(
        step, state_in.astype(F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v1,
                                              log_g.astype(F32))))
    return jnp.moveaxis(y, 0, 1).reshape(rows, length, hq, dv), state
