"""The plain reference: code2vec's forward pass, loss, gradients and Adam
in straightforward `jax.numpy`, float32, matmuls at "highest".

It imports nothing of the program and takes nothing the program made.
The benchmark makes the weights from `--seed` (`make_params`, a
counter-based hash, so a table row is a pure function of seed, table and
index) and hands the same weights to the program; the reference makes
its own copy by the same call.

The model (Alon et al., "code2vec", POPL 2019; the reference
implementation's tensorflow_model.py):

    ctx    = [token[src] ; path[pth] ; token[tgt]]            (B, M, 384)
    ctx    = dropout(ctx, keep)                 train only, scaled 1/keep
    t      = tanh(ctx @ TRANSFORM)                            (B, M, 384)
    w      = t @ ATTENTION, -inf where the context is padding (B, M)
    a      = softmax(w) over contexts
    code   = sum_m a[m] t[m]                                  (B, 384)
    logits = code @ TARGET^T                                  (B, V)
    loss   = sum_b CE(logits[b], label[b]) / B

Adam is the reference's (lr 1e-3, b1 .9, b2 .999, eps 1e-8), moments in
float32. The program keeps its moments in bfloat16 (the configuration
states so); that is part of what the comparison's limits absorb.

`lower` is the CONTROL, the reference computed in the nearest precision
below the one the configurations state (float32 parameters, bfloat16
matmul operands). It has two halves, which can be read apart:
`"storage"` holds the parameters in bfloat16 between steps, `"operands"`
rounds the matmul operands to int8 (per-tensor absmax scale), `"both"`
does both. It has to come out as not correct.

Departure from the program, stated: the program draws its dropout mask
from the chip's `rbg` generator inside the step; nobody outside the
step can draw the same bits. The reference draws its own mask
(threefry, from the seed) at the same keep rate. So the numbers compared
are norms and means over a whole batch, whose spread from mask to mask
is part of the readings the limits were set from (PERF.md section 2).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


class Dims(NamedTuple):
    token_rows: int
    path_rows: int
    target_rows: int
    token_dim: int = 128
    path_dim: int = 128

    @property
    def code_dim(self) -> int:
        return self.path_dim + 2 * self.token_dim

    def shapes(self) -> Dict[str, tuple]:
        d = self.code_dim
        return {"token_embedding": (self.token_rows, self.token_dim),
                "path_embedding": (self.path_rows, self.path_dim),
                "target_embedding": (self.target_rows, d),
                "transform": (d, d),
                "attention": (d, 1)}

    def num_params(self) -> int:
        return sum(r * c for r, c in self.shapes().values())


def init_limits(dims: Dims) -> Dict[str, float]:
    """Half-width of each leaf's uniform initializer, as published:
    embeddings variance_scaling(1.0, fan_out, uniform) = sqrt(3 / dim);
    TRANSFORM and ATTENTION glorot_uniform = sqrt(6 / (fan_in + fan_out))
    (tensorflow_model.py:204-219)."""
    out = {}
    for name, (rows, cols) in dims.shapes().items():
        if name.endswith("_embedding"):
            out[name] = math.sqrt(3.0 / cols)
        else:
            out[name] = math.sqrt(6.0 / (rows + cols))
    return out


def seed_words(seed: int, dims: Dims) -> np.ndarray:
    """(leaves, 2) uint32: two 32-bit words for each leaf from a seed of
    any size. They enter the generator as an argument, so one compiled
    program serves every seed."""
    seed = int(seed)
    return np.array(
        [[(seed ^ (i * 0x9E3779B1)) & 0xFFFFFFFF,
          ((seed >> 32) + 0x7F4A7C15 * (i + 1)) & 0xFFFFFFFF]
         for i in range(len(dims.shapes()))], dtype=np.uint32)


def hash_uniform(words, rows, cols: int, limit: float) -> jax.Array:
    """(len(rows), cols) float32 in [-limit, limit): element (r, c) is a
    pure function of (the leaf's two words, r * cols + c) through
    murmur3's 32-bit finalizer, so the program's full table and any
    subset of rows agree bit for bit. `rows` is an int array of row
    indices."""
    lo, hi = words[0], words[1]
    rows = jnp.asarray(rows).astype(jnp.uint32)
    idx = rows[:, None] * jnp.uint32(cols) + jnp.arange(
        cols, dtype=jnp.uint32)[None, :]
    x = idx * jnp.uint32(0x9E3779B1) + lo
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = (x ^ hi) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    unit = (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return (unit * 2.0 - 1.0) * jnp.float32(limit)


def params_from_words(words, dims: Dims) -> Dict[str, jax.Array]:
    """Every leaf from the seed's words, float32 (the type the program
    holds and serves its parameters in); traceable."""
    limits = init_limits(dims)
    return {name: hash_uniform(words[i], jnp.arange(rows, dtype=jnp.uint32),
                               cols, limits[name])
            for i, (name, (rows, cols)) in enumerate(dims.shapes().items())}


@functools.lru_cache(maxsize=None)
def _params_program(dims: Dims, shardings: Optional[tuple]):
    out = None if shardings is None else dict(shardings)
    return jax.jit(functools.partial(params_from_words, dims=dims),
                   out_shardings=out)


def make_params(seed: int, dims: Dims, shardings: Optional[dict] = None
                ) -> Dict[str, jax.Array]:
    """The weights of `seed`, made on the device in one jitted call (one
    compiled program for all seeds); `shardings` places each leaf."""
    key = None if shardings is None else tuple(sorted(shardings.items()))
    return _params_program(dims, key)(jnp.asarray(seed_words(seed, dims)))


# ------------------------------------------------------------- precision

def _int8(x: jax.Array) -> jax.Array:
    """Per-tensor absmax int8 rounding, returned dequantized; the
    gradient passes straight through, as in int8 training."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return x + jax.lax.stop_gradient(jnp.round(x / scale) * scale - x)


LOWER = ("storage", "operands", "both")     # the control and its halves


def _operand(x: jax.Array, lower: str) -> jax.Array:
    return _int8(x) if lower in ("operands", "both") else x


def _stored(x: jax.Array, lower: str) -> jax.Array:
    """`x` as bfloat16 storage would hold it. `reduce_precision`, not a
    pair of converts: inside a jitted program the TPU compiler may keep
    the excess precision of a float32 -> bfloat16 -> float32 round trip
    (seen on the chip: a control that rounded this way moved nothing)."""
    if lower not in ("storage", "both"):
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# --------------------------------------------------------------- forward

def forward(params, src, pth, tgt, mask, drop_mask=None, keep: float = 1.0,
            lower: str = ""):
    """(logits (B, V), code vectors (B, D), attention (B, M)), float32."""
    with jax.default_matmul_precision("highest"):
        ctx = jnp.concatenate([params["token_embedding"][src],
                               params["path_embedding"][pth],
                               params["token_embedding"][tgt]], axis=-1)
        if drop_mask is not None:
            ctx = jnp.where(drop_mask, ctx / keep, 0.0)
        t = jnp.tanh(jnp.einsum("bmc,cd->bmd", _operand(ctx, lower),
                                _operand(params["transform"], lower)))
        w = jnp.einsum("bmd,d->bm", _operand(t, lower),
                       _operand(params["attention"][:, 0], lower))
        w = jnp.where(mask > 0, w, -jnp.inf)
        a = jax.nn.softmax(w, axis=1)
        code = jnp.einsum("bm,bmd->bd", _operand(a, lower),
                          _operand(t, lower))
        logits = jnp.einsum("bd,vd->bv", _operand(code, lower),
                            _operand(params["target_embedding"], lower))
    return logits, code, a


def cross_entropy_sum(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("keep", "lower", "batch_rows"))
def _block_loss_and_grads(params, src, pth, tgt, mask, labels, key, *,
                          keep: float, lower: str, batch_rows: int):
    """Loss share and gradient share of one block of rows of a batch of
    `batch_rows` rows (the loss divides by the whole batch)."""
    def loss_fn(p):
        drop = None
        if keep < 1.0:
            drop = jax.random.bernoulli(
                key, keep, src.shape + (2 * p["token_embedding"].shape[1]
                                        + p["path_embedding"].shape[1],))
        logits, _, _ = forward(p, src, pth, tgt, mask, drop, keep, lower)
        return cross_entropy_sum(logits, labels) / batch_rows
    return jax.value_and_grad(loss_fn)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _tree_add(acc, new):
    return jax.tree.map(jnp.add, acc, new)


@functools.partial(jax.jit, static_argnames=("lower",),
                   donate_argnums=(0, 2, 3))
def _adam_update(params, grads, m, v, step, *, lower: str):
    """One Adam step (Kingma & Ba), float32 moments; `step` counts from 1."""
    b1, b2, lr, eps = ADAM["b1"], ADAM["b2"], ADAM["lr"], ADAM["eps"]
    t = step.astype(jnp.float32)
    out_p, out_m, out_v = {}, {}, {}
    for name in params:
        g = grads[name]
        out_m[name] = b1 * m[name] + (1.0 - b1) * g
        out_v[name] = b2 * v[name] + (1.0 - b2) * g * g
        m_hat = out_m[name] / (1.0 - b1 ** t)
        v_hat = out_v[name] / (1.0 - b2 ** t)
        out_p[name] = _stored(
            params[name] - lr * m_hat / (jnp.sqrt(v_hat) + eps), lower)
    return out_p, out_m, out_v


@jax.jit
def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _delta_norms(new, old) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(new[k] - old[k]))) for k in new}


class Followed(NamedTuple):
    """What the comparison reads, one entry per followed step."""
    losses: List[float]
    grad_norms: Dict[str, float]      # step 1, as the optimizer gets it
    delta_norms: Dict[str, float]     # parameters after the last step - start


def follow_steps(seed: int, dims: Dims, batches: Sequence[dict], *,
                 keep: float, lower: str = "",
                 block_rows: int = 256) -> Followed:
    """Train the reference through `batches` (host arrays: src, pth, tgt
    (B, M) int32, mask (B, M) float32, labels (B,) int32) from the seed's
    weights. Rows go through in blocks so that a (B, V) logit block and
    the dense float32 gradients fit beside the state on one chip."""
    params = {k: _stored(v, lower)
              for k, v in make_params(seed, dims).items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    base = jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)
    losses, grad_norms = [], {}
    for step, batch in enumerate(batches, start=1):
        rows = batch["labels"].shape[0]
        grads, loss = None, 0.0
        for start in range(0, rows, block_rows):
            sl = slice(start, start + block_rows)
            key = jax.random.fold_in(jax.random.fold_in(base, step), start)
            part, g = _block_loss_and_grads(
                params, batch["src"][sl], batch["pth"][sl], batch["tgt"][sl],
                batch["mask"][sl], batch["labels"][sl], key,
                keep=float(keep), lower=lower, batch_rows=rows)
            loss += float(part)
            grads = g if grads is None else _tree_add(grads, g)
        losses.append(loss)
        if step == 1:
            grad_norms = {k: float(x) for k, x in leaf_norms(grads).items()}
        params, m, v = _adam_update(params, grads, m, v,
                                    jnp.asarray(step, jnp.int32),
                                    lower=lower)
        del grads
    delta = _delta_norms(params, {k: _stored(x, lower) for k, x
                                  in make_params(seed, dims).items()})
    return Followed(losses, grad_norms,
                    {k: float(x) for k, x in delta.items()})


# ------------------------------------------------------------- comparing

def worst_leaf_gap(program: Dict[str, float],
                   reference: Dict[str, float]) -> tuple:
    """The gap between the program's norm and the reference's, leaf by
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero).
    Returns (worst gap, its leaf)."""
    median = float(np.median([reference[k] for k in reference]))
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, median, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), name
        if gap >= worst:
            worst, where = gap, name
    return worst, where


# --------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("lower",))
def _score_block(params, src, pth, tgt, mask, *, lower: str):
    logits, _, _ = forward(params, src, pth, tgt, mask, lower=lower)
    return logits


def served_gap(params, src, pth, tgt, mask, served_ids: np.ndarray,
               served_logp: np.ndarray, *, control: str = "",
               block_rows: int = 256) -> Dict[str, float]:
    """For served methods (eval mode, no dropout). `served_ids` (N, K)
    are the names a method was answered with, best first, -1 where the
    answer had fewer; `served_logp` their log-probabilities (a softmax
    over the served logits, so their differences are logit differences).

    `top_gap`: the widest gap by which a served top name's float32 logit
    lies below the reference's best logit of that method (the special
    word at index 0, which the server never names, left out).
    `score_gap`: the widest difference between a served logit difference
    (name k against the top name) and the reference's for the same two
    names. Both are divided by the spread (best - mean) of the method's
    reference logits, so that they compare across seeds. With `control`
    (one of `LOWER`), `control_*` are the same two numbers for that
    lower precision: the name it puts first, and its own logit
    differences."""
    out = {"top_gap": 0.0, "score_gap": 0.0}
    if control:
        out.update(control_top_gap=0.0, control_score_gap=0.0)
        lower_params = {k: _stored(v, control) for k, v in params.items()}

    def widest(key, value):
        out[key] = max(out[key], float(value))

    # whole blocks only, the last one filled with copies of the last row
    # (a widest gap does not change): the number of served methods
    # differs from run to run, and a block of another size is another
    # program to compile (~30 s at these widths, after every window)
    fill = -src.shape[0] % block_rows
    if fill:
        src, pth, tgt, mask, served_ids, served_logp = (
            np.concatenate([x, np.repeat(x[-1:], fill, axis=0)])
            for x in (src, pth, tgt, mask, served_ids, served_logp))
    for start in range(0, src.shape[0], block_rows):
        sl = slice(start, start + block_rows)
        ids = jnp.asarray(served_ids[sl])
        have = ids >= 0
        safe = jnp.where(have, ids, 0)
        ref = _score_block(params, src[sl], pth[sl], tgt[sl], mask[sl],
                           lower="")
        ref = ref.at[:, 0].set(-jnp.inf)
        best = jnp.max(ref, axis=1)
        spread = jnp.maximum(best - jnp.mean(ref[:, 1:], axis=1), 1e-30)

        def differences(logits):
            picked = jnp.take_along_axis(logits, safe, axis=1)
            return picked - picked[:, :1]
        widest("top_gap", jnp.max(
            (best - jnp.take_along_axis(ref, safe[:, :1], axis=1)[:, 0])
            / spread))
        served = jnp.asarray(served_logp[sl])
        served = served - served[:, :1]
        widest("score_gap", jnp.max(jnp.where(
            have, jnp.abs(served - differences(ref)), 0.0)
            / spread[:, None]))
        if control:
            low = _score_block(lower_params, src[sl], pth[sl], tgt[sl],
                               mask[sl], lower=control)
            low_top = jnp.argmax(low.at[:, 0].set(-jnp.inf), axis=1)
            widest("control_top_gap", jnp.max(
                (best - jnp.take_along_axis(ref, low_top[:, None],
                                            axis=1)[:, 0]) / spread))
            widest("control_score_gap", jnp.max(jnp.where(
                have, jnp.abs(differences(low) - differences(ref)), 0.0)
                / spread[:, None]))
    return out
