"""The dense chain of the train step over the live blocks only.

`embed_live_rows` (ops/embed.py) hands over the embedding rows by slot:
live block `t` of the batch's schedule in slot `t` of a compact buffer.
`encode_live_blocks` runs what `Code2VecModule.transform_gathered` and
`masked_single_query_attention` run over the whole `(B, M)` grid
(concat -> cast -> dropout -> `@ transform` -> tanh -> `. attention` ->
softmax -> weighted sum) over the first `count` slots and nothing else:
a padded slot's attention weight is exactly 0 and its gradient row
exactly 0.0, so a block no row reaches into changes nothing.

Three loops, each with the batch's own trip count (its live blocks in
chunks of `SLOT_CHUNK` slots):

- forward, per slot: the transformed contexts (kept for the backward)
  and their scores. The scores go back to the `(B, M)` grid, where the
  softmax runs as everywhere else (float32, the batch's real mask,
  which may have holes under the depth); the weights come back by slot.
- forward, per slot: the weighted sum over a slot's contexts; the sums
  of a row group's slots are added into the group's code vectors.
- backward, per slot, ONE pass: with `s` the scores, `w` the weights,
  `T` the transformed contexts and `c` the code vector of a row,
  `dL/ds = w * (T . dL/dc - c . dL/dc)` (the softmax's own transpose,
  its row sum taken from the output instead of from a second pass over
  the slots), then tanh, the two transposes of the matmul, dropout
  (the same key, so the same mask) and the split into the three
  lookups' cotangents, by slot as they came.

Reverse-mode differentiation cannot run through a loop with a dynamic
trip count, and through a `lax.switch` over static prefixes it makes
every branch carry every other's residuals: hence the VJP of its own.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.ops import embed
from code2vec_tpu.ops.attention import masked_softmax


def _chunk(x: jax.Array, t0) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(x, t0, embed.SLOT_CHUNK, axis=0)


def _put(buffer: jax.Array, chunk: jax.Array, t0) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(
        buffer, chunk.astype(buffer.dtype), t0, axis=0)


def _dropped(rows, t0, key, keep: float):
    """concat -> dropout of one chunk of slots: (SLOT_CHUNK, entries,
    3d) in the compute dtype, and the mask (None at keep 1): the step's
    key folded with the chunk's first slot, so that forward and
    backward draw the same one."""
    ctx = jnp.concatenate([_chunk(r, t0) for r in rows], axis=-1)
    if keep >= 1.0:
        return ctx, None
    mask = jax.random.bernoulli(jax.random.fold_in(key, t0), keep, ctx.shape)
    return jnp.where(mask, ctx / jnp.asarray(keep, ctx.dtype),
                     jnp.zeros((), ctx.dtype)), mask


def _by_context(x: jax.Array) -> jax.Array:
    """(chunk, entries, ...) -> (chunk, BLOCK_CONTEXTS, BLOCK_ROWS, ...):
    a slot is context-major (ops/embed.py to_slots)."""
    return x.reshape(x.shape[0], embed.BLOCK_CONTEXTS, embed.BLOCK_ROWS,
                     *x.shape[2:])


def _trips(count):
    return -(-count // embed.SLOT_CHUNK)


def encode_live_blocks(rows: Tuple[jax.Array, jax.Array, jax.Array],
                       transform: jax.Array, attention: jax.Array,
                       context_valid_mask: jax.Array, depth: jax.Array,
                       key: jax.Array, keep: float) -> jax.Array:
    """(B, code_dim) float32 code vectors from the three lookups' rows by
    slot (`embed_live_rows` under `depth`, source | path | target, in the
    compute dtype), `transform` (3d, D) and `attention` (D,) float32,
    the `(B, M)` mask and the step's dropout `key` (unused at `keep`
    1.0). Matmul operands in the rows' dtype, float32 accumulation,
    float32 softmax.

    The profiler's op view shows the parts under `transform`,
    `attention`, `transpose(jvp(transform))` and
    `transpose(jvp(attention))`, as it shows the chain over the grid
    (models/code2vec.py): the names are set inside the loops' bodies,
    and the outer scope keeps `jvp(...)` off them."""
    with jax.named_scope("encode_live"):
        return _encode(rows, transform, attention, context_valid_mask,
                       depth, key, keep)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _encode(rows, transform, attention, mask, depth, key, keep):
    code_vectors, _ = _encode_fwd(rows, transform, attention, mask, depth,
                                  key, keep)
    return code_vectors


def _encode_fwd(rows, transform, attention, mask, depth, key, keep):
    dtype = rows[0].dtype
    batch, contexts = mask.shape
    groups, across = embed._grid(batch, contexts)
    slots, entries = rows[0].shape[:2]
    order, count = embed.live_slots(depth, contexts)
    weight = transform.astype(dtype)
    query = attention.astype(dtype)

    def transform_chunk(i, carry):
        transformed, scores = carry
        t0 = i * embed.SLOT_CHUNK
        with jax.named_scope("transform"):
            ctx, _ = _dropped(rows, t0, key, keep)
            got = jnp.tanh(jnp.einsum(
                "tec,cd->ted", ctx, weight,
                preferred_element_type=jnp.float32)).astype(dtype)
            transformed = _put(transformed, got, t0)
        with jax.named_scope("attention"):
            scores = _put(scores, jnp.einsum(
                "ted,d->te", got, query,
                preferred_element_type=jnp.float32), t0)
        return transformed, scores

    transformed, scores = jax.lax.fori_loop(
        0, _trips(count), transform_chunk,
        (jnp.zeros((slots, entries, transform.shape[1]), dtype),
         jnp.zeros((slots, entries), jnp.float32)))

    with jax.named_scope("attention"):
        weights = embed.to_slots(masked_softmax(
            embed.to_grid(scores, order, batch, contexts), mask), order)

        def sum_chunk(i, sums):
            t0 = i * embed.SLOT_CHUNK
            return _put(sums, jnp.einsum(
                "tcr,tcrd->trd", _by_context(_chunk(weights, t0)).astype(dtype),
                _by_context(_chunk(transformed, t0)),
                preferred_element_type=jnp.float32), t0)

        sums = jax.lax.fori_loop(
            0, _trips(count), sum_chunk,
            jnp.zeros((slots, embed.BLOCK_ROWS, transform.shape[1]),
                      jnp.float32))
        # a slot's rows are those of its block's row group
        code_vectors = jax.ops.segment_sum(
            sums[:groups * across], order // across, num_segments=groups)
        code_vectors = code_vectors.reshape(-1, transform.shape[1])[:batch]
    return code_vectors, (rows, transform, attention, mask, order, count, key,
                          transformed, weights, code_vectors)


def _encode_bwd(keep, residuals, code_cotangent):
    (rows, transform, attention, mask, order, count, key, transformed,
     weights, code_vectors) = residuals
    dtype = rows[0].dtype
    groups, across = embed._grid(*mask.shape)
    weight = transform.astype(dtype)
    query = attention.astype(jnp.float32)

    def by_group(x):
        """(B, ...) rows -> (groups, BLOCK_ROWS, ...)."""
        pad = groups * embed.BLOCK_ROWS - x.shape[0]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape(groups, embed.BLOCK_ROWS, *x.shape[1:])

    code_ct = by_group(code_cotangent)
    # the softmax transpose's row sum, sum_m w * dL/dw = c . dL/dc
    row_sum = by_group(jnp.sum(code_vectors * code_cotangent, axis=-1))
    group = jnp.pad(order // across, (0, weights.shape[0] - order.shape[0]))
    widths = [r.shape[-1] for r in rows]

    def chunk_backward(i, carry):
        row_cts, transform_ct, attention_ct = carry
        t0 = i * embed.SLOT_CHUNK
        mine = _chunk(group, t0)
        with jax.named_scope("transpose(jvp(attention))"):
            got = _by_context(_chunk(transformed, t0))      # (t, c, r, D)
            w = _by_context(_chunk(weights, t0))            # (t, c, r) f32
            ct = jnp.take(code_ct, mine, axis=0)            # (t, r, D) f32
            score_ct = w * (jnp.einsum(
                "tcrd,trd->tcr", got, ct.astype(dtype),
                preferred_element_type=jnp.float32)
                - jnp.take(row_sum, mine, axis=0)[:, None, :])
            attention_ct = attention_ct + jnp.einsum(
                "tcrd,tcr->d", got, score_ct.astype(dtype),
                preferred_element_type=jnp.float32)
            got_ct = (w[..., None] * ct[:, None]
                      + score_ct[..., None] * query)
        with jax.named_scope("transpose(jvp(transform))"):
            got = got.astype(jnp.float32)
            pre_ct = (got_ct * (1.0 - got * got)).astype(dtype)
            pre_ct = pre_ct.reshape(pre_ct.shape[0], -1, pre_ct.shape[-1])
            ctx, dropout = _dropped(rows, t0, key, keep)
            transform_ct = transform_ct + jnp.einsum(
                "tec,ted->cd", ctx, pre_ct,
                preferred_element_type=jnp.float32)
            ctx_ct = jnp.einsum("ted,cd->tec", pre_ct, weight,
                                preferred_element_type=jnp.float32
                                ).astype(dtype)
            if dropout is not None:
                ctx_ct = jnp.where(dropout,
                                   ctx_ct / jnp.asarray(keep, dtype),
                                   jnp.zeros((), dtype))
            at, new = 0, []
            for row_ct, width in zip(row_cts, widths):
                new.append(_put(row_ct, ctx_ct[..., at:at + width], t0))
                at += width
        return tuple(new), transform_ct, attention_ct

    row_cts, transform_ct, attention_ct = jax.lax.fori_loop(
        0, _trips(count), chunk_backward,
        (tuple(jnp.zeros_like(r) for r in rows),
         jnp.zeros(transform.shape, jnp.float32),
         jnp.zeros(attention.shape, jnp.float32)))
    return (row_cts, transform_ct.astype(transform.dtype),
            attention_ct.astype(attention.dtype), None, None, None)


_encode.defvjp(_encode_fwd, _encode_bwd)
