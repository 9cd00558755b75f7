"""Embedding lookup over the live part of a padded `(rows, contexts)` grid.

A row gather or scatter-add costs the chip a fixed time per ROW (11-17 ns
for a 512 B row on a v5e, PERF.md section 6), whatever the row is used
for, and most rows of a padded train batch are padding: id 0, attention
weight exactly 0 (ops/attention.py), gradient row exactly 0.0.
`embed_live_rows` does the row work of the live entries only, those up
to each row's `depth` (its deepest valid context), under static shapes:

- forward: the grid is covered with static blocks of `BLOCK_ROWS` x
  `BLOCK_CONTEXTS`; a device-side `fori_loop` whose trip count is the
  number of blocks some row reaches into gathers those, the others stay
  zero. With the rows ordered by depth the live blocks form a staircase
  under the batch's own counts; any other order is still correct and
  only skips less.
- backward: the ids are sorted once, the dead ones last, and one sorted
  scatter-add takes the shortest of `SCATTER_SIZES` static prefixes of
  the list that holds every live entry (`_embed_bwd` says why not a loop).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Chosen once on the chip (PERF.md, PR 25).
BLOCK_ROWS = 64
BLOCK_CONTEXTS = 50
SCATTER_SIZES = 8


def context_depth(context_valid_mask: jax.Array) -> jax.Array:
    """(B, M) mask -> (B,) int32: index of each row's deepest valid
    context + 1 (0 for a row of padding). A mask with holes is covered
    up to its last valid context."""
    m = context_valid_mask.shape[1]
    position = jnp.arange(1, m + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(context_valid_mask > 0, position, 0), axis=1)


def _grid(rows: int, contexts: int) -> Tuple[int, int]:
    return -(-rows // BLOCK_ROWS), -(-contexts // BLOCK_CONTEXTS)


def _schedule(depth: jax.Array, contexts: int):
    """The live blocks first, as flat indices into the (groups,
    context blocks) grid, and how many they are. `depth` holds whole
    groups of rows."""
    groups, across = _grid(depth.shape[0], contexts)
    reach = jnp.max(depth.reshape(groups, BLOCK_ROWS), axis=1)
    live = (jnp.arange(across, dtype=jnp.int32)[None, :] * BLOCK_CONTEXTS
            < reach[:, None]).reshape(-1)
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    return order.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)


def _pad_grid(x: jax.Array) -> jax.Array:
    """Pad the two leading axes up to whole blocks (static; a no-op when
    the grid already divides)."""
    groups, across = _grid(x.shape[0], x.shape[1])
    pad = [(0, groups * BLOCK_ROWS - x.shape[0]),
           (0, across * BLOCK_CONTEXTS - x.shape[1])]
    if not any(p[1] for p in pad):
        return x
    return jnp.pad(x, pad + [(0, 0)] * (x.ndim - 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def embed_live_rows(table: jax.Array, ids: Tuple[jax.Array, ...],
                    depth: jax.Array, dtype) -> Tuple[jax.Array, ...]:
    """`tuple(jnp.take(table, i, axis=0).astype(dtype) for i in ids)` on
    every entry `[b, m]` with `m < depth[b]`, zero on every other. `ids`
    are `(B, M)` int arrays of ONE table, so their gradient rows land in
    one table-shaped float gradient."""
    outs, _ = _embed_fwd(table, ids, depth, dtype)
    return outs


def _embed_fwd(table, ids, depth, dtype):
    rows, contexts = ids[0].shape
    groups, across = _grid(rows, contexts)
    reach = jnp.pad(depth, (0, groups * BLOCK_ROWS - rows))
    order, count = _schedule(reach, contexts)
    padded_ids = tuple(_pad_grid(i) for i in ids)
    width = table.shape[1]
    position = jnp.arange(BLOCK_CONTEXTS, dtype=jnp.int32)[None, :]

    def gather_block(t, outs):
        block = order[t]
        r0 = (block // across) * BLOCK_ROWS
        c0 = (block % across) * BLOCK_CONTEXTS
        below = (c0 + position
                 < jax.lax.dynamic_slice(reach, (r0,), (BLOCK_ROWS,))[:, None])
        new = []
        for block_ids, out in zip(padded_ids, outs):
            got = jnp.take(table, jax.lax.dynamic_slice(
                block_ids, (r0, c0), (BLOCK_ROWS, BLOCK_CONTEXTS)), axis=0)
            # Zero past each row's depth by a PRODUCT, not a select: XLA
            # moves the cast through a select and a gather onto the
            # table and hoists it out of the loop, a pass over every
            # row of the table (2 ms and 0.5 GB at java14m); it cannot
            # move it through a product with the loop's own mask.
            got = (got * below[:, :, None].astype(got.dtype)).astype(dtype)
            new.append(jax.lax.dynamic_update_slice(out, got, (r0, c0, 0)))
        return tuple(new)

    outs = jax.lax.fori_loop(
        0, count, gather_block,
        tuple(jnp.zeros(i.shape + (width,), dtype) for i in padded_ids))
    outs = tuple(out[:rows, :contexts] for out in outs)
    return outs, (table, ids, depth)


def _embed_bwd(dtype, residuals, cotangents):
    """Scatter-add of the live entries' cotangent rows into one
    table-shaped gradient. On the chip a scatter is either unsorted and
    pays the memory's latency for every row (75 ns on a v5e), or sorted:
    one pass over the table (2.1 ms for java14m's token table) plus 11 ns
    a row, which is what XLA makes of `jnp.take`'s transpose. A loop of
    scatters pays one or the other once a block (measured, PERF.md PR
    25). So the ids are sorted once, as there, with the dead entries
    behind a key past the table's end (dropped), and ONE sorted scatter
    adds a prefix of the list: the shortest of `SCATTER_SIZES` static
    lengths that holds every live entry."""
    table, ids, depth = residuals
    contexts = ids[0].shape[1]
    width = table.shape[1]
    live = jnp.arange(contexts, dtype=jnp.int32)[None, :] < depth[:, None]
    past_end = table.shape[0]
    keys = jnp.concatenate(
        [jnp.where(live, i, past_end).reshape(-1) for i in ids])
    keys, source = jax.lax.sort_key_val(
        keys, jnp.arange(keys.shape[0], dtype=jnp.int32))
    updates = jnp.concatenate([ct.reshape(-1, width) for ct in cotangents])
    step = -(-keys.shape[0] // SCATTER_SIZES)

    def scatter_prefix(length):
        def scatter():
            update = jnp.take(updates, source[:length], axis=0)
            return jnp.zeros_like(table).at[keys[:length]].add(
                update.astype(table.dtype), indices_are_sorted=True,
                mode="drop")
        return scatter

    entries = len(ids) * jnp.sum(depth)
    grad = jax.lax.switch(
        jnp.maximum(entries - 1, 0) // step,
        [scatter_prefix(min((n + 1) * step, keys.shape[0]))
         for n in range(SCATTER_SIZES)])
    return grad, None, None


embed_live_rows.defvjp(_embed_fwd, _embed_bwd)


def live_block_ratio(context_valid_mask: np.ndarray, chips: int = 1) -> float:
    """Host-side (numpy) count of what `embed_live_rows`'s forward
    gathers of a batch whose rows the step orders by depth: live blocks
    over all blocks of the grid. With `chips` > 1 the batch's rows are
    `chips` equal slices, each ordered and gathered on its own chip."""
    mask = np.asarray(context_valid_mask) > 0
    contexts = mask.shape[1]
    depth = np.where(mask.any(axis=1),
                     contexts - np.argmax(mask[:, ::-1], axis=1), 0)
    live = blocks = 0
    for rows in np.split(depth, chips):
        groups, across = _grid(len(rows), contexts)
        deepest = -np.sort(-rows)[::BLOCK_ROWS]
        live += int((-(-deepest // BLOCK_CONTEXTS)).sum())
        blocks += groups * across
    return live / blocks
