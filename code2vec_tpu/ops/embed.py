"""Embedding lookup over the live part of a padded `(rows, contexts)` grid.

A row gather or scatter-add costs the chip a fixed time per ROW (11-17 ns
for a 512 B row on a v5e, PERF.md section 6), whatever the row is used
for, and most rows of a padded train batch are padding: id 0, attention
weight exactly 0 (ops/attention.py), gradient row exactly 0.0.
`embed_live_rows` does the row work of the live entries only, those up
to each row's `depth` (its deepest valid context), under static shapes:

- the grid is covered with static blocks of `BLOCK_ROWS` x
  `BLOCK_CONTEXTS`; the blocks some row reaches into are live. With the
  rows ordered by depth they form a staircase under the batch's own
  counts; any other order is still correct and only skips less.
- forward: a device-side `fori_loop` whose trip count is the number of
  live blocks gathers live block `t` of the schedule (`live_slots`)
  into slot `t` of a COMPACT `(slots, entries, width)` buffer, the
  other slots stay zero. A flat slot is whole tiles where a `(64, 50)`
  corner of the grid is not (200 = 8 x 25), and what consumes the rows
  (ops/encode_live.py) runs over the filled slots and no others.
- backward: the ids are sorted once, the dead ones last
  (`_sorted_entries`). Where the table's Adam takes lists
  (training/step.py `adam_row_list_tables`) a chip stops there: the
  train step hands the sorted `(key, row)` list to the table's Adam
  (`sorted_row_list`, ops/adam_rows.py), on a data mesh after an
  all-gather has laid every chip's list end to end, and no table-shaped
  gradient exists. Any other step differentiates through the lookup:
  `_embed_bwd`, its VJP, takes the list in by ONE sorted scatter-add
  into a table, which a data mesh then all-reduces. Either moves the
  rows of the shortest of `SCATTER_SIZES` static prefixes of the list
  that holds every live entry (`_embed_bwd` says why not a loop).
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
# Slots the dense chain over the lookup's output (ops/encode_live.py)
# takes in one trip of its loops (PR 29).
SLOT_CHUNK = 4


def context_depth(context_valid_mask: jax.Array) -> jax.Array:
    """(B, M) mask -> (B,) int32: index of each row's deepest valid
    context + 1 (0 for a row of padding). A mask with holes is covered
    up to its last valid context."""
    m = context_valid_mask.shape[1]
    position = jnp.arange(1, m + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(context_valid_mask > 0, position, 0), axis=1)


def _grid(rows: int, contexts: int) -> Tuple[int, int]:
    return -(-rows // BLOCK_ROWS), -(-contexts // BLOCK_CONTEXTS)


def slot_count(rows: int, contexts: int) -> int:
    """Slots of the compact buffer of a `(rows, contexts)` grid: one a
    block, rounded up to whole `SLOT_CHUNK`s."""
    groups, across = _grid(rows, contexts)
    return -(-groups * across // SLOT_CHUNK) * SLOT_CHUNK


def live_slots(depth: jax.Array, contexts: int):
    """The schedule of a batch's blocks: `order[t]` is the flat index,
    in the (groups, context blocks) grid, of the block that slot `t` of
    a compact buffer holds, the live blocks (those some row of their
    group reaches into) first; and how many are live."""
    groups, across = _grid(depth.shape[0], contexts)
    reach = jnp.max(jnp.pad(depth, (0, groups * BLOCK_ROWS - depth.shape[0])
                            ).reshape(groups, BLOCK_ROWS), axis=1)
    live = (jnp.arange(across, dtype=jnp.int32)[None, :] * BLOCK_CONTEXTS
            < reach[:, None]).reshape(-1)
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    return order.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)


def to_slots(grid: jax.Array, order: jax.Array) -> jax.Array:
    """(rows, contexts) values -> (slots, BLOCK_CONTEXTS * BLOCK_ROWS):
    slot `t` holds block `order[t]`, context-major (entry `c *
    BLOCK_ROWS + r` is context `c` of the block's row `r`, so that a
    slot of a `(slots, entries, width)` buffer splits into whole
    `(BLOCK_ROWS, width)` tiles, one a context). Entries past the
    grid's edge and the slots past the last block read 0."""
    groups, across = _grid(*grid.shape)
    padded = jnp.pad(grid, ((0, groups * BLOCK_ROWS - grid.shape[0]),
                            (0, across * BLOCK_CONTEXTS - grid.shape[1])))
    blocks = padded.reshape(groups, BLOCK_ROWS, across, BLOCK_CONTEXTS)
    blocks = blocks.transpose(0, 2, 3, 1).reshape(groups * across, -1)
    slots = jnp.take(blocks, order, axis=0)
    return jnp.pad(slots, ((0, slot_count(*grid.shape) - groups * across),
                           (0, 0)))


def to_grid(slots: jax.Array, order: jax.Array, rows: int,
            contexts: int) -> jax.Array:
    """`to_slots` undone: (slots, entries) values -> (rows, contexts)."""
    groups, across = _grid(rows, contexts)
    blocks = jnp.zeros((groups * across,) + slots.shape[1:], slots.dtype
                       ).at[order].set(slots[:groups * across],
                                       unique_indices=True)
    blocks = blocks.reshape(groups, across, BLOCK_CONTEXTS, BLOCK_ROWS)
    return blocks.transpose(0, 3, 1, 2).reshape(
        groups * BLOCK_ROWS, across * BLOCK_CONTEXTS)[:rows, :contexts]


def _slot_ids(ids, depth):
    """Each id array by slot, and which entries lie under their row's
    depth (none of a dead block, by the schedule)."""
    contexts = ids[0].shape[1]
    order, count = live_slots(depth, contexts)
    below = to_slots(
        jnp.arange(contexts, dtype=jnp.int32)[None, :] < depth[:, None],
        order)
    return tuple(to_slots(i, order) for i in ids), below, count


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def embed_live_rows(table: jax.Array, ids: Tuple[jax.Array, ...],
                    depth: jax.Array, dtype) -> Tuple[jax.Array, ...]:
    """`tuple(jnp.take(table, i, axis=0).astype(dtype) for i in ids)` on
    every entry `[b, m]` with `m < depth[b]`, zero on every other, by
    slot: `(slot_count, entries, width)` in the layout of `to_slots`
    under the schedule `live_slots(depth, contexts)`. `ids` are `(B, M)`
    int arrays of ONE table, so their gradient rows land in one
    table-shaped float gradient."""
    outs, _ = _embed_fwd(table, ids, depth, dtype)
    return outs


def _embed_fwd(table, ids, depth, dtype):
    slot_ids, below, count = _slot_ids(ids, depth)

    def gather_slot(t, outs):
        # Zero past each row's depth by a PRODUCT, not a select: XLA
        # moves the cast through a select and a gather onto the table
        # and hoists it out of the loop, a pass over every row of the
        # table (2 ms and 0.5 GB at java14m); it cannot move it through
        # a product with the loop's own mask.
        under = below[t][:, None].astype(table.dtype)
        return tuple(
            jax.lax.dynamic_update_slice(
                out, (jnp.take(table, i[t], axis=0) * under
                      ).astype(dtype)[None], (t, 0, 0))
            for i, out in zip(slot_ids, outs))

    outs = jax.lax.fori_loop(
        0, count, gather_slot,
        tuple(jnp.zeros(below.shape + (table.shape[1],), dtype)
              for _ in ids))
    return outs, (table, slot_ids, below)


def _sorted_entries(residuals, cotangents):
    """The backward's list before any row moves: the entries' keys
    SORTED, the dead ones (past their row's depth) behind a key past the
    table's end; each sorted key's `source`, a row of `updates`; the
    cotangent rows by slot as the outputs went; and how many entries
    are live."""
    table, slot_ids, below = residuals
    width = table.shape[1]
    past_end = table.shape[0]
    keys = jnp.concatenate(
        [jnp.where(below, i, past_end).reshape(-1) for i in slot_ids])
    keys, source = jax.lax.sort_key_val(
        keys, jnp.arange(keys.shape[0], dtype=jnp.int32))
    updates = jnp.concatenate([ct.reshape(-1, width) for ct in cotangents])
    entries = len(slot_ids) * jnp.sum(below, dtype=jnp.int32)
    return keys, source, updates, entries


def _over_live_prefix(entries, total: int, of_length):
    """`of_length(n)()` for the shortest of `SCATTER_SIZES` static
    prefix lengths `n` of a list of `total` that holds its `entries`
    live ones (they come first)."""
    step = -(-total // SCATTER_SIZES)
    return jax.lax.switch(
        jnp.maximum(entries - 1, 0) // step,
        [of_length(min((n + 1) * step, total))
         for n in range(SCATTER_SIZES)])


def _embed_bwd(dtype, residuals, cotangents):
    """Scatter-add of the live entries' cotangent rows into one
    table-shaped gradient: what a step needs whose Adam takes tables
    (a width the row-list kernel does not take, float32 rows; every
    other step hands the list itself to its Adam, `sorted_row_list`).
    On the chip a scatter is either unsorted and
    pays the memory's latency for every row (75 ns on a v5e), or sorted:
    one pass over the table (2.1 ms for java14m's token table) plus 11 ns
    a row, which is what XLA makes of `jnp.take`'s transpose. A loop of
    scatters pays one or the other once a block (measured, PERF.md PR
    25). So the ids are sorted once, as there, with the dead entries
    behind a key past the table's end (dropped), and ONE sorted scatter
    adds a prefix of the list: the shortest of `SCATTER_SIZES` static
    lengths that holds every live entry. The cotangents come by slot, as
    the outputs went, so a sorted key's `source` is a compact row."""
    table = residuals[0]
    keys, source, updates, entries = _sorted_entries(residuals, cotangents)

    def scatter_prefix(length):
        def scatter():
            update = jnp.take(updates, source[:length], axis=0)
            return jnp.zeros_like(table).at[keys[:length]].add(
                update.astype(table.dtype), indices_are_sorted=True,
                mode="drop")
        return scatter

    grad = _over_live_prefix(entries, keys.shape[0], scatter_prefix)
    return grad, None, None


embed_live_rows.defvjp(_embed_fwd, _embed_bwd)


def live_rows_and_entries(table: jax.Array, ids: Tuple[jax.Array, ...],
                          depth: jax.Array, dtype):
    """`embed_live_rows`'s outputs, and the `entries` that
    `sorted_row_list` needs beside their cotangents: the lookup taken
    OUT of the differentiated function, for a step whose optimizer
    takes the table's gradient as a list (training/step.py
    `_make_row_list_train_step`)."""
    return _embed_fwd(table, ids, depth, dtype)


def sorted_row_list(entries, cotangents) -> Tuple[jax.Array, jax.Array]:
    """The table's gradient as `(keys, rows)`, the first half of
    `_embed_bwd`: the keys of ALL entries sorted, a dead entry's a key
    past the table's end, and each key's cotangent row (the compute
    dtype, as the outputs were), moved by one gather over the shortest
    static prefix that holds every live entry; zero rows behind it, so
    the list is whole at one static length whatever the batch (what an
    all-gather of several chips' lists needs)."""
    keys, source, updates, live = _sorted_entries(entries, cotangents)
    total = keys.shape[0]

    def gather_prefix(length):
        def gather():
            # `source` is a permutation: no row to fill in, and no pass
            # over the gathered rows to look for one
            rows = updates.at[source[:length]].get(
                mode="promise_in_bounds")
            return jnp.pad(rows, ((0, total - length), (0, 0)))
        return gather

    return keys, _over_live_prefix(live, total, gather_prefix)


def live_block_ratio(context_valid_mask: np.ndarray, chips: int = 1,
                     chunk: int = 1) -> float:
    """Host-side (numpy) count of what the step runs over of a batch
    whose rows it orders by depth: live blocks, in whole chunks of
    `chunk`, over all blocks of the grid. `embed_live_rows`'s forward
    gathers block by block (`chunk` 1), the dense chain
    (ops/encode_live.py) runs `SLOT_CHUNK` slots a trip. With `chips` >
    1 the batch's rows are `chips` equal slices, each ordered and run
    on its own chip."""
    mask = np.asarray(context_valid_mask) > 0
    contexts = mask.shape[1]
    depth = np.where(mask.any(axis=1),
                     contexts - np.argmax(mask[:, ::-1], axis=1), 0)
    run = blocks = 0
    for rows in np.split(depth, chips):
        groups, across = _grid(len(rows), contexts)
        deepest = -np.sort(-rows)[::BLOCK_ROWS]
        live = int((-(-deepest // BLOCK_CONTEXTS)).sum())
        run += -(-live // chunk) * chunk
        blocks += groups * across
    return run / blocks
