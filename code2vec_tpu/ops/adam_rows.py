"""Dense Adam of one embedding table whose gradient is a sorted list of
rows, or several such lists, not a table.

A train batch touches a few per cent of a table's rows (java14m: ~180K
live entries against 1,301,136 token rows), yet dense Adam moves every
row: a row the batch did not touch still decays its moments and still
moves by its momentum. The table-shaped float32 gradient that carries
this to `optax` is 4 bytes a parameter written (zeros, then one sorted
scatter) and 4 read back by the update, and almost all of them say
"zero". `adam_rows_into_table` is the same update, operation for
operation in float32 (training/state.py `_scale_by_adam_nu_dtype`,
`optax.scale(-lr)`, `optax.apply_updates`), fed by what the lookup's
backward has BEFORE its scatter (ops/embed.py `sorted_row_list`): the
sorted keys, dead entries behind a key past the table's end, and the
cotangent rows in that order, in the compute dtype. A data mesh's chips
each make such a list and all-gather them: the update then takes `runs`
lists laid end to end, each sorted, and adds every run's rows, run by
run (on every chip in the same order, so the replicas stay bit-equal).

On a TPU it is one Pallas kernel that walks the table once, 16 bytes a
parameter. The grid runs over ITEMS, a (tile of table rows, chunk of
the list) pair each, tile by tile, within a tile run by run and within
a run chunk by chunk (`_schedule`: a tile's entries of a run are those
between its edges in the run's sorted keys; a tile has one item of a run
none of whose entries fall into it). The three blocks
of a tile stay where they are while its items pass, so the pipeline
moves each once. A tile's float32 gradient is built in VMEM, a PIECE of
128 entries at a time, by a one-hot `(rows, 128) @ (128, width)` product,
exact in one bfloat16 pass because the rows ARE in the compute dtype and
the accumulator is float32; a key of another tile (or a dead one)
matches no row. A run is whole chunks, so a piece lies within one run
and its keys are sorted: they lie between its first key and its last
(`_piece_edges`), and ONE product takes the piece in, over the fewest
rows of `LEVELS` that hold that span, from the span's first 128-row BAND
on. The
tile's last item forms the update and writes parameters and moments in
place. The ids are Zipf: more than half of a batch's entries name the
first thousand rows, and a row hit by thousands of entries is many
items of one tile and ONE band a piece, nothing else; a piece of the
tail spans half a tile, and a product costs the piece's way into the MXU
before the rows that stream past it, so one product over a thousand rows
beats nine over a band each. (Timed on a v5e against an add a row at a
dynamic sublane and against the whole tile for every piece, PERF.md
section 6, PR 43; against a product a band and other levels, PR 46: with
four runs the band loop took 10.3 ms for java14m's token table, this
7.6.)

Everywhere else it is the plain form: the scatter-add into zeros (sorted
only where the list is ONE run) and the same arithmetic, which is also
what the kernel is tested against (tests/test_adam_rows.py runs it
through the Pallas interpreter).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# Table rows a tile, list entries a chunk (one block of the pipeline),
# entries a one-hot product, and table rows a product. Tile and chunk are
# the best of the shapes timed on a v5e at java14m's tables that fit the
# default VMEM (PERF.md section 6, PR 43: the kernel alone 4.52 / 3.11 ms
# against 4.83 / 3.28 at 1,024 and 512 and 4.47 / 3.07 at 4,096 and 2,048
# under a raised VMEM limit; inside the step the shapes differ by 0.05 ms).
TILE = 2048
CHUNK = 1024
PIECE = 128
BAND = 128
# Table rows a piece's one product may cover, ascending; the tile itself
# is always the last.
LEVELS = (128, 512, 1024, 2048)
_LANES = 128


def kernel_takes(width: int, rows_dtype) -> bool:
    """Whether a TPU runs the kernel for a table of this width under
    cotangent rows of this dtype: 128 lanes, and bfloat16 rows (the
    one-hot product is exact in one bfloat16 pass and in no other). The
    one test of it: training/step.py `adam_row_list_tables` asks it which
    step to build, `adam_rows_into_table` which form to lower."""
    return width == _LANES and jnp.dtype(rows_dtype) == jnp.bfloat16


def adam_rows_into_table(table: jax.Array, mu: jax.Array, nu: jax.Array,
                         keys: jax.Array, rows: jax.Array,
                         bias1: jax.Array, bias2: jax.Array, *,
                         lr: float, b1: float, b2: float, eps: float,
                         runs: int = 1, name: str = "adam_rows"
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One Adam step of `table` `(R, W)` float32 with moments `mu`, `nu`
    (any float storage dtype) under the gradient `zeros.at[keys].add(
    rows)`: `keys` `(runs * L,)` int32, `runs` lists of `L` laid end to
    end and each SORTED, an entry with a key of `R` or more is dead;
    `rows` `(runs * L, W)` in that order. `bias1` / `bias2` are
    `1 - b1**t` / `1 - b2**t` of the step being taken. Returns the new
    (table, mu, nu); donated inputs are updated in place. `name` is the
    kernel's in a profile."""
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, runs=runs)
    if not kernel_takes(table.shape[1], rows.dtype):
        return _plain(table, mu, nu, keys, rows, bias1, bias2, **hyper)
    return jax.lax.platform_dependent(
        table, mu, nu, keys, rows, bias1, bias2,
        tpu=functools.partial(_pallas, name=name, **hyper),
        default=functools.partial(_plain, **hyper))


def _update(p, m, v, g, bias1, bias2, *, lr, b1, b2, eps):
    """What `_scale_by_adam_nu_dtype`, `optax.scale(-lr)` and
    `optax.apply_updates` make of a float32 gradient `g`, in their order;
    the moments are rounded to their storage AFTER the update is formed."""
    mean = b1 * m.astype(g.dtype) + (1.0 - b1) * g
    square = b2 * v.astype(g.dtype) + (1.0 - b2) * (g * g)
    step = (mean / bias1) / (jnp.sqrt(square / bias2) + eps)
    return p + (-lr) * step, mean.astype(m.dtype), square.astype(v.dtype)


def _plain(table, mu, nu, keys, rows, bias1, bias2, *, runs: int = 1,
           **hyper):
    # several sorted runs end to end are not one sorted list
    grad = jnp.zeros_like(table).at[keys].add(
        rows.astype(table.dtype), indices_are_sorted=runs == 1, mode="drop")
    return _update(table, mu, nu, grad, bias1, bias2, **hyper)


def _entries_below(keys: jax.Array, edges: jax.Array, chunk: int):
    """How many of the sorted `keys` (whole chunks of them) lie below
    each of `edges`: `searchsorted(keys, edges)` in two levels of
    compare-and-count, the chunk whose first key is the last one below
    the edge and then that chunk's keys. `jnp.searchsorted`'s bisection
    is a loop of some twenty dependent gathers, 0.3 ms a table on a v5e
    (my chip runs, PR 43)."""
    heads = keys[::chunk]
    at = jnp.maximum(jnp.sum(heads[None, :] < edges[:, None], axis=1,
                             dtype=jnp.int32) - 1, 0)
    within = jnp.sum(keys.reshape(-1, chunk)[at] < edges[:, None], axis=1,
                     dtype=jnp.int32)
    return at * chunk + within


def _schedule(keys: jax.Array, table_rows: int, tile: int, chunk: int,
              runs: int = 1):
    """The grid's items, all int32: `tile_of[i]` and `chunk_of[i]` of
    item `i` (`runs * tiles + chunks` of them, the static bound; the
    ones past `total` repeat the last and do nothing), and
    `offsets[r * (tiles + 1) + t]`, the first list entry of run `r`
    whose key is in tile `t` or later (at `t = tiles`: the end of the
    run's live entries). A tile's items are its chunks of the first run,
    then of the second, and so on; chunks and entries count from the
    head of the whole list."""
    tiles, chunks = -(-table_rows // tile), keys.shape[0] // chunk // runs
    edges = jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * tile,
                        table_rows)
    offsets = jax.vmap(lambda run: _entries_below(run, edges, chunk))(
        keys.reshape(runs, -1))
    first = jnp.minimum(offsets[:, :-1] // chunk, chunks - 1)
    last = jnp.maximum(first, (offsets[:, 1:] - 1) // chunk)
    run_head = jnp.arange(runs, dtype=jnp.int32)[:, None] * chunks
    # by (tile, run) pair, in the order the items go
    first, last = ((x + run_head).T.reshape(-1) for x in (first, last))
    counts = last - first + 1
    starts = jnp.cumsum(counts) - counts
    item = jnp.arange(runs * (tiles + chunks), dtype=jnp.int32)
    # the pair whose items hold item `i`: the last that starts at or
    # before it
    pair_of = jnp.sum(starts[None, :] <= item[:, None], axis=1,
                      dtype=jnp.int32) - 1
    chunk_of = jnp.minimum(first[pair_of] + item - starts[pair_of],
                           last[pair_of])
    return (jax.lax.div(pair_of, runs), chunk_of,
            (starts[-1] + counts[-1])[None],
            (offsets + run_head * chunk).reshape(-1))


def _piece_edges(keys: jax.Array) -> jax.Array:
    """The first key of every `PIECE` entries, then the last of each: a
    piece lies within one run, whose keys are sorted, so piece `j`'s lie
    in `[edges[j], edges[pieces + j]]`."""
    return jnp.concatenate([keys[::PIECE], keys[PIECE - 1::PIECE]])


def _kernel(tile_of, chunk_of, total, offsets, edges, bias_ref, keys_ref,
            rows_ref, p_ref, mu_ref, nu_ref, p_out, mu_out, nu_out, grad_ref,
            *, hyper, tiles, run_chunks, levels):
    from jax.experimental import pallas as pl
    item, items = pl.program_id(0), pl.num_programs(0)
    tile, chunk = p_ref.shape[0], rows_ref.shape[0]
    t, c = tile_of[item], chunk_of[item]
    pieces = edges.shape[0] // 2
    live = item < total[0]
    opens = (item == 0) | (tile_of[jnp.maximum(item - 1, 0)] != t)
    closes = ((item == total[0] - 1)
              | (tile_of[jnp.minimum(item + 1, items - 1)] != t))

    @pl.when(live & opens)
    def _():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    def add_piece(n, _):
        row = keys_ref[0, pl.ds(n, 1), :] - t * tile               # (1, PIECE)
        piece = rows_ref[pl.ds(pl.multiple_of(n * PIECE, PIECE), PIECE), :]

        # the piece's keys are sorted: they lie between its first key and
        # its last, so only the bands of that span can be hit (a piece of
        # one hot row is one band; one with no key of this tile names
        # none). ONE product takes the piece in, over the fewest rows of
        # `levels` that hold the span: a product's cost is the piece's way
        # into the MXU before it is the rows that stream past it
        j = c * (chunk // PIECE) + n
        first = jnp.maximum(edges[j] - t * tile, 0)
        last = jnp.minimum(edges[pieces + j] - t * tile, tile - 1)
        lowest = jax.lax.div(first, BAND)
        bands = jax.lax.div(last, BAND) - lowest + 1

        for under, rows_at_once in zip((0,) + levels, levels):
            @pl.when((bands > under // BAND) & (bands <= rows_at_once // BAND))
            def _(rows_at_once=rows_at_once):
                # one-hot: a key outside these rows, of another tile or
                # a dead one matches no row
                at_row = pl.multiple_of(
                    jnp.minimum(lowest * BAND, tile - rows_at_once), BAND)
                hit = at_row + jax.lax.broadcasted_iota(
                    jnp.int32, (rows_at_once, PIECE), 0) == row
                grad_ref[pl.ds(at_row, rows_at_once), :] += jnp.dot(
                    jnp.where(hit, 1.0, 0.0).astype(piece.dtype), piece,
                    preferred_element_type=jnp.float32)

    @pl.when(live)
    def _():
        # the chunk's pieces that hold an entry of this tile, among its
        # run's
        run = jax.lax.div(c, run_chunks) * (tiles + 1)
        mine = [jnp.clip(offsets[run + at] - c * chunk, 0, chunk)
                for at in (t, t + 1)]
        jax.lax.fori_loop(jax.lax.div(mine[0], PIECE),
                          jax.lax.div(mine[1] + PIECE - 1, PIECE),
                          add_piece, None)

    @pl.when(live & closes)
    def _():
        p_out[...], mu_out[...], nu_out[...] = _update(
            p_ref[...], mu_ref[...], nu_ref[...], grad_ref[...],
            bias_ref[0], bias_ref[1], **hyper)


def _pallas(table, mu, nu, keys, rows, bias1, bias2, *, name: str,
            runs: int = 1, interpret: bool = False, tile: int = TILE,
            chunk: int = CHUNK, levels: Tuple[int, ...] = LEVELS, **hyper):
    # imported where a step is traced around the kernel (ops/head_ce.py)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    # a table under one tile (a toy's) is one ragged tile of whole bands
    tile = min(tile, -(-table.shape[0] // BAND) * BAND)
    width = table.shape[1]
    fill = -(keys.shape[0] // runs) % chunk
    if fill:    # every run whole chunks, the filling dead: no chunk, and
        # so no piece, holds entries of two runs
        keys = jnp.pad(keys.reshape(runs, -1), ((0, 0), (0, fill)),
                       constant_values=table.shape[0]).reshape(-1)
        rows = jnp.pad(rows.reshape(runs, -1, width),
                       ((0, 0), (0, fill), (0, 0))).reshape(-1, width)
    schedule = _schedule(keys, table.shape[0], tile, chunk, runs)
    by_tile = pl.BlockSpec((tile, width),
                           lambda i, tile_of, *_: (tile_of[i], 0))
    # the last level is the tile, whatever the table
    levels = tuple(sorted({min(level, tile) for level in levels + (tile,)}))
    return pl.pallas_call(
        functools.partial(_kernel, hyper=hyper, levels=levels,
                          tiles=-(-table.shape[0] // tile),
                          run_chunks=keys.shape[0] // chunk // runs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(schedule[0].shape[0],),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, chunk // PIECE, PIECE),
                             lambda i, _, chunk_of, *__: (chunk_of[i], 0, 0)),
                pl.BlockSpec((chunk, width),
                             lambda i, _, chunk_of, *__: (chunk_of[i], 0)),
                by_tile, by_tile, by_tile],
            out_specs=[by_tile, by_tile, by_tile],
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (table, mu, nu)],
        # the operands count from the first scalar: table, mu, nu
        input_output_aliases={8: 0, 9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name, interpret=interpret,
    )(*schedule, _piece_edges(keys),
      jnp.stack([bias1, bias2]).astype(jnp.float32),
      keys.reshape(-1, chunk // PIECE, PIECE), rows, table, mu, nu)
