"""Dense Adam of a table from the backward's sorted row list
(ops/adam_rows.py), held against what it replaces: the scatter-add into
a table of zeros, then `_scale_by_adam_nu_dtype` + `optax.scale(-lr)` +
`optax.apply_updates` on that table. A row no entry touches must come
out BIT-equal (`b1 * mu + (1 - b1) * 0` is `b1 * mu`); a touched row
within float32 summation order. The TPU kernel runs here through the
Pallas interpreter, at a small tile, for one sorted list and for several
laid end to end. And the train step built around it (training/step.py),
on one chip and on a data mesh whose chips all-gather their lists,
against the step that builds table-shaped gradients."""

import functools

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.ops import adam_rows, embed
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
from code2vec_tpu.training import step as step_mod
from code2vec_tpu.training.state import (
    _scale_by_adam_nu_dtype, create_train_state, make_optimizer,
)
from code2vec_tpu.training.step import TrainStepBuilder

from test_train_step_selection import _plain_step, _train_an_epoch

HYPER = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)
WIDTH = 128
TILE, CHUNK = 256, 128          # the interpreter's; the chip's are larger
STEP = 3                        # the Adam count of the step being taken


def _keys(rng, rows, live, slots):
    keys = np.full(slots, rows, np.int32)
    keys[:len(live)] = live
    return np.sort(keys)


def _heavy_duplicates(rng):
    """One row hit by more entries than a chunk holds, across a chunk's
    edge, beside rows hit once."""
    rows = 600
    live = np.concatenate([np.full(300, 77), rng.integers(0, rows, 100)])
    return rows, _keys(rng, rows, live, 512)


def _tile_edges(rng):
    """Entries on each tile's first and last row, and nowhere else."""
    rows = 3 * TILE
    live = np.repeat([0, TILE - 1, TILE, 2 * TILE - 1, 2 * TILE, rows - 1], 3)
    return rows, _keys(rng, rows, live, 256)


def _ragged_last_tile(rng):
    """Rows no tile divides; the last row is hit, and the key past the
    end falls inside the last tile's block."""
    rows = 2 * TILE + 37
    live = np.concatenate([rng.integers(0, rows, 200), [rows - 1] * 5])
    return rows, _keys(rng, rows, live, 384)


def _mostly_dead(rng):
    """A few live entries before chunks of dead ones."""
    rows = 520
    return rows, _keys(rng, rows, rng.integers(0, rows, 9), 640)


def _empty_list(rng):
    rows = 300
    return rows, _keys(rng, rows, [], 256)


def _not_whole_chunks(rng):
    """A list that is no multiple of the chunk, every entry live."""
    rows = 700
    return rows, _keys(rng, rows, rng.integers(0, rows, 333), 333)


def _under_one_tile(rng):
    """A table (a toy's) of fewer rows than a tile: one ragged tile of
    whole bands."""
    rows = 100
    return rows, _keys(rng, rows, rng.integers(0, rows, 60), 128)


def _zipf_runs(runs, rng):
    """`runs` sorted lists end to end, as many chips' lists arrive: Zipf
    keys, one hot row hit in every run, a tile no run touches, a ragged
    last tile whose last row is hit, and (of four) one run all dead.
    Each run is no multiple of the chunk."""
    rows, per_run = 3 * TILE + 37, 3 * CHUNK + 50
    lists = []
    for run in range(runs):
        live = np.minimum(rng.zipf(1.3, 2 * CHUNK + 17 * run), rows - 1)
        live = live[(live < TILE) | (live >= 2 * TILE)]
        live = np.concatenate([live, np.full(CHUNK // 2, 77), [rows - 1]])
        lists.append(_keys(rng, rows, [] if run == 2 else live, per_run))
    return rows, np.concatenate(lists), runs


CASES = {f.__name__.lstrip("_"): f for f in (
    _heavy_duplicates, _tile_edges, _ragged_last_tile, _mostly_dead,
    _empty_list, _not_whole_chunks, _under_one_tile)}
CASES.update({f"zipf_runs_{n}": functools.partial(_zipf_runs, n)
              for n in (1, 2, 4)})


def _what_it_replaces(table, mu, nu, keys, rows):
    """The table-shaped gradient of the entries, whatever their order,
    through the optimizer's own transform, from a state whose count
    stands at `STEP - 1`."""
    grad = jnp.zeros_like(table).at[keys].add(
        rows.astype(jnp.float32), mode="drop")
    optimizer = optax.chain(
        _scale_by_adam_nu_dtype(HYPER["b1"], HYPER["b2"], HYPER["eps"],
                                mu.dtype, nu.dtype),
        optax.scale(-HYPER["lr"]))
    state = (optax.ScaleByAdamState(
        count=jnp.asarray(STEP - 1, jnp.int32), mu=mu, nu=nu),
        optax.EmptyState())
    updates, state = optimizer.update(grad, state, table)
    return optax.apply_updates(table, updates), state[0].mu, state[0].nu


def _the_kernel(*args, runs=1):
    return adam_rows._pallas(*args, name="adam_rows_test", interpret=True,
                             tile=TILE, chunk=CHUNK, runs=runs, **HYPER)


def _the_plain_form(*args, runs=1):
    return adam_rows._plain(*args, runs=runs, **HYPER)


def _against_the_optimizer(*args, runs):
    """Primitive by primitive, as written: under one `jit` each the
    CPU's compiler contracts `a * b + c` in one program and not in the
    other, and a few elements in ten thousand move by one step of
    float32."""
    with jax.disable_jit():
        return (_the_plain_form(*args, runs=runs),
                _what_it_replaces(*args[:5]))


def _kernel_against_the_plain_form(*args, runs):
    return (jax.jit(functools.partial(_the_kernel, runs=runs))(*args),
            jax.jit(functools.partial(_the_plain_form, runs=runs))(*args))


PAIRS = {"plain_form_and_optimizer": _against_the_optimizer,
         "kernel_and_plain_form": _kernel_against_the_plain_form}


@pytest.mark.parametrize("moments", ["bfloat16", "float32"])
@pytest.mark.parametrize("pair", sorted(PAIRS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_row_list_adam_is_the_scatter_and_the_optimizers_update(
        case, pair, moments):
    """Two links of one chain: the plain form against the optimizer's
    own transform on the scattered table, and the kernel (through the
    interpreter) against the plain form. The `zipf_runs` cases hand
    both several sorted runs end to end."""
    rng = np.random.default_rng(sorted(CASES).index(case))
    table_rows, keys, *runs = CASES[case](rng)
    table = jnp.asarray(rng.normal(size=(table_rows, WIDTH)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=table.shape) * 0.1, moments)
    nu = jnp.asarray(rng.random(size=table.shape) * 0.01, moments)
    rows = jnp.asarray(rng.normal(size=(len(keys), WIDTH)), jnp.bfloat16)
    keys = jnp.asarray(keys)
    count = jnp.asarray(STEP, jnp.float32)
    got, want = PAIRS[pair](
        table, mu, nu, keys, rows,
        1.0 - HYPER["b1"] ** count, 1.0 - HYPER["b2"] ** count,
        runs=runs[0] if runs else 1)
    touched = np.zeros(table_rows, bool)
    touched[np.asarray(keys)[np.asarray(keys) < table_rows]] = True
    for name, g, w in zip(("table", "mu", "nu"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g[~touched], w[~touched],
                                      err_msg=f"{name}, untouched rows")
        # a bfloat16 moment may round a sum of another order one step off
        step = 2.0 ** -8 if (moments == "bfloat16" and name != "table") \
            else 1e-5
        np.testing.assert_allclose(g[touched], w[touched], rtol=step,
                                   atol=1e-6, err_msg=f"{name}, touched")
    if case == "empty_list":
        assert not touched.any()


@pytest.mark.parametrize("runs", [1, 2])
def test_a_piece_goes_in_by_one_product_at_every_level(runs):
    """At the chip's own tile and chunk: pieces whose keys span one band
    (a hot row), a few, half a tile and a whole one each take the level
    of `LEVELS` that holds their span, pieces at the tile's end take it
    from the end back, and the table is the plain form's."""
    rng = np.random.default_rng(11)
    tile, table_rows = adam_rows.TILE, 2 * adam_rows.TILE + 300
    assert adam_rows.LEVELS[-1] == tile and len(adam_rows.LEVELS) > 2
    spans = [1, 3 * 128, 7 * 128, tile, 128, 5 * 128]
    lists = []
    for run in range(runs):
        live = [np.full(128, 5 + run)]
        for n, span in enumerate(spans):
            start = (n * 700 + 130 * run) % (table_rows - span)
            live.append(np.sort(rng.integers(start, start + span, 128)))
        live.append(np.sort(rng.integers(table_rows - 200, table_rows, 100)))
        lists.append(_keys(rng, table_rows, np.concatenate(live),
                           2 * adam_rows.CHUNK))
    keys = jnp.asarray(np.concatenate(lists))
    table = jnp.asarray(rng.normal(size=(table_rows, WIDTH)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=table.shape) * 0.1, jnp.bfloat16)
    nu = jnp.asarray(rng.random(size=table.shape) * 0.01, jnp.bfloat16)
    rows = jnp.asarray(rng.normal(size=(len(keys), WIDTH)), jnp.bfloat16)
    count = jnp.asarray(STEP, jnp.float32)
    args = (table, mu, nu, keys, rows, 1.0 - HYPER["b1"] ** count,
            1.0 - HYPER["b2"] ** count)
    got = jax.jit(lambda *a: adam_rows._pallas(
        *a, name="adam_rows_test", interpret=True, runs=runs, **HYPER))(*args)
    want = jax.jit(functools.partial(_the_plain_form, runs=runs))(*args)
    for name, g, w in zip(("table", "mu", "nu"), got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=2.0 ** -8 if name != "table" else 1e-5, atol=1e-6,
            err_msg=name)


@pytest.mark.parametrize("rows, width, dtype, kernel", [
    (64, 8, jnp.bfloat16, False), (2048, 128, jnp.float32, False),
    (2048, 256, jnp.bfloat16, False), (64, 128, jnp.bfloat16, True),
], ids=["narrow", "float32_rows", "wide", "the_kernels"])
def test_one_test_says_what_the_kernel_takes(monkeypatch, rows, width,
                                             dtype, kernel):
    """128-wide tables under rows in bfloat16, of any number of rows;
    anything else is the scatter and the update wherever it is lowered,
    and `kernel_takes` is what says so to the step's choice too."""
    asked = []
    monkeypatch.setattr(
        adam_rows, "_pallas",
        lambda *args, name, **hyper: asked.append(name) or adam_rows._plain(
            *args, **hyper))
    assert adam_rows.kernel_takes(width, dtype) == kernel
    rng = np.random.default_rng(0)
    count = jnp.asarray(STEP, jnp.float32)
    table = jnp.asarray(rng.normal(size=(rows, width)), jnp.float32)
    keys = jnp.asarray(np.sort(rng.integers(0, rows + 1, 32)), jnp.int32)
    updates = jnp.asarray(rng.normal(size=(32, width)), dtype)
    mu = nu = jnp.zeros(table.shape, jnp.bfloat16)
    got = jax.jit(functools.partial(adam_rows.adam_rows_into_table, **HYPER))(
        table, mu, nu, keys, updates, 1.0 - HYPER["b1"] ** count,
        1.0 - HYPER["b2"] ** count)
    # under `jit`, `platform_dependent` traces the TPU's form as well
    assert asked == (["adam_rows"] if kernel else [])
    want = _what_it_replaces(table, mu, nu, keys, updates)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)


def _schedule_of_one_list(keys, table_rows, tile, chunk):
    """`_schedule` as it stood while the kernel took ONE sorted list (PR
    43), word for word: what one run's must still be."""
    tiles, chunks = -(-table_rows // tile), keys.shape[0] // chunk
    edges = jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * tile,
                        table_rows)
    offsets = adam_rows._entries_below(keys, edges, chunk)
    first = jnp.minimum(offsets[:-1] // chunk, chunks - 1)
    last = jnp.maximum(first, (offsets[1:] - 1) // chunk)
    counts = last - first + 1
    starts = jnp.cumsum(counts) - counts
    item = jnp.arange(tiles + chunks, dtype=jnp.int32)
    tile_of = jnp.sum(starts[None, :] <= item[:, None], axis=1,
                      dtype=jnp.int32) - 1
    chunk_of = jnp.minimum(first[tile_of] + item - starts[tile_of],
                           last[tile_of])
    return tile_of, chunk_of, (starts[-1] + counts[-1])[None], offsets


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_runs_schedule_is_the_one_lists(case):
    """With one run the grid's items are, array for array, those the
    kernel had when it took one list."""
    table_rows, keys, *_ = CASES[case](np.random.default_rng(7))
    keys = np.concatenate([keys, np.full(-len(keys) % CHUNK, table_rows,
                                         np.int32)])
    tile = min(TILE, -(-table_rows // 128) * 128)
    for got, want in zip(
            adam_rows._schedule(jnp.asarray(keys), table_rows, tile, CHUNK),
            _schedule_of_one_list(jnp.asarray(keys), table_rows, tile,
                                  CHUNK)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("runs", [1, 2, 4])
def test_the_schedule_gives_every_tile_its_chunks_once(runs):
    """Every tile has at least one item a run, its items go run by run
    and their chunks cover its entries of each run, tiles never go back
    and chunks go back only where the next tile starts again at the
    first run, and the items past the total repeat the last."""
    rng = np.random.default_rng(1)
    rows, per_run = 5 * TILE + 9, 1024
    chunks = per_run // CHUNK
    keys = np.concatenate([_keys(rng, rows, np.concatenate(
        [np.full(500 - 100 * run, 3),
         rng.integers(4 * TILE, rows, 40 + run)]), per_run)
        for run in range(runs)])
    tile_of, chunk_of, total, offsets = (
        np.asarray(x) for x in adam_rows._schedule(
            jnp.asarray(keys), rows, TILE, CHUNK, runs))
    total = int(total[0])
    assert len(tile_of) == runs * (6 + chunks) and total <= len(tile_of)
    assert sorted(set(tile_of[:total])) == list(range(6))
    assert (np.diff(tile_of) >= 0).all()
    assert (tile_of[total:] == 5).all()
    assert (chunk_of[total:] == chunk_of[total - 1]).all()
    offsets = offsets.reshape(runs, 7)
    for run in range(runs):
        live = 540 - 99 * run
        assert offsets[run, 0] == run * per_run
        assert offsets[run, -1] == run * per_run + live
        assert (np.diff(offsets[run]) >= 0).all()
    run_of = chunk_of // chunks
    for t in range(6):
        mine = tile_of[:total] == t
        np.testing.assert_array_equal(
            np.unique(run_of[:total][mine]), np.arange(runs))
        assert (np.diff(run_of[:total][mine]) >= 0).all()
        for run in range(runs):
            its = chunk_of[:total][mine & (run_of[:total] == run)]
            np.testing.assert_array_equal(
                its, np.arange(its[0], its[-1] + 1))
            if offsets[run, t + 1] > offsets[run, t]:
                assert its[0] * CHUNK <= offsets[run, t]
                assert offsets[run, t + 1] <= (its[-1] + 1) * CHUNK


# ------------------------------------------------------------- the step

B, M = 10, 7
DIMS = ModelDims(token_vocab_size=300, path_vocab_size=280,
                 target_vocab_size=24, token_dim=WIDTH, path_dim=WIDTH)


@pytest.fixture
def toy_blocks(monkeypatch):
    monkeypatch.setattr(embed, "BLOCK_ROWS", 4)
    monkeypatch.setattr(embed, "BLOCK_CONTEXTS", 3)
    monkeypatch.setattr(embed, "SCATTER_SIZES", 3)
    monkeypatch.setattr(embed, "SLOT_CHUNK", 2)


def _toy(mesh=None, **overrides):
    config = Config(**{**dict(
        train_data_path_prefix="unused", train_batch_size=B, max_contexts=M,
        dropout_keep_rate=1.0), **overrides})
    module = Code2VecModule(dims=DIMS, dropout_keep_rate=1.0,
                            compute_dtype=jnp.dtype(config.compute_dtype))
    optimizer = make_optimizer(config)
    state = create_train_state(module, optimizer, jax.random.PRNGKey(0),
                               mesh=mesh, config=config)
    return TrainStepBuilder(module, optimizer, config, mesh=mesh), state


def _toy_batch(seed=0):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, M + 1, B)
    counts[0], counts[1] = M, 0
    mask = (np.arange(M)[None, :] < counts[:, None]).astype(np.float32)
    ids = [np.where(mask > 0, rng.integers(1, hi, (B, M)), 0).astype(
        np.int32) for hi in (300, 280, 300)]
    ids[0][0, :3] = 5           # one token row hit again and again
    return (ids[0], ids[1], ids[2], mask,
            rng.integers(1, 24, (B,)).astype(np.int32), counts > 0)


def _first_moment(state):
    return step_mod._adam_moments(state.opt_state).mu


def _through_the_interpreter(*args, name, **hyper):
    return adam_rows._pallas(*args, name=name, interpret=True, tile=TILE,
                             chunk=CHUNK, **hyper)


def _three_steps_agree(step, state, table_step, table_state, batch_of):
    """Three steps of both from one seed each: every loss, Adam's first
    moment after step one and the parameters after step three agree
    within float32 summation order. Returns `step`'s last state."""
    for n in range(3):
        batch, rng = batch_of(n), jax.random.PRNGKey(n)
        state, loss = step(state, *batch, rng)
        table_state, table_loss = table_step(table_state, *batch, rng)
        np.testing.assert_allclose(float(loss), float(table_loss), rtol=1e-6)
        pairs = {"mu": (_first_moment(state), _first_moment(table_state))
                 } if n == 0 else {}
        if n == 2:
            pairs["parameters"] = (state.params, table_state.params)
        for what, (got, want) in pairs.items():
            assert set(got) == set(want)
            for key in want:
                np.testing.assert_allclose(
                    np.asarray(got[key], np.float32),
                    np.asarray(want[key], np.float32), rtol=2.0 ** -7,
                    atol=1e-6, err_msg=f"step {n + 1} {what} {key}")
    return state


@pytest.mark.parametrize("moments", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_three_one_chip_steps_equal_the_table_shaped_steps(
        monkeypatch, toy_blocks, form, moments):
    """From one seed: each step's loss, Adam's first moment after step
    one and the parameters after step three of the one-chip step equal
    those of the step that builds table-shaped gradients (a mesh's, here
    forced onto one chip), within float32 summation order; the state
    keeps its tree, shapes and dtypes, and its one count."""
    dtypes = dict(adam_mu_dtype=moments, adam_nu_dtype=moments)
    if form == "kernel":
        monkeypatch.setattr(step_mod, "adam_rows_into_table",
                            _through_the_interpreter)
    builder, state = _toy(**dtypes)
    assert step_mod.adam_row_list_tables(builder.config, None) == 2
    step = builder.make_train_step(state)
    monkeypatch.setattr(step_mod, "adam_row_list_tables", lambda c, m: 0)
    table_builder, table_state = _toy(**dtypes)
    table_step = table_builder.make_train_step(table_state)
    tree = jax.tree.structure(state)
    kinds = [(x.shape, x.dtype) for x in jax.tree.leaves(state)]
    state = _three_steps_agree(step, state, table_step, table_state,
                               lambda n: _toy_batch(seed=n))
    assert jax.tree.structure(state) == tree
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(state)] == kinds
    assert int(state.step) == 3
    assert int(step_mod._adam_moments(state.opt_state).count) == 3


def _refuse(*args, **kwargs):
    raise AssertionError("the row-list step's alone")


def _mesh_batch(seed, chips):
    """`chips` toy batches, one a chip, row slices of one global batch."""
    return tuple(np.concatenate(x) for x in zip(
        *(_toy_batch(seed=chips * seed + chip) for chip in range(chips))))


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_three_steps_of_a_data_mesh_equal_the_table_shaped_steps(
        monkeypatch, toy_blocks, form):
    """On four (forced host) devices under `dp`: each step's loss,
    Adam's first moment after step one and the parameters after step
    three of the step that all-gathers the chips' lists equal those of
    the step whose chips all-reduce table-shaped gradients, within
    float32 summation order. And a drift between replicas is a wrong
    result: after the three steps every chip's copy of every table and
    moment is BIT-equal to chip 0's."""
    if form == "kernel":
        monkeypatch.setattr(step_mod, "adam_rows_into_table",
                            _through_the_interpreter)
    mesh = make_mesh(MeshPlan(4, 1, 1))
    builder, state = _toy(mesh=mesh, dp=4, train_batch_size=4 * B)
    assert step_mod.adam_row_list_tables(builder.config, mesh) == 2
    assert step_mod.row_list_runs(builder.config, mesh) == 4
    step = builder.make_train_step(state)
    monkeypatch.setattr(step_mod, "adam_row_list_tables", lambda c, m: 0)
    table_builder, table_state = _toy(mesh=mesh, dp=4,
                                      train_batch_size=4 * B)
    table_step = table_builder.make_train_step(table_state)
    state = _three_steps_agree(step, state, table_step, table_state,
                               lambda n: _mesh_batch(n, 4))
    assert int(state.step) == 3
    moments = step_mod._adam_moments(state.opt_state)
    for name in ("token_embedding", "path_embedding", "target_embedding"):
        for what, leaf in (("table", state.params[name]),
                           ("mu", moments.mu[name]),
                           ("nu", moments.nu[name])):
            copies = [np.asarray(shard.data)
                      for shard in leaf.addressable_shards]
            assert len(copies) == 4 and copies[0].shape == leaf.shape
            for chip, copy in enumerate(copies[1:], 1):
                np.testing.assert_array_equal(
                    copy.view(np.uint8), copies[0].view(np.uint8),
                    err_msg=f"{name} {what}: chip {chip} against chip 0")


@pytest.mark.parametrize("plan", [(2, 1, 1), (2, 2, 1)],
                         ids=["dp2", "dp2_tp2"])
def test_a_mesh_that_shards_a_table_still_builds_table_shaped_gradients(
        monkeypatch, toy_blocks, plan):
    """The choice is the mesh's: where `model` shards the tables the
    step lowers without the list or its Adam; one chip's, and a data
    mesh's of two (forced host) devices, do not lower without them."""
    for op in ("sorted_row_list", "live_rows_and_entries",
               "adam_rows_into_table"):
        monkeypatch.setattr(step_mod, op, _refuse)
    mesh = make_mesh(MeshPlan(*plan))
    builder, state = _toy(mesh=mesh, dp=plan[0], tp=plan[1])
    lower = lambda: builder.make_train_step(state).lower(
        state, *_toy_batch(), jax.random.PRNGKey(1))
    if plan[1] == 1:
        with pytest.raises(AssertionError, match="row-list step's alone"):
            lower()
    else:
        lower()
    builder, state = _toy()
    with pytest.raises(AssertionError, match="row-list step's alone"):
        builder.make_train_step(state).lower(
            state, *_toy_batch(), jax.random.PRNGKey(1))


@pytest.mark.parametrize("plan, overrides, tables, runs", [
    (None, {}, 2, 1), ((1, 1, 1), {}, 2, 1),
    ((2, 1, 1), {}, 2, 2), ((4, 1, 1), {}, 2, 4),
    ((1, 2, 1), {}, 0, 0), ((2, 1, 2), {}, 0, 0),
    (None, {"use_sparse_embedding_update": True}, 0, 0),
    (None, {"adam_nu_dtype": "float32"}, 0, 0),
    ((4, 1, 1), {"adam_nu_dtype": "float32"}, 0, 0),
    (None, {"adam_nu_dtype": "float32", "adam_mu_dtype": "float32"}, 2, 1),
    (None, {"compute_dtype": "float32"}, 0, 0),
    ((4, 1, 1), {"compute_dtype": "float32"}, 0, 0),
    (None, {"default_embeddings_size": 64}, 0, 0),
    (None, {"path_embeddings_size": 256}, 0, 0),
], ids=["no_mesh", "mesh_of_one", "dp2", "dp4", "tp2", "dp2_cp2", "sparse",
        "stock_adam_bf16_mu", "dp4_stock_adam_bf16_mu", "stock_adam_float32",
        "float32_rows", "dp4_float32_rows", "narrow_tables",
        "wide_path_table"])
def test_which_steps_hand_adam_a_row_list(plan, overrides, tables, runs):
    """Whole tables on every chip, one chip or a data mesh of any size,
    and no mesh that shards anything else; tables the kernel takes (128
    wide, rows in bfloat16), so the gauge never says "a row list" over a
    scatter; and not the one optimizer whose arithmetic the list's Adam
    does not follow (stock optax.adam over a bfloat16 first moment). One
    Adam then takes every chip's list."""
    config = Config(train_data_path_prefix="unused", **overrides)
    mesh = None if plan is None else make_mesh(MeshPlan(*plan))
    assert step_mod.adam_row_list_tables(config, mesh) == tables
    assert step_mod.row_list_runs(config, mesh) == runs


@pytest.mark.parametrize("plan, tables, runs", [
    (None, 2, 1), ((2, 1, 1), 2, 2), ((4, 1, 1), 2, 4), ((1, 2, 1), 0, 0),
], ids=["no_mesh", "dp2", "dp4", "tp2"])
def test_the_trainer_says_how_many_tables_adam_takes_as_a_row_list(
        tiny_config, plan, tables, runs):
    """`train_adam_row_list_tables` and `train_row_list_runs`, and the
    same numbers in the first step's log line."""
    lines = []
    tiny_config.verbose_mode = 0
    tiny_config.log = lines.append
    mesh = None if plan is None else make_mesh(MeshPlan(*plan))
    registry = obs.default_registry()
    gauges = [registry.gauge(name) for name in (
        "train_adam_row_list_tables", "train_row_list_runs")]
    for gauge in gauges:
        gauge.set(-1)
    _train_an_epoch(tiny_config, _plain_step, batches=1, rows=8, mesh=mesh)
    assert [gauge.value for gauge in gauges] == [tables, runs]
    first, = [ln for ln in lines if ln.startswith("First train step")]
    assert (f"Adam takes {tables} table(s)' gradient as a row list, "
            f"{runs} chip(s)' lists each") in first
