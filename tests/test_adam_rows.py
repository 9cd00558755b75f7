"""Dense Adam of a table from the backward's sorted row list
(ops/adam_rows.py), held against what it replaces: the scatter-add into
a table of zeros, then `_scale_by_adam_nu_dtype` + `optax.scale(-lr)` +
`optax.apply_updates` on that table. A row no entry touches must come
out BIT-equal (`b1 * mu + (1 - b1) * 0` is `b1 * mu`); a touched row
within float32 summation order. The TPU kernel runs here through the
Pallas interpreter, at a small tile. And the one-chip train step built
around it (training/step.py) against the table-shaped step a mesh of
chips keeps."""

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


CASES = {f.__name__.lstrip("_"): f for f in (
    _heavy_duplicates, _tile_edges, _ragged_last_tile, _mostly_dead,
    _empty_list, _not_whole_chunks, _under_one_tile)}


def _what_it_replaces(table, mu, nu, keys, rows):
    """The table-shaped gradient through the optimizer's own transform,
    from a state whose count stands at `STEP - 1`."""
    grad = jnp.zeros_like(table).at[keys].add(
        rows.astype(jnp.float32), indices_are_sorted=True, mode="drop")
    optimizer = optax.chain(
        _scale_by_adam_nu_dtype(HYPER["b1"], HYPER["b2"], HYPER["eps"],
                                mu.dtype, nu.dtype),
        optax.scale(-HYPER["lr"]))
    state = (optax.ScaleByAdamState(
        count=jnp.asarray(STEP - 1, jnp.int32), mu=mu, nu=nu),
        optax.EmptyState())
    updates, state = optimizer.update(grad, state, table)
    return optax.apply_updates(table, updates), state[0].mu, state[0].nu


def _the_kernel(*args):
    return adam_rows._pallas(*args, name="adam_rows_test", interpret=True,
                             tile=TILE, chunk=CHUNK, **HYPER)


def _the_plain_form(*args):
    return adam_rows._plain(*args, **HYPER)


def _against_the_optimizer(*args):
    """Primitive by primitive, as written: under one `jit` each the
    CPU's compiler contracts `a * b + c` in one program and not in the
    other, and a few elements in ten thousand move by one step of
    float32."""
    with jax.disable_jit():
        return _the_plain_form(*args), _what_it_replaces(*args[:5])


def _kernel_against_the_plain_form(*args):
    return jax.jit(_the_kernel)(*args), jax.jit(_the_plain_form)(*args)


PAIRS = {"plain_form_and_optimizer": _against_the_optimizer,
         "kernel_and_plain_form": _kernel_against_the_plain_form}


@pytest.mark.parametrize("moments", ["bfloat16", "float32"])
@pytest.mark.parametrize("pair", sorted(PAIRS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_row_list_adam_is_the_scatter_and_the_optimizers_update(
        case, pair, moments):
    """Two links of one chain: the plain form against the optimizer's
    own transform on the scattered table, and the kernel (through the
    interpreter) against the plain form."""
    rng = np.random.default_rng(sorted(CASES).index(case))
    table_rows, keys = CASES[case](rng)
    table = jnp.asarray(rng.normal(size=(table_rows, WIDTH)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=table.shape) * 0.1, moments)
    nu = jnp.asarray(rng.random(size=table.shape) * 0.01, moments)
    rows = jnp.asarray(rng.normal(size=(len(keys), WIDTH)), jnp.bfloat16)
    keys = jnp.asarray(keys)
    count = jnp.asarray(STEP, jnp.float32)
    got, want = PAIRS[pair](
        table, mu, nu, keys, rows,
        1.0 - HYPER["b1"] ** count, 1.0 - HYPER["b2"] ** count)
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


def test_the_schedule_gives_every_tile_its_chunks_once():
    """Every tile has at least one item, its items' chunks cover its
    entries, tiles and chunks never go back, and the items past the
    total repeat the last."""
    rng = np.random.default_rng(1)
    rows = 5 * TILE + 9
    keys = _keys(rng, rows, np.concatenate(
        [np.full(500, 3), rng.integers(4 * TILE, rows, 40)]), 1024)
    tile_of, chunk_of, total, offsets = (
        np.asarray(x) for x in adam_rows._schedule(
            jnp.asarray(keys), rows, TILE, CHUNK))
    total = int(total[0])
    assert len(tile_of) == 6 + 1024 // CHUNK and total <= len(tile_of)
    assert sorted(set(tile_of[:total])) == list(range(6))
    assert (np.diff(tile_of) >= 0).all() and (np.diff(chunk_of) >= 0).all()
    assert (tile_of[total:] == 5).all()
    assert (chunk_of[total:] == chunk_of[total - 1]).all()
    assert offsets[0] == 0 and offsets[-1] == 540
    for t in range(6):
        mine = chunk_of[:total][tile_of[:total] == t]
        np.testing.assert_array_equal(mine, np.arange(mine[0], mine[-1] + 1))
        if offsets[t + 1] > offsets[t]:
            assert mine[0] * CHUNK <= offsets[t]
            assert offsets[t + 1] <= (mine[-1] + 1) * CHUNK


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
    config = Config(train_data_path_prefix="unused", train_batch_size=B,
                    max_contexts=M, dropout_keep_rate=1.0, **overrides)
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
    for n in range(3):
        batch, rng = _toy_batch(seed=n), jax.random.PRNGKey(n)
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
    assert jax.tree.structure(state) == tree
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(state)] == kinds
    assert int(state.step) == 3
    assert int(step_mod._adam_moments(state.opt_state).count) == 3


def _refuse(*args, **kwargs):
    raise AssertionError("the one-chip step's alone")


@pytest.mark.parametrize("plan", [(2, 1, 1), (2, 2, 1)],
                         ids=["dp2", "dp2_tp2"])
def test_a_mesh_of_two_chips_still_builds_table_shaped_gradients(
        monkeypatch, toy_blocks, plan):
    """The choice is the mesh's: on a data mesh of two (forced host)
    devices the step lowers without the list or its Adam, the token
    table's gradient a scatter into a table; one chip's does not lower
    without them."""
    for op in ("sorted_row_list", "live_rows_and_entries",
               "adam_rows_into_table"):
        monkeypatch.setattr(step_mod, op, _refuse)
    mesh = make_mesh(MeshPlan(*plan))
    builder, state = _toy(mesh=mesh, dp=plan[0], tp=plan[1])
    text = builder.make_train_step(state).lower(
        state, *_toy_batch(), jax.random.PRNGKey(1)).as_text()
    if plan[1] == 1:
        assert "stablehlo.scatter" in text
    builder, state = _toy()
    with pytest.raises(AssertionError, match="one-chip step's alone"):
        builder.make_train_step(state).lower(
            state, *_toy_batch(), jax.random.PRNGKey(1))


@pytest.mark.parametrize("plan, overrides, tables", [
    (None, {}, 2), ((1, 1, 1), {}, 2),
    ((2, 1, 1), {}, 0), ((4, 1, 1), {}, 0),
    ((1, 2, 1), {}, 0), ((2, 1, 2), {}, 0),
    (None, {"use_sparse_embedding_update": True}, 0),
    (None, {"adam_nu_dtype": "float32"}, 0),
    (None, {"adam_nu_dtype": "float32", "adam_mu_dtype": "float32"}, 2),
    (None, {"compute_dtype": "float32"}, 0),
    (None, {"default_embeddings_size": 64}, 0),
    (None, {"path_embeddings_size": 256}, 0),
], ids=["no_mesh", "mesh_of_one", "dp2", "dp4", "tp2", "dp2_cp2", "sparse",
        "stock_adam_bf16_mu", "stock_adam_float32", "float32_rows",
        "narrow_tables", "wide_path_table"])
def test_which_steps_hand_adam_a_row_list(plan, overrides, tables):
    """One chip with whole tables, no mesh of more chips; tables the
    kernel takes (128 wide, rows in bfloat16), so the gauge never says
    "a row list" over a scatter; and not the one optimizer whose
    arithmetic the list's Adam does not follow (stock optax.adam over a
    bfloat16 first moment)."""
    config = Config(train_data_path_prefix="unused", **overrides)
    mesh = None if plan is None else make_mesh(MeshPlan(*plan))
    assert step_mod.adam_row_list_tables(config, mesh) == tables


@pytest.mark.parametrize("plan, tables", [
    (None, 2), ((2, 1, 1), 0), ((1, 2, 1), 0),
], ids=["no_mesh", "dp2", "tp2"])
def test_the_trainer_says_how_many_tables_adam_takes_as_a_row_list(
        tiny_config, plan, tables):
    """`train_adam_row_list_tables`, and the same number in the first
    step's log line."""
    lines = []
    tiny_config.verbose_mode = 0
    tiny_config.log = lines.append
    mesh = None if plan is None else make_mesh(MeshPlan(*plan))
    gauge = obs.default_registry().gauge("train_adam_row_list_tables")
    gauge.set(-1)
    _train_an_epoch(tiny_config, _plain_step, batches=1, rows=8, mesh=mesh)
    assert gauge.value == tables
    first, = [ln for ln in lines if ln.startswith("First train step")]
    assert f"Adam takes {tables} table(s)' gradient as a row list" in first
