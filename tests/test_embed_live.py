"""The live-rows embedding lookup (ops/embed.py), the dense chain over
the slots it fills (ops/encode_live.py) and the dense train step built
around both (training/step.py).

The lookup is held against `jnp.take` on every live entry, forward and
VJP, at toy widths with toy block sizes; the chain against
`transform_gathered` + `masked_single_query_attention` over the whole
grid; the step against the step it replaced (the `jnp.take` path a
mesh still takes), over three updates with the batch's rows in
shuffled order."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import EpochEnd, RowBatch
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.ops import embed
from code2vec_tpu.ops.attention import masked_single_query_attention
from code2vec_tpu.ops.encode_live import encode_live_blocks
from code2vec_tpu.parallel.mesh import AXIS_DATA, MeshPlan, make_mesh
from code2vec_tpu.training import step as step_mod
from code2vec_tpu.training.loop import Trainer
from code2vec_tpu.training.state import create_train_state, make_optimizer
from code2vec_tpu.training.step import TrainStepBuilder

from test_head_ce import _optax_form

ROWS, CONTEXTS = 4, 3           # toy block: 4 rows x 3 contexts
VOCAB, WIDTH = 40, 8


@pytest.fixture(autouse=True)
def toy_blocks(monkeypatch):
    monkeypatch.setattr(embed, "BLOCK_ROWS", ROWS)
    monkeypatch.setattr(embed, "BLOCK_CONTEXTS", CONTEXTS)
    monkeypatch.setattr(embed, "SCATTER_SIZES", 3)
    monkeypatch.setattr(embed, "SLOT_CHUNK", 2)


def _prefix(counts, m):
    return (np.arange(m)[None, :] < np.asarray(counts)[:, None]
            ).astype(np.float32)


def _hole(counts, m):
    mask = _prefix(counts, m)
    mask[0, 1] = 0.0            # a hole under the deepest context
    mask[2, 0] = 0.0
    return mask


# name -> (B, M) mask
MASKS = {
    "prefix": _prefix([9, 1, 5, 7, 2, 9, 3, 4], 9),
    "hole": _hole([9, 6, 5, 7, 2, 8, 3, 4], 9),
    "all_padding_row": _prefix([5, 0, 9, 0, 2, 0, 0, 1], 9),
    "depth_on_block_edge": _prefix([3, 6, 9, 3, 6, 6, 3, 9], 9),
    "every_row_full": _prefix([9] * 8, 9),
    "rows_not_a_multiple": _prefix([7, 2, 5, 4, 1, 6, 3, 7, 2, 5], 7),
    "nothing_live": _prefix([0] * 8, 9),
}


def _case(name):
    mask = MASKS[name]
    rng = np.random.default_rng(sorted(MASKS).index(name))
    table = jnp.asarray(rng.normal(size=(VOCAB, WIDTH)).astype(np.float32))
    ids = tuple(jnp.asarray(np.where(
        mask > 0, rng.integers(1, VOCAB, mask.shape), 0).astype(np.int32))
        for _ in range(2))
    depth = embed.context_depth(jnp.asarray(mask))
    live = np.arange(mask.shape[1])[None, :] < np.asarray(depth)[:, None]
    return mask, table, ids, depth, live, rng


def _on_grid(by_slot, depth, shape):
    """A `(slots, entries, width)` output of the lookup back on the
    `(B, M, width)` grid."""
    order, _ = embed.live_slots(depth, shape[1])
    return jax.vmap(lambda v: embed.to_grid(v, order, *shape),
                    in_axes=2, out_axes=2)(by_slot)


def _numpy_depth(mask):
    return np.array([max([j + 1 for j in range(len(r)) if r[j] > 0],
                         default=0) for r in mask])


@pytest.mark.parametrize("name", sorted(MASKS))
def test_forward_is_take_on_every_live_entry_and_zero_elsewhere(name):
    mask, table, ids, depth, live, _ = _case(name)
    np.testing.assert_array_equal(np.asarray(depth), _numpy_depth(mask))
    outs = jax.jit(lambda t: embed.embed_live_rows(
        t, ids, depth, jnp.float32))(table)
    assert len(outs) == len(ids)
    for got, i in zip(outs, ids):
        want = np.asarray(jnp.take(table, i, axis=0))
        assert got.shape == (embed.slot_count(*mask.shape),
                             ROWS * CONTEXTS, WIDTH)
        got = np.asarray(_on_grid(got, depth, mask.shape))
        assert got.shape == want.shape and np.isfinite(got).all()
        np.testing.assert_array_equal(got[live], want[live])
        assert (got[~live] == 0).all()


@pytest.mark.parametrize("name", sorted(MASKS))
def test_vjp_is_takes_on_cotangents_that_vanish_off_the_mask(name):
    """The model's cotangent is exactly 0 wherever the mask is 0 (the
    attention weight there is 0), so the two gradients must agree;
    inside a row every addition is the same float32 addition in another
    order."""
    mask, table, ids, depth, _, rng = _case(name)
    weights = [jnp.asarray(rng.normal(size=mask.shape + (WIDTH,)).astype(
        np.float32) * mask[:, :, None]) for _ in ids]

    def through_op(t):
        outs = embed.embed_live_rows(t, ids, depth, jnp.float32)
        return sum(jnp.sum(_on_grid(o, depth, mask.shape) * w)
                   for o, w in zip(outs, weights))

    def through_take(t):
        return sum(jnp.sum(jnp.take(t, i, axis=0) * w)
                   for i, w in zip(ids, weights))
    got_value, got = jax.jit(jax.value_and_grad(through_op))(table)
    want_value, want = jax.jit(jax.value_and_grad(through_take))(table)
    np.testing.assert_allclose(got_value, want_value, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_the_output_takes_the_compute_dtype_and_the_gradient_the_tables():
    _, table, ids, depth, live, _ = _case("prefix")
    outs, vjp = jax.vjp(lambda t: embed.embed_live_rows(
        t, ids, depth, jnp.bfloat16), table)
    assert all(o.dtype == jnp.bfloat16 for o in outs)
    want = np.asarray(jnp.take(table, ids[0], axis=0).astype(jnp.bfloat16))
    np.testing.assert_array_equal(
        np.asarray(_on_grid(outs[0], depth, live.shape))[live], want[live])
    grad, = vjp(tuple(jnp.ones_like(o) for o in outs))
    assert grad.dtype == table.dtype and grad.shape == table.shape


def _numpy_live_ratio(mask):
    """Blocks some row of its group reaches into, rows by depth."""
    depth = sorted(_numpy_depth(mask), reverse=True)
    groups = [depth[i:i + ROWS] for i in range(0, len(depth), ROWS)]
    across = -(-mask.shape[1] // CONTEXTS)
    live = sum(1 for g in groups for k in range(across)
               if any(d > k * CONTEXTS for d in g))
    return live / (len(groups) * across)


@pytest.mark.parametrize("name", sorted(MASKS))
def test_live_block_ratio_counts_what_the_forward_gathers(name):
    mask, table, ids, depth, _, _ = _case(name)
    assert embed.live_block_ratio(mask) == pytest.approx(
        _numpy_live_ratio(mask))
    # and the device-side schedule of the same rows, ordered, agrees
    groups, across = embed._grid(*mask.shape)
    _, count = embed.live_slots(jnp.sort(depth)[::-1], mask.shape[1])
    assert int(count) / (groups * across) == pytest.approx(
        embed.live_block_ratio(mask))


# ----------------------------------------------------------- the chain

CHAIN_DIMS = ModelDims(token_vocab_size=VOCAB, path_vocab_size=VOCAB,
                       target_vocab_size=5, token_dim=WIDTH, path_dim=WIDTH)


def _by_slot(grid_rows, depth):
    """`(B, M, width)` rows as the lookup hands them over."""
    order, _ = embed.live_slots(depth, grid_rows.shape[1])
    return jax.vmap(lambda v: embed.to_slots(v, order),
                    in_axes=2, out_axes=2)(grid_rows)


def _chain_case(mask, seed=0):
    """For a mask: three grids of rows that vanish past each row's
    depth (as the lookup's do), the two parameters and a cotangent for
    the code vectors."""
    rng = np.random.default_rng(seed)
    depth = embed.context_depth(jnp.asarray(mask))
    under = (np.arange(mask.shape[1])[None, :]
             < np.asarray(depth)[:, None])[:, :, None]
    rows = tuple(jnp.asarray((rng.normal(size=mask.shape + (WIDTH,))
                              * under).astype(np.float32))
                 for _ in range(3))
    transform = jnp.asarray(
        rng.normal(size=(3 * WIDTH, 3 * WIDTH)).astype(np.float32) * 0.3)
    attention = jnp.asarray(rng.normal(size=(3 * WIDTH,)).astype(np.float32))
    weigh = jnp.asarray(
        rng.normal(size=(mask.shape[0], 3 * WIDTH)).astype(np.float32))
    return jnp.asarray(mask), depth, rows, transform, attention, weigh


def _over_the_grid(rows, transform, attention, mask, dropout=None, keep=1.0):
    """`transform_gathered` + `masked_single_query_attention` over the
    whole grid; with `dropout` (a `(B, M, 3d)` mask) the same chain by
    hand under THAT mask."""
    if dropout is None:
        module = Code2VecModule(dims=CHAIN_DIMS, dropout_keep_rate=1.0,
                                compute_dtype=jnp.float32)
        params = {"transform": transform, "attention": attention[:, None],
                  "token_embedding": jnp.zeros((VOCAB, WIDTH)),
                  "path_embedding": jnp.zeros((VOCAB, WIDTH)),
                  "target_embedding": jnp.zeros((5, 3 * WIDTH))}
        transformed = module.apply(
            {"params": params}, *rows, deterministic=True,
            method=Code2VecModule.transform_gathered)
    else:
        ctx = jnp.where(dropout, jnp.concatenate(rows, axis=-1) / keep, 0.0)
        transformed = jnp.tanh(ctx @ transform)
    return masked_single_query_attention(transformed, attention, mask)[0]


@pytest.mark.parametrize("name", sorted(MASKS))
def test_the_chain_over_the_slots_is_the_chain_over_the_grid(name):
    """Keep 1.0: code vectors and the gradients to the rows, `transform`
    and `attention` equal those of the module's chain over the whole
    grid, within float32 summation order."""
    mask, depth, rows, transform, attention, weigh = _chain_case(
        MASKS[name], seed=sorted(MASKS).index(name))

    def live(rows, transform, attention):
        code = encode_live_blocks(
            tuple(_by_slot(r, depth) for r in rows), transform, attention,
            mask, depth, jax.random.key(0), 1.0)
        return jnp.sum(code * weigh), code

    def grid(rows, transform, attention):
        code = _over_the_grid(rows, transform, attention, mask)
        return jnp.sum(code * weigh), code
    (_, got_code), got = jax.jit(jax.value_and_grad(
        live, argnums=(0, 1, 2), has_aux=True))(rows, transform, attention)
    (_, want_code), want = jax.jit(jax.value_and_grad(
        grid, argnums=(0, 1, 2), has_aux=True))(rows, transform, attention)
    assert got_code.shape == want_code.shape
    assert got_code.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_code), np.asarray(want_code),
                               rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
    # a slot no context is valid in gets gradient exactly 0.0
    for g in got[0]:
        assert (np.asarray(g)[np.asarray(mask) == 0] == 0.0).all()


def _dropout_case():
    """A batch large enough to count a share in: 32 rows x 12 contexts,
    rows already by depth, a hole, an all-padding row."""
    counts = np.sort(np.random.default_rng(0).integers(0, 13, 32))[::-1].copy()
    counts[0], counts[-1] = 12, 0
    mask = _prefix(counts, 12)
    mask[0, 3] = 0.0
    return _chain_case(mask)


def _with_dropout(key, keep=0.75):
    """Code vectors and gradients of the chain at `keep`, the rows'
    gradient on the grid."""
    mask, depth, rows, transform, attention, weigh = _dropout_case()

    def live(rows, transform, attention):
        code = encode_live_blocks(
            tuple(_by_slot(r, depth) for r in rows), transform, attention,
            mask, depth, key, keep)
        return jnp.sum(code * weigh), code
    (_, code), grads = jax.jit(jax.value_and_grad(
        live, argnums=(0, 1, 2), has_aux=True))(rows, transform, attention)
    return code, grads


def test_dropout_keeps_three_quarters_of_the_live_elements():
    """Keep 0.75: of the elements a valid context holds, the share whose
    gradient is not 0 is 0.75 within sampling error (8,000 elements:
    sigma 0.005); every other element's gradient is exactly 0.0; and the
    backward drew the forward's mask: the chain by hand over the grid
    under the mask the gradient shows gives the same code vectors and
    the same parameter gradients."""
    mask, depth, rows, transform, attention, weigh = _dropout_case()
    code, (row_grads, transform_grad, attention_grad) = _with_dropout(
        jax.random.key(3, impl="rbg"))
    grad = np.concatenate([np.asarray(g) for g in row_grads], axis=-1)
    valid = np.asarray(mask) > 0
    assert valid.sum() * 3 * WIDTH > 4000
    assert abs((grad[valid] != 0).mean() - 0.75) < 0.02
    assert (grad[~valid] == 0.0).all()

    def by_hand(transform, attention):
        got = _over_the_grid(rows, transform, attention, mask,
                             dropout=jnp.asarray(grad != 0), keep=0.75)
        return jnp.sum(got * weigh), got
    (_, want_code), want = jax.value_and_grad(
        by_hand, argnums=(0, 1), has_aux=True)(transform, attention)
    np.testing.assert_allclose(np.asarray(code), np.asarray(want_code),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(transform_grad),
                               np.asarray(want[0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(attention_grad),
                               np.asarray(want[1]), rtol=1e-4, atol=1e-5)


def test_two_steps_draw_two_masks_and_one_key_draws_one():
    key = jax.random.key(3, impl="rbg")
    first, (first_grads, _, _) = _with_dropout(jax.random.fold_in(key, 0))
    again, (again_grads, _, _) = _with_dropout(jax.random.fold_in(key, 0))
    second, (second_grads, _, _) = _with_dropout(jax.random.fold_in(key, 1))
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    kept = [np.concatenate([np.asarray(g) != 0 for g in grads], axis=-1)
            for grads in (first_grads, again_grads, second_grads)]
    np.testing.assert_array_equal(kept[0], kept[1])
    valid = np.asarray(_dropout_case()[0]) > 0
    # two independent masks at keep 0.75 agree on 0.75^2 + 0.25^2 = 0.625
    assert abs((kept[0] == kept[2])[valid].mean() - 0.625) < 0.03
    assert not np.allclose(np.asarray(first), np.asarray(second))


# ------------------------------------------------------------ the step

B, M = 10, 7
DIMS = ModelDims(token_vocab_size=64, path_vocab_size=32,
                 target_vocab_size=24, token_dim=8, path_dim=8)


def _toy(keep=1.0, **overrides):
    config = Config(train_data_path_prefix="unused", train_batch_size=B,
                    max_contexts=M, dropout_keep_rate=keep,
                    compute_dtype="float32", **overrides)
    module = Code2VecModule(dims=DIMS, dropout_keep_rate=keep,
                            compute_dtype=jnp.float32)
    optimizer = make_optimizer(config)
    state = create_train_state(module, optimizer, jax.random.PRNGKey(0),
                               config=config)
    return config, TrainStepBuilder(module, optimizer, config), state


def _toy_batch(seed=0):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, M + 1, B)
    counts[0], counts[1] = M, 0
    mask = _prefix(counts, M)
    mask[0, 2] = 0.0
    ids = [np.where(mask > 0, rng.integers(1, hi, (B, M)), 0).astype(
        np.int32) for hi in (64, 32, 64)]
    return (ids[0], ids[1], ids[2], mask,
            rng.integers(1, 24, (B,)).astype(np.int32),
            counts > 0)


def _first_moment(state):
    for node in jax.tree.leaves(state.opt_state,
                                is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise AssertionError("no Adam first moment")


def test_three_steps_equal_the_take_steps_on_shuffled_rows(monkeypatch):
    """Dropout keep 1.0: loss, Adam's first moment and the parameters of
    three updates equal those of the step over `jnp.take`, within
    float32 summation order, with the rows given in another order."""
    _, builder, state = _toy()
    live_step = builder.make_train_step(state)
    monkeypatch.setattr(step_mod, "gathers_live_rows", lambda c, m: False)
    _, take_builder, take_state = _toy()
    take_step = take_builder.make_train_step(take_state)
    lowered = [s.lower(st, *_toy_batch(), jax.random.PRNGKey(1)).as_text()
               for s, st in ((live_step, state), (take_step, take_state))]
    # the rows' ordering and the backward's id sort mark the new step
    assert "stablehlo.sort" in lowered[0]
    assert "stablehlo.sort" not in lowered[1]
    shuffle = np.random.default_rng(5).permutation(B)
    for n in range(3):
        batch = _toy_batch(seed=n)
        rng = jax.random.PRNGKey(n)
        state, loss = live_step(state, *(a[shuffle] for a in batch), rng)
        take_state, take_loss = take_step(take_state, *batch, rng)
        np.testing.assert_allclose(float(loss), float(take_loss), rtol=1e-6)
        for got, want in ((_first_moment(state), _first_moment(take_state)),
                          (state.params, take_state.params)):
            for key in want:
                np.testing.assert_allclose(
                    np.asarray(got[key]), np.asarray(want[key]),
                    rtol=1e-4, atol=2e-5, err_msg=f"step {n + 1} {key}")
    assert int(state.step) == 3


def _optax_head(*args):
    """The head as autodiff of the einsum's logits under optax's
    cross-entropy (what `head_cross_entropy` replaced), whatever the
    mesh."""
    return _optax_form(*args[:6])


@pytest.mark.parametrize("step", ["live_rows", "take", "sparse"])
def test_three_steps_equal_the_steps_on_the_optax_head(monkeypatch, step):
    """The head's own VJP (ops/head_ce.py) against autodiff of the optax
    form, float32, dropout keep 1.0: loss, Adam's first moment and the
    parameters of three updates, in each step that holds whole rows of
    logits."""
    overrides = ({"use_sparse_embedding_update": True}
                 if step == "sparse" else {})
    if step == "take":
        monkeypatch.setattr(step_mod, "gathers_live_rows", lambda c, m: False)
    _, builder, state = _toy(**overrides)
    own_step = builder.make_train_step(state)
    monkeypatch.setattr(step_mod, "head_cross_entropy", _optax_head)
    _, optax_builder, optax_state = _toy(**overrides)
    optax_step = optax_builder.make_train_step(optax_state)
    for n in range(3):
        batch, rng = _toy_batch(seed=n), jax.random.PRNGKey(n)
        state, loss = own_step(state, *batch, rng)
        optax_state, optax_loss = optax_step(optax_state, *batch, rng)
        np.testing.assert_allclose(float(loss), float(optax_loss), rtol=1e-6)
        pairs = [(state.params, optax_state.params)]
        if step != "sparse":
            pairs.append((_first_moment(state), _first_moment(optax_state)))
        for got, want in pairs:
            for key in want:
                np.testing.assert_allclose(
                    np.asarray(got[key]), np.asarray(want[key]),
                    rtol=1e-4, atol=2e-5, err_msg=f"step {n + 1} {key}")
    assert int(state.step) == 3


def _refuse(*args, **kwargs):
    raise AssertionError("an op of the GSPMD train steps alone")


# which steps never reach which ops: the live-rows lookup and chain are
# the dense GSPMD train step's, the head's own VJP the two GSPMD train
# steps' (the sparse one holds whole rows of logits too)
_LIVE_ROWS_OPS = ("embed_live_rows", "encode_live_blocks")
_HEAD_OP = ("head_cross_entropy",)


@pytest.mark.parametrize("which,ops", [
    ("eval", _LIVE_ROWS_OPS), ("predict_k1", _LIVE_ROWS_OPS),
    ("sparse_train", _LIVE_ROWS_OPS), ("manual_train", _LIVE_ROWS_OPS),
    ("eval", _HEAD_OP), ("predict_k1", _HEAD_OP), ("manual_train", _HEAD_OP),
])
def test_the_other_steps_lower_to_what_they_lower_to_without_the_op(
        monkeypatch, which, ops):
    """The eval and predict steps (and the sparse and the manual train
    steps) never reach the ops: with them taken away they lower to the
    same program."""
    overrides = {"sparse_train": {"use_sparse_embedding_update": True},
                 "manual_train": {"dp": 2, "tp": 2,
                                  "use_manual_tp_kernels": True}
                 }.get(which, {})

    def lower():
        if which == "manual_train":
            config, _, _ = _toy(**overrides)
            module = Code2VecModule(dims=DIMS, dropout_keep_rate=1.0,
                                    compute_dtype=jnp.float32)
            optimizer = make_optimizer(config)
            mesh = make_mesh(MeshPlan(dp=2, tp=2, cp=1))
            state = create_train_state(
                module, optimizer, jax.random.PRNGKey(0), mesh=mesh,
                config=config)
            builder = TrainStepBuilder(module, optimizer, config, mesh=mesh)
            assert builder.manual
            return builder.make_train_step(state).lower(
                state, *_toy_batch(), jax.random.PRNGKey(1)).as_text()
        _, builder, state = _toy(**overrides)
        batch = _toy_batch()
        if which == "sparse_train":
            return builder.make_train_step(state).lower(
                state, *batch, jax.random.PRNGKey(1)).as_text()
        k = 1 if which == "predict_k1" else 3
        return builder.make_eval_step(state, k=k).lower(
            state.params, *batch).as_text()
    with_op = lower()
    for op in ops:
        monkeypatch.setattr(step_mod, op, _refuse)
    assert lower() == with_op


@pytest.mark.parametrize("plan,sparse,want", [
    (None, False, True),
    (MeshPlan(dp=4, tp=1, cp=1), False, True),
    (MeshPlan(dp=2, tp=2, cp=1), False, False),
    (MeshPlan(dp=2, tp=1, cp=2), False, False),
    (None, True, False),
])
def test_which_steps_gather_live_rows(plan, sparse, want):
    config = Config(train_data_path_prefix="unused",
                    use_sparse_embedding_update=sparse)
    mesh = make_mesh(plan) if plan else None
    assert step_mod.gathers_live_rows(config, mesh) is want


@pytest.mark.parametrize("dp", [2, 5])
def test_a_data_mesh_runs_the_lookup_chip_by_chip(dp):
    """Under `--dp` each chip orders and gathers its own rows and the
    tables' gradients meet in one all-reduce each: three updates equal
    the single-device step's (keep 1.0), and no collective sits inside
    a loop."""
    _, builder, state = _toy()
    single = builder.make_train_step(state)
    plan = MeshPlan(dp=dp, tp=1, cp=1)
    mesh = make_mesh(plan)
    config = Config(train_data_path_prefix="unused", train_batch_size=B,
                    max_contexts=M, dropout_keep_rate=1.0,
                    compute_dtype="float32", dp=dp)
    module = Code2VecModule(dims=DIMS, dropout_keep_rate=1.0,
                            compute_dtype=jnp.float32)
    optimizer = make_optimizer(config)
    mesh_state = create_train_state(module, optimizer, jax.random.PRNGKey(0),
                                    mesh=mesh, config=config)
    meshed = TrainStepBuilder(module, optimizer, config,
                              mesh=mesh).make_train_step(mesh_state)
    text = meshed.lower(mesh_state, *_toy_batch(),
                        jax.random.PRNGKey(1)).compile().as_text()
    bodies = [block for block in text.split("\n\n")
              if "all-reduce" in block]
    assert bodies and not any(
        "while_body" in b.split("{")[0] or "region" in b.split("{")[0]
        and "scatter" in b for b in bodies)
    for n in range(3):
        batch, rng = _toy_batch(seed=n), jax.random.PRNGKey(n)
        state, loss = single(state, *batch, rng)
        mesh_state, mesh_loss = meshed(mesh_state, *batch, rng)
        np.testing.assert_allclose(float(mesh_loss), float(loss), rtol=1e-6)
        for key, want in state.params.items():
            np.testing.assert_allclose(
                np.asarray(mesh_state.params[key]), np.asarray(want),
                rtol=1e-4, atol=2e-5, err_msg=f"step {n + 1} {key}")


def test_two_chips_draw_two_masks():
    """Under a data mesh each chip folds its index into the step's key:
    two chips given the same rows return other code vectors at keep
    0.75, and the same ones where the index is not folded in."""
    half = _toy_batch(seed=3)
    src, pth, tgt, mask = (np.concatenate([a, a]) for a in half[:4])
    depth = embed.context_depth(jnp.asarray(mask))
    mesh = make_mesh(MeshPlan(dp=2, tp=1, cp=1))
    _, builder, state = _toy(keep=0.75)
    params = {k: state.params[k] for k in step_mod._ENCODER_PARAMS}
    rows, ids = P(AXIS_DATA), P(AXIS_DATA, None)

    def codes(axis_name):
        return np.asarray(jax.jit(jax.shard_map(
            functools.partial(builder._encode_live_rows,
                              axis_name=axis_name),
            mesh=mesh, in_specs=(P(), ids, ids, ids, ids, rows, P()),
            out_specs=ids, check_vma=False))(
                params, src, pth, tgt, mask, depth,
                jax.random.key(5, impl="rbg")))
    same = codes(None)
    np.testing.assert_array_equal(same[:B], same[B:])
    apart = codes(AXIS_DATA)
    live = np.asarray(half[3]).any(axis=1)
    assert live.sum() > 2
    assert (np.abs(apart[:B] - apart[B:])[live].max(axis=1) > 1e-4).all()


def test_the_trainer_observes_what_the_dense_chain_runs_over(tiny_config):
    """`train_dense_blocks_run_ratio` reads, once a batch, the live
    blocks in whole `SLOT_CHUNK`s over all blocks: at or a little above
    `train_context_blocks_live_ratio`."""
    tiny_config.verbose_mode = 0
    masks = [MASKS[name] for name in ("prefix", "hole", "every_row_full",
                                      "nothing_live")]

    def stream():
        for mask in masks:
            ids = np.ones(mask.shape, np.int32)
            yield RowBatch(ids, ids, ids, mask,
                           np.ones((mask.shape[0],), np.int32),
                           np.ones((mask.shape[0],), bool))
        yield EpochEnd(1)

    class State:
        step = np.zeros((), np.int32)
    hist = obs.default_registry().histogram("train_dense_blocks_run_ratio")
    count, total = hist.count, hist.sum
    Trainer(tiny_config, lambda state, *args: (state, np.float32(1.0))
            ).train(State(), stream(), rng=np.zeros((2,), np.uint32))
    assert hist.count - count == len(masks)

    def run_ratio(mask):
        groups, across = embed._grid(*mask.shape)
        live = round(_numpy_live_ratio(mask) * groups * across)
        return -(-live // 2) * 2 / (groups * across)      # SLOT_CHUNK 2
    assert hist.sum - total == pytest.approx(sum(map(run_ratio, masks)))
    for mask in masks:
        _, count_on_device = embed.live_slots(
            jnp.sort(embed.context_depth(jnp.asarray(mask)))[::-1],
            mask.shape[1])
        assert embed.live_block_ratio(mask, chunk=2) == pytest.approx(
            run_ratio(mask))
        assert (embed.live_block_ratio(mask)
                <= embed.live_block_ratio(mask, chunk=2)
                < embed.live_block_ratio(mask) + 2 / 6)
        assert -(-int(count_on_device) // 2) * 2 == round(
            run_ratio(mask) * np.prod(embed._grid(*mask.shape)))


def test_the_trainer_observes_each_batchs_live_ratio(tiny_config):
    """`train_context_blocks_live_ratio` reads, once a batch, what a
    numpy count of the same batch's mask gives."""
    tiny_config.verbose_mode = 0
    masks = [MASKS[name] for name in ("prefix", "hole", "every_row_full")]

    def stream():
        for mask in masks:
            ids = np.ones(mask.shape, np.int32)
            yield RowBatch(ids, ids, ids, mask,
                           np.ones((mask.shape[0],), np.int32),
                           np.ones((mask.shape[0],), bool))
        yield EpochEnd(1)

    class State:
        step = np.zeros((), np.int32)
    hist = obs.default_registry().histogram("train_context_blocks_live_ratio")
    count, total = hist.count, hist.sum
    Trainer(tiny_config, lambda state, *args: (state, np.float32(1.0))
            ).train(State(), stream(), rng=np.zeros((2,), np.uint32))
    assert hist.count - count == len(masks)
    assert hist.sum - total == pytest.approx(
        sum(_numpy_live_ratio(m) for m in masks))
    # under --dp each chip orders and gathers its own slice of the rows
    halves = np.split(MASKS["prefix"], 2)
    assert embed.live_block_ratio(MASKS["prefix"], chips=2) == pytest.approx(
        np.mean([_numpy_live_ratio(h) for h in halves]))


def test_a_partly_committed_state_compiles_the_step_once():
    """Parameters put on their device by hand (a restore, the
    benchmark's seeded weights) beside the program's own fresh moments,
    then the step's own output: one compiled program serves both."""
    from jax._src import monitoring
    _, builder, state = _toy()
    step = builder.make_train_step(state)
    placed = jax.tree.map(lambda x: jax.device_put(x + 0, x.sharding),
                          state.params)
    assert all(x.committed for x in jax.tree.leaves(placed))
    state = state.replace(params=placed)
    batches = [tuple(jnp.asarray(a) for a in _toy_batch(seed=n))
               for n in range(3)]
    compiles = []

    def listener(event, seconds, **kwargs):
        if event.endswith("backend_compile_duration"):
            compiles.append(seconds)
    monitoring.register_event_duration_secs_listener(listener)
    try:
        for n, batch in enumerate(batches):
            state, _ = step(state, *batch, jax.random.PRNGKey(n))
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert len(compiles) == 1
