"""The live-rows embedding lookup (ops/embed.py) and the dense train
step built around it (training/step.py).

The op is held against `jnp.take` on every live entry, forward and
VJP, at toy widths with toy block sizes; the step against the step it
replaced (the `jnp.take` path a mesh still takes), over three updates
with the batch's rows in shuffled order."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import EpochEnd, RowBatch
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.ops import embed
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
from code2vec_tpu.training import step as step_mod
from code2vec_tpu.training.loop import Trainer
from code2vec_tpu.training.state import create_train_state, make_optimizer
from code2vec_tpu.training.step import TrainStepBuilder

ROWS, CONTEXTS = 4, 3           # toy block: 4 rows x 3 contexts
VOCAB, WIDTH = 40, 8


@pytest.fixture(autouse=True)
def toy_blocks(monkeypatch):
    monkeypatch.setattr(embed, "BLOCK_ROWS", ROWS)
    monkeypatch.setattr(embed, "BLOCK_CONTEXTS", CONTEXTS)
    monkeypatch.setattr(embed, "SCATTER_SIZES", 3)


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
        got = np.asarray(got)
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
        return sum(jnp.sum(o * w) for o, w in zip(outs, weights))

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
    np.testing.assert_array_equal(np.asarray(outs[0])[live], want[live])
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
    ordered = jnp.pad(jnp.sort(depth)[::-1],
                      (0, groups * ROWS - mask.shape[0]))
    _, count = embed._schedule(ordered, mask.shape[1])
    assert int(count) / (groups * across) == pytest.approx(
        embed.live_block_ratio(mask))


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


def _refuse(*args, **kwargs):
    raise AssertionError("embed_live_rows is the dense train step's alone")


@pytest.mark.parametrize("which", ["eval", "predict_k1", "sparse_train"])
def test_the_other_steps_lower_to_what_they_lower_to_without_the_op(
        monkeypatch, which):
    """The eval and predict steps (and the sparse train step) never
    reach the op: with it taken away they lower to the same program."""
    overrides = ({"use_sparse_embedding_update": True}
                 if which == "sparse_train" else {})

    def lower():
        _, builder, state = _toy(**overrides)
        batch = _toy_batch()
        if which == "sparse_train":
            return builder.make_train_step(state).lower(
                state, *batch, jax.random.PRNGKey(1)).as_text()
        k = 1 if which == "predict_k1" else 3
        return builder.make_eval_step(state, k=k).lower(
            state.params, *batch).as_text()
    with_op = lower()
    monkeypatch.setattr(step_mod, "embed_live_rows", _refuse)
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
