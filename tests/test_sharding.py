"""Sharding correctness on the 8-virtual-device CPU mesh: GSPMD and manual
shard_map train/eval steps must match the single-device computation."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import RowBatch
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.ops import sharded as tp_ops
from code2vec_tpu.parallel.mesh import (
    MeshPlan, make_mesh, replicated_axes_for_spec, make_mesh as _mm,
)
from code2vec_tpu.training.state import (
    TrainState, create_train_state, make_optimizer,
)
from code2vec_tpu.training.step import (
    TrainStepBuilder, device_put_batch,
)
from jax.sharding import PartitionSpec as P


def _make_batch(rng, B, M, dims, all_valid_rows=True):
    src = rng.integers(0, dims.token_vocab_size, (B, M)).astype(np.int32)
    pth = rng.integers(0, dims.path_vocab_size, (B, M)).astype(np.int32)
    tgt = rng.integers(0, dims.token_vocab_size, (B, M)).astype(np.int32)
    mask = (rng.random((B, M)) > 0.3).astype(np.float32)
    mask[:, 0] = 1.0
    labels = rng.integers(1, dims.real_target_vocab_size, (B,)).astype(np.int32)
    return RowBatch(
        source_token_indices=src, path_indices=pth, target_token_indices=tgt,
        context_valid_mask=mask, target_index=labels,
        example_valid=np.ones((B,), bool))


def _config(**kw):
    defaults = dict(train_data_path_prefix="unused", compute_dtype="float32",
                    train_batch_size=8, test_batch_size=8, max_contexts=8)
    defaults.update(kw)
    return Config(**defaults)


def _module_and_state(config, dims, mesh=None):
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                            dropout_keep_rate=config.dropout_keep_rate)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(7), mesh=mesh)
    return module, opt, state


DIMS = ModelDims(token_vocab_size=24, path_vocab_size=16,
                 target_vocab_size=16, token_dim=4, path_dim=4)


def test_replicated_axes_rule():
    assert replicated_axes_for_spec(P("model", None)) == ("data", "ctx")
    assert replicated_axes_for_spec(P()) == ("data", "model", "ctx")
    assert replicated_axes_for_spec(P("data", "ctx")) == ("model",)


def test_tp_ops_match_dense():
    """tp_embedding_lookup / tp_softmax_ce / tp_top_k vs dense equivalents."""
    mesh = make_mesh(MeshPlan(dp=1, tp=4, cp=1))
    rng = np.random.default_rng(0)
    table = rng.standard_normal((16, 4)).astype(np.float32)
    ids = rng.integers(0, 16, (8,)).astype(np.int32)
    logits = rng.standard_normal((8, 16)).astype(np.float32)
    labels = rng.integers(0, 16, (8,)).astype(np.int32)

    def per_shard(table_shard, ids, logits_shard, labels):
        emb = tp_ops.tp_embedding_lookup(table_shard, ids, "model")
        ce = tp_ops.tp_softmax_ce(logits_shard, labels, "model")
        vals, idx = tp_ops.tp_top_k(logits_shard, 3, "model")
        return emb, ce, vals, idx

    f = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("model", None), P(), P(None, "model"), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False)
    emb, ce, vals, idx = f(table, ids, logits, labels)

    np.testing.assert_allclose(np.asarray(emb), table[ids], atol=1e-6)
    ref_ce = (np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1))
              + logits.max(-1) - logits[np.arange(8), labels])
    np.testing.assert_allclose(np.asarray(ce), ref_ce, rtol=1e-5, atol=1e-5)
    ref_idx = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(ref_idx))
    np.testing.assert_allclose(
        np.asarray(vals), np.take_along_axis(logits, ref_idx, -1),
        rtol=1e-6)


@pytest.mark.parametrize("plan", [
    MeshPlan(dp=8, tp=1, cp=1),
    MeshPlan(dp=2, tp=2, cp=2),
    MeshPlan(dp=1, tp=4, cp=2),
])
def test_gspmd_train_step_matches_single_device(plan):
    config = _config(dp=plan.dp, tp=plan.tp, cp=plan.cp,
                     use_manual_tp_kernels=False)
    dims = DIMS.padded_to(plan.tp) if plan.tp > 1 else DIMS
    batch = _make_batch(np.random.default_rng(1), 8, 8, dims)
    rng = jax.random.PRNGKey(3)

    # single-device baseline (eval first: the train step donates its state)
    cfg1 = _config(use_manual_tp_kernels=False)
    module1, opt1, state1 = _module_and_state(cfg1, dims)
    builder1 = TrainStepBuilder(module1, opt1, cfg1, mesh=None)
    arrays1 = device_put_batch(batch, None)
    eval1 = builder1.make_eval_step(state1, k=3)
    out1 = eval1(state1.params, *arrays1)

    mesh = make_mesh(plan)
    module, opt, state = _module_and_state(config, dims, mesh=mesh)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)
    assert not builder.manual
    arrays = device_put_batch(batch, mesh)
    evalN = builder.make_eval_step(state, k=3)
    outN = evalN(state.params, *arrays)

    # Dropout RNG folding differs across shardings, so the stochastic train
    # losses are not bit-comparable; check finiteness of a train step on
    # each layout and exact equality of the deterministic eval forward.
    step1 = builder1.make_train_step(state1)
    new1, loss1 = step1(state1, *arrays1, rng)
    step = builder.make_train_step(state)
    new, loss = step(state, *arrays, rng)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss))
    np.testing.assert_allclose(np.asarray(out1.topk_values),
                               np.asarray(outN.topk_values), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out1.topk_indices),
                                  np.asarray(outN.topk_indices))
    np.testing.assert_allclose(float(out1.loss_sum), float(outN.loss_sum),
                               rtol=1e-4)


@pytest.mark.parametrize("plan", [
    MeshPlan(dp=2, tp=2, cp=2),
    MeshPlan(dp=1, tp=8, cp=1),
    MeshPlan(dp=2, tp=1, cp=4),
])
def test_manual_shard_map_matches_single_device(plan):
    config = _config(dp=plan.dp, tp=plan.tp, cp=plan.cp,
                     use_manual_tp_kernels=True)
    dims = DIMS.padded_to(plan.tp) if plan.tp > 1 else DIMS
    batch = _make_batch(np.random.default_rng(2), 8, 8, dims)
    rng = jax.random.PRNGKey(5)

    cfg1 = _config(use_manual_tp_kernels=False)
    module1, opt1, state1 = _module_and_state(cfg1, dims)
    arrays1 = device_put_batch(batch, None)
    eval1 = TrainStepBuilder(module1, opt1, cfg1, mesh=None).make_eval_step(state1, k=3)
    out1 = eval1(state1.params, *arrays1)

    mesh = make_mesh(plan)
    module, opt, state = _module_and_state(config, dims, mesh=mesh)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)
    assert builder.manual
    arrays = device_put_batch(batch, mesh)
    evalN = builder.make_eval_step(state, k=3)
    outN = evalN(state.params, *arrays)

    np.testing.assert_allclose(np.asarray(out1.topk_values),
                               np.asarray(outN.topk_values), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out1.topk_indices),
                                  np.asarray(outN.topk_indices))
    np.testing.assert_allclose(float(out1.loss_sum), float(outN.loss_sum),
                               rtol=1e-4)

    # Manual train step runs and decreases loss over a few steps.
    step = builder.make_train_step(state)
    losses = []
    for i in range(5):
        state, loss = step(state, *arrays, jax.random.PRNGKey(0))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_manual_grads_match_single_device_grads():
    """Deterministic (no-dropout) gradient parity: manual shard_map grads
    == single-device grads. Verifies the storage-replication psum rule."""
    plan = MeshPlan(dp=2, tp=2, cp=2)
    dims = DIMS.padded_to(plan.tp)
    config = _config(dp=plan.dp, tp=plan.tp, cp=plan.cp,
                     dropout_keep_rate=1.0)
    batch = _make_batch(np.random.default_rng(3), 8, 8, dims)
    rng = jax.random.PRNGKey(11)

    cfg1 = _config(dropout_keep_rate=1.0)
    module1, opt1, state1 = _module_and_state(cfg1, dims)
    step1 = TrainStepBuilder(module1, opt1, cfg1, mesh=None).make_train_step(state1)
    arrays1 = device_put_batch(batch, None)
    new1, loss1 = step1(state1, *arrays1, rng)

    mesh = make_mesh(plan)
    module, opt, state = _module_and_state(config, dims, mesh=mesh)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)
    step = builder.make_train_step(state)
    arrays = device_put_batch(batch, mesh)
    new, loss = step(state, *arrays, rng)

    np.testing.assert_allclose(float(loss1), float(loss), rtol=1e-5)
    for name in new1.params:
        np.testing.assert_allclose(
            np.asarray(new1.params[name]), np.asarray(new.params[name]),
            rtol=2e-4, atol=2e-5, err_msg=f"param {name} diverged")
