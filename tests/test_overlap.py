"""Bucketed async all-reduce overlap suite (parallel/overlap.py).

Pins the roofline PR's correctness contract: the overlapped composite
(backward + K bucket reduce+apply dispatches) computes the SAME step as
the unbucketed single-program GSPMD step — loss bit-equal, params
within a documented float tolerance (the program split changes XLA's
fusion/reduction order for the token table's two-gather gradient; the
mesh path additionally reorders the cross-shard sum) — plus the bucket
planner's size/order laws and the config guard rails.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.config import Config

pytestmark = pytest.mark.roofline

# Documented parity tolerance (see module docstring): everything
# observed is <= 2e-9 absolute on the tiny model; the bound leaves room
# for platform-dependent fusion without letting a real bug through.
PARITY_RTOL = 2e-6
PARITY_ATOL = 1e-7


def _build(overlap, mesh=None, *, dropout_keep=1.0, bucket_mb=0.003,
           nu_dtype="bfloat16", in_backward=False):
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.training.state import create_train_state, make_optimizer
    from code2vec_tpu.training import step as step_mod
    from code2vec_tpu.training.step import TrainStepBuilder
    config = Config(train_data_path_prefix="<t>", train_batch_size=8,
                    max_contexts=6, compute_dtype="float32",
                    dropout_keep_rate=dropout_keep,
                    dp=(2 if mesh is not None else 1),
                    adam_nu_dtype=nu_dtype,
                    overlap_grad_allreduce=overlap,
                    overlap_in_backward=in_backward,
                    overlap_bucket_mb=bucket_mb)
    dims = ModelDims(token_vocab_size=50, path_vocab_size=40,
                     target_vocab_size=30, token_dim=8, path_dim=8)
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                            dropout_keep_rate=dropout_keep)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(0),
                               mesh=mesh, config=config)
    # The overlapped step differentiates the module over `jnp.take`; its
    # bit-level reference is the monolithic step over the same lookup,
    # not the live-rows one (ops/embed.py sums a table row's gradient in
    # another order; tests/test_embed_live.py holds the two together).
    with mock.patch.object(step_mod, "gathers_live_rows",
                           lambda config, mesh: False):
        step = TrainStepBuilder(module, opt, config,
                                mesh=mesh).make_train_step(state)
    return step, state


def _batch(mesh=None):
    rng = np.random.default_rng(3)
    b, m = 8, 6
    arrays = (rng.integers(2, 50, (b, m)).astype(np.int32),
              rng.integers(2, 40, (b, m)).astype(np.int32),
              rng.integers(2, 50, (b, m)).astype(np.int32),
              np.ones((b, m), np.float32),
              rng.integers(2, 30, (b,)).astype(np.int32),
              np.ones((b,), bool))
    if mesh is None:
        return tuple(jnp.asarray(a) for a in arrays)
    import collections

    from code2vec_tpu.training.step import device_put_batch
    Batch = collections.namedtuple("Batch", [
        "source_token_indices", "path_indices", "target_token_indices",
        "context_valid_mask", "target_index", "example_valid"])
    return device_put_batch(Batch(*arrays), mesh)


def _run_parity(mesh, steps=3, in_backward=False):
    step_ref, s_ref = _build(False, mesh)
    step_ov, s_ov = _build(True, mesh, in_backward=in_backward)
    assert step_ov.overlap_buckets >= 2, step_ov.overlap_description
    arrays = _batch(mesh)
    key = jax.random.PRNGKey(7)
    for i in range(steps):
        s_ref, l_ref = step_ref(s_ref, *arrays, key)
        s_ov, l_ov = step_ov(s_ov, *arrays, key)
        if in_backward:
            # the loss comes from bucket 0's restricted backward, whose
            # program fuses differently — same math, not bit-pinned
            np.testing.assert_allclose(float(l_ref), float(l_ov),
                                       rtol=1e-6, err_msg=f"step {i}")
        else:
            assert float(l_ref) == float(l_ov), \
                f"step {i}: loss {float(l_ref)} != {float(l_ov)}"
    for k in s_ref.params:
        np.testing.assert_allclose(
            np.asarray(s_ov.params[k]), np.asarray(s_ref.params[k]),
            rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=k)
    # optimizer state advanced identically: shared count, all moment
    # leaves present and matching within the same tolerance
    assert int(np.asarray(s_ov.opt_state[0].count)) == steps
    for k in s_ref.params:
        np.testing.assert_allclose(
            np.asarray(s_ov.opt_state[0].mu[k], dtype=np.float32),
            np.asarray(s_ref.opt_state[0].mu[k], dtype=np.float32),
            rtol=1e-3, atol=1e-6, err_msg=f"mu/{k}")  # bf16 storage
    return s_ref, s_ov


def test_overlap_parity_single_device():
    """mesh=None: pure apply pipelining — loss bit-equal to the
    unbucketed step, params within the documented tolerance."""
    _run_parity(None)


def test_overlap_parity_dp2_mesh():
    """dp=2 mesh: the per-shard backward + per-bucket psum computes the
    same step as the in-program all-reduce."""
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    mesh = make_mesh(MeshPlan(dp=2))
    _run_parity(mesh)


def test_overlap_parity_in_backward_single_device():
    """overlap_in_backward: per-bucket backwards (one extra forward per
    bucket, shared dropout draw) produce the same update as the
    whole-model backward."""
    _run_parity(None, in_backward=True)


def test_overlap_parity_in_backward_dp2_mesh():
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    mesh = make_mesh(MeshPlan(dp=2))
    step, _ = _build(True, mesh, in_backward=True)
    assert step.overlap_in_backward
    assert "in-backward" in step.overlap_description
    _run_parity(mesh, in_backward=True)


def _build_manual(overlap, mesh, *, in_backward=False, dropout_keep=1.0):
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.training.state import create_train_state, make_optimizer
    from code2vec_tpu.training.step import TrainStepBuilder
    config = Config(train_data_path_prefix="<t>", train_batch_size=8,
                    max_contexts=6, compute_dtype="float32",
                    dropout_keep_rate=dropout_keep,
                    dp=2, tp=2, use_manual_tp_kernels=True,
                    overlap_grad_allreduce=overlap,
                    overlap_in_backward=in_backward,
                    overlap_bucket_mb=0.003)
    config.verify()
    # vocab sizes divisible by tp=2, so no target padding in play
    dims = ModelDims(token_vocab_size=50, path_vocab_size=40,
                     target_vocab_size=30, token_dim=8, path_dim=8)
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32,
                            dropout_keep_rate=dropout_keep)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(0),
                               mesh=mesh, config=config)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)
    assert builder.manual
    return builder.make_train_step(state), state


def test_overlap_parity_manual_tp_mesh():
    """The manual-kernel tp/cp backward through the overlap builder
    computes the same step as the monolithic manual shard_map step
    (identical dropout folding discipline, so losses line up too)."""
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    mesh = make_mesh(MeshPlan(dp=2, tp=2))
    arrays = _batch(mesh)
    key = jax.random.PRNGKey(7)
    step_ref, s_ref = _build_manual(False, mesh)
    step_ov, s_ov = _build_manual(True, mesh)
    assert step_ov.overlap_buckets >= 2, step_ov.overlap_description
    assert "manual" in step_ov.overlap_description
    for i in range(3):
        s_ref, l_ref = step_ref(s_ref, *arrays, key)
        s_ov, l_ov = step_ov(s_ov, *arrays, key)
        np.testing.assert_allclose(float(l_ref), float(l_ov),
                                   rtol=1e-6, err_msg=f"step {i}")
    for k in s_ref.params:
        np.testing.assert_allclose(
            np.asarray(s_ov.params[k]), np.asarray(s_ref.params[k]),
            rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=k)


def test_overlap_parity_manual_in_backward():
    """Manual tp/cp x in-backward completion: still the same step."""
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    mesh = make_mesh(MeshPlan(dp=2, tp=2))
    arrays = _batch(mesh)
    key = jax.random.PRNGKey(7)
    step_ref, s_ref = _build_manual(False, mesh)
    step_ib, s_ib = _build_manual(True, mesh, in_backward=True)
    assert step_ib.overlap_in_backward
    for i in range(2):
        s_ref, l_ref = step_ref(s_ref, *arrays, key)
        s_ib, l_ib = step_ib(s_ib, *arrays, key)
        np.testing.assert_allclose(float(l_ref), float(l_ib),
                                   rtol=1e-6, err_msg=f"step {i}")
    for k in s_ref.params:
        np.testing.assert_allclose(
            np.asarray(s_ib.params[k]), np.asarray(s_ref.params[k]),
            rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=k)


def test_overlap_parity_f32_adam_state():
    """The bucket slicing also handles the plain optax.adam state
    (nu_dtype float32 skips the custom transform)."""
    step_ref, s_ref = _build(False, nu_dtype="float32")
    step_ov, s_ov = _build(True, nu_dtype="float32")
    arrays = _batch()
    key = jax.random.PRNGKey(5)
    s_ref, l_ref = step_ref(s_ref, *arrays, key)
    s_ov, l_ov = step_ov(s_ov, *arrays, key)
    assert float(l_ref) == float(l_ov)
    for k in s_ref.params:
        np.testing.assert_allclose(
            np.asarray(s_ov.params[k]), np.asarray(s_ref.params[k]),
            rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=k)


def test_overlap_with_dropout_trains():
    """Dropout draws differ from the unbucketed step by design (the
    mesh path folds the data-axis index); the overlapped step must
    still train — finite losses, params move, moments update."""
    step_ov, state = _build(True, dropout_keep=0.75)
    arrays = _batch()
    key = jax.random.PRNGKey(9)
    before = np.asarray(state.params["transform"]).copy()
    for _ in range(2):
        state, loss = step_ov(state, *arrays, key)
        assert np.isfinite(float(loss))
    assert not np.array_equal(before, np.asarray(state.params["transform"]))


def test_plan_buckets_order_and_bounds():
    from code2vec_tpu.parallel.overlap import plan_buckets

    class L:  # noqa: N801 — shape-only stand-in
        def __init__(self, *shape):
            self.shape = shape

    params = {"token_embedding": L(100, 8), "path_embedding": L(50, 8),
              "target_embedding": L(30, 24), "transform": L(24, 24),
              "attention": L(24, 1)}
    buckets = plan_buckets(params, bucket_bytes=3000)
    flat = [n for b in buckets for n in b]
    # backward-completion order: classifier side first, gathers last
    assert flat == ["target_embedding", "attention", "transform",
                    "path_embedding", "token_embedding"]
    # every bucket respects the byte bound unless a single leaf exceeds
    # it alone
    for b in buckets:
        nbytes = sum(int(np.prod(params[n].shape)) * 4 for n in b)
        assert nbytes <= 3000 or len(b) == 1
    # one-bucket degenerate case with a huge budget
    assert plan_buckets(params, bucket_bytes=1 << 30) == [flat]
    # a leaf larger than the budget still lands (its own bucket)
    tiny = plan_buckets(params, bucket_bytes=1)
    assert [n for b in tiny for n in b] == flat
    assert all(len(b) == 1 for b in tiny)


def test_overlap_step_exposes_plan():
    step, _ = _build(True)
    assert step.overlap_buckets >= 2
    assert "gradient bucket" in step.overlap_description


def test_config_rejects_overlap_with_sparse_or_tp():
    base = dict(train_data_path_prefix="<t>", overlap_grad_allreduce=True)
    with pytest.raises(ValueError, match="sparse"):
        Config(**base, use_sparse_embedding_update=True).verify()
    # tp/cp sharding needs the manual-kernel path (GSPMD tp/cp keeps
    # the stock fused step)
    with pytest.raises(ValueError, match="manual_tp_kernels"):
        Config(**base, tp=2, max_contexts=200,
               use_manual_tp_kernels=False).verify()
    with pytest.raises(ValueError, match="manual_tp_kernels"):
        Config(**base, cp=2, max_contexts=200,
               use_manual_tp_kernels=False).verify()
    with pytest.raises(ValueError, match="overlap_bucket_mb"):
        Config(train_data_path_prefix="<t>",
               overlap_bucket_mb=0).verify()
    with pytest.raises(ValueError, match="overlap_in_backward"):
        Config(train_data_path_prefix="<t>",
               overlap_in_backward=True).verify()
    # the supported combos pass
    Config(**base, dp=2).verify()
    Config(**base, tp=2, max_contexts=200,
           use_manual_tp_kernels=True).verify()
    Config(**base, dp=2, overlap_in_backward=True).verify()


def test_overlap_refuses_foreign_opt_state():
    """A non-Adam optax state must be refused loudly, not mis-sliced."""
    from code2vec_tpu.parallel.overlap import build_overlap_train_step

    class FakeBuilder:
        config = Config(train_data_path_prefix="<t>",
                        overlap_grad_allreduce=True)
        module = optimizer = None
        mesh = None

    class FakeState:
        params = {"transform": np.zeros((2, 2), np.float32)}
        opt_state = (object(),)

    with pytest.raises(ValueError, match="ScaleByAdamState"):
        build_overlap_train_step(FakeBuilder(), FakeState())
