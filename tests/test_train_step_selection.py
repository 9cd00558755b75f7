"""Which of the four train steps `TrainStepBuilder.make_train_step`
builds (training/step.py, module docstring): manual or not from the mesh
and `use_manual_tp_kernels`, sparse or not from the opt-state's type,
and nothing else. The four `_make_*` methods are spied on; nothing is
compiled."""

import functools

import jax
import jax.numpy as jnp
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
from code2vec_tpu.training.state import create_train_state, make_optimizer
from code2vec_tpu.training.step import TrainStepBuilder

DIMS = ModelDims(token_vocab_size=64, path_vocab_size=32,
                 target_vocab_size=24, token_dim=8, path_dim=8)
STEPS = ("_make_gspmd_train_step", "_make_gspmd_sparse_train_step",
         "_make_manual_train_step", "_make_manual_sparse_train_step")

# name -> (dp, tp, cp, use_manual_tp_kernels, manual step expected)
MESHES = {
    "no_mesh": (1, 1, 1, True, False),
    "dp4": (4, 1, 1, True, False),
    "dp2_tp2_manual": (2, 2, 1, True, True),
    "dp2_tp2_gspmd": (2, 2, 1, False, False),
    "dp2_cp2": (2, 1, 2, True, True),
}


def _config(name, sparse):
    dp, tp, cp, manual_kernels, _ = MESHES[name]
    return Config(train_data_path_prefix="unused", train_batch_size=8,
                  max_contexts=4, compute_dtype="float32",
                  dp=dp, tp=tp, cp=cp,
                  use_manual_tp_kernels=manual_kernels,
                  use_sparse_embedding_update=sparse)


@functools.lru_cache(maxsize=None)
def _module_and_state(sparse):
    """The choice reads the state's type only: one device holds it, and
    every mesh of this file is shown the same one."""
    config = _config("no_mesh", sparse)
    module = Code2VecModule(dims=DIMS, dropout_keep_rate=1.0,
                            compute_dtype=jnp.float32)
    return module, create_train_state(
        module, make_optimizer(config), jax.random.PRNGKey(0), config=config)


def _builder_and_state(name, sparse_config, sparse_state):
    dp, tp, cp, _, _ = MESHES[name]
    config = _config(name, sparse_config)
    module, state = _module_and_state(sparse_state)
    mesh = (make_mesh(MeshPlan(dp=dp, tp=tp, cp=cp))
            if dp * tp * cp > 1 else None)
    return TrainStepBuilder(module, make_optimizer(config), config,
                            mesh=mesh), state


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("name", sorted(MESHES))
def test_make_train_step_builds_the_step_its_docstring_names(
        monkeypatch, name, sparse):
    for step in STEPS:
        monkeypatch.setattr(TrainStepBuilder, step,
                            lambda self, state, step=step: step)
    builder, state = _builder_and_state(name, sparse, sparse)
    manual = MESHES[name][4]
    assert builder.make_train_step(state) == (
        f"_make_{'manual' if manual else 'gspmd'}"
        f"{'_sparse' if sparse else ''}_train_step")


@pytest.mark.parametrize("sparse_state", [False, True],
                         ids=["dense_state", "sparse_state"])
def test_a_state_made_for_the_other_update_is_refused(sparse_state):
    builder, state = _builder_and_state("no_mesh", not sparse_state,
                                        sparse_state)
    with pytest.raises(ValueError, match="use_sparse_embedding_update"):
        builder.make_train_step(state)


@pytest.mark.parametrize("argv", [
    ["--overlap_allreduce"],
    ["--overlap_allreduce", "--overlap_in_backward"],
    ["--overlap_bucket_mb", "8"],
    ["--prefetch_double_buffer"],
], ids=["overlap_allreduce", "overlap_in_backward", "overlap_bucket_mb",
        "prefetch_double_buffer"])
def test_the_retired_step_and_feed_options_are_refused(argv, capsys):
    """PR 28 took the bucketed all-reduce step (both modes) and the
    prefetcher's second hand-over order: their options are unknown
    again, not accepted and ignored."""
    from code2vec_tpu.cli import config_from_args
    with pytest.raises(SystemExit) as refused:
        config_from_args(["--data", "unused"] + argv)
    assert refused.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
