"""Model math tests: attention/softmax numerics vs hand-computed numpy
(the spec is tensorflow_model.py:235-264)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.ops.attention import masked_single_query_attention


def _numpy_reference_forward(params, src, pth, tgt, mask):
    """Direct numpy transcription of the reference math
    (tensorflow_model.py:237-262), no dropout."""
    tok = params["token_embedding"]
    path = params["path_embedding"]
    ctx = np.concatenate([tok[src], path[pth], tok[tgt]], axis=-1)
    transformed = np.tanh(ctx @ params["transform"])
    scores = transformed @ params["attention"][:, 0]
    scores = scores + np.log(mask)          # log(0) = -inf on invalid
    scores = scores - scores.max(axis=1, keepdims=True)
    e = np.exp(scores)
    attn = e / e.sum(axis=1, keepdims=True)
    code = (transformed * attn[..., None]).sum(axis=1)
    logits = code @ params["target_embedding"].T
    return code, attn, logits


@pytest.fixture
def small_module_and_params():
    dims = ModelDims(token_vocab_size=11, path_vocab_size=7,
                     target_vocab_size=5, token_dim=4, path_dim=4)
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, 1), jnp.int32)
    params = module.init({"params": rng}, dummy, dummy, dummy,
                         jnp.zeros((1, 1)))["params"]
    return module, params


def test_forward_matches_numpy_reference(small_module_and_params):
    module, params = small_module_and_params
    rng = np.random.default_rng(0)
    B, M = 3, 6
    src = rng.integers(0, 11, (B, M)).astype(np.int32)
    pth = rng.integers(0, 7, (B, M)).astype(np.int32)
    tgt = rng.integers(0, 11, (B, M)).astype(np.int32)
    mask = (rng.random((B, M)) > 0.3).astype(np.float32)
    mask[:, 0] = 1.0  # every row has a valid context

    logits, code, attn = module.apply({"params": params}, src, pth, tgt, mask,
                                      deterministic=True)
    np_params = jax.tree.map(np.asarray, params)
    ref_code, ref_attn, ref_logits = _numpy_reference_forward(
        np_params, src, pth, tgt, mask)

    np.testing.assert_allclose(np.asarray(code), ref_code, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(attn), ref_attn, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits), ref_logits, rtol=1e-4, atol=1e-4)


def test_attention_invalid_contexts_get_zero_weight():
    B, M, D = 2, 4, 3
    transformed = jnp.ones((B, M, D))
    att = jnp.ones((D,))
    mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]], jnp.float32)
    code, attn = masked_single_query_attention(transformed, att, mask)
    np.testing.assert_allclose(np.asarray(attn[0]), [0.5, 0.5, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(attn[1]), [1, 0, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(code), np.ones((B, D)), atol=1e-6)


def test_attention_all_invalid_row_is_finite():
    # Padded eval rows have no valid context; weights must be 0 (not NaN)
    # so downstream psums stay finite.
    transformed = jnp.ones((1, 4, 3))
    mask = jnp.zeros((1, 4), jnp.float32)
    code, attn = masked_single_query_attention(transformed, jnp.ones((3,)), mask)
    assert np.isfinite(np.asarray(attn)).all()
    np.testing.assert_allclose(np.asarray(attn), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(code), 0.0, atol=1e-6)


def test_dropout_scales_and_zeroes(small_module_and_params):
    module, params = small_module_and_params
    B, M = 2, 5
    src = np.zeros((B, M), np.int32)
    pth = np.zeros((B, M), np.int32)
    tgt = np.zeros((B, M), np.int32)
    mask = np.ones((B, M), np.float32)
    out1 = module.apply({"params": params}, src, pth, tgt, mask,
                        deterministic=False,
                        rngs={"dropout": jax.random.PRNGKey(1)})
    out2 = module.apply({"params": params}, src, pth, tgt, mask,
                        deterministic=True)
    # stochastic forward differs from deterministic one
    assert not np.allclose(np.asarray(out1[0]), np.asarray(out2[0]))


def test_padded_target_dims_mask_logits():
    dims = ModelDims(token_vocab_size=8, path_vocab_size=8,
                     target_vocab_size=8, token_dim=4, path_dim=4,
                     real_target_vocab_size=5)
    module = Code2VecModule(dims=dims, compute_dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, 2), jnp.int32)
    params = module.init({"params": rng}, dummy, dummy, dummy,
                         jnp.ones((1, 2)))["params"]
    logits, _, _ = module.apply({"params": params}, dummy, dummy, dummy,
                                jnp.ones((1, 2)), deterministic=True)
    assert np.asarray(logits)[:, 5:].max() == -np.inf
    assert np.isfinite(np.asarray(logits)[:, :5]).all()


def test_padded_to_rounds_up():
    dims = ModelDims(token_vocab_size=10, path_vocab_size=9,
                     target_vocab_size=7, token_dim=4, path_dim=4)
    p = dims.padded_to(4)
    assert (p.token_vocab_size, p.path_vocab_size, p.target_vocab_size) == (12, 12, 8)
    assert p.real_target_vocab_size == 7
    assert p.has_padded_targets


# ------------------------------------------------- scope names in the step

_SCOPES = {
    # the dense step runs the chain over the live blocks
    # (ops/encode_live.py): its loops' bodies carry the chain's names,
    # forward and transposed, as a scope sum of a trace reads them;
    # the head's own VJP names its forward and its backward
    # `logits_ce` alike (ops/head_ce.py)
    False: ("embed_gather", "/transform/", "/attention/",
            "/transpose(jvp(transform))/", "/transpose(jvp(attention))/",
            "/jvp(head_ce)/logits_ce/", "/transpose(jvp(head_ce))/logits_ce/",
            "adam_token", "adam_path", "adam_target", "adam_dense",
            # one chip: the backward's sorted (key, row) list, which the
            # two tables' Adam takes in place of a table-shaped gradient
            "embed_row_list"),
    # the touched-rows step gathers outside the differentiated function
    # and updates the two tables row-wise under the same two names
    True: ("embed_gather", "transform", "attention",
           "/jvp(head_ce)/logits_ce/", "/transpose(jvp(head_ce))/logits_ce/",
           "adam_token", "adam_path", "adam_dense"),
}


def _lower_toy_train_step(sparse: bool):
    from code2vec_tpu.config import Config
    from code2vec_tpu.training.state import (
        create_train_state, make_optimizer,
    )
    from code2vec_tpu.training.step import TrainStepBuilder
    b, m = 8, 8
    dims = ModelDims(token_vocab_size=64, path_vocab_size=32,
                     target_vocab_size=32, token_dim=16, path_dim=16)
    config = Config(train_data_path_prefix="unused", train_batch_size=b,
                    max_contexts=m, use_sparse_embedding_update=sparse)
    module = Code2VecModule(dims=dims)
    optimizer = make_optimizer(config)
    state = create_train_state(module, optimizer, jax.random.PRNGKey(0),
                               config=config)
    step = TrainStepBuilder(module, optimizer, config).make_train_step(state)
    ids = np.arange(b * m, dtype=np.int32).reshape(b, m) % 16
    return step.lower(state, ids, ids, ids, np.ones((b, m), np.float32),
                      np.ones((b,), np.int32), np.ones((b,), bool),
                      jax.random.PRNGKey(1))


@pytest.mark.parametrize("sparse", [False, True])
def test_scope_names_are_in_the_step_and_change_nothing_else(monkeypatch,
                                                             sparse):
    """`jax.named_scope` names every part of the train step for the
    profiler's op view and is metadata only: the same step lowered with
    every scope switched off is the same program once locations are
    stripped (which is also what the compile-cache key is taken from)."""
    from jax._src import source_info_util
    scoped = _lower_toy_train_step(sparse)
    named = scoped.as_text(debug_info=True)
    for scope in _SCOPES[sparse]:
        assert scope in named, scope
    # one chip's lookups stand outside the differentiated function: no
    # transposed lookup (a mesh's table-shaped gradient scatter, the
    # backward of ops/embed.py embed_live_rows) is in its step
    assert "transpose(jvp(embed_gather))" not in named
    manager = source_info_util.ExtendNameStackContextManager
    monkeypatch.setattr(manager, "__enter__", lambda self: None)
    monkeypatch.setattr(manager, "__exit__", lambda self, *exc: None)
    bare = _lower_toy_train_step(sparse)
    assert "adam_token" not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped.as_text()


def test_scoped_adam_update_is_one_update_of_the_whole_tree():
    """Adam run once per table under its own scope gives bit for bit
    what one `optimizer.update` over the whole tree gives, state
    structure included (a checkpoint restores either way)."""
    import optax
    from code2vec_tpu.training.step import scoped_adam_update
    rng = np.random.default_rng(0)
    shapes = {"token_embedding": (6, 4), "path_embedding": (5, 4),
              "target_embedding": (7, 12), "transform": (12, 12),
              "attention": (12, 1)}
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
              for k, s in shapes.items()}
    optimizer = optax.adam(1e-2, mu_dtype=jnp.bfloat16)
    whole_state, split_state = optimizer.init(params), optimizer.init(params)
    whole, split = params, params
    for _ in range(3):
        grads = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
                 for k, s in shapes.items()}
        updates, whole_state = optimizer.update(grads, whole_state, whole)
        whole = optax.apply_updates(whole, updates)
        split, split_state = scoped_adam_update(optimizer, grads,
                                                split_state, split)
    assert jax.tree.structure(split_state) == jax.tree.structure(whole_state)
    for a, b in zip(jax.tree.leaves((whole, whole_state)),
                    jax.tree.leaves((split, split_state))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
