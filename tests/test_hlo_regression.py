"""HLO-level sharding regression tests.

The manual tensor-parallel kernels (ops/sharded.py, training/step.py)
exist to prevent two specific compiled-program failure modes; these
tests pin them by grepping the actual post-SPMD compiled HLO:

1. logits stay vocab-sharded: no collective ever materializes a full
   (B, target_vocab) logits tensor (ops/sharded.py tp_softmax_ce /
   tp_top_k rationale — at java14m scale that tensor is (B, 261K));
2. the touched-rows sparse optimizer replaces the table-shaped gradient
   all-reduce with a (ids, rows) all-gather exchange
   (training/sparse_adam.py; at java14m scale the dense exchange moves
   the full 1.3M x 128 table per step, the sparse one ~5x less).

Shapes at test scale: B=8, target vocab 32 (padded), token table shard
64/2 x 16 = (32, 16). The dense/sparse pair is differential: the same
table-shaped all-reduce the dense HLO must contain, the sparse HLO must
not — so a change that merely renames HLO ops can't silently pass.
"""

import re

import numpy as np
import jax
import pytest
import jax.numpy as jnp

from code2vec_tpu.config import Config
from code2vec_tpu.data.reader import RowBatch
from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
from code2vec_tpu.training.state import create_train_state, make_optimizer
from code2vec_tpu.training.step import TrainStepBuilder, device_put_batch

B, M = 8, 8
PLAN = MeshPlan(dp=2, tp=2, cp=2)
# token vocab 64 over tp=2 -> (32, 16) table shards; target vocab 32
# (already tp-divisible) -> full logits would be (8, 32)
DIMS = ModelDims(token_vocab_size=64, path_vocab_size=32,
                 target_vocab_size=32, token_dim=16, path_dim=16)
TOKEN_TABLE_SHARD = f"f32[{DIMS.token_vocab_size // PLAN.tp},{DIMS.token_dim}]"
FULL_LOGITS = f"f32[{B},{DIMS.target_vocab_size}]"

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all)")


def _build(sparse: bool):
    config = Config(train_data_path_prefix="unused", compute_dtype="float32",
                    dp=PLAN.dp, tp=PLAN.tp, cp=PLAN.cp,
                    use_manual_tp_kernels=True,
                    train_batch_size=B, max_contexts=M,
                    use_sparse_embedding_update=sparse)
    mesh = make_mesh(PLAN)
    module = Code2VecModule(dims=DIMS, compute_dtype=jnp.float32)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(0),
                               mesh=mesh, config=config)
    builder = TrainStepBuilder(module, opt, config, mesh=mesh)
    assert builder.manual
    rng = np.random.default_rng(0)
    batch = RowBatch(
        source_token_indices=rng.integers(0, 16, (B, M)).astype(np.int32),
        path_indices=rng.integers(0, 16, (B, M)).astype(np.int32),
        target_token_indices=rng.integers(0, 16, (B, M)).astype(np.int32),
        context_valid_mask=np.ones((B, M), np.float32),
        target_index=rng.integers(1, 16, (B,)).astype(np.int32),
        example_valid=np.ones((B,), bool))
    arrays = device_put_batch(batch, mesh)
    return builder, state, arrays


def _collective_lines(hlo_text: str):
    return [ln for ln in hlo_text.splitlines() if _COLLECTIVE_RE.search(ln)]


def _train_hlo(sparse: bool) -> str:
    builder, state, arrays = _build(sparse)
    step = builder.make_train_step(state)
    return step.lower(state, *arrays, jax.random.PRNGKey(1)).compile().as_text()


def test_no_full_logits_collective_in_tp_steps():
    """(i) Nothing in the compiled tp train/eval programs all-gathers a
    full (B, target_vocab) logits tensor."""
    builder, state, arrays = _build(sparse=False)
    eval_step = builder.make_eval_step(state, k=3)
    eval_text = eval_step.lower(state.params, *arrays).compile().as_text()
    train_text = _train_hlo(sparse=False)
    for label, text in (("eval", eval_text), ("train", train_text)):
        offending = [ln for ln in _collective_lines(text) if FULL_LOGITS in ln]
        assert not offending, (
            f"{label} step materializes full logits {FULL_LOGITS} in a "
            f"collective:\n" + "\n".join(offending[:4]))


# The data mesh's dense step (`--dp 4`): 16 rows over 4 chips, and a
# target table whose 30 rows leave 2 modulo 4 (java14m's 261,245 leave
# 1), so the chips' shards are 8 rows and the last holds 2 of filling.
DP, DP_ROWS = 4, 16
DP_DIMS = ModelDims(token_vocab_size=64, path_vocab_size=40,
                    target_vocab_size=30, token_dim=16, path_dim=16)
_RESULT_TYPE = re.compile(
    r"= (.*?) (?:all-gather|all-reduce|reduce-scatter|all-to-all)"
    r"(?:-start)?\(")


def _dp_train_step_lowered(dp: int, dims: ModelDims = DP_DIMS):
    config = Config(train_data_path_prefix="unused",
                    compute_dtype="bfloat16", dp=dp,
                    default_embeddings_size=dims.token_dim,
                    train_batch_size=DP_ROWS, max_contexts=M)
    mesh = make_mesh(MeshPlan(dp=dp, tp=1, cp=1)) if dp > 1 else None
    module = Code2VecModule(dims=dims, compute_dtype=jnp.bfloat16)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(0),
                               mesh=mesh, config=config)
    rng = np.random.default_rng(0)
    ids = lambda: rng.integers(0, 16, (DP_ROWS, M)).astype(np.int32)
    arrays = device_put_batch(RowBatch(
        source_token_indices=ids(), path_indices=ids(),
        target_token_indices=ids(),
        context_valid_mask=np.ones((DP_ROWS, M), np.float32),
        target_index=rng.integers(1, 16, (DP_ROWS,)).astype(np.int32),
        example_valid=np.ones((DP_ROWS,), bool)), mesh)
    step = TrainStepBuilder(module, opt, config,
                            mesh=mesh).make_train_step(state)
    return step.lower(state, *arrays, jax.random.PRNGKey(1))


def test_the_dp_steps_target_gradient_is_gathered_not_reduced():
    """On a data mesh the chips split the head's target rows
    (ops/head_ce.py): the target table's gradient is whole on its chip,
    so no all-reduce carries an array of the table's shape, padded or
    not, exactly one all-gather puts the shards together, in the compute
    dtype (read in the lowered text: the CPU's compiler widens a
    bfloat16 collective, the TPU's does not), and no collective moves a
    `(B, V)` array of logits. The detector detects: the token table's
    gradient IS all-reduced."""
    step = _dp_train_step_lowered(DP)
    text = step.compile().as_text()
    rows, dim = DP_DIMS.target_vocab_size, DP_DIMS.code_dim
    filled = -(-rows // DP) * DP
    table = re.compile(rf"\[(?:{rows}|{filled}),{dim}\]")
    results = [(m.group(1), ln) for ln in _collective_lines(text)
               if (m := _RESULT_TYPE.search(ln))]
    reduced = [r for r, ln in results if "all-reduce" in ln]
    assert any(f"f32[{DP_DIMS.token_vocab_size},{DP_DIMS.token_dim}]" in r
               for r in reduced), "the shape pattern is stale"
    assert not [r for r in reduced if table.search(r)]
    gathered = [r for r, ln in results
                if "all-gather" in ln and table.search(r)]
    assert len(gathered) == 1 and f"[{filled},{dim}]" in gathered[0], gathered
    lowered = [ln for ln in step.as_text().splitlines()
               if "all_gather" in ln and f"{filled}x{dim}x" in ln]
    assert len(lowered) == 1 and lowered[0].rstrip().endswith(
        f"-> tensor<{filled}x{dim}xbf16>"), lowered
    logits = re.compile(rf"\[{DP_ROWS},(?:{rows}|{filled})\]")
    assert not [r for r, _ in results if logits.search(r)]


# Tables the row-list Adam's kernel takes (ops/adam_rows.py: 128 wide
# under bfloat16 rows), so the data mesh's step exchanges LISTS.
LIST_DIMS = ModelDims(token_vocab_size=64, path_vocab_size=40,
                      target_vocab_size=30, token_dim=128, path_dim=128)


def _adam_without_a_scatter(table, mu, nu, keys, rows, bias1, bias2, **_):
    """Stands where the TPU's kernel does (off the chip the row-list
    Adam is a scatter into a table of zeros): reads the whole list and
    builds no table from it."""
    read = jnp.sum(keys) + jnp.sum(rows.astype(jnp.float32))
    return table + 0.0 * read, mu, nu


@pytest.mark.parametrize("lists", [True, False],
                         ids=["row_lists", "table_shaped"])
def test_the_dp_step_exchanges_the_chips_lists_and_sums_no_table(
        monkeypatch, lists):
    """On the 4-device data mesh, with tables the kernel takes: the
    compiled step holds no all-reduce of an array of the token or the
    path table's shape and no scatter into one, and does hold the four
    all-gathers that lay the chips' sorted lists end to end (keys int32;
    rows in the compute dtype, read in the lowered text as above).
    Differential: the table-shaped step of the same mesh (the one a
    width the kernel does not take keeps) HAS both all-reduces and both
    scatters, and gathers no list."""
    from code2vec_tpu.ops import embed
    from code2vec_tpu.training import step as step_mod
    monkeypatch.setattr(step_mod, "adam_rows_into_table",
                        _adam_without_a_scatter)
    if not lists:
        monkeypatch.setattr(step_mod, "adam_row_list_tables",
                            lambda config, mesh: 0)
    step = _dp_train_step_lowered(DP, LIST_DIMS)
    text = step.compile().as_text()
    tables = [f"[{rows},{LIST_DIMS.token_dim}]" for rows in (
        LIST_DIMS.token_vocab_size, LIST_DIMS.path_vocab_size)]
    reduced = [m.group(1) for ln in _collective_lines(text)
               if "all-reduce" in ln and (m := _RESULT_TYPE.search(ln))]
    summed = [t for t in tables if any(t in r for r in reduced)]
    scattered = [t for t in tables if re.search(
        rf"= f32{re.escape(t)}\S* scatter\(", text)]
    # a chip's lists: every entry of its slots, of two id arrays or one
    slots = embed.slot_count(DP_ROWS // DP, M)
    entries = slots * embed.BLOCK_ROWS * embed.BLOCK_CONTEXTS
    lengths = [DP * 2 * entries, DP * entries]
    gathered = [ln for ln in step.as_text().splitlines()
                if "all_gather" in ln]
    keys = [n for n in lengths
            if any(ln.rstrip().endswith(f"-> tensor<{n}xi32>")
                   for ln in gathered)]
    rows = [n for n in lengths if any(
        ln.rstrip().endswith(f"-> tensor<{n}x{LIST_DIMS.token_dim}xbf16>")
        for ln in gathered)]
    if lists:
        assert not summed and not scattered
        assert keys == lengths and rows == lengths
    else:
        assert summed == tables and scattered == tables
        assert not keys and not rows


def test_the_one_device_step_holds_no_collective():
    assert not _collective_lines(
        _dp_train_step_lowered(1).compile().as_text())


def test_sparse_step_exchanges_rows_not_tables():
    """(ii) Differential: the dense step's table-shaped gradient
    all-reduce disappears under use_sparse_embedding_update, replaced by
    an integer ids all-gather (+ gathered rows)."""
    dense_text = _train_hlo(sparse=False)
    sparse_text = _train_hlo(sparse=True)

    # The op ITSELF must produce a table-shaped all-reduce result: the
    # shape sits in the result type between `= ` and ` all-reduce(`,
    # alone (`= f32[32,16]{...} all-reduce(`) or as one element of the
    # tuple XLA's all-reduce combiner builds (`= (f32[16,16]{...},
    # f32[32,16]{...}) all-reduce(`). Consumers that merely mention an
    # all-reduce operand (get-tuple-element, fusions) do not match.
    result_type = re.compile(r"= (.*?) all-reduce\(")

    def table_allreduces(text):
        return [ln for ln in _collective_lines(text)
                if (m := result_type.search(ln))
                and TOKEN_TABLE_SHARD in m.group(1)]

    # the detector must actually detect: dense HAS the table exchange
    assert table_allreduces(dense_text), (
        "expected a table-shaped gradient all-reduce in the dense step; "
        "the test's shape pattern is stale")
    assert not table_allreduces(sparse_text), (
        "sparse step still all-reduces table-shaped gradients:\n"
        + "\n".join(table_allreduces(sparse_text)[:4]))

    # and the sparse exchange is the (ids, rows) all-gather
    id_gathers = [ln for ln in _collective_lines(sparse_text)
                  if "all-gather" in ln and re.search(r"s32\[\d+\]", ln)]
    assert id_gathers, "sparse step has no integer ids all-gather"
    # dense moves no ids at all
    assert not [ln for ln in _collective_lines(dense_text)
                if "all-gather" in ln and re.search(r"s32\[\d+\]", ln)]


# ------------------------------------------------- the served head (PR 40)

SERVE_ROWS, SERVE_K = 32, 10
SERVE_DIMS = ModelDims(token_vocab_size=64, path_vocab_size=40,
                       target_vocab_size=9000, token_dim=16, path_dim=16)


def _eval_step_text(served: bool, group=None) -> str:
    """The lowered eval step at a small vocabulary (three 4,096-row
    blocks, the last one clamped), fed the float32 masters as training-
    time eval feeds it, or the table in the compute dtype as the facade
    serves it (`Code2VecModel._served_params`). Read in the LOWERED
    text: the CPU's compiler widens every bfloat16 operand to float32
    and narrows it again, the TPU's does not (tests/test_tpu_compile.py
    reads the text compiled for the chip)."""
    from code2vec_tpu.ops import topk
    config = Config(train_data_path_prefix="unused",
                    compute_dtype="bfloat16", topk_block_size=4096,
                    test_batch_size=SERVE_ROWS,
                    train_batch_size=SERVE_ROWS, max_contexts=M)
    module = Code2VecModule(dims=SERVE_DIMS, compute_dtype=jnp.bfloat16)
    opt = make_optimizer(config)
    state = create_train_state(module, opt, jax.random.PRNGKey(0),
                               mesh=None, config=config)
    builder = TrainStepBuilder(module, opt, config, mesh=None)
    assert builder._eval_topk_block() == 4096
    params = state.params
    if served:
        params = dict(params, target_embedding=params[
            "target_embedding"].astype(module.compute_dtype))
    batch = (jnp.zeros((SERVE_ROWS, M), jnp.int32),) * 3 + (
        jnp.ones((SERVE_ROWS, M), jnp.float32),
        jnp.ones((SERVE_ROWS,), jnp.int32), jnp.ones((SERVE_ROWS,), bool))
    with pytest.MonkeyPatch.context() as patch:
        if group is not None:
            patch.setattr(topk, "_GROUP", group)
        return builder.make_eval_step(state).lower(params, *batch).as_text()


def test_the_served_step_converts_no_table_and_sorts_no_whole_block():
    """The served step is handed the target table in its compute dtype
    (cast once per state, model_facade.py), so it holds no `convert`
    that makes a bfloat16 array of the table's shape or of a block's;
    the training-time eval step, fed the float32 masters that change
    every step, still converts. Behind the exact group prefilter
    (ops/topk.py) no array of `block + k` columns is left to sort; with
    the prefilter off (a group of 0) the same step holds one: the
    detector detects."""
    from code2vec_tpu.ops.topk import _prefilter_group, sorted_columns
    rows, dim = SERVE_DIMS.target_vocab_size, SERVE_DIMS.code_dim
    table_cast = re.compile(
        rf"convert .*tensor<(?:{rows}|4096)x{dim}xf32>\) -> "
        rf"tensor<(?:{rows}|4096)x{dim}xbf16>")
    whole_block = f"tensor<{SERVE_ROWS}x{4096 + SERVE_K}xf32>"
    served = _eval_step_text(served=True)
    assert not table_cast.search(served)
    assert whole_block not in served
    merged = (sorted_columns(SERVE_ROWS, 4096, SERVE_K)
              - 4096 // _prefilter_group(SERVE_ROWS, 4096, SERVE_K))
    assert f"tensor<{SERVE_ROWS}x{merged}xf32>" in served  # [running|chosen]
    assert table_cast.search(_eval_step_text(served=False))
    assert whole_block in _eval_step_text(served=True, group=0)
