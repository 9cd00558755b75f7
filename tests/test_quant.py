"""Quantized release-artifact suite: blockwise top-k parity, int8
round-trip error bounds, artifact save/load (+ named-field rejection),
AOT serve lowerings, eval-step blockwise parity, cache fingerprinting.

The blockwise merge's exactness claim (ops/topk.py docstring: identical
indices AND values to full `lax.top_k`, ties included) is pinned here
across block sizes, including ties from a coarse value grid, k larger
than a block, and block larger than the vocab.
"""

import dataclasses
import json
import os
import pickle
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.config import Config

pytestmark = pytest.mark.quant


# ----------------------------------------------------- blockwise top-k


def _assert_filters(rows, width, k, filters=True):
    """The case runs the merge it is named for (ops/topk.py picks by the
    static shapes): the exact group prefilter, or the plain merge."""
    from code2vec_tpu.ops.topk import _prefilter_group
    assert bool(_prefilter_group(rows, width, k)) == filters, (
        rows, width, k)


# (rows, vocab) beside each block: the small shapes run the plain merge
# (a block under k x g columns), as do 1 and 16 rows at any width (too
# few rows for a sort to outweigh the prefilter's small ops); from 32
# rows up the 4,096-column blocks run the group prefilter, the last
# (ragged) block of 8,200 the plain one behind it.
@pytest.mark.parametrize("b,v,block,filters", [
    (9, 97, 1, False), (9, 97, 3, False), (9, 97, 7, False),
    (9, 97, 16, False), (9, 97, 64, False), (9, 97, 100, False),
    (9, 97, 1000, False),
    (1, 8192, 4096, False), (16, 8200, 4096, False),
    (32, 8200, 4096, True), (64, 12288, 4096, True),
    (64, 32768 + 5, 16384, True),
])
def test_blockwise_from_logits_matches_lax_top_k(b, v, block, filters):
    from code2vec_tpu.ops.topk import blockwise_top_k_from_logits
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((b, v)), jnp.float32)
    k = 10
    _assert_filters(b, min(block, v), k, filters)
    fv, fi = jax.lax.top_k(logits, k)
    bv, bi = blockwise_top_k_from_logits(logits, k, block)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(bv))


def _tied_logits(kind, rng, shape):
    if kind == "grid":
        # ties everywhere: four distinct values
        return rng.choice([-1.0, 0.0, 0.5, 2.0], size=shape)
    if kind == "grid_inf":
        # ... and whole stretches of -inf, so that groups tie at -inf
        return rng.choice([-np.inf, -np.inf, -np.inf, 0.0, 0.5, 2.0],
                          size=shape)
    # "sparse": mostly one value, 0.4 % of the entries 1-3, so that
    # groups with DIFFERENT maxima hold equal elements: a merge that
    # visits the chosen groups in lax.top_k's value order (not in
    # ascending id order) puts a later group's 1 ahead of an earlier
    # group's 1 and answers with the higher index
    x = np.zeros(shape)
    hit = rng.random(shape) < 0.004
    x[hit] = rng.integers(1, 4, int(hit.sum()))
    return x


@pytest.mark.parametrize("kind,b,v,block", [
    ("grid", 6, 83, 2), ("grid", 6, 83, 5), ("grid", 6, 83, 16),
    ("grid", 6, 83, 41),
    ("grid", 32, 8192, 4096), ("grid_inf", 32, 8192, 4096),
    ("sparse", 32, 8192, 4096), ("sparse", 40, 12288, 4096),
    ("sparse", 6, 8192, 4096),      # few rows: the plain merge
])
def test_blockwise_tie_breaking_matches(kind, b, v, block):
    """Ties everywhere: every top-k selection is decided by
    lax.top_k's lower-index-first rule — the merge must reproduce it
    exactly, through the group prefilter too (the 4,096 blocks at 32
    rows and more)."""
    from code2vec_tpu.ops.topk import blockwise_top_k_from_logits
    rng = np.random.default_rng(1)
    for trial in range(1 if v < 100 else 3):
        logits = jnp.asarray(_tied_logits(kind, rng, (b, v)), jnp.float32)
        for k in (1, 5, 10, 64):
            fv, fi = jax.lax.top_k(logits, k)
            bv, bi = blockwise_top_k_from_logits(logits, k, block)
            np.testing.assert_array_equal(
                np.asarray(fi), np.asarray(bi),
                err_msg=f"k={k} block={block} trial={trial}")
            np.testing.assert_array_equal(np.asarray(fv), np.asarray(bv))
    if block == 4096:
        _assert_filters(b, block, 10, filters=b >= 32)
        _assert_filters(b, block, 64, filters=False)


def test_unsorted_group_ids_would_fail_the_sparsely_tied_case(monkeypatch):
    """The detector detects: with the chosen groups left in lax.top_k's
    VALUE order a later group's 1 stands before an earlier group's 1
    among the candidates and the answer names the higher index first,
    so the sort of the group ids in `_merge_top_k` is not removable."""
    from code2vec_tpu.ops import topk
    g = topk._prefilter_group(32, 4096, 10)
    x = np.zeros((32, 8192), np.float32)
    x[:, 7 * g + 5] = 3.0       # group 7 leads by its maximum ...
    x[:, 7 * g + 9] = 1.0       # ... and holds a 1
    x[:, 2 * g + 1] = 1.0       # group 2's maximum is an equal 1
    logits = jnp.asarray(x)
    fv, fi = jax.lax.top_k(logits, 10)
    assert list(np.asarray(fi)[0, :3]) == [7 * g + 5, 2 * g + 1, 7 * g + 9]
    bv, bi = topk.blockwise_top_k_from_logits(logits, 10, 4096)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(bi))
    monkeypatch.setattr(topk.jnp, "sort", lambda x, axis=-1: x)
    bv, bi = topk.blockwise_top_k_from_logits(logits, 10, 4096)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(bv))
    assert list(np.asarray(bi)[0, :3]) == [7 * g + 5, 7 * g + 9, 2 * g + 1]


@pytest.mark.parametrize("b,v,block,k,filters", [
    (5, 1000, 96, 10, False),     # clamped last block (1000 % 96 != 0)
    (5, 1000, 1024, 10, False),   # block > vocab: one full block
    (5, 50, 8, 20, False),        # k larger than a block
    (5, 7, 3, 7, False),          # k == vocab
    # the served shapes: 64 rows behind the group prefilter, the last
    # block clamped (9,000 % 4,096 != 0, as 261,245 % 4,096 and % 16,384)
    (64, 9000, 4096, 10, True),
    (64, 40000, 16384, 10, True),
    # where the plain merge must stay: the token models' rows ...
    (1, 9000, 4096, 10, False),
    (16, 9000, 4096, 10, False),
    # ... and where k x g is no small share of the block
    (32, 9000, 4096, 100, False),  # a retrieval k
    (32, 9000, 256, 10, False),    # a block under k x g columns
])
def test_blockwise_matmul_matches_full(b, v, block, k, filters):
    from code2vec_tpu.ops.topk import blockwise_matmul_top_k
    _assert_filters(b, min(block, v), min(k, v), filters)
    rng = np.random.default_rng(2)
    cv = jnp.asarray(rng.standard_normal((b, 24)), jnp.float32)
    tbl = jnp.asarray(rng.standard_normal((v, 24)), jnp.float32)
    full = jnp.einsum("bd,vd->bv", cv, tbl,
                      preferred_element_type=jnp.float32)
    fv, fi = jax.lax.top_k(full, k)
    out = jax.jit(lambda c, t: blockwise_matmul_top_k(c, t, k, block))(
        cv, tbl)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(out.indices))
    # The MERGE is exact (test_blockwise_tie_breaking_matches pins it
    # bitwise on shared logits); here each logit comes from a (B, block)
    # matmul on one side and a (B, V) matmul on the other, and XLA may
    # sum the 24 products in another order: an ulp or two of f32.
    np.testing.assert_allclose(np.asarray(fv), np.asarray(out.values),
                               rtol=1e-6)
    # the streamed logsumexp must agree with the full-row one
    ref_lse = jax.scipy.special.logsumexp(full, axis=-1)
    np.testing.assert_allclose(np.asarray(out.lse), np.asarray(ref_lse),
                               rtol=1e-5)


@pytest.mark.parametrize("b,v,real,block,k,filters", [
    (4, 128, 119, 48, 8, False),
    # the second block holds 4 live columns: every group of it but one
    # is all -inf, fewer than k finite entries reach its merge
    (32, 8192, 4100, 4096, 10, True),
    # the clamped last block's already-visited prefix AND the padded
    # classifier rows masked in one block
    (32, 9000, 8990, 4096, 10, True),
])
def test_blockwise_matmul_bf16_and_valid_rows(b, v, real, block, k, filters):
    """bf16 compute parity with the full bf16 einsum, and padded
    classifier rows (valid_rows) never selected."""
    from code2vec_tpu.ops.topk import blockwise_matmul_top_k
    _assert_filters(b, block, k, filters)
    rng = np.random.default_rng(3)
    cv = jnp.asarray(rng.standard_normal((b, 16)), jnp.float32)
    # a coarse grid: exact in bf16 and full of ties across the blocks
    tbl = jnp.asarray(rng.integers(-2, 3, (v, 16)) if filters
                      else rng.standard_normal((v, 16)), jnp.float32)
    full = jnp.einsum("bd,vd->bv", cv.astype(jnp.bfloat16),
                      tbl.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    full = jnp.where(jnp.arange(v)[None, :] < real, full, -jnp.inf)
    fv, fi = jax.lax.top_k(full, k)
    out = jax.jit(lambda c, t: blockwise_matmul_top_k(
        c, t, k, block, valid_rows=real, compute_dtype=jnp.bfloat16))(cv, tbl)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(out.indices))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(out.values))
    assert int(np.asarray(out.indices).max()) < real
    # the served copy of the table (cast once a state, model_facade.py)
    # answers bitwise as the float32 master cast block by block
    served = jax.jit(lambda c, t: blockwise_matmul_top_k(
        c, t, k, block, valid_rows=real, compute_dtype=jnp.bfloat16))(
            cv, tbl.astype(jnp.bfloat16))
    for got, want in zip(served, out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,v,block,k,filters", [
    (6, 300, 64, 7, False), (32, 9000, 4096, 10, True)])
def test_blockwise_int8_scales_match_dequantized_full(b, v, block, k,
                                                      filters):
    """The fused-dequant block matmul selects the same top-k as a full
    matmul against the explicitly dequantized table."""
    from code2vec_tpu.ops.quant import quantize_rows
    from code2vec_tpu.ops.topk import blockwise_matmul_top_k
    _assert_filters(b, block, k, filters)
    rng = np.random.default_rng(4)
    tbl = rng.standard_normal((v, 24)).astype(np.float32)
    q, s = quantize_rows(tbl)
    deq = q.astype(np.float32) * s
    cv = jnp.asarray(rng.standard_normal((b, 24)), jnp.float32)
    full = jnp.einsum("bd,vd->bv", cv, jnp.asarray(deq),
                      preferred_element_type=jnp.float32)
    fv, fi = jax.lax.top_k(full, k)
    out = jax.jit(lambda c, t, sc: blockwise_matmul_top_k(
        c, t, k, block, scales=sc))(cv, jnp.asarray(q), jnp.asarray(s))
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(out.indices))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(out.values),
                               rtol=1e-6)


@pytest.mark.parametrize("rows,width,k,columns", [
    (64, 16384, 10, 128 + 10 + 1280),   # the served step at the defaults
    (1024, 16384, 10, 1418),            # the training-time eval step
    (64, 4096, 10, 32 + 10 + 1280),     # `--topk_block 4096`
    (1, 4096, 10, 4106),        # a token model's one-row step ...
    (16, 4096, 10, 4106),       # ... and a rerank burst's sixteen rows
    (64, 4096, 100, 4196),      # retrieval's k: k x g is most of the block
    (64, 256, 10, 266),         # a block under k x g columns
    (64, 4100, 10, 4110),       # no whole number of groups
    (64, 97, 10, 107),          # a tiny vocabulary
])
def test_merge_form_follows_the_static_shapes(rows, width, k, columns):
    """What `head_topk_sorted_columns` says of a built step."""
    from code2vec_tpu.ops.topk import sorted_columns
    assert sorted_columns(rows, width, k) == columns


def test_gathered_label_logits_match_full_column():
    from code2vec_tpu.ops.topk import gathered_label_logits
    rng = np.random.default_rng(5)
    cv = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
    tbl = jnp.asarray(rng.standard_normal((40, 12)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 40, 8), jnp.int32)
    full = jnp.einsum("bd,vd->bv", cv, tbl,
                      preferred_element_type=jnp.float32)
    want = np.take_along_axis(np.asarray(full),
                              np.asarray(labels)[:, None], axis=1)[:, 0]
    got = np.asarray(gathered_label_logits(cv, tbl, labels))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_blockwise_nonfinite_logits_keep_loss_finite():
    """CE-guard parity with the full eval path: a weight blow-up that
    produces Inf/NaN logits must leave the blockwise lse and the label
    logit finite — the full path substitutes -1e30 (safe_logits in
    training/step.py) before CE, and a poisoned eval-loss gauge would
    break best-checkpoint-by-loss comparisons and TB scalars."""
    from code2vec_tpu.ops.topk import (
        blockwise_matmul_top_k, gathered_label_logits,
    )
    rng = np.random.default_rng(8)
    tbl = rng.standard_normal((60, 12)).astype(np.float32)
    tbl[7, :] = np.inf      # blown-up row: its logits are Inf or NaN
    cv = jnp.asarray(rng.standard_normal((4, 12)), jnp.float32)
    tblj = jnp.asarray(tbl)
    out = jax.jit(lambda c, t: blockwise_matmul_top_k(c, t, 5, 16))(
        cv, tblj)
    assert np.isfinite(np.asarray(out.lse)).all()
    # the streamed lse equals the full path's safe-substituted one
    full = jnp.einsum("bd,vd->bv", cv, tblj,
                      preferred_element_type=jnp.float32)
    safe = jnp.where(jnp.isfinite(full), full, -1e30)
    ref_lse = jax.scipy.special.logsumexp(safe, axis=-1)
    np.testing.assert_allclose(np.asarray(out.lse), np.asarray(ref_lse),
                               rtol=1e-5)
    # a nonfinite label logit clamps exactly as safe_logits[label] would
    labels = jnp.asarray([7, 0, 7, 3], jnp.int32)
    ll = np.asarray(gathered_label_logits(cv, tblj, labels))
    assert np.isfinite(ll).all()
    np.testing.assert_array_equal(ll[[0, 2]], np.float32(-1e30))
    want = np.take_along_axis(np.asarray(safe),
                              np.asarray(labels)[:, None], axis=1)[:, 0]
    np.testing.assert_allclose(ll, want, rtol=1e-6)


# ----------------------------------------------------------- int8 ops


def test_int8_round_trip_error_bound():
    """Per-row symmetric absmax: |x - dequant(quant(x))| <= scale/2 =
    max|row| / 254 elementwise, and the row absmax survives exactly
    (it quantizes to +-127 by construction)."""
    from code2vec_tpu.ops.quant import dequantize_rows, quantize_rows
    rng = np.random.default_rng(6)
    tbl = (rng.standard_normal((64, 48))
           * rng.lognormal(0, 2, (64, 1))).astype(np.float32)
    tbl[13, :] = 0.0  # all-zero row (untouched vocab tail)
    q, s = quantize_rows(tbl)
    assert q.dtype == np.int8 and s.shape == (64, 1)
    deq = dequantize_rows(q, s)
    err = np.abs(deq - tbl)
    bound = np.abs(tbl).max(axis=1, keepdims=True) / 254 + 1e-9
    assert (err <= bound).all(), float((err / bound).max())
    np.testing.assert_array_equal(deq[13], np.zeros(48, np.float32))
    # absmax element is exactly representable
    flat_amax = np.abs(tbl).argmax(axis=1)
    rows = np.arange(64)
    np.testing.assert_allclose(np.abs(deq[rows, flat_amax]),
                               np.abs(tbl[rows, flat_amax]), rtol=1e-6)


def test_dequant_gather_matches_host_dequant():
    from code2vec_tpu.ops.quant import dequant_gather, quantize_rows
    rng = np.random.default_rng(7)
    tbl = rng.standard_normal((30, 8)).astype(np.float32)
    q, s = quantize_rows(tbl)
    ids = jnp.asarray(rng.integers(0, 30, (4, 5)), jnp.int32)
    got = np.asarray(dequant_gather(jnp.asarray(q), jnp.asarray(s), ids))
    want = (q.astype(np.float32) * s)[np.asarray(ids)]
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ------------------------------------------------- eval-step blockwise


def _tiny_model(tmp_path, **config_overrides):
    from code2vec_tpu.model_facade import Code2VecModel
    rng = random.Random(0)
    tokens = [f"tok{i}" for i in range(6)]
    paths = [f"p{i}" for i in range(4)]
    targets = [f"name|x{i}" for i in range(40)]
    rows = []
    for _ in range(48):
        t = rng.randrange(len(targets))
        ctxs = [f"{tokens[t % 6]},{rng.choice(paths)},{tokens[t % 6]}"
                for _ in range(rng.randint(2, 6))]
        rows.append(f"{targets[t]} " + " ".join(ctxs)
                    + " " * (16 - len(ctxs)))
    prefix = str(tmp_path / "synthetic")
    with open(prefix + ".train.c2v", "w") as f:
        f.write("\n".join(rows) + "\n")
    with open(prefix + ".dict.c2v", "wb") as f:
        pickle.dump({w: 10 for w in tokens}, f)
        pickle.dump({p: 10 for p in paths}, f)
        pickle.dump({t: 10 for t in targets}, f)
        pickle.dump(len(rows), f)
    kwargs = dict(train_data_path_prefix=prefix, max_contexts=16,
                  train_batch_size=8, test_batch_size=8,
                  compute_dtype="float32", verbose_mode=0,
                  serve_batch_size=4, serve_buckets="4,8",
                  num_train_epochs=1, save_every_epochs=1000)
    kwargs.update(config_overrides)
    return Code2VecModel(Config(**kwargs))


def _rand_batch_arrays(model, b=8):
    rng = np.random.default_rng(11)
    d = model.dims
    m = model.config.max_contexts
    return (jnp.asarray(rng.integers(0, d.token_vocab_size, (b, m)), jnp.int32),
            jnp.asarray(rng.integers(0, d.path_vocab_size, (b, m)), jnp.int32),
            jnp.asarray(rng.integers(0, d.token_vocab_size, (b, m)), jnp.int32),
            jnp.asarray((rng.random((b, m)) > 0.3), jnp.float32),
            jnp.asarray(rng.integers(2, d.real_target_vocab_size, (b,)),
                        jnp.int32),
            jnp.asarray(np.ones(b, bool)))


def test_eval_step_blockwise_matches_full(tmp_path):
    """The production eval step with topk_block_size engaged returns
    identical top-k indices/values and a matching CE sum vs the
    full-logits path (target vocab 40+specials, block 8 -> 6 blocks)."""
    model = _tiny_model(tmp_path)
    arrays = _rand_batch_arrays(model)
    full_cfg = dataclasses.replace(model.config, topk_block_size=0)
    from code2vec_tpu.training.step import TrainStepBuilder
    full_step = TrainStepBuilder(model.module, model.optimizer, full_cfg,
                                 mesh=None).make_eval_step(model.state)
    block_cfg = dataclasses.replace(model.config, topk_block_size=8)
    builder = TrainStepBuilder(model.module, model.optimizer, block_cfg,
                               mesh=None)
    assert builder._eval_topk_block() == 8
    block_step = builder.make_eval_step(model.state)
    fo = full_step(model.state.params, *arrays)
    bo = block_step(model.state.params, *arrays)
    np.testing.assert_array_equal(np.asarray(fo.topk_indices),
                                  np.asarray(bo.topk_indices))
    np.testing.assert_array_equal(np.asarray(fo.topk_values),
                                  np.asarray(bo.topk_values))
    np.testing.assert_allclose(np.asarray(fo.code_vectors),
                               np.asarray(bo.code_vectors), rtol=1e-6)
    np.testing.assert_allclose(float(fo.loss_sum), float(bo.loss_sum),
                               rtol=1e-5)


def test_eval_topk_block_gates(tmp_path):
    """Blockwise disengages when it cannot help: block 0, block >= vocab,
    tp-sharded tables."""
    from code2vec_tpu.training.step import TrainStepBuilder
    model = _tiny_model(tmp_path)
    mk = lambda **kw: TrainStepBuilder(  # noqa: E731
        model.module, model.optimizer,
        dataclasses.replace(model.config, **kw),
        mesh=None)._eval_topk_block()
    assert mk(topk_block_size=0) == 0
    assert mk(topk_block_size=100_000) == 0     # >= vocab: full path
    assert mk(topk_block_size=8) == 8
    assert mk(topk_block_size=8, tp=2) == 0


# ------------------------------------------------- artifact round trip


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("quant-artifact")
    model = _tiny_model(tmp)
    from code2vec_tpu.release.artifact import export_artifact
    art_dir = str(tmp / "artifact")
    meta = export_artifact(model, art_dir, log=lambda m: None)
    return model, art_dir, meta


def test_artifact_save_load_round_trip(exported):
    from code2vec_tpu.release.artifact import load_artifact
    model, art_dir, meta = exported
    art = load_artifact(art_dir)
    assert art.meta["fingerprint"] == meta["fingerprint"]
    assert art.scheme == "int8_rowwise_symmetric"
    # quantized tables carry scales shaped (V, 1); dense params are f32
    for name in ("token_embedding", "path_embedding", "target_embedding"):
        assert art.tables[name].dtype == np.int8
        assert art.tables[f"{name}.scale"].shape == \
            (art.tables[name].shape[0], 1)
    assert art.tables["transform"].dtype == np.float32
    # >= 3x smaller tables than fp32 (int8 + one f32 scale per row)
    tb = meta["table_bytes"]
    assert tb["fp32"] / tb["artifact"] >= 3.0
    # vocabularies round-trip through the artifact's dictionaries.bin
    from code2vec_tpu.vocab import Code2VecVocabs
    v = Code2VecVocabs.load(art.dictionaries_path)
    assert v.target_vocab.size == model.vocabs.target_vocab.size


def test_artifact_fp32_consumer_rejected_with_named_field(exported):
    from code2vec_tpu.release.artifact import ArtifactError, load_artifact
    _, art_dir, _ = exported
    with pytest.raises(ArtifactError, match="quantization.scheme") as ei:
        load_artifact(art_dir, expect_scheme="float32")
    assert ei.value.field == "quantization.scheme"


def test_artifact_dtype_mismatch_rejected(exported, tmp_path):
    """A tampered bundle (meta says int8, file holds f32) must fail
    naming the table, not dequantize garbage."""
    import shutil

    from code2vec_tpu.release.artifact import ArtifactError, load_artifact
    _, art_dir, _ = exported
    broken = str(tmp_path / "broken")
    shutil.copytree(art_dir, broken)
    q = np.load(os.path.join(broken, "token_embedding.npy"))
    np.save(os.path.join(broken, "token_embedding.npy"),
            q.astype(np.float32))
    with pytest.raises(ArtifactError, match="token_embedding.dtype"):
        load_artifact(broken)


@pytest.mark.parametrize("field", ["topk", "buckets", "compute_dtype",
                                   "serve_batch_size", "max_contexts"])
def test_artifact_missing_meta_field_rejected(exported, tmp_path, field):
    """A torn or hand-edited meta that lost a runtime-consumed field
    must fail at LOAD with the field named (ArtifactError), not as a
    bare KeyError later in ReleaseModel/make_release_step."""
    import shutil

    from code2vec_tpu.release.artifact import ArtifactError, load_artifact
    _, art_dir, _ = exported
    broken = str(tmp_path / f"missing_{field}")
    shutil.copytree(art_dir, broken)
    mp = os.path.join(broken, "release_meta.json")
    with open(mp) as f:
        meta = json.load(f)
    del meta[field]
    with open(mp, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ArtifactError, match=field) as ei:
        load_artifact(broken)
    assert ei.value.field == field


def test_artifact_missing_runtime_dims_rejected(exported, tmp_path):
    """dims fields only the runtime reads (real_target_vocab_size,
    target_oov_floor) are part of the load-time contract too."""
    import shutil

    from code2vec_tpu.release.artifact import ArtifactError, load_artifact
    _, art_dir, _ = exported
    broken = str(tmp_path / "missing_dims")
    shutil.copytree(art_dir, broken)
    mp = os.path.join(broken, "release_meta.json")
    with open(mp) as f:
        meta = json.load(f)
    del meta["dims"]["real_target_vocab_size"]
    with open(mp, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ArtifactError, match="real_target_vocab_size"):
        load_artifact(broken)


def test_artifact_non_artifact_dir_rejected(tmp_path):
    from code2vec_tpu.release.artifact import ArtifactError, load_artifact
    with pytest.raises(ArtifactError, match="not a release artifact"):
        load_artifact(str(tmp_path))


def test_facade_load_rejects_artifact(exported, tmp_path):
    """--load pointed at a release artifact fails up front with the
    quantization field named (never reaches the Orbax restore)."""
    from code2vec_tpu.model_facade import Code2VecModel
    _, art_dir, _ = exported
    config = Config(model_load_path=art_dir, verbose_mode=0)
    with pytest.raises(ValueError, match="quantization.scheme"):
        Code2VecModel(config)


def test_export_requires_load():
    with pytest.raises(ValueError, match="artifact_out.*requires --load"):
        Config(train_data_path_prefix="x",
               export_artifact_path="/tmp/nope").verify()


# --------------------------------------------------- release runtime


def test_release_model_predictions_and_aot(exported, tmp_path):
    """ReleaseModel serves the artifact: predictions match the facade's
    (int8 quantization of this tiny model preserves the ranking), AOT
    lowerings are used for exported shapes, jit fallback covers others,
    and quality flows through the standard Evaluator."""
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir)
    rm = ReleaseModel(config, log=lambda m: None)
    lines = ["alpha tok0,p0,tok0 tok0,p1,tok0", "beta tok1,p2,tok1"]
    base = model.predict(lines, batch_size=4)
    rel = rm.predict(lines, batch_size=4)
    assert [r.topk_predicted_words for r in rel] == \
        [r.topk_predicted_words for r in base]
    assert rm.aot_loads["aot"] == 1 and rm.aot_loads["jit_error"] == 0
    # un-exported shape -> jit fallback, same answers
    rel2 = rm.predict(lines, batch_size=2)
    assert [r.topk_predicted_words for r in rel2] == \
        [r.topk_predicted_words for r in base]
    assert rm.aot_loads["jit_fallback"] == 1
    # distinct fingerprints: facade vs artifact (cache-key separation)
    assert rm.model_fingerprint() != model.model_fingerprint()
    assert rm.model_fingerprint().startswith("artifact:")


def test_release_predict_defaults_to_serve_batch_size(exported):
    """predict() without an explicit batch_size must chunk at the
    artifact's serve_batch_size — not the facade's test_batch_size
    (1024 default) — so `--predict --artifact` and offline predict hit
    the shipped AOT lowerings instead of tracing unseen shapes."""
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir)
    rm = ReleaseModel(config, log=lambda m: None)
    assert rm._default_predict_batch_size() == int(meta["serve_batch_size"])
    rm.predict(["alpha tok0,p0,tok0 tok0,p1,tok0"])
    assert rm.aot_loads["aot"] == 1 and rm.aot_loads["jit_fallback"] == 0
    rows = {shape[0] for shape in rm._predict_steps}
    assert rows == {int(meta["serve_batch_size"])}


def test_release_eval_step_close_to_fp32(exported):
    """EvalOutputs from the release runtime (int8 + blockwise) track the
    fp32 eval step on random batches: identical top-1 for this model,
    loss within the quantization tolerance."""
    model, art_dir, _ = exported
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir)
    rm = ReleaseModel(config, log=lambda m: None)
    arrays = _rand_batch_arrays(model)
    fo = model._get_eval_step()(model.state.params, *arrays)
    ro = rm.eval_step(None, *arrays)
    assert np.asarray(ro.topk_indices).shape == \
        np.asarray(fo.topk_indices).shape
    np.testing.assert_allclose(np.asarray(ro.code_vectors),
                               np.asarray(fo.code_vectors),
                               rtol=0.1, atol=0.05)
    np.testing.assert_allclose(float(ro.loss_sum), float(fo.loss_sum),
                               rtol=0.1)


def test_aot_export_round_trip_exact(exported):
    """Deserialized AOT lowering == jit of the same step, bitwise, on
    the same platform."""
    from jax import export as jax_export

    from code2vec_tpu.release.artifact import load_artifact
    from code2vec_tpu.release.runtime import make_release_step
    model, art_dir, meta = exported
    art = load_artifact(art_dir)
    rows = int(meta["serve_batch_size"])
    m = int(meta["buckets"][0])
    path = art.aot_path(rows, m)
    assert path is not None
    with open(path, "rb") as f:
        exported_fn = jax_export.deserialize(bytearray(f.read()))
    params = {k.replace(".scale", "_scale"): jnp.asarray(v)
              for k, v in art.tables.items()}
    rng = np.random.default_rng(13)
    d = meta["dims"]
    batch = (jnp.asarray(rng.integers(0, d["token_vocab_size"], (rows, m)),
                         jnp.int32),
             jnp.asarray(rng.integers(0, d["path_vocab_size"], (rows, m)),
                         jnp.int32),
             jnp.asarray(rng.integers(0, d["token_vocab_size"], (rows, m)),
                         jnp.int32),
             jnp.ones((rows, m), jnp.float32),
             jnp.asarray(rng.integers(0, d["real_target_vocab_size"],
                                      (rows,)), jnp.int32),
             jnp.asarray(np.ones(rows, bool)))
    aot_out = exported_fn.call(params, *batch)
    jit_out = jax.jit(make_release_step(meta))(params, *batch)
    for a, b in zip(aot_out, jit_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_release_fp32_forward_matches_facade(exported, tmp_path):
    """Drift guard for the hand-mirrored forward in make_release_step:
    on an fp32 artifact the release eval outputs must match the facade
    eval step tightly — identical top-k indices, values/code vectors/
    loss to float tolerance. Any change to the canonical forward in
    models/code2vec.py that is not mirrored in release/runtime.py
    fails here."""
    import dataclasses as dc

    from code2vec_tpu.release.artifact import export_artifact
    from code2vec_tpu.release.runtime import ReleaseModel
    model, _, _ = exported
    art_dir = str(tmp_path / "fp32_parity")
    export_artifact(model, art_dir, quantize=False, aot=False,
                    log=lambda m: None)
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir)
    rm = ReleaseModel(config, log=lambda m: None)
    arrays = _rand_batch_arrays(model)
    fo = model._get_eval_step()(model.state.params, *arrays)
    ro = rm.eval_step(None, *arrays)
    np.testing.assert_array_equal(np.asarray(fo.topk_indices),
                                  np.asarray(ro.topk_indices))
    np.testing.assert_allclose(np.asarray(ro.topk_values),
                               np.asarray(fo.topk_values), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ro.code_vectors),
                               np.asarray(fo.code_vectors), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(ro.attention),
                               np.asarray(fo.attention), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(float(ro.loss_sum), float(fo.loss_sum),
                               rtol=1e-5)


def test_release_model_topk_artifact_authoritative(exported):
    """A serve-time --topk override cannot change the baked step: the
    artifact's exported k wins (silent truncation bugfix)."""
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir,
                        top_k_words_considered_during_prediction=3)
    rm = ReleaseModel(config, log=lambda m: None)
    assert rm.config.top_k_words_considered_during_prediction == \
        int(meta["topk"])


def test_release_model_explicit_serve_batch_size_respected(exported):
    """An EXPLICIT --serve_batch_size is honored even when it equals the
    Config default: only an unset knob adopts the artifact's
    AOT-exported size (the operator may be bounding per-request
    latency/memory on a small replica)."""
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported
    default_rows = Config.__dataclass_fields__["serve_batch_size"].default
    assert default_rows != int(meta["serve_batch_size"])
    base = dc.replace(model.config, train_data_path_prefix=None,
                      serve_artifact=art_dir,
                      serve_batch_size=default_rows)
    # unset: the artifact's exported size is adopted (AOT lowerings win)
    implicit = dc.replace(base, explicit_knobs=())
    rm = ReleaseModel(implicit, log=lambda m: None)
    assert rm.config.serve_batch_size == int(meta["serve_batch_size"])
    # explicitly typed, even at the default value: the flag wins
    explicit = dc.replace(base, explicit_knobs=("serve_batch_size",))
    rm = ReleaseModel(explicit, log=lambda m: None)
    assert rm.config.serve_batch_size == default_rows


def test_config_rejects_artifact_plus_training():
    with pytest.raises(ValueError, match="inference-only"):
        Config(train_data_path_prefix="x",
               serve_artifact="/tmp/somewhere").verify()


def test_config_rejects_export_combined_with_serve_or_test(tmp_path):
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    with pytest.raises(ValueError, match="one-shot job"):
        Config(model_load_path=str(ckpt),
               export_artifact_path="/tmp/out",
               test_data_path="x.c2v").verify()


def test_config_rejects_export_combined_with_training(tmp_path):
    """--data + --artifact_out would train nothing (main() exports the
    loaded checkpoint and exits) — must fail loudly, not skip the run."""
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    with pytest.raises(ValueError, match="combined with training"):
        Config(model_load_path=str(ckpt),
               export_artifact_path="/tmp/out",
               train_data_path_prefix="corpus").verify()


def test_aot_exec_failure_degrades_to_jit(exported, monkeypatch):
    """A lowering that deserializes but fails at first EXECUTION (version
    skew surfacing at run time, not deserialize time) must degrade to the
    jit fallback — counted as jit_error — instead of erroring every
    request on that bucket."""
    import dataclasses as dc

    from jax import export as jax_export

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported

    class _Poisoned:
        def call(self, *a, **kw):
            raise RuntimeError("custom call target not registered")

    monkeypatch.setattr(jax_export, "deserialize",
                        lambda data: _Poisoned())
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir)
    rm = ReleaseModel(config, log=lambda m: None)
    lines = ["alpha tok0,p0,tok0 tok0,p1,tok0"]
    rel = rm.predict(lines, batch_size=int(meta["serve_batch_size"]))
    assert [r.topk_predicted_words for r in rel] == \
        [r.topk_predicted_words for r in model.predict(lines, batch_size=4)]
    assert rm.aot_loads["jit_error"] == 1 and rm.aot_loads["aot"] == 0


def test_release_step_honors_block_zero(exported, monkeypatch):
    """meta topk_block_size=0 (exporter pinned the full-logits path) must
    reach the blockwise kernel as one block spanning the table — not be
    coerced back to the 4096 default by a falsy-0 check. Absent key
    (older meta) still defaults to 4096."""
    import code2vec_tpu.release.runtime as runtime_mod
    from code2vec_tpu.release.runtime import (
        batch_specs, make_release_step, param_specs,
    )
    model, art_dir, meta = exported
    seen = []
    real = runtime_mod.blockwise_matmul_top_k

    def spy(q, table, k, block_rows, **kw):
        seen.append(block_rows)
        return real(q, table, k, block_rows, **kw)

    monkeypatch.setattr(runtime_mod, "blockwise_matmul_top_k", spy)
    rows, m = 2, int(meta["buckets"][0])
    for pinned, want in ((0, int(meta["dims"]["target_vocab_size"])),
                        (None, 4096)):
        meta2 = dict(meta, topk_block_size=pinned)
        if pinned is None:
            del meta2["topk_block_size"]
        seen.clear()
        jax.eval_shape(make_release_step(meta2), param_specs(meta2),
                       *batch_specs(rows, m))
        assert seen == [want], (pinned, seen)


def test_release_model_evaluate_via_test_surface(exported, tmp_path):
    """`--artifact DIR --test data.c2v`: ReleaseModel.evaluate() scores
    the artifact with the standard Evaluator — same metric surface as
    the facade's --test (the CLI wiring's backing method)."""
    import dataclasses as dc

    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, _ = exported
    test_path = model.config.train_data_path_prefix + ".train.c2v"
    config = dc.replace(model.config, train_data_path_prefix=None,
                        serve_artifact=art_dir, test_data_path=test_path,
                        test_batch_size=16)
    rm = ReleaseModel(config, log=lambda m: None)
    results = rm.evaluate()
    assert 0.0 <= float(results.subtoken_f1) <= 1.0
    assert results.topk_acc.shape == \
        (model.config.top_k_words_considered_during_prediction,)


def test_reexport_into_same_dir_drops_stale_files(exported, tmp_path):
    """fp32 re-export over a prior int8 export must fingerprint the
    same as a clean fp32 export (stale scale files and AOT lowerings
    must not survive into — or be hashed into — the new bundle)."""
    from code2vec_tpu.release.artifact import export_artifact, load_artifact
    model, _, _ = exported
    clean = str(tmp_path / "clean_fp32")
    reused = str(tmp_path / "reused")
    meta_clean = export_artifact(model, clean, quantize=False, aot=False,
                                 log=lambda m: None)
    export_artifact(model, reused, quantize=True, aot=True,
                    log=lambda m: None)
    meta_reused = export_artifact(model, reused, quantize=False, aot=False,
                                  log=lambda m: None)
    assert meta_reused["fingerprint"] == meta_clean["fingerprint"]
    assert not os.path.exists(
        os.path.join(reused, "token_embedding.scale.npy"))
    assert not os.path.isdir(os.path.join(reused, "aot"))
    art = load_artifact(reused)
    assert art.scheme == "float32"


@pytest.mark.parametrize("backend,platforms,want", [
    ("cpu", ["cpu"], True),
    ("tpu", ["tpu"], True),
    ("gpu", ["cuda"], True),        # jax.export says cuda, backend says gpu
    ("gpu", ["rocm"], True),
    ("cpu", ["cuda"], False),
    ("tpu", ["cpu"], False),
    ("cpu", [None], False),         # torn meta: no platform recorded
])
def test_backend_matches_aot_platform_vocabulary(backend, platforms, want):
    from code2vec_tpu.release.runtime import _backend_matches
    assert _backend_matches(backend, platforms) is want


def test_serving_cache_key_includes_model_fingerprint(exported):
    """Two servers over different weights never share cache entries:
    the key embeds model_fingerprint() (the PR-8 cache bugfix)."""
    from code2vec_tpu.serving.cache import cache_key
    model, art_dir, _ = exported
    code = "class A { int get() { return 1; } }"
    k_ckpt = cache_key(code, endpoint="predict", topk=10,
                       model=model.model_fingerprint())
    k_art = cache_key(code, endpoint="predict", topk=10,
                      model=f"artifact:deadbeefdeadbeef")
    assert k_ckpt != k_art
    # same fingerprint + reformatted source still hits
    assert cache_key("class A {\n  int get() {\n    return 1; } }",
                     endpoint="predict", topk=10,
                     model=model.model_fingerprint()) == k_ckpt


# ------------------------------- sub-byte / fp8 schemes (roofline PR)


roofline = pytest.mark.roofline


@roofline
@pytest.mark.parametrize("fmt,mbits,sub_half", [
    ("e4m3", 3, 2.0 ** -9),
    ("e5m2", 2, 2.0 ** -16),
])
def test_fp8_round_trip_error_bound(fmt, mbits, sub_half):
    """fp8 rounding is RELATIVE: err <= |w| * 2^-(mantissa+1) for
    normals, <= scale * half-subnormal-step near zero. All-zero rows
    reproduce exactly."""
    from code2vec_tpu.ops.quant import (
        dequantize_rows_fp8, quantize_rows_fp8,
    )
    rng = np.random.default_rng(5)
    t = (rng.standard_normal((200, 33))
         * rng.gamma(1.5, 2, (200, 1))).astype(np.float32)
    t[7] = 0
    q, s = quantize_rows_fp8(t, fmt)
    assert q.dtype == np.uint8 and q.shape == t.shape
    assert s.shape == (200, 1) and float(s[7, 0]) == 0.0
    r = dequantize_rows_fp8(q, s, fmt)
    err = np.abs(r - t)
    bound = np.maximum(np.abs(t) * 2.0 ** -(mbits + 1), s * sub_half)
    assert (err <= bound + 1e-12).all()
    assert (r[7] == 0).all()


@roofline
def test_fp8_rejects_unknown_format():
    from code2vec_tpu.ops.quant import quantize_rows_fp8
    with pytest.raises(ValueError, match="fp8 format"):
        quantize_rows_fp8(np.zeros((2, 2), np.float32), "e3m4")


@roofline
@pytest.mark.parametrize("d", [16, 33])   # even and odd widths
def test_int4_round_trip_error_bound_and_packing(d):
    """int4 worst-case round-trip error is s_r/2 (s_r = absmax/7); the
    payload is two nibbles per byte with odd widths padded by an
    encoded zero."""
    from code2vec_tpu.ops.quant import (
        dequantize_rows_int4, quantize_rows_int4, unpack_int4_host,
    )
    rng = np.random.default_rng(6)
    t = (rng.standard_normal((100, d))
         * rng.gamma(2, 1, (100, 1))).astype(np.float32)
    t[4] = 0
    q, s = quantize_rows_int4(t)
    assert q.dtype == np.uint8 and q.shape == (100, (d + 1) // 2)
    r = dequantize_rows_int4(q, s, d)
    assert (np.abs(r - t) <= s / 2 + 1e-9).all()
    assert (r[4] == 0).all()
    # nibble values stay in the signed [-7, 7] code book
    u = unpack_int4_host(q, d)
    assert u.min() >= -7 and u.max() <= 7
    # at production table widths the packed payload+scales are >= 1.8x
    # smaller than int8's (narrow test rows amortize the per-row scale
    # worse): 128-wide rows -> (128+4)/(64+4) = 1.94x
    assert (128 + 4) / ((128 + 1) // 2 + 4) >= 1.8


@roofline
def test_int4_device_gather_and_blockwise_match_dequantized():
    """The packed-gather + in-kernel unpack and the int4 blockwise
    top-k both equal the same ops over the host-dequantized table."""
    from code2vec_tpu.ops.quant import (
        dequant_gather_int4, dequantize_rows_int4, quantize_rows_int4,
    )
    from code2vec_tpu.ops.topk import (
        blockwise_matmul_top_k, gathered_label_logits,
    )
    rng = np.random.default_rng(7)
    v, d = 300, 24
    t = rng.standard_normal((v, d)).astype(np.float32)
    q, s = quantize_rows_int4(t)
    deq = dequantize_rows_int4(q, s, d)
    ids = jnp.asarray(rng.integers(0, v, (5, 4)))
    g = dequant_gather_int4(jnp.asarray(q), jnp.asarray(s), ids, d)
    np.testing.assert_allclose(np.asarray(g),
                               deq[np.asarray(ids)], rtol=1e-6)
    cv = jnp.asarray(rng.standard_normal((6, d)), jnp.float32)
    full = jnp.einsum("bd,vd->bv", cv, jnp.asarray(deq),
                      preferred_element_type=jnp.float32)
    fv, fi = jax.lax.top_k(full, 7)
    out = jax.jit(lambda c, tb, sc: blockwise_matmul_top_k(
        c, tb, 7, 64, scales=sc, int4_dim=d))(
        cv, jnp.asarray(q), jnp.asarray(s))
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(out.indices))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(out.values),
                               rtol=1e-6)
    labels = jnp.asarray(rng.integers(0, v, (6,)), jnp.int32)
    ll = gathered_label_logits(cv, jnp.asarray(q), labels,
                               scales=jnp.asarray(s), int4_dim=d)
    ref = np.einsum("bd,bd->b", np.asarray(cv),
                    deq[np.asarray(labels)])
    np.testing.assert_allclose(np.asarray(ll), ref, rtol=1e-5)


@roofline
@pytest.mark.parametrize("knob,scheme,dtype", [
    ("fp8_e4m3", "fp8_e4m3_rowwise", np.uint8),
    ("fp8_e5m2", "fp8_e5m2_rowwise", np.uint8),
    ("int4", "int4_rowwise_packed", np.uint8),
])
def test_scheme_artifact_round_trip(exported, tmp_path, knob, scheme,
                                    dtype):
    """Every sub-int8 scheme exports, validates on load, and its
    ReleaseModel step matches the fp32 release step over the
    host-dequantized tables (the fused dequant is where the bytes are
    saved, not where the math changes)."""
    from code2vec_tpu.ops import quant
    from code2vec_tpu.release.artifact import (
        export_artifact, load_artifact,
    )
    from code2vec_tpu.release.runtime import ReleaseModel
    model, _, _ = exported
    art_dir = str(tmp_path / f"art_{knob}")
    meta = export_artifact(model, art_dir, scheme=scheme, aot=False,
                           log=lambda m: None)
    assert meta["quantization"]["scheme"] == scheme
    art = load_artifact(art_dir)
    for name in ("token_embedding", "path_embedding",
                 "target_embedding"):
        assert art.tables[name].dtype == dtype
        assert art.tables[f"{name}.scale"].dtype == np.float32
    if knob == "int4":
        d = model.dims.token_dim
        assert art.tables["token_embedding"].shape[1] == (d + 1) // 2
        # >= 1.8x smaller than the int8 flavor of the same tables
        tb = meta["table_bytes"]
        int8_bytes = sum(
            np.asarray(jax.device_get(
                model.state.params[n])).size
            + 4 * model.state.params[n].shape[0]
            for n in ("token_embedding", "path_embedding",
                      "target_embedding"))
        assert int8_bytes / tb["artifact"] >= 1.8
    cfg = dataclasses.replace(model.config, train_data_path_prefix=None,
                              model_load_path=None,
                              serve_artifact=art_dir)
    rm = ReleaseModel(cfg, log=lambda m: None)
    arrays = _rand_batch_arrays(model, b=4)
    out = rm.eval_step(None, *arrays)
    assert np.isfinite(np.asarray(out.topk_values)).all()
    assert np.isfinite(float(out.loss_sum))
    # fp32 reference over explicitly dequantized tables: same math,
    # different byte layout
    fp32_dir = str(tmp_path / f"art_{knob}_fp32ref")
    export_artifact(model, fp32_dir, scheme="float32", aot=False,
                    log=lambda m: None)
    for name in ("token_embedding", "path_embedding",
                 "target_embedding"):
        q = np.load(os.path.join(art_dir, f"{name}.npy"))
        s = np.load(os.path.join(art_dir, f"{name}.scale.npy"))
        if knob == "int4":
            d = {"token_embedding": model.dims.token_dim,
                 "path_embedding": model.dims.path_dim,
                 "target_embedding": model.dims.code_dim
                 if hasattr(model.dims, "code_dim")
                 else model.dims.path_dim + 2 * model.dims.token_dim}[name]
            deq = quant.dequantize_rows_int4(q, s, d)
        else:
            fmt = "e4m3" if "e4m3" in knob else "e5m2"
            deq = quant.dequantize_rows_fp8(q, s, fmt)
        np.save(os.path.join(fp32_dir, f"{name}.npy"),
                deq.astype(np.float32))
    cfg_ref = dataclasses.replace(cfg, serve_artifact=fp32_dir)
    rm_ref = ReleaseModel(cfg_ref, log=lambda m: None)
    ref = rm_ref.eval_step(None, *arrays)
    np.testing.assert_array_equal(np.asarray(out.topk_indices),
                                  np.asarray(ref.topk_indices))
    np.testing.assert_allclose(np.asarray(out.topk_values),
                               np.asarray(ref.topk_values), rtol=1e-4,
                               atol=1e-5)


@roofline
def test_scheme_rejection_matrix(exported, tmp_path):
    """The loader's named-field validation across the new schemes: a
    tampered dtype, a truncated int4 payload, a missing scale file, an
    unknown scheme and an expect_scheme mismatch all fail naming the
    offending field."""
    import shutil

    from code2vec_tpu.release.artifact import (
        ArtifactError, export_artifact, load_artifact,
    )
    model, _, _ = exported
    base = str(tmp_path / "int4")
    export_artifact(model, base, scheme="int4_rowwise_packed", aot=False,
                    log=lambda m: None)

    def corrupt(name, fn):
        broken = str(tmp_path / f"broken_{np.random.randint(1 << 30)}")
        shutil.copytree(base, broken)
        fn(broken)
        return broken

    # int4 meta with an f32 payload -> dtype named
    b = corrupt("dtype", lambda d: np.save(
        os.path.join(d, "token_embedding.npy"),
        np.zeros_like(np.load(os.path.join(d, "token_embedding.npy")),
                      dtype=np.float32)))
    with pytest.raises(ArtifactError, match="token_embedding.dtype"):
        load_artifact(b)
    # truncated packed payload -> shape named (packed width checked)
    b = corrupt("shape", lambda d: np.save(
        os.path.join(d, "path_embedding.npy"),
        np.load(os.path.join(d, "path_embedding.npy"))[:, :-1]))
    with pytest.raises(ArtifactError, match="path_embedding.shape"):
        load_artifact(b)
    # missing scale -> scale named
    b = corrupt("scale", lambda d: os.remove(
        os.path.join(d, "target_embedding.scale.npy")))
    with pytest.raises(ArtifactError, match="target_embedding.scale"):
        load_artifact(b)
    # unknown scheme -> quantization.scheme named

    def bad_scheme(d):
        with open(os.path.join(d, "release_meta.json")) as f:
            meta = json.load(f)
        meta["quantization"]["scheme"] = "int2_hypothetical"
        with open(os.path.join(d, "release_meta.json"), "w") as f:
            json.dump(meta, f)

    b = corrupt("scheme", bad_scheme)
    with pytest.raises(ArtifactError, match="quantization.scheme"):
        load_artifact(b)
    # expect_scheme mismatch (an int8-only consumer handed int4)
    with pytest.raises(ArtifactError, match="quantization.scheme"):
        load_artifact(base, expect_scheme="int8_rowwise_symmetric")


@roofline
def test_release_scheme_knob_drives_export(exported, tmp_path):
    """config.release_scheme picks the scheme; --no_quantize still
    forces fp32 regardless of the knob."""
    from code2vec_tpu.release.artifact import export_artifact
    model, _, _ = exported
    cfg = dataclasses.replace(model.config, release_scheme="int4")
    old_cfg = model.config
    model.config = cfg
    try:
        meta = export_artifact(model, str(tmp_path / "a"), aot=False,
                               log=lambda m: None)
        assert meta["quantization"]["scheme"] == "int4_rowwise_packed"
        meta = export_artifact(model, str(tmp_path / "b"), aot=False,
                               quantize=False, log=lambda m: None)
        assert meta["quantization"]["scheme"] == "float32"
    finally:
        model.config = old_cfg


@roofline
def test_config_release_scheme_validation():
    with pytest.raises(ValueError, match="release_scheme"):
        Config(train_data_path_prefix="<t>",
               release_scheme="int2").verify()


# ------------------------------------- the one served head (exact)

_PROBE = "name|x1 tok1,p1,tok1 tok2,p2,tok2"
# two sets of batch-mates, every row inside the 4-context bucket: the
# probe rides the same compiled shape whoever comes with it
_MATES = (["name|x2 tok3,p3,tok3",
           "name|x3 tok1,p2,tok2 tok4,p0,tok4 tok5,p1,tok5",
           "name|x4 tok0,p0,tok0"],
          ["name|x5 tok5,p3,tok5 tok0,p1,tok1",
           "name|x6 tok2,p2,tok2",
           "name|x7 tok4,p1,tok3 tok3,p0,tok4 tok1,p3,tok0 tok2,p0,tok2"])


def _release_config(model, art_dir):
    return dataclasses.replace(model.config, train_data_path_prefix=None,
                               model_load_path=None,
                               serve_artifact=art_dir)


def _assert_same_answer(mine, ref):
    assert mine.topk_predicted_words == ref.topk_predicted_words
    np.testing.assert_array_equal(
        np.asarray(mine.topk_predicted_words_scores),
        np.asarray(ref.topk_predicted_words_scores))
    np.testing.assert_array_equal(mine.code_vector, ref.code_vector)


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("surface", ["release", "facade"])
def test_a_rows_answer_does_not_depend_on_its_batch_mates(exported,
                                                          surface, n):
    """One head, one padded shape a bucket: a row's served names,
    scores and code vector are the same bits alone, beside one other
    row and in a full batch of `serve_batch_size` live rows, whoever
    the others are; at another place in the batch, the same names."""
    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, _ = exported
    served = model if surface == "facade" else ReleaseModel(
        _release_config(model, art_dir), log=lambda m: None)
    bs = int(model.config.serve_batch_size)
    assert bs == 4

    def predict(lines):
        return served.predict(lines, batch_size=bs,
                              with_code_vectors=True)

    before = set(served._predict_steps)
    ref = predict([_PROBE] + _MATES[1])[0]
    _assert_same_answer(predict([_PROBE] + _MATES[0][:n - 1])[0], ref)
    last = predict(_MATES[0][:n - 1] + [_PROBE])[-1]
    assert last.topk_predicted_words == ref.topk_predicted_words
    np.testing.assert_allclose(last.code_vector, ref.code_vector,
                               rtol=1e-5, atol=1e-6)
    # every call rode the one shape
    assert (bs, 4) in served._predict_steps
    assert set(served._predict_steps) - before <= {(bs, 4)}


@pytest.mark.parametrize("surface", ["release", "facade"])
def test_warmup_compiles_one_step_a_bucket_and_a_lone_row_none(
        exported, tmp_path, surface):
    """After `warmup()` the served model holds exactly one compiled step
    a context bucket, and a predict of ONE row finds its step there: no
    second shape exists for few rows."""
    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, _ = exported
    if surface == "facade":
        served = _tiny_model(tmp_path)
    else:
        served = ReleaseModel(_release_config(model, art_dir),
                              log=lambda m: None)
    bs = int(served.config.serve_batch_size)
    served.warmup()
    buckets = served.context_buckets
    assert served.predict_compile_count() == len(buckets) == 3
    steps = dict(served._predict_steps)
    assert set(steps) == {(bs, m) for m in buckets}
    [answer] = served.predict([_PROBE], batch_size=bs)
    assert answer.topk_predicted_words
    assert served._predict_steps == steps           # the same objects
    assert all(step._cache_size() == 1 for step in steps.values())


@pytest.mark.parametrize("knob", ["float32", "int8", "int4"])
def test_served_names_are_lax_top_k_over_the_dequantised_logits(
        exported, tmp_path, knob):
    """Through `ReleaseModel.predict`, whatever the table's bytes: the
    served top-k names and their order are `lax.top_k` over the full
    logits of the row's code vector against the dequantised table (the
    real rows of it)."""
    from code2vec_tpu.ops import quant
    from code2vec_tpu.release.artifact import (
        SCHEME_BY_KNOB, export_artifact, load_artifact,
    )
    from code2vec_tpu.release.runtime import ReleaseModel
    model, _, _ = exported
    art_dir = str(tmp_path / f"art_{knob}")
    meta = export_artifact(model, art_dir, scheme=SCHEME_BY_KNOB[knob],
                           aot=False, log=lambda m: None)
    rm = ReleaseModel(_release_config(model, art_dir), log=lambda m: None)
    lines = [_PROBE] + _MATES[0]
    served = rm.predict(lines, with_code_vectors=True)
    tables = load_artifact(art_dir).tables
    q = np.asarray(tables["target_embedding"])
    if knob == "float32":
        table = q
    elif knob == "int8":
        table = quant.dequantize_rows(
            q, np.asarray(tables["target_embedding.scale"]))
    else:
        d = model.dims.path_dim + 2 * model.dims.token_dim
        table = quant.dequantize_rows_int4(
            q, np.asarray(tables["target_embedding.scale"]), d)
    real = int(meta["dims"]["real_target_vocab_size"])
    k = min(int(meta["topk"]), real)
    for row in served:
        logits = jnp.asarray(row.code_vector, jnp.float32) \
            @ jnp.asarray(table[:real], jnp.float32).T
        _, want = jax.lax.top_k(logits, k)
        assert row.topk_predicted_words == [
            rm.vocabs.target_vocab.lookup_word(int(j)) for j in want]


def test_an_older_exporters_mips_keys_are_ignored(exported, tmp_path):
    """An artifact written under the removed `--serve_mips_nprobe`
    carries `mips_crossover` and `mips_calibration` in its meta: it
    loads, has the fingerprint of the same artifact without them and
    answers bit for bit like it (the exact head serves every batch)."""
    import shutil

    from code2vec_tpu.release.artifact import META_NAME, load_artifact
    from code2vec_tpu.release.runtime import ReleaseModel
    model, art_dir, meta = exported
    old_dir = str(tmp_path / "written_by_an_older_exporter")
    shutil.copytree(art_dir, old_dir)
    with open(os.path.join(old_dir, META_NAME)) as f:
        old_meta = json.load(f)
    assert "mips_crossover" not in old_meta
    old_meta["mips_crossover"] = 2
    old_meta["mips_calibration"] = {
        "1": {"exact": 911.4, "mips": 402.7},
        "2": {"exact": 930.2, "mips": 671.9},
        "4": {"exact": 951.0, "mips": 1290.3}}
    with open(os.path.join(old_dir, META_NAME), "w") as f:
        json.dump(old_meta, f, indent=2, sort_keys=True)
        f.write("\n")
    assert load_artifact(old_dir).fingerprint == meta["fingerprint"]
    plain = ReleaseModel(_release_config(model, art_dir),
                         log=lambda m: None)
    old = ReleaseModel(_release_config(model, old_dir),
                       log=lambda m: None)
    assert old.model_fingerprint() == plain.model_fingerprint()
    for lines in ([_PROBE], [_PROBE] + _MATES[0]):      # 1 row, a full batch
        for mine, ref in zip(
                old.predict(lines, with_code_vectors=True),
                plain.predict(lines, with_code_vectors=True)):
            _assert_same_answer(mine, ref)
    assert set(old._predict_steps) == set(plain._predict_steps)
