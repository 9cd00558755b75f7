"""The grouped-query / selected-key / softmax-expert language model at
tiny widths on the CPU: each op against its plain form (the selection
against `lax.top_k`, ties and all; the masked attention against a plain
GATHERED one), the model through its two caches against the plain
reference's one full forward with the selection LIVE (16 keys of ~100)
and idle (more keys allowed than there are), the softmax router by hand
and by shares, and registered contexts scored through the facade."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import lm_common
from code2vec_tpu.models import sparse_gqa_moe_lm as lm
from code2vec_tpu.models import sparse_gqa_moe_lm_reference as ref
from code2vec_tpu.ops import moe, sparse_attn
from code2vec_tpu.serving.context_cache import chunks

TINY = dict(
    model_type="KeyeVL2", hidden_size=64, num_hidden_layers=4, layers=2,
    vocab_size=512, vocab_rows=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 4, 2], "rope_type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "topk": 16, "q_chunk_size": 512,
               "kv_chunk_size": 512},
    moe_intermediate_size=48, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    rms_norm_eps=1e-6)
CHUNK, CAPACITY = 32, 128
F32 = jnp.float32


def _cfg(topk):
    return lm.LMConfig.from_dict(dict(
        TINY, sa_config=dict(TINY["sa_config"], topk=topk)))


@pytest.fixture(scope="module")
def cfg():
    return _cfg(16)


@pytest.fixture(scope="module")
def params(cfg):
    """The program's initializer, the attention and indexer projections
    widened so that scores spread: at normal(0, 0.02) the softmax is all
    but uniform, every context reads alike and WHICH keys were kept
    would not show."""
    out = lm_common.init_leaves(cfg, lm.leaf_specs(cfg), 3)
    wider = {".wq": 10.0, ".wk": 10.0, ".idx_q": 10.0, ".idx_k": 10.0,
             ".idx_w": 10.0}
    return {name: (next((by for end, by in wider.items()
                         if name.endswith(end)), 1.0)
                   * leaf.astype(F32)).astype(leaf.dtype)
            for name, leaf in out.items()}


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 128, (n,)).astype(np.int32)


def _register(cfg, params, cache, contexts, chunk=CHUNK):
    step = jax.jit(lm.ctx_register_step, static_argnums=(0,))
    for slot, tokens in contexts.items():
        for start, real in chunks(len(tokens), chunk):
            ids = np.zeros((chunk,), np.int32)
            ids[:real] = tokens[start:start + real]
            cache = step(cfg, params, cache, ids, np.int32(real),
                         np.int32(slot), np.int32(start))
    return cache


def _score(cfg, params, cache, questions, slots, held, length=32, k=5):
    rows = len(questions)
    ids = np.zeros((rows, length), np.int32)
    lengths = np.zeros((rows,), np.int32)
    for i, q in enumerate(questions):
        ids[i, :len(q)], lengths[i] = q, len(q)
    return jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, k, 64, params, ids, lengths, cache,
        np.asarray(slots, np.int32), np.asarray(held, np.int32))


# -------------------------------------------------------------------- the ops

def test_equal_rotary_streams_are_plain_rotary():
    """`mrope_section` deals the pairs to three streams; text sets them
    equal, which is half-split rotary by one position: pair (i, i + d/2)
    turned by position * theta^(-2i/d)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    at = jnp.array([[0, 1, 2, 3, 4], [70, 71, 72, 73, 74]])
    got = np.asarray(sparse_attn.rotate(
        x, jnp.broadcast_to(at[None], (3, 2, 5)), 1e7, (2, 4, 2)))
    plain = np.asarray(sparse_attn.rotate(x, at[None], 1e7))
    np.testing.assert_allclose(got, plain, atol=1e-6)
    angle = (np.asarray(at, np.float64)[..., None]
             * 1e7 ** (-np.arange(8) / 8.0))[:, :, None, :]
    a, b = np.asarray(x[..., :8], np.float64), np.asarray(x[..., 8:],
                                                          np.float64)
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           a * np.sin(angle) + b * np.cos(angle)], -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got[0, 0], np.asarray(x[0, 0]), atol=1e-6)
    # streams apart (an image token): the pairs of a section turn by
    # their own stream's position
    apart = jnp.stack([at, at + 5, at + 9])
    moved = np.asarray(sparse_attn.rotate(x, apart, 1e7, (2, 4, 2)))
    same = [0, 1, 8, 9]             # the temporal section's dimensions
    np.testing.assert_allclose(moved[..., same], got[..., same], atol=1e-6)
    assert np.abs(moved[..., 2:8] - got[..., 2:8]).max() > 0.1


def _plain_select(scores, visible, k):
    """`lax.top_k` over each query's row (it keeps the lower index of
    equal values), then the kept indices as a mask."""
    s = np.where(visible, scores, -np.inf)
    _, idx = jax.lax.top_k(jnp.asarray(s), min(k, s.shape[-1]))
    kept = np.zeros(s.shape, bool)
    np.put_along_axis(kept, np.asarray(idx), True, axis=-1)
    return kept & visible


@pytest.mark.parametrize("k", [16, 1, 300], ids=["live", "one", "all"])
def test_select_is_top_k_over_the_visible_keys(k):
    rng = np.random.RandomState(k)
    scores = rng.standard_normal((2, 24, 200)).astype(np.float32)
    scores[0, 3] = -np.abs(scores[0, 3])            # a row all negative
    scores[1, 5, 10:60] = np.inf
    visible = np.asarray(sparse_attn.visible_keys(
        2, 24, 176, jnp.array([150, 9]), jnp.array([24, 20])))
    got = np.asarray(sparse_attn.select(jnp.asarray(scores),
                                        jnp.asarray(visible), k))
    assert (got == _plain_select(scores, visible, k)).all()
    seen = visible.sum(-1)
    assert (got.sum(-1) == np.minimum(seen, k)).all()


def test_ties_go_to_the_lower_position():
    """Equal scores straddling the k-th place: the earlier keys are
    kept, over lane (128) and word (32) boundaries too."""
    scores = np.zeros((1, 3, 300), np.float32)
    scores[0, 0, ::3] = 1.0             # 100 ones, then zeros
    scores[0, 1, :] = 2.5               # all equal
    scores[0, 2, 250:] = -1.0
    visible = np.ones((1, 3, 300), bool)
    visible[0, 1, 5] = False
    got = np.asarray(sparse_attn.select(jnp.asarray(scores),
                                        jnp.asarray(visible), 140))
    ones = np.arange(0, 300, 3)
    zeros = np.setdiff1d(np.arange(300), ones)
    assert sorted(np.flatnonzero(got[0, 0])) == sorted(
        list(ones) + list(zeros[:40]))
    assert list(np.flatnonzero(got[0, 1])) == [
        i for i in range(142) if i != 5][:140]
    assert list(np.flatnonzero(got[0, 2])) == list(range(140))
    assert (got == _plain_select(scores, visible, 140)).all()


def test_packed_bits_unpack_to_the_positions():
    flags = np.random.RandomState(0).rand(3, 2, 205) < 0.3
    words = np.asarray(sparse_attn.pack_bits(jnp.asarray(flags)))
    assert words.shape == (3, 2, 7) and words.dtype == np.uint32
    for r in range(3):
        for c in range(2):
            assert list(sparse_attn.unpack_bits(words[r, c])) == list(
                np.flatnonzero(flags[r, c]))


def _attend_inputs(length, held, capacity=128, rows=2, hq=4, hkv=2, d=16):
    k = jax.random.split(jax.random.PRNGKey(length), 6)
    bf16 = jnp.bfloat16
    return dict(
        q=2 * jax.random.normal(k[0], (rows, length, hq, d), bf16),
        own_k=jax.random.normal(k[1], (rows, length, hkv, d), bf16),
        own_v=jax.random.normal(k[2], (rows, length, hkv, d), bf16),
        cached_kv=jax.random.normal(k[3], (rows, capacity, 2 * hkv * d),
                                    bf16),
        slot=jnp.arange(rows)[::-1],
        cached_len=jnp.asarray(held, jnp.int32)), jax.random.normal(
            k[4], (rows, length, capacity + length))


def _attend_gathered(a, kept):
    """GATHERED: each query reads only the keys and values it kept, by
    index, float32: the other form of the same mathematics."""
    rows, length, hq, d = a["q"].shape
    hkv = a["own_k"].shape[2]
    capacity = a["cached_kv"].shape[1]
    out = np.zeros((rows, length, hq * d), np.float32)
    for r in range(rows):
        slot = np.asarray(a["cached_kv"][a["slot"][r]], np.float32)
        keys = np.concatenate([slot[:, :hkv * d].reshape(capacity, hkv, d),
                               np.asarray(a["own_k"][r], np.float32)])
        values = np.concatenate([slot[:, hkv * d:].reshape(capacity, hkv, d),
                                 np.asarray(a["own_v"][r], np.float32)])
        for t in range(length):
            idx = np.flatnonzero(kept[r, t])
            if not idx.size:
                continue
            for n in range(hq):
                g = n // (hq // hkv)
                s = keys[idx, g] @ np.asarray(a["q"][r, t, n],
                                              np.float32) / d ** 0.5
                p = np.exp(s - s.max())
                out[r, t, n * d:(n + 1) * d] = (p / p.sum()) @ values[idx, g]
    return out


@pytest.mark.parametrize("length,held,k", [
    (8, (100, 37), 16), (40, (0, 128), 16), (24, (64, 5), 500)],
    ids=["short", "one_row_uncached", "all_keys"])
def test_masked_attend_is_the_gathered_attention(length, held, k):
    a, scores = _attend_inputs(length, held)
    own_len = jnp.asarray([length, max(length - 3, 1)], jnp.int32)
    kept = sparse_attn.select(scores, sparse_attn.visible_keys(
        2, length, 128, a["cached_len"], own_len), k)
    got = np.asarray(sparse_attn.attend(**a, selected=kept, block=32),
                     np.float32)
    want = _attend_gathered(a, np.asarray(kept))
    assert np.isfinite(got).all()                       # padding too
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()


def test_index_scores_are_the_plain_sum_over_heads():
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    bf16 = jnp.bfloat16
    q_i = jax.random.normal(k[0], (2, 6, 4, 8), bf16)
    w = jax.random.normal(k[1], (2, 6, 4))
    own = jax.random.normal(k[2], (2, 6, 8), bf16)
    cached = jax.random.normal(k[3], (3, 64, 8), bf16)
    slot, held = jnp.array([2, 0]), jnp.array([50, 17])
    got = np.asarray(sparse_attn.index_scores(q_i, w, own, cached, slot,
                                              held, block=16))
    for r in range(2):
        keys = np.concatenate([np.asarray(cached[slot[r]], np.float32),
                               np.asarray(own[r], np.float32)])
        dots = np.einsum("lhd,kd->lhk", np.asarray(q_i[r], np.float32),
                         keys)
        want = (np.asarray(w[r])[..., None] * np.maximum(dots, 0)).sum(1)
        seen = np.r_[np.arange(64) < int(held[r]), np.ones(6, bool)]
        np.testing.assert_allclose(got[r][:, seen], want[:, seen],
                                   atol=1e-4, rtol=1e-5)


def test_softmax_router_by_hand():
    """softmax over ALL experts in float32, the k largest, renormalised
    over the chosen: against a loop in float64."""
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    u = np.asarray(jax.random.normal(k[0], (30, 64)))
    w = np.asarray(0.3 * jax.random.normal(k[1], (64, 16)))
    routed = moe.route(jnp.asarray(u), jnp.asarray(w), None, 4,
                       softmax=True)
    for t in range(30):
        z = u[t].astype(np.float64) @ w
        p = np.exp(z - z.max())
        p /= p.sum()
        chosen = sorted(range(16), key=lambda e: -p[e])[:4]
        assert list(np.asarray(routed.experts[t])) == chosen
        np.testing.assert_allclose(np.asarray(routed.weights[t]),
                                   p[chosen] / p[chosen].sum(), atol=1e-6)
    assert abs(float(routed.weights.sum()) - 30.0) < 1e-4


def test_softmax_shares_add_up_to_the_uncut_layer(cfg):
    """Guide section 4: the routed parts of four shares of four experts
    (no shared expert in this family) are the uncut layer."""
    p = {leaf.name: lm_common.init_leaf(cfg, leaf, jax.random.PRNGKey(i))
         for i, leaf in enumerate(lm.layer_leaf_specs(cfg))}
    u = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    want, chosen = ref.experts(cfg, p, u)
    routed = moe.route(u, p["router"], None, 4, softmax=True)
    assert (np.asarray(routed.experts) == np.asarray(chosen)).all()
    real = jnp.ones((40,), bool)
    parts = sum(moe.experts_grouped(
        u, routed, p["w_up"][4 * c:4 * c + 4].astype(F32),
        p["w_down"][4 * c:4 * c + 4].astype(F32), 4 * c, real,
        w_gate=p["w_gate"][4 * c:4 * c + 4].astype(F32))[0]
        for c in range(4))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               atol=2e-4)
    share = dataclasses.replace(cfg, experts_held=4, expert_first=8)
    assert [leaf.shape for leaf in lm.layer_leaf_specs(share)
            if leaf.name == "w_up"] == [(4, 64, 48)]
    assert dict((leaf.name, leaf.shape) for leaf in lm.layer_leaf_specs(
        share))["router"] == (64, 16)


# ------------------------------------------------------------------ the model

CONTEXTS = {1: _tokens(11, 96), 3: _tokens(12, 77), 2: _tokens(13, 64)}
QUESTIONS = [_tokens(21, 20), _tokens(22, 32), _tokens(23, 5),
             _tokens(24, 17)]
SLOTS, HELD = [1, 3, 1, 0], [96, 77, 96, 0]


@pytest.fixture(scope="module")
def cache(cfg, params):
    return _register(cfg, params, lm.init_cache(cfg, 4, CAPACITY), CONTEXTS)


def _hold_to_reference(cfg, params, cache, capacity=CAPACITY):
    """Each row's answer against the reference's over context ++
    question: (each row's widest logit gap over the reference's spread,
    the share of the last query's kept keys the two agree on, a layer)."""
    from code2vec_tpu.lm_facade import selected_positions
    out = _score(cfg, params, cache, QUESTIONS, SLOTS, HELD)
    gaps, overlap = [], []
    for r, q in enumerate(QUESTIONS):
        before = CONTEXTS[SLOTS[r]][:HELD[r]] if HELD[r] else q[:0]
        logits, chosen, kept = ref.logits(cfg, params,
                                          np.concatenate([before, q]))
        logits = np.asarray(logits)
        served = np.asarray(out.topk_indices[r])
        spread = logits.max() - logits.mean()
        gaps.append(max(
            np.abs(logits[served] - np.asarray(out.topk_values[r])).max(),
            logits.max() - logits[served[0]]) / spread)
        for layer in range(cfg.layers):
            got = selected_positions(
                np.asarray(out.stats.selected_last[r, layer]), capacity,
                HELD[r])
            want = np.flatnonzero(np.asarray(kept[layer]))
            assert len(got) == len(want) == min(cfg.topk, HELD[r] + len(q))
            overlap.append(len(set(got) & set(want)) / len(want))
    return out, np.asarray(gaps), np.asarray(overlap)


def test_scores_through_the_caches_are_the_full_forward_selection_live(
        cfg, params, cache):
    """16 keys of up to 128 a query: rows of one batch name different
    slots (one twice, one none). Where bfloat16 and float32 index scores
    order two keys at the k-th place differently, one of 16 attended
    keys is another and that row's logits move by a step: the median row
    is held tight, the widest loosely, and the kept sets must agree."""
    out, gaps, overlap = _hold_to_reference(cfg, params, cache)
    assert np.median(gaps) < 0.02 and gaps.max() < 0.3, gaps
    assert overlap.mean() > 0.95 and overlap.min() >= 14 / 16, overlap
    assert out.stats.load.shape == (2, 16)
    assert int(out.stats.real_tokens) == 20 + 32 + 5 + 17
    # keys kept over the real queries of a layer: min(16, visible) each
    want = sum(min(16, h + t + 1) for q, h in zip(QUESTIONS, HELD)
               for t in range(len(q)))
    assert list(np.asarray(out.stats.selected_keys)) == [want, want]


def test_scores_with_more_keys_allowed_than_there_are_attend_all(params):
    """`topk` 200 of at most 128 keys: every visible key is kept, the
    comparison is bfloat16 against float32 alone."""
    all_keys = _cfg(200)
    cache = _register(all_keys, params, lm.init_cache(all_keys, 4, CAPACITY),
                      CONTEXTS)
    _, gaps, overlap = _hold_to_reference(all_keys, params, cache)
    assert gaps.max() < 0.02, gaps
    assert overlap.min() == 1.0


def test_the_selection_shows_in_the_answer(cfg, params, cache):
    """Dense attention (the selection ignored) over the same cache is
    another answer: what the benchmark's comparison has to catch."""
    q = [QUESTIONS[0]]
    right = _score(cfg, params, cache, q, [1], [96])
    dense = _score(_cfg(200), params, cache, q, [1], [96])
    assert np.abs(np.asarray(right.topk_values)
                  - np.asarray(dense.topk_values)).max() > 0.01
    for slots, held in (([3], [96]), ([1], [95]), ([1], [0])):
        wrong = _score(cfg, params, cache, q, slots, held)
        assert np.abs(np.asarray(right.topk_values)
                      - np.asarray(wrong.topk_values)).max() > 0.01


@pytest.mark.parametrize("chunk", [16, 128], ids=["sixths", "one_shot"])
def test_chunked_registration_is_the_one_shot(cfg, params, cache, chunk):
    other = _register(cfg, params, lm.init_cache(cfg, 4, CAPACITY),
                      CONTEXTS, chunk=chunk)
    # layer 0's state is a function of the token and its position alone
    for slot, tokens in CONTEXTS.items():
        for a, b in zip(cache[0], other[0]):
            np.testing.assert_allclose(
                np.asarray(a[slot, :len(tokens)], np.float32),
                np.asarray(b[slot, :len(tokens)], np.float32), atol=0.05)
    # deeper layers see the chunking through a rounding of layer 0's
    # output, and a key at the k-th place may then change places: most
    # rows answer alike, one may move by a step
    got = [np.abs(np.asarray(_score(cfg, params, c, QUESTIONS, SLOTS,
                                    HELD).topk_values)) for c in (cache,
                                                                  other)]
    gaps = np.abs(got[0] - got[1]).max(axis=1)
    assert np.median(gaps) < 5e-3 and gaps.max() < 0.1, gaps


def test_right_padding_and_neighbours_change_no_answer(cfg, params, cache):
    q = QUESTIONS[0]
    alone = _score(cfg, params, cache, [q], [1], [96])
    junk = np.concatenate([q, _tokens(5, 12)])      # junk behind the end
    ids = np.stack([junk, _tokens(6, 32)])
    both = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, 5, 64, params, ids, np.array([20, 32], np.int32), cache,
        np.array([1, 2], np.int32), np.array([96, 64], np.int32))
    assert (np.asarray(alone.topk_indices[0])
            == np.asarray(both.topk_indices[0])).all()
    np.testing.assert_allclose(np.asarray(alone.topk_values[0]),
                               np.asarray(both.topk_values[0]), atol=1e-4)
    assert (np.asarray(alone.stats.selected_last[0])
            == np.asarray(both.stats.selected_last[0])).all()
    # a row of no real token (a request whose context went) is padding
    none = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, 5, 64, params, ids, np.array([20, 0], np.int32), cache,
        np.array([1, 0], np.int32), np.array([96, 0], np.int32))
    assert np.isfinite(np.asarray(none.topk_values)).all()
    np.testing.assert_allclose(np.asarray(alone.topk_values[0]),
                               np.asarray(none.topk_values[0]), atol=1e-4)


def test_parameter_count_and_cache_bytes_at_the_published_widths():
    """ISSUE 34's arithmetic: 625,381,760 parameters a layer, 2,176 B a
    token and layer in the two caches."""
    published = dict(
        model_type="KeyeVL2", hidden_size=2048, num_hidden_layers=48,
        vocab_size=151936, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, rope_theta=10000000,
        rope_scaling={"mrope_section": [16, 24, 24]},
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "topk": 2048},
        moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8,
        rms_norm_eps=1e-6)
    whole = lm.LMConfig.from_dict(published)
    assert lm_common.count_leaves(lm.layer_leaf_specs(whole)) == 625_381_760
    assert lm_common.count_leaves(lm.leaf_specs(whole)) == (
        48 * 625_381_760 + 2 * 311_164_928 + 2048)
    stage = lm.LMConfig.from_dict(dict(published, layers=6))
    assert lm_common.count_leaves(lm.leaf_specs(stage)) == 4_374_622_464
    assert stage.cache_width * 2 == 2176 and stage.pattern == "EEEEEE"
    assert stage.index_sections == (8, 12, 12)
    shapes = jax.eval_shape(lambda: lm.init_cache(stage, 8, 40960))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)
               ) == 8 * 40960 * 6 * 2176 == 4_278_190_080
    for bad in (dict(mlp_only_layers=[0]), dict(norm_topk_prob=False),
                dict(rope_scaling={"mrope_section": [16, 24, 20]})):
        with pytest.raises(ValueError):
            lm.LMConfig.from_dict(dict(published, **bad))


# ----------------------------------------------------------------- the facade

@pytest.fixture(scope="module")
def model(tmp_path_factory):
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    work = tmp_path_factory.mktemp("keye")
    path = work / "tiny.json"
    path.write_text(json.dumps(dict(TINY, serve={
        "length_buckets": [16, 32], "token_budget": 64, "top_k": 5,
        "context_cache": {"slots": 3, "tokens_per_slot": CAPACITY,
                          "register_chunk": CHUNK}})))
    common = ["--model_config", str(path), "--serve_token_budget", "64",
              "--seed", "5"]
    saved = ScoringModel(config_from_args(
        common + ["--save", str(work / "ck" / "saved")])).save()
    return ScoringModel(config_from_args(["serve", "--load", saved]
                                         + common))


def test_the_facade_runs_two_arrays_a_layer_on_the_shared_lines(model):
    """`KeyeVL2` picks the module; the cache is two arrays a layer and
    the facade registers, donates and scores through the lines every
    cached model runs; the selection's counters and the answer's kept
    keys come out."""
    from code2vec_tpu import lm_facade, obs
    assert model.module is lm and model.served_endpoints == ("score",
                                                             "contexts")
    assert [len(layer) for layer in model.cache] == [2, 2]
    assert sum(a.nbytes for a in jax.tree.leaves(model.cache)) == (
        3 * CAPACITY * 2 * model.lm.cache_width * 2)
    context = _tokens(31, 100)
    got = model.register_context(context.tolist())
    assert got["tokens"] == 100 and not got["held"]
    assert model.register_context(context.tolist())["held"]

    def total(name):
        return sum(m.value for m in
                   obs.default_registry().collect().get(name, {}).values())
    before = {n: total(n) for n in (
        "score_index_pairs_scored_total", "score_keys_visible_total",
        "score_keys_selected_total", "sparse_attend_steps_total")}
    question = _tokens(32, 12)
    [r] = model.score_batch([model.validate(question.tolist(), 5,
                                            got["context"])])
    assert r.context_tokens == 100 and r.tokens == 12
    pairs = 2 * (12 * 100 + 12 * 13 // 2)
    assert total("score_index_pairs_scored_total") - before[
        "score_index_pairs_scored_total"] == pairs
    assert total("score_keys_visible_total") - before[
        "score_keys_visible_total"] == pairs
    assert total("score_keys_selected_total") - before[
        "score_keys_selected_total"] == 2 * 12 * 16
    assert total("sparse_attend_steps_total") - before[
        "sparse_attend_steps_total"] == 1
    kept = [lm_facade.selected_positions(words, CAPACITY, 100)
            for words in r.selected_last]
    assert all(len(k) == 16 and k.max() <= 111 and (np.diff(k) > 0).all()
               for k in kept)
    _, _, want = ref.logits(model.lm, model.params,
                            np.concatenate([context, question]))
    assert np.mean([len(set(k) & set(np.flatnonzero(np.asarray(w)))) / 16
                    for k, w in zip(kept, want)]) > 0.9


def test_the_server_returns_the_kept_keys_when_asked(model):
    from code2vec_tpu.serving.server import PredictionServer
    server = PredictionServer(model, model.config)
    server.start(0, "127.0.0.1")
    try:
        body = {"ids": _tokens(41, 70).tolist()}
        context = json.loads(server.handle(
            "contexts", json.dumps(body), params=body))["context"]
        ask = {"context": context, "ids": _tokens(42, 9).tolist(),
               "top_k": 3, "return_routing": True, "return_selected": True}
        answer = json.loads(server.handle("score", json.dumps(ask),
                                          params=ask))
        assert answer["context_tokens"] == 70 and len(answer["top"]) == 3
        assert np.asarray(answer["routing_last"]).shape == (2, 4)
        kept = answer["selected_last"]
        assert len(kept) == 2 and all(len(k) == 16 for k in kept)
        assert all(0 <= p < 79 for k in kept for p in k)
        plain = dict(ask, return_selected=False)
        assert "selected_last" not in json.loads(server.handle(
            "score", json.dumps(plain), params=plain))
    finally:
        server.drain(timeout=5.0)
