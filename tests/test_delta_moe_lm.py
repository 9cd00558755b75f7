"""The delta-rule / grouped-query expert model at tiny widths on the CPU
(hidden 64, 4 heads of 16 over 2 key/value heads, 4 linear heads of 16,
8 experts top-2 of which 4 are held, the delta rule in chunks of 8, a
page and a registration chunk of 8 tokens, pattern `G K K K`): the
chunked delta rule against the recurrence, the carried conv against the
whole one, the model against the plain reference's one full forward,
sessions registered chunk by chunk and GROWN by kept turns whose writes
start in the middle of a page, the book of a state and pages a context,
through `ScoringModel` and an in-process `PredictionServer`; and every
fault the benchmark's comparison has to catch, here in small."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import delta_moe_lm as lm
from code2vec_tpu.models import delta_moe_lm_reference as ref
from code2vec_tpu.models import lm_common
from code2vec_tpu.ops import delta_rule
from code2vec_tpu.serving.context_cache import (
    ContextSlots, HeldPages, PoolTooSmall, TooLong, context_id, extended_id,
)

P = 8           # a page, a registration chunk and the delta rule's chunk
LIST = 8        # pages a session may hold: sessions of up to 64 tokens
TINY = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=8, layers=4,
    gqa_layers=[0, 4], gqa_interval=3, vocab_size=256, vocab_rows=96,
    max_position_embeddings=4096, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, use_rope=False, use_gqa_gate=True,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    experts_held=4, expert_first=0, num_experts_per_tok=2,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    first_k_dense_replace=0, tie_word_embeddings=False, rms_norm_eps=1e-5,
    rope_theta=10000, partial_rotary_factor=1)
# Logits reach 0.4 at these widths. The program rounds matmul operands,
# activations and the pages' keys and values to bfloat16 (8 bits) where
# the reference keeps float32 (the state, the decays and the conv are
# float32 on both sides): the widest difference seen over these tests'
# sequences is 0.008, and every fault below reads 0.03 or more on its
# sequence.
TOLERANCE = 0.015


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    monkeypatch.setattr(lm, "CHUNK", P)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


# ------------------------------------------------------------------ the ops

def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("entering", ["zeros", "a state"])
@pytest.mark.parametrize("length", [5, 8, 40])
def test_the_chunked_delta_rule_is_the_recurrence(length, entering):
    """Lengths under, equal to and several times the chunk of 8; with
    and without an entering state; rows padded to 0, a few and no
    tokens short; decays from mild to a channel gone in one token."""
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    rows, h, d = 3, 4, 16
    q = _unit(jax.random.normal(keys[0], (rows, length, h, d))) / 4
    k = _unit(jax.random.normal(keys[1], (rows, length, h, d)))
    v = jax.random.normal(keys[2], (rows, length, h, d))
    g = -jnp.exp(1.5 * jax.random.normal(keys[3], (rows, length, h, d)))
    b = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (rows, length, h)))
    state = jax.random.normal(keys[5], (rows, h, d, d))
    if entering == "zeros":
        state = jnp.zeros_like(state)
    lengths = jnp.array([length, max(1, length - 3), 0])
    o1, s1 = delta_rule.delta_recurrence(q, k, v, g, b, state, lengths)
    o2, s2 = delta_rule.delta_chunked(q, k, v, g, b, state, lengths, P)
    real = (jnp.arange(length)[None, :] < lengths[:, None])[..., None, None]
    assert float(jnp.max(jnp.abs(jnp.where(real, o1 - o2, 0.0)))) < 1e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 2e-5
    # padding writes nothing and moves no state
    assert bool(jnp.all(s2[2] == state[2]))
    o3, s3 = delta_rule.delta_chunked(
        q[1:2, :length - 3 or 1], k[1:2, :length - 3 or 1],
        v[1:2, :length - 3 or 1], g[1:2, :length - 3 or 1],
        b[1:2, :length - 3 or 1], state[1:2], None, P)
    assert float(jnp.max(jnp.abs(s3[0] - s2[1]))) < 2e-5


def test_no_decay_overflows_the_chunk():
    """A channel that forgets everything in one token (exp(-20 x 64)
    underflows, its inverse would overflow): the sub-blocks never form
    a positive exponent."""
    ones = jnp.ones((1, 64, 1, 16))
    o, s = delta_rule.delta_chunked(
        ones, ones / 4, ones, jnp.full((1, 64, 1, 16), -20.0),
        jnp.ones((1, 64, 1)), jnp.zeros((1, 1, 16, 16)), None, 64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())


def test_the_carried_conv_over_two_halves_is_the_conv_over_the_whole():
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(keys[0], (2, 12, 6))
    w = jax.random.normal(keys[1], (6, 4))
    whole, tail = delta_rule.conv_carried(x, w)
    first, carried = delta_rule.conv_carried(x[:, :5], w)
    second, left = delta_rule.conv_carried(x[:, 5:], w, carried)
    assert float(jnp.max(jnp.abs(
        jnp.concatenate([first, second], 1) - whole))) == 0.0
    assert bool((left == tail).all()) and bool((tail == x[:, -3:]).all())
    # a row's real length: the tail is what lies behind ITS last token
    _, short = delta_rule.conv_carried(x, w, None, None, jnp.array([2, 0]))
    assert bool((short[0, 1:] == x[0, :2]).all())
    assert bool((short[0, 0] == 0).all()) and bool((short[1] == 0).all())


def test_the_hybrid_models_conv_is_the_same_function():
    """`hybrid_lm` pads with zeros and adds a bias: the carried conv
    without a tail."""
    from code2vec_tpu.models import hybrid_lm
    assert not hasattr(hybrid_lm, "causal_conv1d")
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (2, 9, 6))
    w = jax.random.normal(keys[1], (6, 4))
    bias = jax.random.normal(keys[2], (6,))
    got, _ = delta_rule.conv_carried(x, w, bias=bias)
    want = jax.lax.conv_general_dilated(
        x, w.T[:, None, :], (1,), [(3, 0)], feature_group_count=6,
        dimension_numbers=("NWC", "WIO", "NWC")) + bias
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# ---------------------------------------------------------------- the model

@pytest.fixture(scope="module")
def model():
    cfg = lm.LMConfig.from_dict(TINY)
    return cfg, lm_common.init_leaves(cfg, lm.leaf_specs(cfg), 3)


_REF = jax.jit(ref.logits, static_argnums=(0,),
               static_argnames=("fault", "starts", "page"))


def _reference(cfg, params, ids, fault=None, starts=(), other=None, page=1):
    """`ref.logits`, one compiled program a length (run op by op it
    compiles every op of every length apart)."""
    return _REF(cfg, params, jnp.asarray(ids), fault=fault,
                starts=tuple(int(s) for s in starts), other=other, page=page)


_SCORE = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))
_EXTEND = jax.jit(lm.ctx_extend_step, static_argnums=(0, 1, 2))


def _gap(out, row, want):
    got = np.asarray(out.topk_values[row])
    at = np.asarray(out.topk_indices[row])
    return max(float(np.abs(np.asarray(want)[at] - got).max()),
               float(np.max(want) - np.asarray(want)[at[0]]))


def _rows(cfg, params, cache, rows, width, extend=True):
    """One step on `rows` = [(slot, tokens held, page list, new
    tokens)]. -> (cache, outputs)."""
    n = len(rows)
    ids = np.zeros((n, width), np.int32)
    pages = np.zeros((n, LIST), np.int32)
    for r, (_, _, listed, new) in enumerate(rows):
        ids[r, :len(new)] = new
        pages[r, :len(listed)] = listed
    lengths = np.array([len(r[3]) for r in rows], np.int32)
    slot = np.array([r[0] for r in rows], np.int32)
    held = np.array([r[1] for r in rows], np.int32)
    if extend:
        return _EXTEND(cfg, 5, 32, params, cache, ids, lengths, slot, held,
                       pages)
    return cache, _SCORE(cfg, 5, 32, params, ids, lengths, cache, slot,
                         held, pages)


def _register(cfg, params, cache, slot, listed, tokens):
    for start in range(0, len(tokens), P):
        cache, _ = _rows(cfg, params, cache,
                         [(slot, start, listed, tokens[start:start + P])], P)
    return cache


def test_the_configuration_reads_the_published_keys(model):
    cfg, params = model
    assert cfg.pattern == "G K K K" and cfg.kinds == ("G", "K", "K", "K")
    assert (cfg.state_layers, cfg.full_layers) == (3, 1)
    assert (cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.short_conv_kernel_size) == (4, 16, 4)
    assert cfg.cache_width == 64 and cfg.conv_channels == 192
    assert lm.CACHE_KIND == "state+pages"
    names = [leaf.name for leaf in lm.leaf_specs(cfg)]
    assert "layers.00.w_attn_gate" in names
    assert "layers.01.conv_w" in names and "layers.01.a_log" in names
    assert "layers.00.conv_w" not in names
    cache = lm.init_cache(cfg, 3, 12, P)
    assert cache[0].shape == (12, 64, P) and cache[0].dtype == jnp.bfloat16
    assert cache[1][0].shape == (4, 4, 16, 16)          # a spare state
    assert cache[1][1].shape == (4, 3, 192)
    assert cache[1][1].dtype == jnp.bfloat16


@pytest.mark.parametrize("key, value, match", [
    ("use_rope", True, "use_rope"),
    ("kda_use_full_proj", True, "kda_use_full_proj"),
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("linear_attn_config", dict(TINY["linear_attn_config"], num_kv_heads=2),
     "num_kv_heads"),
])
def test_what_the_module_does_not_run_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        lm.LMConfig.from_dict(dict(TINY, **{key: value}))


def test_the_model_is_the_reference(model):
    """Two rows of one step, one padded, no cache: each the reference's
    one forward."""
    cfg, params = model
    long, short = _tokens(1, 40), _tokens(2, 7)
    ids = np.zeros((2, 48), np.int32)
    ids[0, :40], ids[1, :7] = long, short
    out = _SCORE(cfg, 5, 32, params, ids, np.array([40, 7], np.int32))
    for row, sequence in enumerate((long, short)):
        want, chosen = _reference(cfg, params, sequence)
        assert _gap(out, row, want) < TOLERANCE
        assert chosen.shape == (4, len(sequence), 2)
        assert sorted(np.asarray(out.stats.chosen_last[row, 0])) == sorted(
            np.asarray(chosen[0, -1]))
    assert int(out.stats.real_tokens) == 47


@pytest.mark.parametrize("registered", [16, 13])
def test_a_session_grows_by_kept_turns_that_cross_page_boundaries(
        model, registered):
    """A session registered in chunks that do (16) and do not (13) fill
    its last page, then three kept turns that cross 0, 1 and 2 page
    boundaries, each written from where the session stands: every answer
    is the reference's one forward over everything so far."""
    cfg, params = model
    cache = lm.init_cache(cfg, 3, 12, P)
    listed = [5, 2, 7, 1, 9, 3, 11, 0]
    session = list(_tokens(registered, registered))
    cache = _register(cfg, params, cache, 1, listed, np.array(session))
    crossings = []
    for n in (2 if registered == 13 else 3, 9, 12):
        turn = _tokens(40 + n, n)
        held = len(session)
        crossings.append((held + n - 1) // P - (held - 1) // P)
        cache, out = _rows(cfg, params, cache, [(1, held, listed, turn)], 24)
        session += list(turn)
        want, _ = _reference(cfg, params, np.array(session))
        assert _gap(out, 0, want) < TOLERANCE, (n, held)
    # (a turn behind a FULL last page starts the next page)
    assert crossings == ([0, 1, 2] if registered == 13 else [1, 1, 1])
    # a plain question behind all of it reads what the turns left
    question = _tokens(77, 6)
    _, out = _rows(cfg, params, cache, [(1, len(session), listed, question)],
                   8, extend=False)
    want, _ = _reference(cfg, params, np.array(session + list(question)))
    assert _gap(out, 0, want) < TOLERANCE


def test_two_kept_rows_of_one_step_are_each_that_row_alone(model):
    """Two sessions of different lengths extended in ONE step: each
    session's arrays and answer are what the row alone gives; the other
    slots, the spare zero state and the pages of neither are as they
    were."""
    cfg, params = model
    a, b = _tokens(5, 19), _tokens(6, 8)
    pages_a, pages_b = [0, 1, 2, 3], [6, 7, 8]
    start = lm.init_cache(cfg, 3, 12, P)
    start = _register(cfg, params, start, 0, pages_a, a)
    start = _register(cfg, params, start, 2, pages_b, b)
    turn_a, turn_b = _tokens(7, 11), _tokens(8, 5)
    both, out = _rows(cfg, params, start, [(0, 19, pages_a, turn_a),
                                           (2, 8, pages_b, turn_b)], 16)
    only_a, out_a = _rows(cfg, params, start, [(0, 19, pages_a, turn_a)], 16)
    only_b, out_b = _rows(cfg, params, start, [(2, 8, pages_b, turn_b)], 16)
    np.testing.assert_allclose(out.topk_values[0], out_a.topk_values[0],
                               atol=2e-3)
    np.testing.assert_allclose(out.topk_values[1], out_b.topk_values[0],
                               atol=2e-3)
    for layer in (1, 2, 3):
        for arrays in zip(both[layer], only_a[layer], only_b[layer],
                          start[layer]):
            got, one, other, was = (np.asarray(x, np.float32)
                                    for x in arrays)
            np.testing.assert_allclose(got[0], one[0], atol=2e-4)
            np.testing.assert_allclose(got[2], other[2], atol=2e-4)
            assert (got[1] == was[1]).all() and (got[3] == 0).all()
    pool, was = (np.asarray(x[0], np.float32) for x in (both, start))
    untouched = [4, 5, 9, 10, 11]
    assert (pool[untouched] == was[untouched]).all()
    assert (pool[[0, 1]] == was[[0, 1]]).all()      # full before the turn
    want, _ = _reference(cfg, params, np.concatenate([b, turn_b]))
    assert _gap(out, 1, want) < TOLERANCE


def test_a_row_of_no_token_writes_back_what_it_read(model):
    cfg, params = model
    cache = lm.init_cache(cfg, 3, 12, P)
    cache = _register(cfg, params, cache, 1, [4, 5], _tokens(9, 11))
    after, _ = _rows(cfg, params, cache, [(1, 11, [4, 5], [])], 8)
    for was, now in zip(jax.tree.leaves(cache), jax.tree.leaves(after)):
        assert bool((was == now).all())


def test_the_eight_shares_expert_sums_add_up_to_the_uncut_layer(model):
    """The guide's SHARE test: one expert layer of the uncut model (all
    8 experts held) against the sum over shares of 4 experts each, the
    shared expert counted once."""
    from code2vec_tpu.models.window_moe_lm import expert_block
    from code2vec_tpu.ops import moe
    whole = lm.LMConfig.from_dict(dict(TINY, experts_held=8))
    p = lm_common.layer_params(
        lm_common.init_leaves(whole, lm.leaf_specs(whole), 3), 1)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 64))
    real = jnp.ones((2, 8), bool)
    want, stats, _ = expert_block(whole, p, u, real)
    shared = moe.gated_mlp(u.astype(jnp.bfloat16), p["shared_gate"],
                           p["shared_up"], p["shared_down"])
    total = 0.0
    served = 0
    for first in (0, 4):
        share = lm.LMConfig.from_dict(dict(TINY, experts_held=4,
                                           expert_first=first))
        mine = dict(p, **{w: p[w][first:first + 4]
                          for w in ("w_gate", "w_up", "w_down")})
        got, part, _ = expert_block(share, mine, u, real)
        total = total + (got - shared)
        served += int(part.load.sum())
    assert served == int(stats.load.sum()) == 2 * 8 * 2
    np.testing.assert_allclose(total + shared, want, atol=2e-3)
    # and the uncut layer is the reference's
    r = u.reshape(16, 64)
    plain, _ = ref.experts(whole, p, r)
    np.testing.assert_allclose(want.reshape(16, 64), plain, atol=5e-3)


# ------------------------------------------------------------ the faults

SESSION = dict(registered=21, turns=(9, 12, 7))     # a session and its turns


def _session():
    tokens = list(_tokens(100, SESSION["registered"]))
    starts = []
    for j, n in enumerate(SESSION["turns"]):
        starts.append(len(tokens))
        tokens += list(_tokens(101 + j, n))
    return np.array(tokens), starts


def _served_last_turn(cfg, params):
    """The program's answer to the session's LAST turn, the turns before
    it kept."""
    tokens, starts = _session()
    cache = lm.init_cache(cfg, 3, 12, P)
    listed = [3, 8, 1, 6, 10, 2, 7]
    cache = _register(cfg, params, cache, 2, listed, tokens[:starts[0]])
    for s, e in zip(starts, starts[1:] + [len(tokens)]):
        cache, out = _rows(cfg, params, cache, [(2, s, listed, tokens[s:e])],
                           16)
    return out


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_fault_fails_the_small_comparison(model, fault):
    """The program's sound answer against the reference computed WITH
    the fault: past the tolerance that the sound reference stays under."""
    cfg, params = model
    tokens, starts = _session()
    out = _served_last_turn(cfg, params)
    sound, _ = _reference(cfg, params, tokens)
    assert _gap(out, 0, sound) < TOLERANCE
    faulty, _ = _reference(cfg, params, tokens, fault=fault, starts=starts,
                           other=_tokens(200, 30), page=P)
    assert _gap(out, 0, faulty) > 2 * TOLERANCE, fault


def test_the_cache_faults_are_what_a_wrong_program_would_serve(model):
    """The reference's model of two cache faults against the PROGRAM
    made to commit them: a turn whose writes were dropped
    (`state_not_written`: the K layers' arrays put back as they were,
    the pages kept) and conv inputs zeroed before the last turn."""
    cfg, params = model
    tokens, starts = _session()
    listed = [3, 8, 1, 6, 10, 2, 7]
    cache = lm.init_cache(cfg, 3, 12, P)
    cache = _register(cfg, params, cache, 2, listed, tokens[:starts[0]])
    for s, e in zip(starts[:1], starts[1:2]):
        cache, _ = _rows(cfg, params, cache, [(2, s, listed, tokens[s:e])],
                         16)
    kept, _ = _rows(cfg, params, cache,
                    [(2, starts[1], listed, tokens[starts[1]:starts[2]])],
                    16)
    # the turn before the last scored, its pages written, its state not
    dropped = (kept[0],) + tuple(cache[1:])
    _, out = _rows(cfg, params, dropped,
                   [(2, starts[2], listed, tokens[starts[2]:])], 16)
    want, _ = _reference(cfg, params, tokens, fault="state_not_written",
                         starts=starts, page=P)
    assert _gap(out, 0, want) < TOLERANCE
    assert _gap(out, 0, _reference(cfg, params, tokens)[0]) > 2 * TOLERANCE


# ------------------------------------------------------------------ the book

def test_the_book_keeps_a_state_and_pages_a_context_and_lets_it_grow():
    from code2vec_tpu import obs
    book = ContextSlots(2, 64, fixed_size=True, pages=10, page_tokens=P)
    read = lambda name: [m.value for m in  # noqa: E731
                         obs.default_registry().collect()[name].values()]
    slot, pages, gone = book.acquire_pages(13)
    assert gone == [] and len(pages) == 2
    book.commit(slot, "a", 13, pages)
    assert read("latent_cache_slots_held") == [1]
    assert read("latent_cache_fill_ratio") == [0.5]     # slots over slots
    assert read("page_pool_pages_held") == [2]
    assert read("page_pool_fill_ratio") == [0.2]        # pages over pages
    # three more tokens fit the last page: none taken
    held, taken = book.extend_pages("a", 3)
    assert held == HeldPages(slot, 13, pages) and taken == ()
    assert book.replace("a", "a1", 16, pages + taken)
    assert book.lookup("a") is None
    assert book.lookup("a1") == HeldPages(slot, 16, pages)
    # twenty more cross three boundaries
    held, taken = book.extend_pages("a1", 20)
    assert len(taken) == 3 and not set(taken) & set(pages)
    assert read("page_pool_pages_held") == [5]
    book.release(None, taken)                   # the step failed
    assert read("page_pool_pages_held") == [2]
    assert book.lookup("a1") == HeldPages(slot, 16, pages)
    # the pool: 8 free pages hold 64 more tokens, not 65; nothing is
    # evicted for a turn and nothing was taken
    other, more, _ = book.acquire_pages(40)
    book.commit(other, "b", 40, more)
    with pytest.raises(PoolTooSmall, match=r"\(pool\)"):
        book.extend_pages("a1", 30)
    assert read("page_pool_pages_held") == [7]
    assert set(book.held()) == {"a1", "b"}
    with pytest.raises(TooLong, match=r"\(positions\)"):
        book.extend_pages("b", 25)
    assert book.extend_pages("nobody", 1) is None
    # a context that went between the lookup and the commit
    held, taken = book.extend_pages("a1", 9)
    assert len(taken) == 2
    slot_c, pages_c, gone = book.acquire_pages(30)      # evicts "b" (LRU)
    assert gone == ["b"]
    book.release(slot_c, pages_c)
    assert book.replace("a1", "a2", 25, held.pages + taken)
    assert not book.replace("a1", "a3", 30, ())
    assert read("latent_cache_slots_held") == [1]
    # eviction frees the slot and the pages whole
    slot_d, pages_d, gone = book.acquire_pages(64)
    assert gone == ["a2"] and len(pages_d) == 8 and book.held() == {}


def test_a_grown_contexts_id_is_a_hash_of_the_old_id_and_the_tokens():
    import hashlib
    ids = np.array([3, 1, 4, 1, 5], np.int32)
    first = context_id(ids)
    turn = np.array([9, 2, 6], np.int64)
    want = hashlib.sha256(first.encode("ascii") + np.array(
        [9, 2, 6], "<i4").tobytes()).hexdigest()[:16]
    assert extended_id(first, turn) == want != first
    assert extended_id(want, turn) != want


# ------------------------------------------------------------------- served

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process PredictionServer over the tiny model, built as
    `code2vec.py serve --model_config ... --load ...` builds it: three
    state slots and ten pages."""
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    from code2vec_tpu.serving.server import PredictionServer
    lm.CHUNK = P        # module-scoped: before the function's monkeypatch
    work = tmp_path_factory.mktemp("solar")
    model_config = str(work / "tiny.json")
    with open(model_config, "w") as f:
        json.dump(dict(TINY, serve={
            "length_buckets": [16], "context_cache": {
                "slots": 3, "pages": 10, "tokens_per_slot": 64,
                "register_chunk": P}}), f)
    common = ["--model_config", model_config, "--serve_token_budget", "32",
              "--seed", "5"]
    first = ScoringModel(config_from_args(
        common + ["--save", str(work / "ck" / "saved")]))
    saved = first.save()
    config = config_from_args(["serve", "--load", saved] + common)
    model = ScoringModel(config)
    model.warmup()
    server = PredictionServer(model, config)
    yield server, model
    server.drain(timeout=5.0)
    lm.CHUNK = 64


def _post(server, endpoint, body):
    status, raw, _ = server.handle_request(endpoint, json.dumps(body),
                                           params=body)
    return status, json.loads(raw)


def _answers(model, answer, sequence):
    logits = np.asarray(_reference(model.lm, model.params,
                                   np.asarray(sequence))[0])
    top = [t["id"] for t in answer["top"]]
    return max([logits.max() - logits[top[0]]]
               + [abs(logits[t["id"]] - t["logit"]) for t in answer["top"]])


def _counter(name):
    from code2vec_tpu import obs
    return sum(m.value for m in
               obs.default_registry().collect()[name].values())


def test_a_session_is_registered_grown_scored_and_renamed(served):
    server, model = served
    assert server.endpoints == ("score", "contexts")
    assert model.slot.fixed_size and model.slot.pages and model.extends
    assert model.list_pages == 8 and model.contexts.pages == 10
    # (1, 16) (2, 16) (1, 32), scoring and extending, and the (1, 8)
    # registration shape
    assert model.predict_compile_count() == 2 * len(model.shapes()) + 1 == 7
    # the slot is three layers' states and conv inputs; no page in it
    assert model.slot_bytes == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    session = list(_tokens(1, 13))
    status, got = _post(server, "contexts", {"ids": [int(t) for t in session]})
    assert status == 200 and got["tokens"] == 13 and not got["held"]
    name = got["context"]
    assert name == context_id(np.array(session))
    was = {n: _counter(n) for n in (
        "context_extend_turns_total", "context_extend_tokens_total",
        "context_extend_pages_appended_total", "score_states_read_total",
        "score_state_bytes_read_total", "score_full_keys_read_total",
        "score_pages_needed_total")}
    compiled = model.predict_compile_count()
    for n in (3, 9, 20):
        turn = _tokens(20 + n, n)
        status, got = _post(server, "score", {
            "context": name, "ids": [int(t) for t in turn], "top_k": 5,
            "keep": True})
        assert status == 200, got
        assert (got["tokens"], got["context_tokens"]) == (n, len(session))
        assert got["context"] == extended_id(name, turn)
        session += list(turn)
        assert _answers(model, got, session) < TOLERANCE
        # the old id is gone the moment the turn is committed
        status, old = _post(server, "score", {
            "context": name, "ids": [1, 2], "top_k": 5})
        assert status == 404 and "unknown or evicted" in old["error"]
        name = got["context"]
    assert model.contexts.lookup(name) == HeldPages(0, 45, (0, 1, 2, 3, 4, 5))
    assert model.predict_compile_count() == compiled
    grew = {n: _counter(n) - v for n, v in was.items()}
    assert grew["context_extend_turns_total"] == 3
    assert grew["context_extend_tokens_total"] == 32
    assert grew["context_extend_pages_appended_total"] == 4    # 2 -> 6
    assert grew["score_states_read_total"] == 3 * 3
    assert grew["score_state_bytes_read_total"] == 3 * model.slot_bytes
    assert grew["score_full_keys_read_total"] == 13 + 16 + 25
    assert grew["score_pages_needed_total"] == 2 + 2 + 4
    # a plain /score between turns leaves every cache array bit-identical
    before = [np.asarray(a).copy() for a in jax.tree.leaves(model.cache)]
    question = _tokens(90, 7)
    status, got = _post(server, "score", {
        "context": name, "ids": [int(t) for t in question], "top_k": 5})
    assert status == 200 and "context" not in got
    assert _answers(model, got, session + list(question)) < TOLERANCE
    for a, b in zip(before, jax.tree.leaves(model.cache)):
        assert (a == np.asarray(b)).all()
    assert model.contexts.lookup(name).tokens == 45
    # a turn past the longest session admitted: which limit, and nothing
    # moved
    status, got = _post(server, "score", {
        "context": name, "ids": [int(t) for t in _tokens(5, 32)],
        "top_k": 5, "keep": True})
    assert status == 409 and "(positions)" in got["error"]
    assert model.contexts.lookup(name).tokens == 45
    for a, b in zip(before, jax.tree.leaves(model.cache)):
        assert (a == np.asarray(b)).all()


def test_a_turn_the_pool_cannot_hold_is_refused_and_nothing_moves(served):
    server, model = served
    model.contexts = model.contexts.fresh()
    names = []
    for seed, n in ((11, 30), (12, 30)):
        status, got = _post(server, "contexts", {
            "ids": [int(t) for t in _tokens(seed, n)]})
        assert status == 200 and got["evicted"] is None
        names.append(got["context"])
    # 8 of 10 pages held; a turn of 12 on a session of 30 needs 2 more,
    # one of 20 three
    book = dict(model.contexts.held())
    before = [np.asarray(a).copy() for a in jax.tree.leaves(model.cache)]
    status, got = _post(server, "score", {
        "context": names[0], "ids": [int(t) for t in _tokens(13, 20)],
        "top_k": 5, "keep": True})
    assert status == 409 and "(pool)" in got["error"], got
    assert dict(model.contexts.held()) == book
    for a, b in zip(before, jax.tree.leaves(model.cache)):
        assert (a == np.asarray(b)).all()
    status, got = _post(server, "score", {
        "context": names[0], "ids": [int(t) for t in _tokens(13, 12)],
        "top_k": 5, "keep": True})
    assert status == 200 and len(model.contexts.lookup(
        got["context"]).pages) == 6
    # a third session evicts the least recently used WHOLE: slot and pages
    status, got = _post(server, "contexts", {
        "ids": [int(t) for t in _tokens(14, 30)]})
    assert status == 200 and got["evicted_contexts"] == [names[1]]
    assert model.contexts.lookup(names[1]) is None


def test_two_kept_rows_on_one_session_are_never_one_step(served):
    """Two turns that name ONE id, handed over together: two steps, in
    arrival order; the first extends the session, the second finds the
    id it named gone (a client chains its turns by the ids the answers
    carry). Two turns on TWO sessions ride one step."""
    from code2vec_tpu import lm_facade
    server, model = served
    model.contexts = model.contexts.fresh()
    a, b = _tokens(21, 10), _tokens(22, 12)
    names = [_post(server, "contexts", {"ids": [int(t) for t in s]})[1][
        "context"] for s in (a, b)]
    waited = _counter("context_extend_waited_total")
    steps = _counter("context_extend_turns_total")
    rows = lambda: sum(  # noqa: E731
        m.count for m in __import__("code2vec_tpu").obs.default_registry(
        ).collect()["serving_batch_tokens_fill_ratio"].values())
    calls = rows()
    first, second = _tokens(23, 5), _tokens(24, 6)
    results = model.score_batch([
        lm_facade.ScoreRequest(first, 5, names[0], True),
        lm_facade.ScoreRequest(second, 5, names[0], True)])
    assert rows() - calls == 2
    assert _counter("context_extend_waited_total") - waited == 1
    assert results[0].kept_as == extended_id(names[0], first)
    assert results[0].unknown_context is None
    assert results[1].unknown_context == names[0]
    assert results[1].kept_as is None
    assert model.contexts.lookup(results[0].kept_as).tokens == 15
    calls = rows()
    results = model.score_batch([
        lm_facade.ScoreRequest(second, 5, results[0].kept_as, True),
        lm_facade.ScoreRequest(first, 5, names[1], True)])
    assert rows() - calls == 1
    assert _counter("context_extend_turns_total") - steps == 3
    sequences = (list(a) + list(first) + list(second), list(b) + list(first))
    for r, sequence in zip(results, sequences):
        logits = np.asarray(_reference(model.lm, model.params,
                                       np.asarray(sequence))[0])
        assert np.abs(logits[r.token_ids] - r.logits).max() < TOLERANCE
    # a kept and a plain request never share a step
    calls = rows()
    model.score_batch([
        lm_facade.ScoreRequest(first, 5, results[1].kept_as, True),
        lm_facade.ScoreRequest(first, 5, results[1].kept_as, False)])
    assert rows() - calls == 2


def test_a_kept_turn_is_never_answered_from_the_cache_of_answers(served):
    server, model = served
    model.contexts = model.contexts.fresh()
    session = _tokens(31, 9)
    name = _post(server, "contexts",
                 {"ids": [int(t) for t in session]})[1]["context"]
    body = {"context": name, "ids": [4, 5, 6], "top_k": 5}
    assert _post(server, "score", body)[0] == 200
    hits = _counter("serving_cache_hits_total")
    assert _post(server, "score", body)[0] == 200       # the same answer
    assert _counter("serving_cache_hits_total") == hits + 1
    status, got = _post(server, "score", dict(body, keep=True))
    assert status == 200 and got["context"] != name
    # the same body again names an id that is gone: never a cached 200
    status, again = _post(server, "score", dict(body, keep=True))
    assert status == 404
    assert _counter("serving_cache_hits_total") == hits + 1


def test_keep_is_refused_where_a_module_cannot_extend(served):
    """The five other token modules have no `ctx_extend_step`: `keep` is
    a 400 that says so, and a `keep` with no context names the gap."""
    import types
    from code2vec_tpu import lm_facade
    server, model = served
    other = types.SimpleNamespace(
        _token_ids=model._token_ids, token_budget=32, top_k=5,
        extends=False, contexts=model.contexts, model_name="window_moe_lm")
    with pytest.raises(ValueError, match="window_moe_lm cannot extend"):
        lm_facade.ScoringModel.validate(other, [1, 2], 5, "x", keep=True)
    for module in lm_facade.MODEL_MODULES.values():
        assert hasattr(module, "ctx_extend_step") == (module is lm)
    status, got = _post(server, "score", {"ids": [1, 2], "top_k": 5,
                                          "keep": True})
    assert status == 400 and "name it" in got["error"]


def test_the_cache_kinds_say_what_a_slot_is_in_one_place():
    from code2vec_tpu import lm_facade
    assert lm_facade.cache_kind(lm) == "state+pages"
    kinds = lm_facade.CACHE_KINDS
    assert list(kinds) == ["tokens", "state", "paged", "state+pages"]
    assert [(k.fixed_size, k.pages) for k in kinds.values()] == [
        (False, False), (True, False), (False, True), (True, True)]
    assert "positions" in kinds["state+pages"].longest


def test_the_new_series_are_registered_and_the_turn_has_a_span(served):
    from code2vec_tpu import obs
    server, model = served
    registry = obs.default_registry().collect()
    for name in ("context_extend_turns_total", "context_extend_tokens_total",
                 "context_extend_pages_appended_total",
                 "context_extend_waited_total", "context_extend_seconds",
                 "score_states_read_total", "score_state_bytes_read_total",
                 "latent_cache_slots_held", "page_pool_pages_held",
                 "page_pool_fill_ratio"):
        assert name in registry, name
    spans = registry["context_extend_seconds"]
    assert sum(m.count for m in spans.values()) > 0
