"""The window / full attention expert model at tiny widths on the CPU
(hidden 64, window = page = chunk 8, 8 experts top-2, two dense and six
expert layers `w w w f w w w f`): the two attention ops against a masked
plain softmax, the model against the plain reference's one full forward,
contexts registered chunk by chunk into a ring and pages, rows of one
step on contexts of different lengths, the book over both geometries,
and through `ScoringModel` and an in-process `PredictionServer`; and
every fault the benchmark's comparison has to catch, here in small."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import lm_common
from code2vec_tpu.models import window_moe_lm as lm
from code2vec_tpu.models import window_moe_lm_reference as ref
from code2vec_tpu.ops import window_attn
from code2vec_tpu.serving.context_cache import (
    ContextSlots, HeldPages, PoolTooSmall,
)

W = 8           # the window, a page and a registration chunk
LIST = 6        # pages a context may hold: contexts of up to 48 tokens
TINY = dict(
    model_type="afmoe", hidden_size=64, num_hidden_layers=16, layers=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 4,
    sliding_window=W, global_attn_every_n_layers=4, num_dense_layers=2,
    vocab_size=256, vocab_rows=96, max_position_embeddings=512,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000, rope_scaling=None, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, route_norm=True, route_scale=2.826,
    score_func="sigmoid", n_group=1, topk_group=1, mup_enabled=True,
    tie_word_embeddings=False, rms_norm_eps=1e-5)
# What runs through the FACADE holds four experts: it counts the experts
# hit on process-wide series, and tests/benchmark/test_benchmark_lm.py
# reads those whole and holds them to its own toy's four.
SERVED = dict(TINY, num_experts=4)
# Logits reach 0.5 at these widths. The program rounds matmul operands,
# activations and the cached keys and values to bfloat16 (8 bits) where
# the reference keeps float32: the widest difference seen over these
# tests' sequences is 0.016 (one sequence in a dozen reads 0.09: an
# expert chosen otherwise on a near-tie, which the post-norm carries at
# full size; the tests' sequences are ones without), and every fault
# below reads 0.1 or more on its sequence.
TOLERANCE = 0.035


# ------------------------------------------------------------------ the ops

def _qkv(seed, rows, n, hq=4, hkv=2, d=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bf16 = jnp.bfloat16
    return (jax.random.normal(keys[0], (rows, n, hq, d)).astype(bf16),
            jax.random.normal(keys[1], (rows, n, hkv, d)).astype(bf16),
            jax.random.normal(keys[2], (rows, n, hkv, d)).astype(bf16))


def _plain(q, k, v, window):
    """One sequence (n, heads, d): the whole masked softmax, float32."""
    n, hq, d = q.shape
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = (jnp.repeat(t, hq // k.shape[1], axis=1) for t in (k, v))
    at = jnp.arange(n)
    seen = at[:, None] >= at[None, :]
    if window:
        seen = seen & (at[:, None] - at[None, :] < window)
    s = jnp.einsum("qhd,khd->hqk", q, k) / (d ** 0.5)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(n, hq * d)


def _ring_of(k, v, held):
    """What a ring slot holds after `held` tokens: position p in row p
    mod W, the later of two positions that share a row."""
    ring = np.zeros((W, 2 * k.shape[1] * k.shape[2]), np.float32)
    for p in range(held):
        ring[p % W] = np.concatenate([np.asarray(k[p], np.float32).ravel(),
                                      np.asarray(v[p], np.float32).ravel()])
    return ring


def _paged(k, v, held, pages, pool_pages=7):
    pool = np.zeros((pool_pages, W, 2 * k.shape[1] * k.shape[2]), np.float32)
    for p in range(held):
        pool[pages[p // W], p % W] = np.concatenate(
            [np.asarray(k[p], np.float32).ravel(),
             np.asarray(v[p], np.float32).ravel()])
    return pool


CONTEXTS = {"shorter_than_the_window": 5, "the_window": 8,
            "a_token_past_it": 9, "several_windows": 29}


def _window_case(held, own=6, padded=8):
    q, k, v = _qkv(held, 1, held + own)
    rings = np.zeros((3, 64, W), np.float32)
    rings[2] = _ring_of(k[0], v[0], held).T
    pad = ((0, 0), (0, padded - own), (0, 0), (0, 0))
    got = window_attn.window_attend(
        jnp.pad(q[:, held:], pad), jnp.pad(k[:, held:], pad),
        jnp.pad(v[:, held:], pad), jnp.asarray(rings, jnp.bfloat16),
        jnp.asarray([2]), jnp.asarray([held]), jnp.asarray([own]))
    return np.asarray(got[0, :own], np.float32), (q[0], k[0], v[0])


@pytest.mark.parametrize("held", CONTEXTS.values(), ids=CONTEXTS.keys())
def test_window_attend_is_the_masked_softmax(held):
    got, (q, k, v) = _window_case(held)
    want = np.asarray(_plain(q, k, v, W))[held:]
    np.testing.assert_allclose(got, want, atol=0.02)    # bfloat16 weights


@pytest.mark.parametrize("window", [7, 9])
@pytest.mark.parametrize("held", [9, 29])
def test_a_window_off_by_one_key_reads(held, window):
    got, (q, k, v) = _window_case(held)
    wrong = np.asarray(_plain(q, k, v, window))[held:]
    assert np.abs(got - wrong).max() > 0.1


@pytest.mark.parametrize("held", CONTEXTS.values(), ids=CONTEXTS.keys())
def test_full_attend_is_the_causal_softmax_whatever_the_pages(held):
    own, padded = 6, 8
    q, k, v = _qkv(100 + held, 1, held + own)
    pages = [5, 2, 6, 0][:-(-held // W)]
    pool = jnp.asarray(_paged(k[0], v[0], held, pages).transpose(0, 2, 1),
                       jnp.bfloat16)
    pad = ((0, 0), (0, padded - own), (0, 0), (0, 0))
    lists = np.zeros((1, LIST), np.int32)
    lists[0, :len(pages)] = pages
    got = window_attn.full_attend(
        jnp.pad(q[:, held:], pad), jnp.pad(k[:, held:], pad),
        jnp.pad(v[:, held:], pad), pool, jnp.asarray(lists),
        jnp.asarray([held]), jnp.asarray([own]))
    want = np.asarray(_plain(q[0], k[0], v[0], None))[held:]
    np.testing.assert_allclose(np.asarray(got[0, :own], np.float32), want,
                               atol=0.02)


def test_a_chunk_longer_than_a_block_attends_itself_in_blocks():
    """A registration chunk's own keys are folded block by block once a
    block of scores would pass `_key_step`'s bound."""
    assert window_attn._key_step(2048, 2048) == 512
    assert window_attn._key_step(2048, 256) == 2048
    assert window_attn._key_step(8, 8) == 8
    q, k, v = _qkv(7, 1, 256)
    old = window_attn._key_step
    try:
        window_attn._key_step = lambda keys, queries, most=0: min(keys, 64)
        got = window_attn.full_attend(
            q, k, v, jnp.zeros((2, 64, 64), jnp.bfloat16),
            jnp.zeros((1, LIST), jnp.int32), jnp.asarray([0]),
            jnp.asarray([256]))
    finally:
        window_attn._key_step = old
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(_plain(q[0], k[0], v[0], None)),
                               atol=0.02)


# ---------------------------------------------------------------- the model

@pytest.fixture(scope="module")
def cfg():
    return lm.LMConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return lm_common.init_leaves(cfg, lm.leaf_specs(cfg), 3)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


_REGISTER = jax.jit(lm.ctx_register_step, static_argnums=(0,))
_SCORE = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))


def _page_list(pages):
    out = np.zeros((LIST,), np.int32)
    out[:len(pages)] = pages
    return out


def _register(cfg, params, cache, tokens, slot, pages):
    for start in range(0, len(tokens), W):
        part = np.zeros((W,), np.int32)
        real = min(W, len(tokens) - start)
        part[:real] = tokens[start:start + real]
        cache = _REGISTER(cfg, params, cache, jnp.asarray(part),
                          jnp.int32(real), jnp.int32(slot), jnp.int32(start),
                          jnp.asarray(_page_list(pages)))
    return cache


def _score(cfg, params, cache, questions, slots, held, lists, length=16):
    rows = len(questions)
    ids = np.zeros((rows, length), np.int32)
    for i, q in enumerate(questions):
        ids[i, :len(q)] = q
    out = _SCORE(cfg, 96, 32, params, jnp.asarray(ids),
                 jnp.asarray([len(q) for q in questions], jnp.int32), cache,
                 jnp.asarray(slots, jnp.int32), jnp.asarray(held, jnp.int32),
                 jnp.asarray(np.stack([_page_list(p) for p in lists])))
    logits = np.zeros((rows, 96), np.float32)
    np.put_along_axis(logits, np.asarray(out.topk_indices),
                      np.asarray(out.topk_values), axis=1)
    return logits, out


# contexts that do and do not fill their last page, shorter than the
# window, the window, and five pages; each with the pages it lies in
HELD = {"a": (_tokens(11, 5), 0, [3]),
        "b": (_tokens(12, 8), 1, [7]),
        "c": (_tokens(13, 21), 2, [9, 0, 4]),
        "d": (_tokens(14, 40), 3, [1, 11, 5, 2, 8])}


@pytest.fixture(scope="module")
def cache(cfg, params):
    held = lm.init_cache(cfg, 5, 12, W)
    for tokens, slot, pages in HELD.values():
        held = _register(cfg, params, held, tokens, slot, pages)
    return held


def _gap(cfg, params, logits, sequence, **how):
    want = np.asarray(ref.logits(cfg, params, sequence, **how)[0])
    return float(np.abs(logits - want).max())


def test_the_pattern_and_the_parameter_count(cfg, params):
    assert cfg.pattern == "wD wD wE fE wE wE wE fE"
    attention = 64 * 64 * 3 + 2 * 64 * 32 + 2 * 16 + 4 * 64
    dense = attention + 3 * 64 * 96
    expert = attention + 64 * 8 + 8 + 3 * 64 * 32 * (8 + 1)
    assert lm_common.count_leaves(lm.leaf_specs(cfg)) == (
        2 * dense + 6 * expert + 2 * 96 * 64 + 64)
    assert [a.shape for a in lm.init_cache(cfg, 5, 12, W)] == [
        (12 if kind == "f" else 5, 64, W) for kind, _ in cfg.kinds]
    assert float(jnp.abs(params["layers.02.router_bias"]).max()) > 0


def test_the_model_without_a_context_is_the_reference(cfg, params, cache):
    question = _tokens(20, 13)
    logits, out = _score(cfg, params, cache, [question, []], [4, 0],
                         [0, 0], [[], []])
    assert _gap(cfg, params, logits[0], question) < TOLERANCE
    want = np.asarray(ref.logits(cfg, params, question)[1])[:, -1]
    same = np.mean([set(a) == set(b) for a, b in
                    zip(np.asarray(out.stats.chosen_last[0]), want)])
    assert same >= 0.5      # near-ties flip under bfloat16


@pytest.mark.parametrize("name", sorted(HELD))
def test_a_registered_context_then_a_question_is_the_full_forward(
        cfg, params, cache, name):
    tokens, slot, pages = HELD[name]
    question = _tokens(30, 11)
    logits, _ = _score(cfg, params, cache, [question], [slot],
                       [len(tokens)], [pages])
    sequence = np.concatenate([tokens, question])
    assert _gap(cfg, params, logits[0], sequence) < TOLERANCE
    # and the window is the configuration's, to the key
    if len(tokens) >= W:
        for window in (W - 1, W + 1):
            assert _gap(cfg, params, logits[0], sequence,
                        window=window) > 2 * TOLERANCE


def test_rows_of_one_step_on_one_and_five_pages_are_each_alone(
        cfg, params, cache):
    rows = [("b", _tokens(31, 9)), ("d", _tokens(32, 14))]
    args = lambda picked: (
        [q for _, q in picked], [HELD[n][1] for n, _ in picked],
        [len(HELD[n][0]) for n, _ in picked], [HELD[n][2] for n, _ in picked])
    together, _ = _score(cfg, params, cache, *args(rows))
    for i, row in enumerate(rows):
        alone, _ = _score(cfg, params, cache, *args([row]))
        np.testing.assert_allclose(together[i], alone[0], atol=0.01)
        assert _gap(cfg, params, together[i], np.concatenate(
            [HELD[row[0]][0], row[1]])) < TOLERANCE


def test_a_full_layer_knows_no_order_of_pages_but_knows_its_pages(
        cfg, params, cache):
    """No positions in a full layer: a context of whole pages reads the
    same from its list in any order; another context's page does not."""
    tokens, slot, pages = HELD["d"]
    question = _tokens(33, 10)
    ask = lambda lists: _score(cfg, params, cache, [question], [slot],
                               [len(tokens)], [lists])[0][0]
    sound = ask(pages)
    np.testing.assert_allclose(ask(pages[::-1]), sound, atol=0.01)
    foreign = HELD["c"][2] + pages[3:]
    assert np.abs(ask(foreign) - sound).max() > 2 * TOLERANCE


def test_scoring_writes_nothing(cfg, params, cache):
    before = [np.asarray(a).copy() for a in cache]
    _score(cfg, params, cache, [_tokens(34, 16)], [2], [21], [HELD["c"][2]])
    for now, was in zip(cache, before):
        np.testing.assert_array_equal(np.asarray(now), was)


def test_a_chunk_of_no_real_token_writes_nothing(cfg, params, cache):
    after = _REGISTER(cfg, params, tuple(jnp.array(a) for a in cache),
                      jnp.zeros((W,), jnp.int32), jnp.int32(0), jnp.int32(3),
                      jnp.int32(0), jnp.asarray(_page_list([1])))
    for now, was in zip(after, cache):
        np.testing.assert_array_equal(np.asarray(now), np.asarray(was))


# --------------------------------------------------------------- the faults

def _ring_without_wrap(cfg, params, cache, name):
    """The cache with `name`'s ring slot holding its FIRST window of
    tokens: what a ring written without `p mod W` keeps."""
    tokens, slot, pages = HELD[name]
    first = _register(cfg, params, lm.init_cache(cfg, 5, 12, W),
                      tokens[:W], slot, pages[:1])
    return tuple(was.at[slot].set(new[slot]) if kind == "w" else was
                 for (kind, _), was, new in zip(cfg.kinds, cache, first))


CACHE_FAULTS = {
    "one_token_short": lambda c, p, cache: (cache, 39, HELD["d"][2]),
    "another_contexts_pages": lambda c, p, cache: (
        cache, 40, HELD["c"][2] + HELD["a"][2] + HELD["b"][2]),
    "a_ring_that_never_wraps": lambda c, p, cache: (
        _ring_without_wrap(c, p, cache, "d"), 40, HELD["d"][2]),
}


@pytest.mark.parametrize("fault", sorted(CACHE_FAULTS))
def test_each_fault_of_the_cache_fails_the_small_comparison(
        cfg, params, cache, fault):
    tokens, slot, _ = HELD["d"]
    question = _tokens(35, 12)
    faulty, held, pages = CACHE_FAULTS[fault](cfg, params, cache)
    logits, _ = _score(cfg, params, faulty, [question], [slot], [held],
                       [pages])
    assert _gap(cfg, params, logits[0],
                np.concatenate([tokens, question])) > 2 * TOLERANCE


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_of_the_layers_fails_the_small_comparison(
        cfg, params, cache, fault):
    tokens, slot, pages = HELD["d"]
    question = _tokens(35, 12)
    logits, _ = _score(cfg, params, cache, [question], [slot],
                       [len(tokens)], [pages])
    sequence = np.concatenate([tokens, question])
    assert _gap(cfg, params, logits[0], sequence) < TOLERANCE
    assert _gap(cfg, params, logits[0], sequence,
                fault=fault) > 3 * TOLERANCE


@pytest.mark.parametrize("change,says", [
    ({"n_group": 2}, "group-limited"), ({"topk_group": 2}, "group-limited"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"score_func": "softmax"}, "score_func"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"route_norm": False}, "route_norm"),
    ({"layer_types": ["sliding_attention"] * 15}, "layer_types"),
    ({"sliding_window": None}, "no sliding_window"),
])
def test_what_the_module_does_not_run_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        lm.LMConfig.from_dict(dict(TINY, **change), "tiny.json")


def test_a_page_that_is_not_the_window_is_refused(cfg):
    with pytest.raises(ValueError, match="a page and a registration chunk"):
        lm.init_cache(cfg, 2, 4, 16)


# ----------------------------------------------------------------- the book

def test_the_book_takes_and_frees_a_ring_and_pages_together():
    from code2vec_tpu import obs
    book = ContextSlots(3, 48, pages=10, page_tokens=W)
    gauges = obs.default_registry().collect()
    read = lambda name: [m.value for m in gauges[name].values()]
    slot_a, pages_a, gone = book.acquire_pages(40)
    assert len(pages_a) == 5 and gone == []
    assert book.lookup("a") is None             # not before its commit
    book.commit(slot_a, "a", 40, pages_a)
    slot_b, pages_b, _ = book.acquire_pages(17)
    book.commit(slot_b, "b", 17, pages_b)
    assert not set(pages_a) & set(pages_b) and slot_a != slot_b
    assert book.lookup("a") == HeldPages(slot_a, 40, pages_a)   # now newest
    assert read("page_pool_pages_held") == [8]
    assert read("page_pool_fill_ratio") == [0.8]
    assert read("window_ring_slots_held") == [2]
    # 3 pages: "b", the least recently used, goes whole; "a" stays
    slot_c, pages_c, gone = book.acquire_pages(24)
    assert gone == ["b"] and book.lookup("b") is None
    assert set(pages_c) <= set(pages_b) | {8, 9} and slot_c != slot_a
    book.commit(slot_c, "c", 24, pages_c)
    # 9 pages: both go, oldest first, until it fits
    assert book.lookup("a") is not None
    slot_d, pages_d, gone = book.acquire_pages(70)
    assert gone == ["c", "a"] and len(set(pages_d)) == 9
    assert book.held() == {}
    book.release(slot_d, pages_d)               # its filling failed
    assert read("page_pool_pages_held") == [0]
    assert len(book.acquire_pages(80)[1]) == 10
    with pytest.raises(PoolTooSmall, match="11 pages"):
        book.acquire_pages(81)
    with pytest.raises(LookupError, match="being filled"):
        book.acquire_pages(8)       # the pool is taken and not committed


def test_ring_slots_run_out_before_pages_and_evict_too():
    book = ContextSlots(2, 48, pages=10, page_tokens=W)
    for name in "ab":
        slot, pages, _ = book.acquire_pages(8)
        book.commit(slot, name, 8, pages)
    slot, pages, gone = book.acquire_pages(8)
    assert gone == ["a"] and len(pages) == 1
    fresh = book.fresh()
    assert (fresh.slots, fresh.pages, fresh.page_tokens) == (2, 10, W)
    assert fresh.held() == {}


# ------------------------------------------------------------------- served

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process PredictionServer over the tiny model, built as
    `code2vec.py serve --model_config ... --load ...` builds it: three
    ring slots and eight pages, so that a long context evicts."""
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    from code2vec_tpu.serving.server import PredictionServer
    work = tmp_path_factory.mktemp("afmoe")
    model_config = str(work / "tiny.json")
    with open(model_config, "w") as f:
        json.dump(dict(SERVED, serve={
            "length_buckets": [16], "context_cache": {
                "slots": 3, "pages": 8, "tokens_per_slot": 48,
                "register_chunk": W}}), f)
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


def _post(server, endpoint, body):
    status, raw, _ = server.handle_request(endpoint, json.dumps(body),
                                           params=body)
    return status, json.loads(raw)


def _answers(model, answer, sequence):
    logits = np.asarray(ref.logits(model.lm, model.params, sequence)[0])
    top = [t["id"] for t in answer["top"]]
    return max([logits.max() - logits[top[0]]]
               + [abs(logits[t["id"]] - t["logit"]) for t in answer["top"]])


def test_contexts_of_two_geometries_are_scored_and_evicted_whole(served):
    import concurrent.futures
    from code2vec_tpu import obs
    server, model = served
    assert server.endpoints == ("score", "contexts")
    assert model.paged_cache and not model.state_cache
    assert model.list_pages == 6 and model.contexts.pages == 8
    contexts = [_tokens(41, 5), _tokens(42, 37)]
    ids = []
    for tokens in contexts:
        status, got = _post(server, "contexts", {"ids": tokens.tolist()})
        assert status == 200 and got["tokens"] == len(tokens)
        assert not got["held"] and got["evicted_contexts"] == []
        ids.append(got["context"])
    assert len(model.contexts.lookup(ids[1]).pages) == 5
    before = [np.asarray(layer).copy() for layer in model.cache]
    registry = obs.default_registry().collect()
    value = lambda name: next(iter(registry[name].values())).value
    names = ("score_window_keys_read_total", "score_full_keys_read_total",
             "score_pages_needed_total", "score_pages_visited_total")
    was = [value(n) for n in names]
    compiled = model.predict_compile_count()
    # questions of one length: the reference, run op by op, compiles
    # every operation anew for a sequence of another length
    bodies = [{"context": ids[i % 2], "ids": _tokens(50 + i, 11).tolist(),
               "top_k": 4, "return_routing": True} for i in range(6)]
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        answers = list(pool.map(lambda b: _post(server, "score", b), bodies))
    assert model.predict_compile_count() == compiled
    gaps = []
    for i, (body, (status, answer)) in enumerate(zip(bodies, answers)):
        assert status == 200, answer
        assert answer["context_tokens"] == len(contexts[i % 2])
        gaps.append(_answers(model, answer, np.concatenate(
            [contexts[i % 2], np.asarray(body["ids"], np.int32)])))
        assert np.asarray(answer["routing_last"]).shape == (6, 2)
    # all but the odd sequence whose experts flip on a near-tie (the
    # note at TOLERANCE); another context's ring or pages reads 0.2
    assert sorted(gaps)[-2] < TOLERANCE and max(gaps) < 0.15, gaps
    for layer, old in zip(model.cache, before):
        np.testing.assert_array_equal(np.asarray(layer), old)
    # three rows on 5 tokens, three on 37: six window and two full layers
    grew = [value(n) - w for n, w in zip(names, was)]
    assert grew[0] == 3 * (5 + 7) * 6 and grew[1] == 3 * (5 + 37) * 2
    assert grew[2] == 3 * (1 + 5) * 2
    assert grew[3] >= grew[2]       # == only if no step mixed the two
    assert value("page_pool_pages_held") == 6
    assert value("window_ring_slots_held") == 2
    # (eight threads posted the bodies at once, and which step ran last
    # is the scheduler's: one more lookup, alone, says which was used
    # last: the context registered FIRST, so only a lookup keeps it)
    status, _ = _post(server, "score", {
        "context": ids[0], "ids": [1, 2, 3], "top_k": 4})
    assert status == 200
    # 27 tokens need 4 pages of the 2 left: the context not used last
    # goes whole, ring slot and pages, and its 5 pages are enough (had
    # the lookup not counted, the 5-token one would have gone first, for
    # one page, and the other after it)
    third_tokens = _tokens(43, 27)
    status, third = _post(server, "contexts", {"ids": third_tokens.tolist()})
    gone = third["evicted_contexts"]
    assert status == 200 and gone == [ids[1]] and third["evicted"] == ids[1]
    status, answer = _post(server, "score", {
        "context": gone[0], "ids": [1, 2, 3], "top_k": 4})
    assert status == 404 and "evicted" in answer["error"]
    question = _tokens(60, 15)       # 27 + 15: a length seen above
    status, answer = _post(server, "score", {
        "context": third["context"], "ids": question.tolist(), "top_k": 4})
    assert status == 200
    assert _answers(model, answer, np.concatenate(
        [third_tokens, question])) < TOLERANCE
    # other weights: what the old ones left answers nothing
    model.set_params(dict(model.params))
    assert not model.contexts.held() and model.contexts.pages == 8
    assert value("page_pool_pages_held") == 0


@pytest.mark.parametrize("endpoint,body,status,says", [
    ("score", {"context": "feedfeedfeedfeed", "ids": [1, 2]}, 404,
     "unknown or evicted"),
    ("contexts", {"ids": [1] * 49}, 400,
     "positions less the longest question"),
    ("contexts", {"ids": [1, 999]}, 400, "token ids must lie in"),
], ids=["unknown_context", "context_over_the_admission_limit",
        "id_outside_slice"])
def test_what_cannot_be_answered_is_refused(served, endpoint, body, status,
                                            says):
    server, _ = served
    got, answer = _post(server, endpoint, body)
    assert got == status and says in answer["error"]


def test_a_context_larger_than_the_pool_is_refused_and_says_so(tmp_path):
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    path = str(tmp_path / "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(SERVED, serve={
            "length_buckets": [16], "context_cache": {
                "slots": 2, "pages": 3, "tokens_per_slot": 48,
                "register_chunk": W}}), f)
    model = ScoringModel(config_from_args(
        ["--model_config", path, "--serve_token_budget", "16",
         "--save", str(tmp_path / "saved")]))
    with pytest.raises(ValueError, match=r"the pool has 3.*\(the page pool\)"):
        model.register_context(list(range(25)))
    assert model.register_context(list(range(24)))["tokens"] == 24
    assert model.contexts.held() and not model.contexts._free_pages


def test_the_cache_kind_is_one_of_three():
    import types
    from code2vec_tpu import lm_facade
    assert lm_facade.cache_kind(lm) == "paged"
    assert lm_facade.cache_kind(types.SimpleNamespace()) == "tokens"
    with pytest.raises(ValueError, match="tokens, state, paged"):
        lm_facade.cache_kind(types.SimpleNamespace(
            __name__="m", CACHE_KIND="rows"))
