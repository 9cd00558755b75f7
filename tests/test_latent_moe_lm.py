"""The latent-attention / gated-expert language model at tiny widths on
the CPU: each op against its plain form, the model through its cache
against the plain reference's one full forward, the shares of an
expert-parallel group, the slot book, and registered contexts scored
through an in-process `PredictionServer`."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import hybrid_lm
from code2vec_tpu.models import latent_moe_lm as lm
from code2vec_tpu.models import latent_moe_lm_reference as ref
from code2vec_tpu.ops import mla, moe
from code2vec_tpu.serving.context_cache import (
    ContextSlots, chunks, context_id,
)

TINY = dict(
    model_type="glm4_moe_lite", hidden_size=64, num_hidden_layers=5,
    layers=3, first_k_dense_replace=1, vocab_size=512, vocab_rows=128,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
    intermediate_size=96, moe_intermediate_size=48, n_routed_experts=16,
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=1.8,
    rms_norm_eps=1e-5)
CHUNK, CAPACITY = 64, 256


@pytest.fixture(scope="module")
def cfg():
    return lm.LMConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    """The program's initializer, the attention projections widened so
    that the scores spread by about 3: at normal(0, 0.02) the softmax is
    all but uniform and every context reads alike."""
    out = hybrid_lm.init_leaves(cfg, lm.leaf_specs(cfg), 3)
    wider = {".q_b": 40.0, ".kv_a": 5.0, ".kv_b": 5.0}
    return {name: (next((by for end, by in wider.items()
                         if name.endswith(end)), 1.0)
                   * leaf.astype(jnp.float32)).astype(leaf.dtype)
            for name, leaf in out.items()}


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 128, (n,)).astype(np.int32)


def _register(cfg, params, cache, contexts, chunk=CHUNK):
    """{slot: tokens} into the cache, chunk by chunk."""
    step = jax.jit(lm.ctx_register_step, static_argnums=(0,))
    for slot, tokens in contexts.items():
        for start, real in chunks(len(tokens), chunk):
            ids = np.zeros((chunk,), np.int32)
            ids[:real] = tokens[start:start + real]
            cache = step(cfg, params, cache, ids, np.int32(real),
                         np.int32(slot), np.int32(start))
    return cache


def _score(cfg, params, cache, questions, slots, held, length=64, k=5):
    rows = len(questions)
    ids = np.zeros((rows, length), np.int32)
    lengths = np.zeros((rows,), np.int32)
    for i, q in enumerate(questions):
        ids[i, :len(q)], lengths[i] = q, len(q)
    return jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, k, 64, params, ids, lengths, cache,
        np.asarray(slots, np.int32), np.asarray(held, np.int32))


# -------------------------------------------------------------------- the ops

def test_rotation_is_by_position_and_keeps_the_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    at = jnp.array([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11], [0, 0, 0, 0, 0]])
    got = mla.rotate(x, at, 1e6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(x[2]),
                               atol=1e-6)              # position 0: as is
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    # a pair's score depends on the positions' difference alone
    q, k = x[0, 0], x[0, 1]
    a = mla.rotate(q[None], jnp.array([3]), 1e6)[0] @ mla.rotate(
        k[None], jnp.array([1]), 1e6)[0]
    b = mla.rotate(q[None], jnp.array([12]), 1e6)[0] @ mla.rotate(
        k[None], jnp.array([10]), 1e6)[0]
    assert abs(float(a) - float(b)) < 1e-4


def _attend_inputs(length, held, capacity=128, rows=2, heads=4, dn=16,
                   dr=8, dv=16, rank=32):
    k = jax.random.split(jax.random.PRNGKey(length), 5)
    bf16 = jnp.bfloat16
    return dict(
        q_n=jax.random.normal(k[0], (rows, length, heads, dn), bf16),
        q_r=jax.random.normal(k[1], (rows, length, heads, dr), bf16),
        own=jax.random.normal(k[2], (rows, length, rank + dr), bf16),
        cached=jax.random.normal(k[3], (rows, capacity, rank + dr), bf16),
        slot=jnp.arange(rows)[::-1], cached_len=jnp.asarray(held, jnp.int32),
        own_len=jnp.asarray([length, max(length - 3, 1)], jnp.int32),
        kv_b=(0.3 * jax.random.normal(k[4], (rank, heads * (dn + dv)))
              ).astype(bf16))


def _attend_plain(a):
    """The whole score matrix of each row, float32, expanded."""
    f32 = jnp.float32
    rows, length, heads, dn = a["q_n"].shape
    rank = a["kv_b"].shape[0]
    w = a["kv_b"].astype(f32).reshape(rank, heads, -1)
    out = []
    for r in range(rows):
        held, real = int(a["cached_len"][r]), int(a["own_len"][r])
        lat = jnp.concatenate([a["cached"][a["slot"][r], :held],
                               a["own"][r, :real]]
                              ).astype(f32)
        kv = jnp.einsum("kc,chm->khm", lat[:, :rank], w)
        s = (jnp.einsum("qhd,khd->hqk", a["q_n"][r, :real].astype(f32),
                        kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", a["q_r"][r, :real].astype(f32),
                          lat[:, rank:])) / (24 ** 0.5)
        seen = (jnp.arange(held + real)[None, :]
                <= held + jnp.arange(real)[:, None])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khv->qhv", p, kv[..., dn:]))
    return out


@pytest.mark.parametrize("absorbed", [True, False],
                         ids=["absorbed", "expanded"])
@pytest.mark.parametrize("length,held", [(8, (100, 37)), (40, (0, 128)),
                                         (70, (64, 5))],
                         ids=["short", "one_row_uncached", "two_own_blocks"])
def test_both_forms_of_attend_are_the_plain_attention(monkeypatch, absorbed,
                                                      length, held):
    a = _attend_inputs(length, held)
    monkeypatch.setattr(mla, "reads_absorbed", lambda *_: absorbed)
    got = mla.attend(**a, block=32)
    want = _attend_plain(a)
    for r, w in enumerate(want):
        real = w.shape[0]
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(got[r, :real].astype(jnp.float32) - w))
                     ) < 0.03 * scale        # bfloat16 operands
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())    # padding too


def test_the_form_follows_the_queries_a_row():
    # the configuration's widths: the costs meet at 398 queries a row
    widths = dict(heads=20, d_nope=192, d_rope=64, d_v=256, kv_rank=512)
    assert [mla.reads_absorbed(n, **widths)
            for n in (64, 256, 398, 399, 512, 2048)] == [
        True, True, True, False, False, False]


def test_router_bias_steers_the_choice_only():
    """sigmoid scores; chosen = top-k of score + bias; weights from the
    unbiased scores, normalised, times the scale: against a loop."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    u = np.asarray(jax.random.normal(k[0], (30, 64)))
    w = np.asarray(0.2 * jax.random.normal(k[1], (64, 16)))
    bias = np.asarray(0.3 * jax.random.normal(k[2], (16,)))
    routed = moe.route(jnp.asarray(u), jnp.asarray(w), jnp.asarray(bias),
                       4, 1.8)
    for t in range(30):
        s = 1.0 / (1.0 + np.exp(-(u[t].astype(np.float64) @ w)))
        chosen = sorted(range(16), key=lambda e: -(s[e] + bias[e]))[:4]
        assert sorted(np.asarray(routed.experts[t])) == sorted(chosen)
        total = sum(s[e] for e in chosen)
        for e, got in zip(np.asarray(routed.experts[t]),
                          np.asarray(routed.weights[t])):
            assert abs(got - 1.8 * s[e] / total) < 1e-5


def _expert_layer(cfg, seed=0):
    whole = dataclasses.replace(cfg, experts_held=16, expert_first=0)
    return whole, {leaf.name: hybrid_lm.init_leaf(whole, leaf,
                                                  jax.random.PRNGKey(seed + i))
                   for i, leaf in enumerate(lm.layer_leaf_specs(whole, "E"))}


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (12, 4)],
                         ids=["whole", "second_share", "last_share"])
def test_gated_grouped_experts_are_the_loop(cfg, first, held):
    _, p = _expert_layer(cfg)
    f32 = jnp.float32
    u = jax.random.normal(jax.random.PRNGKey(4), (60, 64))
    routed = moe.route(u, p["router"], p["router_bias"], 4, 1.8)
    w = [p[n][first:first + held].astype(f32)
         for n in ("w_up", "w_down", "w_gate")]
    real = jnp.arange(60) < 50
    got, stats = moe.experts_grouped(u, routed, w[0], w[1], first, real,
                                     w_gate=w[2])
    want = moe.experts_loop(u, routed, w[0], w[1], first, w_gate=w[2])
    np.testing.assert_allclose(np.asarray(got[:50]), np.asarray(want[:50]),
                               atol=2e-5)
    assert not np.asarray(got[50:]).any()          # padding gets nothing
    mine = ((np.asarray(routed.experts) >= first)
            & (np.asarray(routed.experts) < first + held))[:50]
    assert int(stats.load.sum()) == mine.sum()


def test_shares_add_up_to_the_uncut_layer(cfg):
    """Guide section 4: the routed parts of four shares of four experts,
    with the shared expert counted once, are the uncut layer."""
    whole, p = _expert_layer(cfg)
    f32 = jnp.float32
    u = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    want, _ = ref.experts(whole, p, u)
    routed = moe.route(u, p["router"], p["router_bias"], 4, 1.8)
    real = jnp.ones((40,), bool)
    parts = sum(moe.experts_grouped(
        u, routed, p["w_up"][4 * c:4 * c + 4].astype(f32),
        p["w_down"][4 * c:4 * c + 4].astype(f32), 4 * c, real,
        w_gate=p["w_gate"][4 * c:4 * c + 4].astype(f32))[0]
        for c in range(4))
    shared = moe.gated_mlp(u, *(p[n].astype(f32) for n in (
        "shared_gate", "shared_up", "shared_down")))
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(want),
                               atol=2e-4)


# ------------------------------------------------------------------ the model

CONTEXTS = {1: _tokens(11, 200), 3: _tokens(12, 77), 2: _tokens(13, 64)}


@pytest.fixture(scope="module")
def cache(cfg, params):
    return _register(cfg, params, lm.init_cache(cfg, 4, CAPACITY), CONTEXTS)


def _hold_to_reference(cfg, params, out, row, sequence):
    logits, chosen = ref.logits(cfg, params, sequence)
    logits = np.asarray(logits)
    served = np.asarray(out.topk_indices[row])
    assert np.abs(logits[served] - np.asarray(out.topk_values[row])
                  ).max() < 0.01               # bfloat16 against float32
    assert logits.max() - logits[served[0]] < 0.01
    assert abs(float(jax.nn.logsumexp(logits)) - float(out.lse[row])) < 0.01
    return chosen


def test_scores_through_the_cache_are_the_full_forward(cfg, params, cache):
    """Rows of one batch name different slots (one twice, one none); each
    answer is the reference's over context ++ question, logits compared."""
    questions = [_tokens(21, 30), _tokens(22, 64), _tokens(23, 5),
                 _tokens(24, 17)]
    slots, held = [1, 3, 1, 0], [200, 77, 200, 0]
    out = _score(cfg, params, cache, questions, slots, held)
    agree = []
    for r, q in enumerate(questions):
        before = CONTEXTS[slots[r]][:held[r]] if held[r] else q[:0]
        chosen = _hold_to_reference(cfg, params, out, r,
                                    np.concatenate([before, q]))
        agree.append((np.sort(np.asarray(out.stats.chosen_last[r]), -1)
                      == np.sort(np.asarray(chosen[:, -1]), -1)).mean())
    assert np.mean(agree) > 0.8
    assert out.stats.load.shape == (2, 16)
    assert int(out.stats.real_tokens) == 30 + 64 + 5 + 17


def test_a_wrong_slot_or_length_is_another_answer(cfg, params, cache):
    """What the comparison with the reference has to catch."""
    q = [_tokens(21, 30)]
    right = _score(cfg, params, cache, q, [1], [200])
    for slots, held in (([3], [200]), ([1], [199]), ([1], [0])):
        wrong = _score(cfg, params, cache, q, slots, held)
        assert np.abs(np.asarray(right.topk_values)
                      - np.asarray(wrong.topk_values)).max() > 0.01


@pytest.mark.parametrize("chunk", [32, 256], ids=["eighths", "one_shot"])
def test_chunked_registration_is_the_one_shot(cfg, params, cache, chunk):
    other = _register(cfg, params, lm.init_cache(cfg, 4, CAPACITY),
                      CONTEXTS, chunk=chunk)
    for slot, tokens in CONTEXTS.items():
        for a, b in zip(cache, other):
            np.testing.assert_allclose(
                np.asarray(a[slot, :len(tokens)], np.float32),
                np.asarray(b[slot, :len(tokens)], np.float32),
                atol=0.1)       # values to 4: a few steps of bfloat16
    q = [_tokens(21, 30)]
    np.testing.assert_allclose(
        np.asarray(_score(cfg, params, cache, q, [1], [200]).topk_values),
        np.asarray(_score(cfg, params, other, q, [1], [200]).topk_values),
        atol=5e-3)


def test_the_scoring_path_absorbed_is_the_expanded(cfg, params, cache,
                                                   monkeypatch):
    q = [_tokens(21, 30), _tokens(22, 64)]
    got = {}
    for absorbed in (True, False):
        monkeypatch.setattr(mla, "reads_absorbed", lambda *_: absorbed)
        jax.clear_caches()
        got[absorbed] = np.asarray(_score(cfg, params, cache, q, [1, 3],
                                          [200, 77]).topk_values)
    jax.clear_caches()
    np.testing.assert_allclose(got[True], got[False], atol=5e-3)


def test_right_padding_and_neighbours_change_no_answer(cfg, params, cache):
    q = _tokens(21, 30)
    alone = _score(cfg, params, cache, [q], [1], [200])
    junk = np.concatenate([q, _tokens(5, 34)])      # junk behind the end
    ids = np.stack([junk, _tokens(6, 64)])
    both = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, 5, 64, params, ids, np.array([30, 64], np.int32), cache,
        np.array([1, 2], np.int32), np.array([200, 64], np.int32))
    assert (np.asarray(alone.topk_indices[0])
            == np.asarray(both.topk_indices[0])).all()
    np.testing.assert_allclose(np.asarray(alone.topk_values[0]),
                               np.asarray(both.topk_values[0]), atol=1e-4)


def test_parameter_count_and_config_file():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "glm47-flash-pp8.json")
    real = lm.LMConfig.from_file(path)
    with open(path) as f:
        raw = json.load(f)
    count = hybrid_lm.count_leaves
    assert count(lm.leaf_specs(real)) == raw["parameters"] == 3_895_625_536
    assert real.pattern == "DEEEEE" and real.experts_held == 64
    layer = {k: count(lm.layer_leaf_specs(real, k)) for k in "DE"}
    assert layer == {"D": 84_677_888, "E": 635_311_424}
    assert real.cache_width * 2 == 1152           # bytes a token and layer
    held = raw["serve"]["context_cache"]
    shapes = jax.eval_shape(lambda: lm.init_cache(
        real, held["slots"], held["tokens_per_slot"]))
    assert sum(a.size * a.dtype.itemsize for a in shapes) \
        == 32 * 16384 * 6 * 1152
    with pytest.raises(ValueError, match="group-limited"):
        lm.LMConfig.from_dict(dict(TINY, n_group=8, topk_group=4))


# --------------------------------------------------------------- the slot book

def test_slots_go_to_the_least_recently_used():
    book = ContextSlots(2, 100)
    s0, gone = book.acquire()
    book.commit(s0, "a", 10)
    s1, gone = book.acquire()
    assert gone is None and s1 != s0
    book.commit(s1, "b", 20)
    assert book.lookup("a") == (s0, 10)         # "a" is now the newer
    s2, gone = book.acquire()
    assert (s2, gone) == (s1, "b")              # so "b" goes
    assert book.lookup("b") is None             # at once, not at commit
    assert book.lookup("c") is None             # nor is "c" there yet
    with pytest.raises(LookupError):         # "a" alone is held, and taken
        book.acquire() and book.acquire()
    book.release(s0)
    book.commit(s2, "c", 30)
    assert book.held() == {"c": (s1, 30)} and book.acquire() == (s0, None)
    book.commit(s0, "a", 10)
    assert book.held() == {"a": (s0, 10), "c": (s1, 30)}
    assert context_id(np.arange(5)) == context_id(list(range(5)))
    assert context_id(np.arange(5)) != context_id(np.arange(6))
    assert chunks(130, 64) == [(0, 64), (64, 64), (128, 2)]


# ------------------------------------------------------------------ the server

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process PredictionServer over the tiny model, built as
    `code2vec.py serve --model_config ... --load ...` builds it: two
    slots, so that a third context evicts."""
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    from code2vec_tpu.serving.server import PredictionServer
    work = tmp_path_factory.mktemp("glm")
    model_config = str(work / "tiny.json")
    with open(model_config, "w") as f:
        json.dump(dict(TINY, serve={
            "length_buckets": [32], "context_cache": {
                "slots": 2, "tokens_per_slot": CAPACITY,
                "register_chunk": CHUNK}}), f)
    common = ["--model_config", model_config, "--serve_token_budget", "64",
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


def test_registered_contexts_are_scored_and_evicted(served):
    import concurrent.futures
    server, model = served
    assert server.endpoints == ("score", "contexts")
    assert model.predict_compile_count() == len(model.shapes()) == 3
    contexts = [_tokens(31, 150), _tokens(32, 256)]
    ids = []
    for tokens in contexts:
        status, got = _post(server, "contexts", {"ids": tokens.tolist()})
        assert status == 200 and got["tokens"] == len(tokens)
        assert not got["held"] and got["evicted"] is None
        ids.append(got["context"])
    status, again = _post(server, "contexts", {"ids": contexts[0].tolist()})
    assert again["context"] == ids[0] and again["held"]
    bodies = [{"context": ids[i % 2], "ids": _tokens(40 + i, n).tolist(),
               "top_k": 4} for i, n in enumerate((5, 32, 33, 64, 17, 2))]
    bodies.append({"ids": _tokens(50, 20).tolist(), "top_k": 4})
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        answers = list(pool.map(lambda b: _post(server, "score", b), bodies))
    assert model.predict_compile_count() == 3      # nothing new compiled
    for i, (body, (status, answer)) in enumerate(zip(bodies, answers)):
        assert status == 200, answer
        before = contexts[i % 2] if "context" in body else _tokens(0, 0)
        assert answer["context_tokens"] == len(before)
        assert answer["tokens"] == len(body["ids"])
        logits, _ = ref.logits(model.lm, model.params, np.concatenate(
            [before, np.asarray(body["ids"], np.int32)]))
        logits = np.asarray(logits)
        top = [t["id"] for t in answer["top"]]
        assert len(set(top)) == 4
        assert logits.max() - logits[top[0]] < 0.01
        for t in answer["top"]:
            assert abs(logits[t["id"]] - t["logit"]) < 0.01
    # a third context takes the slot of the one not used last
    # (eight threads posted the bodies at once, and which step ran last
    # is the scheduler's: one more lookup, alone, says which was used last)
    used_last = ids[1]
    status, _ = _post(server, "score", {
        "context": used_last, "ids": [1, 2, 3], "top_k": 4})
    assert status == 200
    status, third = _post(server, "contexts", {"ids": _tokens(33, 70).tolist()})
    assert status == 200 and third["evicted"] == ids[0]
    status, answer = _post(server, "score", {
        "context": third["evicted"], "ids": [1, 2, 3], "top_k": 4})
    assert status == 404 and "evicted" in answer["error"]
    status, answer = _post(server, "score", {
        "context": used_last, "ids": [1, 2, 3], "top_k": 4})
    assert status == 200
    # other weights: what the old ones left in the slots answers nothing
    model.set_params(dict(model.params))
    status, answer = _post(server, "score", {
        "context": used_last, "ids": [1, 2, 4], "top_k": 4})
    assert status == 404 and not model.contexts.held()


@pytest.mark.parametrize("endpoint,body,status,says", [
    ("score", {"context": "feedfeedfeedfeed", "ids": [1, 2]}, 404,
     "unknown or evicted"),
    ("score", {"ids": list(range(65))}, 400, "1 to 64"),
    ("contexts", {"ids": list(range(100)) * 3}, 400, "1 to 256"),
    ("contexts", {"ids": [1, 999]}, 400, "token ids must lie in"),
    ("contexts", {"tokens": [1]}, 400, "ids"),
], ids=["unknown_context", "question_over_budget", "context_over_a_slot",
        "id_outside_slice", "no_ids"])
def test_what_cannot_be_answered_is_refused(served, endpoint, body, status,
                                            says):
    server, _ = served
    got, answer = _post(server, endpoint, body)
    assert got == status and says in answer["error"]
