"""The power-retention language model at tiny widths on the CPU: the
feature map, the chunked retention against its two plain forms, a state
carried from a prefix to a suffix, the model against the plain
reference's one full forward, and through `ScoringModel` and an
in-process `PredictionServer`: contexts registered as states of fixed
size, rows of one step on different slots, a slot reused, an evicted id,
a cache that scoring leaves as it was; and every fault the benchmark's
comparison has to catch, here in small."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import lm_common
from code2vec_tpu.models import retention_lm as lm
from code2vec_tpu.models import retention_lm_reference as ref
from code2vec_tpu.ops import power_retention as pr
from code2vec_tpu.serving.context_cache import ContextSlots, chunks

TINY = dict(
    model_type="brumby", hidden_size=64, num_hidden_layers=4, layers=2,
    vocab_size=512, vocab_rows=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e6,
    max_position_embeddings=512, intermediate_size=96, rms_norm_eps=1e-6,
    retention_chunk=16, gate_memory_tokens=[20, 200])
CHUNK = 64      # a registration chunk: four chunks of the retention


def _unit(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))


def _inputs(seed, rows=2, length=37, hq=4, hkv=2, d=8):
    """q and k as the model hands them over (unit size an element), v,
    and gates that remember a few dozen tokens."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (_unit(jax.random.normal(keys[0], (rows, length, hq, d))),
            _unit(jax.random.normal(keys[1], (rows, length, hkv, d))),
            jax.random.normal(keys[2], (rows, length, hkv, d)),
            jax.nn.log_sigmoid(3.0 + jax.random.normal(
                keys[3], (rows, length, hkv))))


# -------------------------------------------------------------------- the op

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_phi_of_q_dot_phi_of_k_is_the_squared_product(dtype):
    q, k, _, _ = _inputs(0, d=16)
    q, k = q.astype(dtype), k.astype(dtype)
    assert pr.phi(q).shape[-1] == pr.state_features(16) == 136
    got = jnp.einsum("rlhf,rlhf->rlh", pr.phi(q[:, :, :2]), pr.phi(k))
    want = jnp.einsum("rlhd,rlhd->rlh", q[:, :, :2].astype(jnp.float32),
                      k.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST) ** 2
    # the products are exact in either type; the sum's order differs
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_recurrence_is_the_quadratic_form():
    q, k, v, log_g = _inputs(1)
    y, state = pr.retain_recurrence(q, k, v, log_g)
    np.testing.assert_allclose(y, pr.retain_quadratic(q, k, v, log_g),
                               rtol=2e-3, atol=2e-3)
    assert state.shape == (2, 2, 9, 36)


@pytest.mark.parametrize("chunk", [8, 16, 37, 64],
                         ids=["8_not_dividing", "16_not_dividing",
                              "the_length", "longer_than_the_length"])
def test_chunked_is_quadratic_is_recurrence(chunk):
    """Whatever the chunk, dividing the length or not (the last chunk is
    then padded on the right). The tolerance is bfloat16's: v and the
    weights are rounded to 8 bits for the products, values reach 4."""
    q, k, v, log_g = _inputs(2)
    y, state = pr.retain(q, k, v, log_g, None, chunk)
    np.testing.assert_allclose(y, pr.retain_quadratic(q, k, v, log_g),
                               atol=0.03)
    _, want = pr.retain_recurrence(q, k, v, log_g)
    assert np.abs(state - want).max() < 0.01 * np.abs(want).max()


def test_right_padding_changes_nothing_before_it_nor_the_state():
    """A caller's padding (k = 0, log g = 0 behind the real tokens)
    leaves the answers before it and the state alone."""
    q, k, v, log_g = _inputs(3, length=24)
    y, state = pr.retain(q, k, v, log_g, None, 8)
    pad = [(0, 0), (0, 9), (0, 0), (0, 0)]
    y_pad, state_pad = pr.retain(
        jnp.pad(q, pad, constant_values=1.0), jnp.pad(k, pad),
        jnp.pad(v, pad, constant_values=7.0), jnp.pad(log_g, pad[:3]),
        None, 8)
    np.testing.assert_allclose(y_pad[:, :24], y, atol=1e-6)
    np.testing.assert_allclose(state_pad, state, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("cut", [8, 20, 36])
def test_a_prefix_then_its_suffix_from_the_state_is_the_whole(cut):
    q, k, v, log_g = _inputs(4)
    whole, end = pr.retain(q, k, v, log_g, None, 8)

    def part(a, b, state):
        return pr.retain(q[:, a:b], k[:, a:b], v[:, a:b], log_g[:, a:b],
                         state, 8)
    first, state = part(0, cut, None)
    second, last = part(cut, 37, state)
    # where the chunks fall differs, so the bfloat16 roundings do
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=0.03)
    assert np.abs(last - end).max() < 0.01 * np.abs(end).max()
    # a scoring step drops the state: nothing of the update is computed
    y, none = pr.retain(q[:, cut:], k[:, cut:], v[:, cut:], log_g[:, cut:],
                        state, 64, want_state=False)
    assert none is None
    np.testing.assert_allclose(y, whole[:, cut:], atol=0.03)


# ----------------------------------------------------------------- the model

@pytest.fixture(scope="module")
def cfg():
    return lm.LMConfig.from_dict(TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return lm_common.init_leaves(cfg, lm.leaf_specs(cfg), 3)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 128, (n,)).astype(np.int32)


CONTEXTS = {1: _tokens(11, 150), 3: _tokens(13, 77), 0: _tokens(10, 64)}


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


@pytest.fixture(scope="module")
def cache(cfg, params):
    return _register(cfg, params, lm.init_cache(cfg, 4, 256), CONTEXTS)


# logits spread by ~0.5 (best - mean) at these widths; bfloat16 operands
# against the float32 reference moved a served logit by 0.0006-0.0008
# over the seeds tried: five times that
TOLERANCE = 0.004


def _hold_to_reference(cfg, params, out, row, sequence, **fault):
    logits = np.asarray(_faulted_logits(cfg, params, sequence, **fault)
                        if fault else ref.logits(cfg, params, sequence))
    served = np.asarray(out.topk_indices[row])
    return max(np.abs(logits[served] - np.asarray(out.topk_values[row])
                      ).max(), logits.max() - logits[served[0]])


def _faulted_logits(cfg, params, sequence, power=2, gated=True,
                    normalised=True):
    """The plain reference with its retention replaced by
    `retain_quadratic`, which can have one FAULT of the layer: another
    power, the gates ignored, the normaliser dropped."""
    length = len(sequence)
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    h = jnp.take(params["embed"], jnp.asarray(sequence), axis=0
                 ).astype(jnp.float32)
    for i in range(cfg.layers):
        p = lm_common.layer_params(params, i)
        u = ref._rms(h, p["attn_norm"], cfg.norm_eps)
        q = ref._rotate(ref._rms(ref._mm(u, p["wq"]).reshape(
            length, hq, d), p["q_norm"], cfg.norm_eps), cfg.rope_theta)
        k = ref._rotate(ref._rms(ref._mm(u, p["wk"]).reshape(
            length, hkv, d), p["k_norm"], cfg.norm_eps), cfg.rope_theta)
        v = ref._mm(u, p["wv"]).reshape(length, hkv, d)
        log_g = jax.nn.log_sigmoid(ref._mm(u, p["wg"]) + p["bg"])
        y = pr.retain_quadratic(q[None], k[None], v[None], log_g[None],
                                power, gated, normalised)[0]
        h = h + ref._mm(y.reshape(length, hq * d), p["wo"])
        r = ref._rms(h, p["mlp_norm"], cfg.norm_eps)
        h = h + ref._mm(jax.nn.silu(ref._mm(r, p["gate"]))
                        * ref._mm(r, p["up"]), p["down"])
    last = ref._rms(h[-1], params["final_norm"], cfg.norm_eps)
    return ref._mm(params["head"].astype(jnp.float32), last)


def test_without_a_fault_the_quadratic_op_is_the_references_layer(cfg,
                                                                  params):
    sequence = _tokens(5, 40)
    np.testing.assert_allclose(_faulted_logits(cfg, params, sequence),
                               ref.logits(cfg, params, sequence), atol=1e-4)


def test_the_model_without_a_cache_is_the_reference(cfg, params):
    questions = [_tokens(21, 30), _tokens(22, 32), _tokens(23, 5)]
    ids = np.zeros((4, 32), np.int32)
    lengths = np.zeros((4,), np.int32)
    for i, q in enumerate(questions):
        ids[i, :len(q)], lengths[i] = q, len(q)
    out = jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, 5, 64, params, ids, lengths)
    for r, q in enumerate(questions):
        assert _hold_to_reference(cfg, params, out, r, q) < TOLERANCE
    # a dense model: no expert layer to report on
    assert out.stats.load.shape == (0, 0)
    assert out.stats.chosen_last.shape == (4, 0, 0)
    assert int(out.stats.real_tokens) == 67


def test_scores_through_the_states_are_the_full_forward(cfg, params, cache):
    """Rows of one step on different slots (one twice, one none): each
    answer is the reference's over context ++ question."""
    questions = [_tokens(21, 30), _tokens(22, 32), _tokens(23, 5),
                 _tokens(24, 17)]
    slots, held = [1, 3, 1, 0], [150, 77, 150, 0]
    out = _score(cfg, params, cache, questions, slots, held)
    for r, q in enumerate(questions):
        before = CONTEXTS[slots[r]] if held[r] else q[:0]
        assert _hold_to_reference(cfg, params, out, r, np.concatenate(
            [before, q])) < TOLERANCE


def test_a_state_is_the_same_size_whatever_the_context(cfg, cache):
    assert len(cache) == cfg.layers
    for layer in cache:
        # four slots and the zero state rows without a context start from
        assert layer.shape == (5, 2, 17, 136) and layer.dtype == jnp.float32
        assert not np.asarray(layer[4]).any()
    assert lm.CACHE_KIND == "state"


@pytest.mark.parametrize("chunk", [16, 256], ids=["sixteenths", "one_shot"])
def test_chunked_registration_is_the_one_shot(cfg, params, cache, chunk):
    other = _register(cfg, params, lm.init_cache(cfg, 4, 256), CONTEXTS,
                      chunk=chunk)
    for a, b in zip(cache, other):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 0.02 * np.abs(
            np.asarray(a)).max()
    q = [_tokens(21, 30)]
    np.testing.assert_allclose(
        np.asarray(_score(cfg, params, cache, q, [1], [150]).topk_values),
        np.asarray(_score(cfg, params, other, q, [1], [150]).topk_values),
        atol=5e-3)


def test_a_first_chunk_starts_from_zeros_whatever_the_slot_held(cfg, params,
                                                               cache):
    again = _register(cfg, params, cache, {1: CONTEXTS[3]})
    for a, b in zip(cache, again):
        np.testing.assert_allclose(np.asarray(b[1]), np.asarray(a[3]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(b[3]), np.asarray(a[3]))


# each FAULT the benchmark's comparison has to catch, in small: the served
# answer held against the reference WITH the fault must lie far outside
# what bfloat16 explains
FAULTS = {
    "another_contexts_slot": dict(context=CONTEXTS[3]),
    "one_token_short": dict(context=CONTEXTS[1][:-1]),
    "last_chunk_alone": dict(context=CONTEXTS[1][128:]),
    "gates_ignored": dict(gated=False),
    "normaliser_dropped": dict(normalised=False),
    "power_one": dict(power=1),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_fails_the_small_comparison(cfg, params, cache, fault):
    question = _tokens(21, 30)
    out = _score(cfg, params, cache, [question], [1], [150])
    sound = _hold_to_reference(cfg, params, out, 0, np.concatenate(
        [CONTEXTS[1], question]))
    assert sound < TOLERANCE
    how = dict(FAULTS[fault])
    context = how.pop("context", CONTEXTS[1])
    if fault == "last_chunk_alone":
        # the state of the last registration chunk alone, nothing carried:
        # the question still stands at the whole context's positions, so
        # it is the SERVED side that has the fault here
        alone = _register(cfg, params, lm.init_cache(cfg, 4, 256),
                          {1: context})
        out = _score(cfg, params, alone, [question], [1], [150])
        context = CONTEXTS[1]
    wrong = _hold_to_reference(cfg, params, out, 0, np.concatenate(
        [context, question]), **how)
    # the smallest, a context one token short, read 0.0104
    assert wrong > 2 * TOLERANCE, (fault, wrong)


def test_parameter_count_and_the_gate_bias(cfg, params):
    layer = (64 * 64 * 2 + 64 * 32 * 2 + 2 * 16 + 64 * 2 + 2 + 2 * 64
             + 3 * 64 * 96)
    assert lm_common.count_leaves(lm.leaf_specs(cfg)) == (
        2 * layer + 2 * 128 * 64 + 64)
    gate = np.asarray(jax.nn.sigmoid(params["layers.00.bg"]))
    np.testing.assert_allclose(1.0 / (1.0 - gate), [20.0, 200.0], rtol=1e-3)
    with pytest.raises(ValueError, match="power 2"):
        lm.LMConfig.from_dict(dict(TINY, retention_power=3))
    with pytest.raises(ValueError, match="no intermediate_size"):
        lm.LMConfig.from_dict({k: v for k, v in TINY.items()
                               if k != "intermediate_size"})


def test_a_book_of_fixed_size_states_fills_by_slots():
    from code2vec_tpu import obs
    book = ContextSlots(4, 448, fixed_size=True)
    slot, _ = book.acquire()
    book.commit(slot, "a", 448)
    fill = obs.default_registry().collect()["latent_cache_fill_ratio"]
    assert [m.value for m in fill.values()] == [0.25]
    tokens = ContextSlots(4, 480)
    slot, _ = tokens.acquire()
    tokens.commit(slot, "a", 120)
    assert [m.value for m in fill.values()] == [120 / (4 * 480)]


# ------------------------------------------------------------------- served

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process PredictionServer over the tiny model, built as
    `code2vec.py serve --model_config ... --load ...` builds it: two
    slots, so that a third context evicts."""
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    from code2vec_tpu.serving.server import PredictionServer
    work = tmp_path_factory.mktemp("brumby")
    model_config = str(work / "tiny.json")
    with open(model_config, "w") as f:
        json.dump(dict(TINY, serve={
            "length_buckets": [16, 32], "context_cache": {
                "slots": 2, "tokens_per_slot": 448,
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


def _answers(model, answer, sequence):
    logits = np.asarray(ref.logits(model.lm, model.params, sequence))
    top = [t["id"] for t in answer["top"]]
    return max([logits.max() - logits[top[0]]]
               + [abs(logits[t["id"]] - t["logit"]) for t in answer["top"]])


def test_contexts_registered_in_chunks_are_scored_evicted_and_reused(served):
    import concurrent.futures
    from code2vec_tpu import obs
    server, model = served
    assert server.endpoints == ("score", "contexts")
    # (1, 16) (2, 16) (4, 16) (1, 32) (2, 32) and the budget's (1, 64)
    assert model.predict_compile_count() == len(model.shapes()) == 6
    assert model.state_cache and model.contexts.fixed_size
    assert model.slot_bytes == 2 * 2 * 17 * 136 * 4
    # three chunks, and one of any length up to the admission limit
    contexts = [_tokens(31, 150), _tokens(32, 448)]
    ids = []
    for tokens in contexts:
        status, got = _post(server, "contexts", {"ids": tokens.tolist()})
        assert status == 200 and got["tokens"] == len(tokens)
        assert not got["held"] and got["evicted"] is None
        ids.append(got["context"])
    before = [np.asarray(layer).copy() for layer in model.cache]
    registry = obs.default_registry().collect()
    states = next(iter(registry["retention_states_read_total"].values()))
    rows = next(iter(registry["serving_batch_rows"].values()))
    read, steps = states.value, rows.count
    bodies = [{"context": ids[i % 2], "ids": _tokens(40 + i, n).tolist(),
               "top_k": 4, "return_routing": True}
              for i, n in enumerate((5, 16, 17, 32, 9, 2))]
    bodies.append({"ids": _tokens(50, 20).tolist(), "top_k": 4})
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        answers = list(pool.map(lambda b: _post(server, "score", b), bodies))
    assert model.predict_compile_count() == 6      # nothing new compiled
    for i, (body, (status, answer)) in enumerate(zip(bodies, answers)):
        assert status == 200, answer
        context = contexts[i % 2] if "context" in body else _tokens(0, 0)
        assert answer["context_tokens"] == len(context)
        assert answer["tokens"] == len(body["ids"])
        # logits, not tokens: bfloat16 against the float32 reference's
        # one forward over context ++ question
        assert _answers(model, answer, np.concatenate(
            [context, np.asarray(body["ids"], np.int32)])) < TOLERANCE
        if "return_routing" in body:
            assert answer["routing_last"] == []     # empty, not absent
    # six rows read a state in each of two layers; scoring wrote nothing
    assert states.value - read == 6 * 2
    assert rows.count > steps and rows.sum >= 7
    for layer, was in zip(model.cache, before):
        np.testing.assert_array_equal(np.asarray(layer), was)
    # a third context takes the slot of the one not used last, from zeros
    # (eight threads posted the bodies at once, and which step ran last
    # is the scheduler's: one more lookup, alone, says which was used last)
    used_last = ids[1]
    status, _ = _post(server, "score", {
        "context": used_last, "ids": [1, 2, 3], "top_k": 4})
    assert status == 200
    third_tokens = _tokens(33, 70)
    status, third = _post(server, "contexts", {"ids": third_tokens.tolist()})
    assert status == 200 and third["evicted"] == ids[0]
    status, answer = _post(server, "score", {
        "context": third["evicted"], "ids": [1, 2, 3], "top_k": 4})
    assert status == 404 and "evicted" in answer["error"]
    question = _tokens(60, 12)
    status, answer = _post(server, "score", {
        "context": third["context"], "ids": question.tolist(), "top_k": 4})
    assert status == 200
    assert _answers(model, answer, np.concatenate(
        [third_tokens, question])) < TOLERANCE
    # other weights: what the old ones left in the slots answers nothing
    model.set_params(dict(model.params))
    status, answer = _post(server, "score", {
        "context": used_last, "ids": [1, 2, 4], "top_k": 4})
    assert status == 404 and not model.contexts.held()
    assert model.contexts.fixed_size


@pytest.mark.parametrize("endpoint,body,status,says", [
    ("score", {"context": "feedfeedfeedfeed", "ids": [1, 2]}, 404,
     "unknown or evicted"),
    ("score", {"ids": list(range(65))}, 400, "1 to 64"),
    ("contexts", {"ids": list(range(100)) * 5}, 400, "1 to 448"),
    ("contexts", {"ids": [1, 999]}, 400, "token ids must lie in"),
], ids=["unknown_context", "question_over_budget",
        "context_over_the_admission_limit", "id_outside_slice"])
def test_what_cannot_be_answered_is_refused(served, endpoint, body, status,
                                            says):
    server, _ = served
    got, answer = _post(server, endpoint, body)
    assert got == status and says in answer["error"]


def test_an_admission_limit_past_the_positions_is_refused(tmp_path):
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    path = str(tmp_path / "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(TINY, serve={
            "length_buckets": [16, 32], "context_cache": {
                "slots": 2, "tokens_per_slot": 449,
                "register_chunk": CHUNK}}), f)
    with pytest.raises(ValueError, match="past the model's positions"):
        ScoringModel(config_from_args(
            ["--model_config", path, "--serve_token_budget", "64",
             "--save", str(tmp_path / "saved")]))


def test_the_startup_line_copes_with_no_expert_layer(served):
    _, model = served
    assert model.lm.pattern == "RR"
    assert not hasattr(model.lm, "n_routed_experts")
    model._observe_router(lm_common.StepStats(
        np.zeros((0, 0), np.int32), np.zeros((0,), np.int32),
        np.int32(5), np.zeros((1, 0, 0), np.int32)))
