"""The hybrid state-space / latent-expert language model at tiny widths
on the CPU: each op against its plain form, the whole model against the
plain reference, the share of an expert-parallel group, the restore,
the batcher's token budget, and served answers through an in-process
`PredictionServer`."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import hybrid_lm as lm
from code2vec_tpu.models import hybrid_lm_reference as ref
from code2vec_tpu.ops import moe, ssd
from code2vec_tpu.ops.attention import (
    causal_gqa_attention, causal_gqa_attention_plain,
)

TINY = dict(
    hidden_size=64, pattern="MEM*E", vocab_size=512, vocab_rows=128,
    mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=32,
    conv_kernel=4, chunk_size=128, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=16, experts_held=4, expert_first=4,
    num_experts_per_tok=4, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=5.0,
    norm_eps=1e-5)


@pytest.fixture(scope="module")
def cfg():
    return lm.LMConfig(**TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_params(cfg, 3)


def _scan_inputs(length, b=2, h=8, p=16, g=2, n=32):
    k = jax.random.split(jax.random.PRNGKey(length), 5)
    return (jax.random.normal(k[0], (b, length, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, length, h)) - 3.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (b, length, g, n)),
            jax.random.normal(k[4], (b, length, g, n)),
            jnp.linspace(0.5, 1.5, h))


@pytest.mark.parametrize("length", [128, 256, 300, 77],
                         ids=["one_chunk", "chunk_edge", "no_multiple",
                              "under_a_chunk"])
def test_chunked_scan_is_the_recurrence(length):
    args = _scan_inputs(length)
    want = ssd.ssd_recurrence(*args)
    got = ssd.ssd_chunked(*args, chunk=128, operand_dtype=jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale
    # with the operands the program uses: bfloat16's rounding, no more
    got16 = ssd.ssd_chunked(*args, chunk=128)
    assert float(jnp.max(jnp.abs(got16 - want))) < 2e-2 * scale


def test_blockwise_attention_is_the_plain_one():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k[0], (2, 200, 8, 16))
    kk = jax.random.normal(k[1], (2, 200, 2, 16))
    v = jax.random.normal(k[2], (2, 200, 2, 16))
    got = causal_gqa_attention(q, kk, v, block=64)
    want = causal_gqa_attention_plain(q, kk, v)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_router_choice_weights_and_scaling():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    u = jax.random.normal(k[0], (50, 64))
    w = 0.2 * jax.random.normal(k[1], (64, 16))
    bias = jnp.zeros((16,)).at[5].set(10.0)      # steers the choice only
    routed = moe.route(u, w, bias, 4, 5.0)
    s = np.asarray(jax.nn.sigmoid(u @ w), np.float64)
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :4]
    assert (np.sort(np.asarray(routed.experts), -1)
            == np.sort(want, -1)).all()
    assert (np.asarray(routed.experts) == 5).any(axis=-1).all()
    picked = np.take_along_axis(s, np.asarray(routed.experts), -1)
    np.testing.assert_allclose(
        np.asarray(routed.weights),
        5.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(routed.weights).sum(-1), 5.0,
                               rtol=1e-5)


def test_grouped_experts_are_the_loop():
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    tokens, held, first = 60, 4, 4
    latent = jax.random.normal(k[0], (tokens, 32))
    w1 = 0.2 * jax.random.normal(k[1], (held, 32, 48))
    w2 = 0.2 * jax.random.normal(k[2], (held, 48, 32))
    routed = moe.route(jax.random.normal(k[3], (tokens, 64)),
                       0.2 * jax.random.normal(k[4], (64, 16)),
                       jnp.zeros((16,)), 4, 5.0)
    real = jnp.arange(tokens) < 50
    got, stats = moe.experts_grouped(latent, routed, w1, w2, first, real)
    want = moe.experts_loop(latent, routed, w1, w2, first)
    np.testing.assert_allclose(np.asarray(got[:50]), np.asarray(want[:50]),
                               atol=2e-4)
    assert not np.asarray(got[50:]).any()          # padding gets nothing
    mine = ((np.asarray(routed.experts) >= first)
            & (np.asarray(routed.experts) < first + held))[:50]
    assert int(stats.load.sum()) == mine.sum()
    assert int(stats.unserved_tokens) == (~mine.any(-1)).sum()
    assert int(stats.real_tokens) == 50


def test_shares_add_up_to_the_uncut_layer(cfg):
    """Guide section 4: the routed parts of the four shares, with the
    shared expert and the latent projections counted once, are the
    uncut layer."""
    whole = dataclasses.replace(cfg, experts_held=16, expert_first=0)
    p = {leaf.name: lm.init_leaf(whole, leaf, jax.random.PRNGKey(i))
         for i, leaf in enumerate(lm.layer_leaf_specs(whole, "E"))}
    u = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    want, _ = ref.experts(whole, p, u)
    f32 = jnp.float32
    routed = moe.route(u, p["router"], p["router_bias"], 4, 5.0)
    latent = u @ p["down"].astype(f32)
    real = jnp.ones((40,), bool)
    parts = sum(moe.experts_grouped(
        latent, routed, p["w1"][4 * c:4 * c + 4].astype(f32),
        p["w2"][4 * c:4 * c + 4].astype(f32), 4 * c, real)[0]
        for c in range(4))
    shared = moe.relu2(u @ p["shared_w1"].astype(f32)) \
        @ p["shared_w2"].astype(f32)
    got = parts @ p["up"].astype(f32) + shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def _step(cfg, params, ids, lengths, k=5):
    return jax.jit(lm.lm_score_step, static_argnums=(0, 1, 2))(
        cfg, k, 64, params, ids, lengths)


def test_model_is_the_reference(cfg, params):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (3, 256)).astype(np.int32)
    lengths = np.array([256, 130, 77], np.int32)
    out = _step(cfg, params, ids, lengths)
    for r in range(3):
        logits, chosen = ref.logits(cfg, params, ids[r, :lengths[r]])
        served = np.asarray(out.topk_indices[r])
        gap = np.asarray(logits)[served] - np.asarray(out.topk_values[r])
        assert np.abs(gap).max() < 0.02        # bfloat16 against float32
        assert float(jnp.max(logits)) - float(logits[served[0]]) < 0.02
        assert abs(float(jax.nn.logsumexp(logits)) - float(out.lse[r])) < 0.01
        assert (np.sort(np.asarray(out.stats.chosen_last[r]), -1)
                == np.sort(np.asarray(chosen[:, -1]), -1)).mean() > 0.8
    assert out.stats.load.shape == (2, 4)
    assert int(out.stats.real_tokens) == lengths.sum()


def test_right_padding_changes_no_answer(cfg, params):
    rng = np.random.RandomState(1)
    seq = rng.randint(0, 128, (100,)).astype(np.int32)
    short = np.zeros((1, 128), np.int32)
    short[0, :100] = seq
    wide = rng.randint(0, 128, (2, 256)).astype(np.int32)   # junk padding
    wide[0, :100] = seq
    a = _step(cfg, params, short, np.array([100], np.int32))
    b = _step(cfg, params, wide, np.array([100, 256], np.int32))
    assert (np.asarray(a.topk_indices[0]) == np.asarray(b.topk_indices[0])
            ).all()
    np.testing.assert_allclose(np.asarray(a.topk_values[0]),
                               np.asarray(b.topk_values[0]), atol=1e-5)


def test_parameter_count_and_config_file(tmp_path):
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "nemotron3-super-ep4.json")
    real = lm.LMConfig.from_file(path)
    with open(path) as f:
        raw = json.load(f)
    assert lm.num_params(real) == raw["parameters"] == 4_648_163_712
    assert real.pattern == "MEMEMEMEM*E" and real.experts_held == 128
    per_layer = {k: lm.num_params(dataclasses.replace(real, pattern=k))
                 - lm.num_params(dataclasses.replace(real, pattern="M"))
                 for k in "*E"}
    mamba = sum(int(np.prod(leaf.shape))
                for leaf in lm.layer_leaf_specs(real, "M"))
    assert round(mamba / 1e6, 2) == 109.64
    assert round((mamba + per_layer["*"]) / 1e6, 2) == 35.66
    with pytest.raises(ValueError):
        lm.LMConfig(**dict(TINY, pattern="MXE"))


def test_restore_streams_leaves_into_place(cfg, params, tmp_path,
                                           monkeypatch):
    from code2vec_tpu.training import checkpoint as ckpt
    path = ckpt.save_params(str(tmp_path / "saved"), params, {"seed": 3})
    assert ckpt.load_model_meta(path)["format"] == ckpt.PARAMS_FORMAT
    assert ckpt.resolve_load_path(path) == path
    live, peak = [], [0]
    put = jax.device_put

    def counting_put(host, *a, **kw):
        out = put(host, *a, **kw)
        live.append(out.nbytes)
        # on the host: this leaf alone (a read-only map of its file)
        assert isinstance(host, np.memmap) or isinstance(host.base, np.memmap)
        peak[0] = max(peak[0], sum(live))
        return out
    monkeypatch.setattr(jax, "device_put", counting_put)
    got = ckpt.restore_params(path, lm.abstract_params(cfg))
    total = sum(v.nbytes for v in params.values())
    assert peak[0] == total             # never more than the parameters
    for name, want in params.items():
        assert got[name].dtype == want.dtype
        assert (np.asarray(got[name]) == np.asarray(want)).all()
    other = lm.abstract_params(dataclasses.replace(cfg, hidden_size=32))
    with pytest.raises(ValueError, match="embed"):
        ckpt.restore_params(path, other)


def test_batcher_keeps_the_token_budget():
    import threading
    from code2vec_tpu.serving.batcher import DynamicBatcher, bucket_for
    buckets = (128, 256, 512)
    seen, gate = [], threading.Event()

    def predict(rows):
        gate.wait(5)
        seen.append([len(r) for r in rows])
        return rows
    batcher = DynamicBatcher(
        predict, max_batch_rows=64, buckets=buckets,
        bucket_of=lambda r: bucket_for(len(r), buckets),
        max_batch_tokens=512)
    try:
        first = batcher.submit(["x" * 500])     # fills the budget alone
        futures = [batcher.submit(["x" * n]) for n in (100, 120, 90, 60,
                                                       200, 30)]
        gate.set()
        for f in [first] + futures:
            f.result(timeout=10)
    finally:
        batcher.drain(timeout=5)
    assert seen[0] == [500]
    for batch in seen:
        deepest = max(bucket_for(n, buckets) for n in batch)
        assert len(batch) * deepest <= 512
    assert sum(len(b) for b in seen) == 7


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process PredictionServer over the tiny model, built as
    `code2vec.py serve --model_config ... --load ...` builds it."""
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.lm_facade import ScoringModel
    from code2vec_tpu.serving.server import PredictionServer
    work = tmp_path_factory.mktemp("lm")
    model_config = str(work / "tiny.json")
    with open(model_config, "w") as f:
        json.dump(dict(TINY, serve={"length_buckets": [128, 256]}), f)
    common = ["--model_config", model_config, "--serve_token_budget", "512",
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


def test_served_answers_are_the_references_top_k(served):
    import concurrent.futures
    server, model = served
    assert model.predict_compile_count() == len(model.shapes()) == 6
    rng = np.random.RandomState(7)
    bodies = [{"ids": rng.randint(0, 128, (n,)).tolist(), "top_k": 4}
              for n in (5, 100, 128, 129, 256, 300, 40, 17)]
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        answers = list(pool.map(
            lambda b: server.handle_request("score", json.dumps(b),
                                            params=b), bodies))
    assert model.predict_compile_count() == 6      # nothing new compiled
    for body, (status, raw, _) in zip(bodies, answers):
        assert status == 200, raw
        answer = json.loads(raw)
        assert answer["tokens"] == len(body["ids"])
        logits, _ = ref.logits(model.lm, model.params,
                               np.asarray(body["ids"], np.int32))
        logits = np.asarray(logits)
        ids = [t["id"] for t in answer["top"]]
        assert len(ids) == 4 and len(set(ids)) == 4
        assert logits.max() - logits[ids[0]] < 0.02
        for t in answer["top"]:
            assert abs(logits[t["id"]] - t["logit"]) < 0.02
            want = np.exp(logits[t["id"]] - np.logaddexp.reduce(logits))
            assert abs(want - t["probability"]) < 1e-3


@pytest.mark.parametrize("body,status,says", [
    ({"ids": [1, 2, 999]}, 400, "token ids must lie in"),
    ({"ids": []}, 400, "1 to 512"),
    ({"ids": list(range(100)) * 6}, 400, "1 to 512"),
    ({"ids": [1, 2], "top_k": 99}, 400, "top_k"),
    ({"tokens": [1]}, 400, "ids"),
    ({"ids": [1, 2], "context": "feedfeedfeedfeed"}, 400,
     "keeps no contexts"),
], ids=["id_outside_slice", "empty", "over_budget", "top_k", "no_ids",
        "context_without_a_cache"])
def test_score_refuses_what_it_cannot_answer(served, body, status, says):
    server, _ = served
    got, raw, _ = server.handle_request("score", json.dumps(body),
                                        params=body)
    assert got == status and says in raw.decode()


def test_other_routes_say_what_the_model_serves(served):
    server, _ = served
    status, raw, _ = server.handle_request("predict", "class A {}")
    assert status == 404 and "/score" in raw.decode()
    status, raw, _ = server.handle_request("contexts", '{"ids": [1]}',
                                           params={"ids": [1]})
    assert status == 404 and "/score" in raw.decode()
    assert server.healthz()["extractor_pool"] is None
    assert server.healthz()["buckets"] == [128, 256, 512]
