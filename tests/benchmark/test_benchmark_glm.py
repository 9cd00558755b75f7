"""The context-scoring cell of the benchmark: its files load and say what
ISSUE 31 asked for, the runner kind `serve_lm_ctx` rehearses end to end
on the CPU at toy widths (new files and entries only), a tree whose
program registers no contexts gets no result at once, the reference's
weights, its lower-precision control and a wrong context behave, the
traffic's lengths fall where the mix says, and the floors never pass
the time a chip at its peaks would take."""

import json
import os
import sys

import numpy as np
import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common, loadgen  # noqa: E402
from benchmarks import reference_glm, reference_lm, roofline_glm  # noqa: E402
from benchmarks.runners import serve_lm_ctx  # noqa: E402

CELL = "glm47-flash-pp8.serve_score_ctx_open"
# the per-layer metrics the runner kind `serve_lm_ctx` brought (PR 31)
BROUGHT = ("mla_attend_roofline.serve", "moe_gated_experts_roofline.serve",
           "ctx_score_step_device_ms.serve", "latent_cache_fill_pct.serve",
           "context_register_ms.setup")
TINY = dict(
    model_type="glm4_moe_lite", hidden_size=64, num_hidden_layers=5,
    layers=3, first_k_dense_replace=1, vocab_size=512, vocab_rows=128,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
    intermediate_size=96, moe_intermediate_size=48, n_routed_experts=16,
    experts_held=16, expert_first=0, num_experts_per_tok=4,
    n_shared_experts=1, routed_scaling_factor=1.8, rms_norm_eps=1e-5,
    n_group=1, topk_group=1,
    init_std={"default": 0.02, "q_b": 0.8, "kv_a": 0.1, "kv_b": 0.1,
              "router_bias": 0.01},
    serve={"length_buckets": [16, 32], "token_budget": 64, "top_k": 10,
           "context_cache": {"slots": 4, "tokens_per_slot": 256,
                             "register_chunk": 64}})


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    dest = make_toy_root(str(tmp_path_factory.mktemp("glm") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-glm"), "configs", "toy-glm.json")
    mix = common.load_json(os.path.join(home, "traffic",
                                        "serve_score_ctx_open.json"))
    dump(dict(mix, name="toy_ctx", rate_per_s=12.0, request_pool=80,
              warm_requests=2, checked_requests=6, checked_contexts=3,
              contexts=4, generator_threads=4,
              context_length={"distribution": "lognormal", "median": 150,
                              "sigma": 0.3, "min": 70, "max": 256},
              length={"distribution": "lognormal", "median": 20,
                      "sigma": 0.5, "min": 3, "max": 64}),
         "traffic", "toy_ctx.json")
    dump({"limits": {"served_top_logit_gap": 0.05,
                     "served_score_gap": 0.05,
                     "served_score_gap_median": 0.05}},
         "limits", "toy-glm.ctx.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-glm", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-glm.json"})
    bench["workloads"].append({"name": "toy-glm.ctx", "config": "toy-glm",
                               "traffic": "toy_ctx", "chips": 1,
                               "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-glm.ctx")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    cell = common.Cell(toy_root, "toy-glm.ctx")
    return cell, serve_lm_ctx.run(cell, 2_600_000_123, 2.0, trace=False,
                                  require_tpu=False, emit=False)


def test_ctx_rehearsal_is_correct_and_serves_every_request(rehearsal):
    cell, result = rehearsal
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 24
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_requests_checked"]["value"] == 6
    assert names["served_contexts_checked"]["value"] >= 3
    assert names["served_score_gap"]["value"] < 0.05
    assert names["compiled_inside_window"]["value"] == 0
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))
    ids = common.load_json(os.path.join(cell.work, "data",
                                        "context_ids.json"))
    assert len(set(ids)) == 4


def test_ctx_rehearsal_counters_feed_the_new_metrics(rehearsal):
    """The program's counters, gauges and spans give the runner what the
    new per-layer metrics read."""
    from code2vec_tpu import obs
    cell, _ = rehearsal
    window = common.RegistryWindow(obs.default_registry())
    window.close()          # from the process's start to now
    facts = serve_lm_ctx.attention_facts(window)
    assert facts["latents_per_step"] > facts["tokens_per_step"] > 0
    assert facts["pairs_per_step"] >= facts["latents_per_step"]
    assert facts["experts_hit_per_step_layer"] <= 16
    assert 0.0 < window.gauge("latent_cache_fill_ratio") <= 1.0
    assert window.gauge("latent_cache_slots_held") == 4
    total, count = serve_lm_ctx.registry_total("context_register_seconds")
    assert count >= 4 and total > 0
    from benchmarks import readers
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", window, window_s=2.0,
        facts={"context_register_ms": 1e3 * total / count}))
    assert 0.0 < got["latent_cache_fill_pct.serve"] <= 100.0
    assert got["context_register_ms.setup"] > 0
    # no trace: the device time and the rooflines are left out
    assert not {"mla_attend_roofline.serve", "ctx_score_step_device_ms.serve",
                "moe_gated_experts_roofline.serve"} & set(got)
    assert set(got) <= {m["name"] for m in cell.per_layer()}


def _served(cell, seed, n=3):
    pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
    seqs, contexts = [], []
    for i in range(n):
        c = pool["context_of"][i]
        seqs.append(np.concatenate([pool["contexts"][c],
                                    pool["questions"][i]]))
        contexts.append(c)
    ref = reference_glm.forward(seed, cell.config, seqs)
    ids, logits = reference_lm.own_answers(ref["logits"], 10)
    return pool, {"sequences": seqs, "contexts": contexts, "malformed": 0,
                  "ids": ids, "logits": logits,
                  "routing": ref["chosen_last"]}


def test_an_altered_answer_or_context_reads_not_correct(rehearsal):
    cell, _ = rehearsal
    limits = cell.limits()
    pool, served = _served(cell, 7)
    few = dict(cell.traffic, checked_requests=3, checked_contexts=1)
    cell.traffic, kept = few, cell.traffic
    try:
        sound = serve_lm_ctx.check_answers(cell, 7, served, limits)
        assert all(c["ok"] for c in sound), sound
        swapped = dict(served, ids=served["ids"][:, ::-1].copy())
        assert not all(c["ok"] for c in serve_lm_ctx.check_answers(
            cell, 7, swapped, limits))
        # the answers of another context's cache: each question behind
        # the NEXT context of the pool
        moved = [np.concatenate([pool["contexts"][(c + 1) % 4],
                                 pool["questions"][i]])
                 for i, c in enumerate(served["contexts"])]
        assert not all(c["ok"] for c in serve_lm_ctx.check_answers(
            cell, 7, dict(served, sequences=moved), limits))
        # a position offset of one: the context's first token dropped
        shifted = [s[1:] for s in served["sequences"]]
        assert not all(c["ok"] for c in serve_lm_ctx.check_answers(
            cell, 7, dict(served, sequences=shifted), limits))
    finally:
        cell.traffic = kept
    short = serve_lm_ctx.check_answers(cell, 7, served, limits)
    assert not {c["name"]: c for c in short}["served_requests_checked"]["ok"]


def test_the_control_fails_a_limit_the_reference_passes(rehearsal):
    cell, _ = rehearsal
    _, served = _served(cell, 11, n=4)
    low = reference_glm.forward(11, cell.config, served["sequences"],
                                lower=True)
    ref = reference_glm.forward(11, cell.config, served["sequences"])
    gap = reference_lm.served_gap(
        ref["logits"], *reference_lm.own_answers(low["logits"], 10))
    assert gap["score_gap"] > 0.05
    own = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    assert own["score_gap"] < 1e-5 and own["top_gap"] == 0.0


def test_a_tree_that_registers_no_contexts_gets_no_result_at_once(
        toy_root, monkeypatch):
    from code2vec_tpu import lm_facade
    cell = common.Cell(toy_root, "toy-glm.ctx")
    monkeypatch.delattr(lm_facade.ScoringModel, "register_context")
    with pytest.raises(common.NoResult) as e:
        serve_lm_ctx.run(cell, 1, 1.0, trace=False, require_tpu=False)
    assert e.value.code == 3
    monkeypatch.setitem(sys.modules, "code2vec_tpu.lm_facade", None)
    with pytest.raises(common.NoResult):
        serve_lm_ctx.run(cell, 1, 1.0, trace=False, require_tpu=False)


# ------------------------------------------------------ the cell's own files

@pytest.fixture(scope="module")
def cell():
    return common.Cell(ROOT, CELL)


def test_the_cell_its_files_and_limits_load(cell):
    assert cell.runner == "serve_lm_ctx" and cell.chips == 1
    assert cell.run_module() is serve_lm_ctx
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}
    assert [m["name"] for m in cell.end_to_end()] == ["request_p50_ms",
                                                      "setup_s"]
    mine = {m["name"] for m in cell.per_layer()}
    # every metric the runner kind brought is listed for its cell (PR 36
    # appended the entries; each is found by its name)
    new = set(BROUGHT)
    assert len(new) == 5 and new <= mine
    listed = {m["name"]: m for m in cell.bench["per_layer"]}
    assert CELL not in listed["batches_cut_idle_pct.serve"]["workloads"]
    assert "batches_cut_idle_pct.serve" not in mine
    assert {"batch_wait_mean_ms.serve", "device_phase_mean_ms.serve",
            "compile_s.setup", "restore_s.setup",
            "expert_load_max_over_mean.serve",
            "batch_tokens_fill_pct.serve"} <= mine
    from benchmarks import readers
    for name in mine:
        spec = cell.layer_metric_spec(name)
        assert spec["reader"] in readers.KINDS and spec["name"] == name
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "glm47-flash-pp8")
    assert entry["reduced"] == ["layers", "mtp", "weights"]
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_configuration_keeps_every_published_key(cell):
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    for key, value in published.items():
        assert cell.config[key] == value, key
    c = cell.config
    assert (c["layers"], c["mtp"], c["experts_held"], c["vocab_rows"]) == (
        6, 0, 64, 154880)
    assert set(c["reduced"]) == {"layers", "mtp", "weights"}
    assert reference_glm.num_params(c) == c["parameters"] == 3_895_625_536
    assert c["parameter_bytes"] == 2 * c["parameters"]
    held = c["serve"]["context_cache"]
    assert held["slots"] * held["tokens_per_slot"] * c["layers"] * 1152 \
        == 3_623_878_656
    assert "eight" in c["deployment"].lower() and "head" in c["deployment"]
    assert {"rotary_pairing", "initializers", "traffic"} <= set(c["assumed"])


def test_reference_leaves_are_the_programs(cell):
    from code2vec_tpu.models import latent_moe_lm as lm
    have = [(leaf.name, tuple(leaf.shape), leaf.dtype)
            for leaf in lm.leaf_specs(lm.LMConfig.from_dict(cell.config))]
    assert [w[:3] for w in reference_glm.all_leaves(cell.config)] == have
    a = reference_glm.make_leaf(5, cell.config, "layers.01.q_norm", (768,),
                                "float32", "ones")
    assert float(a.min()) == 1.0
    small = dict(cell.config, init_std={"default": 0.02, "q_b": 0.2})
    q_b = np.asarray(reference_glm.make_leaf(
        5, small, "layers.00.q_b", (64, 256), "bfloat16", "normal"),
        np.float32)
    other = np.asarray(reference_glm.make_leaf(
        5, small, "layers.00.o", (64, 256), "bfloat16", "normal"),
        np.float32)
    assert 0.17 < q_b.std() < 0.23 and 0.017 < other.std() < 0.023
    again = np.asarray(reference_glm.make_leaf(
        5, small, "layers.00.q_b", (64, 256), "bfloat16", "normal"),
        np.float32)
    assert (q_b == again).all() and not (q_b[:, :128] == 10 * other[:, :128]
                                         ).all()


def test_the_mix_draws_the_lengths_it_states(cell):
    """67 % of the questions ride the 256 bucket, the median in its
    middle; every context fits one slot; contexts by Zipf(1.0)."""
    from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
    pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
    serve = cell.config["serve"]
    buckets = parse_buckets(serve["length_buckets"], serve["token_budget"])
    assert buckets == (64, 128, 256, 512)
    lengths = np.asarray(pool["lengths"])
    share = {b: float(np.mean([bucket_for(n, buckets) == b
                               for n in lengths])) for b in buckets}
    assert 0.64 < share[256] < 0.70 and share[64] < 0.01
    assert 180 <= np.median(lengths) <= 204
    assert lengths.min() >= 32 and lengths.max() <= 512
    contexts = [len(c) for c in pool["contexts"]]
    assert len(contexts) == 32 and min(contexts) >= 8192
    assert max(contexts) <= serve["context_cache"]["tokens_per_slot"]
    asked = np.bincount(pool["context_of"], minlength=32) / len(lengths)
    assert 0.22 < asked[0] < 0.28 and asked[0] > asked[1] > asked[7]
    arrivals = loadgen.schedule(3, 20.0, cell.traffic)
    assert len(arrivals) >= 160
    picked = serve_lm_ctx.pick_checked(3, arrivals, pool, cell.traffic)
    asked_now = {pool["context_of"][a["body_index"]] for a in arrivals}
    on = [pool["context_of"][arrivals[i]["body_index"]] for i in picked]
    assert len(picked) == len(set(picked)) == 16 and len(set(on)) >= 8
    assert max(asked_now, key=lambda c: contexts[c]) in on


def test_the_floors_at_the_configurations_sizes(cell):
    """One row of 192 question tokens on 12,288 cached ones: the absorbed
    form is the cheaper, 0.10 TFLOP a layer; at 512 the expanded one;
    and the experts' floor is their weights read once."""
    c = cell.config
    q, held = 192, 12288
    pairs = q * held + q * (q + 1) // 2
    flops = roofline_glm.mla_attend_terms(c, q, held + q, pairs)[0]
    assert flops["flops"] == flops["absorbed_flops"] < flops["expanded_flops"]
    assert 0.09e12 < flops["flops"] < 0.11e12
    q = 512
    pairs = q * held + q * (q + 1) // 2
    flops = roofline_glm.mla_attend_terms(c, q, held + q, pairs)[0]
    assert flops["flops"] == flops["expanded_flops"] < flops["absorbed_flops"]
    floor = roofline_glm.mla_attend_floor(c, 192, 12480, 192 * 12384,
                                          "TPU v5 lite")
    assert floor["bound"] == "flops" and 0.4e-3 < floor["seconds"] < 0.6e-3
    experts = roofline_glm.moe_gated_experts_floor(c, 768, 64, "TPU v5 lite")
    assert experts["bound"] == "bytes"
    assert experts["bytes"] >= 64 * 3 * 2048 * 1536 * 2 == 1_207_959_552
    assert 1.4e-3 < experts["seconds"] < 1.6e-3
    few = roofline_glm.moe_gated_experts_floor(c, 4, 4, "TPU v5 lite")
    assert few["seconds"] < experts["seconds"] / 10
