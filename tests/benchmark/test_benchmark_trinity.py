"""The mixed-context cell of the benchmark: its files load and say what
ISSUE 41 asked for, the runner kind `serve_lm_paged_ctx` rehearses end
to end on the CPU at toy widths (new files and entries only), a tree
whose program lacks the model gets no result at once, the class draw
and the instants are the same for every seed, the reference's weights,
its lower-precision control and the seven faults behave, and the floors
are the hand counts."""

import json
import os

import numpy as np
import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common  # noqa: E402
from benchmarks import reference_lm, reference_trinity, roofline_trinity  # noqa: E402
from benchmarks import loadgen  # noqa: E402
from benchmarks.runners import serve_lm_ctx, serve_lm_paged_ctx  # noqa: E402

CELL = "trinity-mini-pp4.serve_score_mixedctx_open"
BROUGHT = ("full_attend_roofline.serve", "window_attend_roofline.serve",
           "attention_share_of_step_pct.serve", "page_pool_fill_pct.serve",
           "pages_visited_over_needed.serve")
W = 8
TINY = dict(
    model_type="afmoe", hidden_size=64, num_hidden_layers=16, layers=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 4,
    sliding_window=W, global_attn_every_n_layers=4, num_dense_layers=2,
    vocab_size=256, vocab_rows=128, max_position_embeddings=512,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000, rope_scaling=None, intermediate_size=96,
    # FOUR experts: the facade counts the experts hit on process-wide
    # series that tests/benchmark/test_benchmark_lm.py reads whole and
    # holds to its own toy's four
    moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
    num_shared_experts=1, route_norm=True, route_scale=2.826,
    score_func="sigmoid", n_group=1, topk_group=1, mup_enabled=True,
    tie_word_embeddings=False, rms_norm_eps=1e-5, experts_held=4,
    expert_first=0, init_std={"default": 0.02, "router_bias": 0.01},
    init_gain={"q_norm": 2.0},
    serve={"length_buckets": [16], "token_budget": 32, "top_k": 10,
           "context_cache": {"slots": 6, "pages": 40, "tokens_per_slot": 96,
                             "register_chunk": W}})
# an expert chosen otherwise on a near-tie moves one toy sequence's
# logits by a step (tests/test_window_moe_lm.py): the widest gap swings,
# the median does not
TOY_LIMITS = {"served_top_logit_gap": 0.3, "served_score_gap": 0.5,
              "served_score_gap_median": 0.05}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    dest = make_toy_root(str(tmp_path_factory.mktemp("trinity") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-trinity"), "configs", "toy-trinity.json")
    mix = common.load_json(os.path.join(home, "traffic",
                                        "serve_score_mixedctx_open.json"))
    classes = [dict(mix["context_classes"][0], contexts=4, length={
                   "distribution": "lognormal", "median": 12, "sigma": 0.4,
                   "min": 5, "max": 24}),
               dict(mix["context_classes"][1], contexts=2, length={
                   "distribution": "lognormal", "median": 70, "sigma": 0.15,
                   "min": 40, "max": 96})]
    dump(dict(mix, name="toy_mixed", rate_per_s=4.0, request_pool=60,
              warm_requests=2, checked_requests=5, checked_contexts=3,
              checked_modules=2, generator_threads=8,
              context_classes=classes,
              length={"distribution": "lognormal", "median": 10,
                      "sigma": 0.3, "min": 4, "max": 16}),
         "traffic", "toy_mixed.json")
    dump({"limits": TOY_LIMITS}, "limits", "toy-trinity.mixed.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-trinity", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-trinity.json"})
    bench["workloads"].append({"name": "toy-trinity.mixed",
                               "config": "toy-trinity",
                               "traffic": "toy_mixed", "chips": 1,
                               "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-trinity.mixed")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # toy sequences of ~80 tokens padded to the cell's 4,096 would be
    # all padding: the reference's time on the CPU, nothing else
    kept = reference_trinity.PAD_TO, reference_trinity.QUERY_BLOCK
    reference_trinity.PAD_TO, reference_trinity.QUERY_BLOCK = 32, 8
    yield dest
    reference_trinity.PAD_TO, reference_trinity.QUERY_BLOCK = kept


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    cell = common.Cell(toy_root, "toy-trinity.mixed")
    return cell, serve_lm_paged_ctx.run(
        cell, 2_600_000_123, 3.0, trace=False, require_tpu=False, emit=False)


def test_paged_ctx_rehearsal_is_correct_and_serves_every_request(rehearsal):
    cell, result = rehearsal
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 12
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_requests_checked"]["value"] == 5
    assert names["served_contexts_checked"]["value"] >= 3
    assert names["served_modules_checked"]["value"] == 2
    assert names["compiled_inside_window"]["value"] == 0
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))
    ids = common.load_json(os.path.join(cell.work, "data",
                                        "context_ids.json"))
    assert len(set(ids)) == 6


def test_paged_ctx_counters_feed_the_new_metrics(rehearsal):
    """The program's series give the readers what the shared and the new
    per-layer metrics read; without a trace the device shares are left
    out."""
    from code2vec_tpu import obs
    from benchmarks import readers
    cell, _ = rehearsal
    window = common.RegistryWindow(obs.default_registry())
    window.close()          # from the process's start to now
    pool = serve_lm_paged_ctx.make_pool(cell.config, cell.traffic)
    pages = sum(-(-len(c) // W) for c in pool["contexts"])
    assert window.gauge("page_pool_pages_held") == pages
    assert window.gauge("page_pool_fill_ratio") == pages / 40
    assert window.gauge("window_ring_slots_held") == 6
    delta = serve_lm_paged_ctx.serve_lm.counter_delta
    needed = delta(window, "score_pages_needed_total")
    visited = delta(window, "score_pages_visited_total")
    assert visited >= needed > 0 and needed % 2 == 0
    assert delta(window, "score_full_keys_read_total") > delta(
        window, "score_window_keys_read_total") / 3 > 0
    facts = serve_lm_paged_ctx.pool_facts(window)
    assert facts["pages_visited_over_needed"] == visited / needed >= 1.0
    total, count = serve_lm_ctx.registry_total("context_register_seconds")
    assert count >= 6 and total > 0
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", window, window_s=3.0,
        facts=dict(facts, context_register_ms=1e3 * total / count)))
    assert got["page_pool_fill_pct.serve"] == 100.0 * pages / 40
    assert got["pages_visited_over_needed.serve"] >= 1.0
    assert got["expert_load_max_over_mean.serve"] >= 1.0
    assert got["batch_tokens_fill_pct.serve"] > 0
    assert not {"full_attend_roofline.serve", "window_attend_roofline.serve",
                "attention_share_of_step_pct.serve"} & set(got)
    assert set(got) <= {m["name"] for m in cell.per_layer()}
    assert serve_lm_paged_ctx.paged_facts(
        cell, "TPU v5 lite", os.path.join(cell.work, "no_trace"), window,
        [], pool) == {}


def _served(cell, seed, n=3):
    """Answers the reference itself would give: two on a module, one on
    a file."""
    pool = serve_lm_paged_ctx.make_pool(cell.config, cell.traffic)
    contexts = [4, 0, 5][:n]
    seqs = [np.concatenate([pool["contexts"][c], pool["questions"][i]])
            for i, c in enumerate(contexts)]
    ref = reference_trinity.forward(seed, cell.config, seqs)
    ids, logits = reference_lm.own_answers(ref["logits"], 10)
    return pool, ref, {
        "sequences": seqs, "contexts": contexts, "malformed": 0, "ids": ids,
        "logits": logits, "routing": ref["chosen_last"]}


def test_an_altered_answer_or_too_few_modules_reads_not_correct(rehearsal):
    cell, _ = rehearsal
    limits = cell.limits()
    pool, _, served = _served(cell, 7)
    few = dict(cell.traffic, checked_requests=3, checked_contexts=3)
    cell.traffic, kept = few, cell.traffic
    try:
        sound = serve_lm_paged_ctx.check_answers(cell, 7, served, limits,
                                                 pool)
        assert all(c["ok"] for c in sound), sound
        swapped = dict(served, ids=served["ids"][:, ::-1].copy())
        assert not all(c["ok"] for c in serve_lm_paged_ctx.check_answers(
            cell, 7, swapped, limits, pool))
        cell.traffic = dict(few, checked_modules=3)
        short = serve_lm_paged_ctx.check_answers(cell, 7, served, limits,
                                                 pool)
        assert not {c["name"]: c for c in short}[
            "served_modules_checked"]["ok"]
    finally:
        cell.traffic = kept


def test_the_control_and_every_fault_are_other_answers(rehearsal):
    """At toy widths: the control's own answers and each of the seven
    faults move the logits past what the reference reads against itself,
    each reading taken through the runner's own `check_answers`."""
    from benchmarks.control_trinity import (
        FAULTS, fault_runs, reading, variant_readings,
    )
    cell, _ = rehearsal
    limits = cell.limits()
    pool, ref, served = _served(cell, 11)
    few = dict(cell.traffic, checked_requests=3, checked_contexts=3)
    cell.traffic, kept = few, cell.traffic
    try:
        own = reading(cell, 11, served, pool, limits, ref)
        assert own["correct"] and own["fails"] == []
        assert own["served_score_gap"] < 1e-5
        assert own["served_top_logit_gap"] == 0.0
        assert len(own["score_gap_by_request"]) == len(served["sequences"])
        runs = fault_runs(pool, served["sequences"], served["contexts"])
        assert sorted(runs) == sorted(FAULTS) and len(FAULTS) == 7
        got = dict(variant_readings(cell, 11, served, pool, limits, ref,
                                    ("control",) + FAULTS))
        assert sorted(got) == sorted(
            ("control", "foreign_pages_modules_only") + FAULTS)
        for variant, row in got.items():
            assert row["served_score_gap"] > 0.01, (variant, row)
        # the fault in the requests on modules alone: the other requests
        # read what they read sound
        last = len(cell.traffic["context_classes"]) - 1
        for c, alone, everywhere, sound in zip(
                served["contexts"],
                got["foreign_pages_modules_only"]["score_gap_by_request"],
                got["foreign_pages"]["score_gap_by_request"],
                own["score_gap_by_request"]):
            assert alone == (everywhere if pool["class_of"][c] == last
                             else sound)
        # past its time it starts no forward, and says what it left out
        assert dict(variant_readings(
            cell, 11, served, pool, limits, ref, FAULTS[:2],
            go_on=lambda: False)) == dict.fromkeys(FAULTS[:2])
    finally:
        cell.traffic = kept


def test_the_benchmarks_reference_is_the_packages(rehearsal):
    """Two plain forms written apart: the benchmark's own (query blocks,
    four stretches, layer by layer) and the package's
    (`window_moe_lm_reference`), over the seed's weights."""
    from code2vec_tpu.models import window_moe_lm, window_moe_lm_reference
    cell, _ = rehearsal
    cfg = window_moe_lm.LMConfig.from_dict(cell.config)
    params = {name: reference_trinity.make_leaf(3, cell.config, name, shape,
                                                dtype, init)
              for name, shape, dtype, init in reference_trinity.all_leaves(
                  cell.config)}
    assert float(abs(params["layers.02.router_bias"]).max()) > 0
    assert float(params["layers.00.q_norm"][0]) == 2.0
    sequence = np.random.RandomState(1).randint(0, 128, (90,)).astype(
        np.int32)
    np.testing.assert_allclose(
        reference_trinity.forward(3, cell.config, [sequence])["logits"][0],
        window_moe_lm_reference.logits(cfg, params, sequence)[0], atol=2e-5)
    assert reference_trinity.pattern(cell.config) == cfg.pattern


def test_a_tree_without_the_model_gets_no_result_at_once(toy_root,
                                                         monkeypatch):
    from code2vec_tpu import lm_facade
    cell = common.Cell(toy_root, "toy-trinity.mixed")
    monkeypatch.delitem(lm_facade.MODEL_MODULES, "afmoe")
    with pytest.raises(common.NoResult) as e:
        serve_lm_paged_ctx.run(cell, 1, 1.0, trace=False,
                               require_tpu=False)
    assert e.value.code == 3


# ------------------------------------------------------ the cell's own files

@pytest.fixture(scope="module")
def cell():
    return common.Cell(ROOT, CELL)


def test_the_cell_its_files_and_limits_load(cell):
    assert cell.runner == "serve_lm_paged_ctx" and cell.chips == 1
    assert cell.run_module() is serve_lm_paged_ctx
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}
    assert [m["name"] for m in cell.end_to_end()] == ["request_p50_ms",
                                                      "setup_s"]
    mine = {m["name"] for m in cell.per_layer()}
    listed = {m["name"]: m for m in cell.bench["per_layer"]}
    for name in BROUGHT:
        assert listed[name]["workloads"] == [CELL]
        # the pool's fill is a constant of set-up: it moves no tail
        assert listed[name]["moves"] == (
            "setup_s" if name == "page_pool_fill_pct.serve"
            else "request_p50_ms")
    # held with <=, never ==: a later PR that lists the cell under one
    # more metric breaks nothing here
    assert set(BROUGHT) | {
        "batch_wait_mean_ms.serve", "device_phase_mean_ms.serve",
        "generator_late_p95_ms.serve", "request_p95_ms.serve",
        "restore_s.setup", "compile_s.setup", "batch_device_ms.serve",
        "batch_render_ms.serve", "batch_fill_pct.serve",
        "dispatcher_busy_pct.serve", "compiles_in_window.serve",
        "batch_tokens_fill_pct.serve",
        "expert_load_max_over_mean.serve"} <= mine
    # tokens a slot mean nothing here, nor a selection or a state
    assert not {"latent_cache_fill_pct.serve", "keys_selected_pct.serve",
                "retention_read_roofline.serve"} & mine
    from benchmarks import readers
    for name in mine:
        spec = cell.layer_metric_spec(name)
        assert spec["reader"] in readers.KINDS and spec["name"] == name
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "trinity-mini-pp4")
    assert entry["reduced"] == ["layers", "weights"]
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200
    assert [w["name"] for w in cell.bench["workloads"]].count(CELL) == 1
    four = [w for w in cell.bench["workloads"] if w["chips"] == 4]
    assert len(cell.bench["workloads"]) >= 10 and len(four) == 1


def test_the_configuration_keeps_every_published_key(cell):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Trinity-Mini")
    for key, value in row["config"].items():
        assert cell.config[key] == value, key
    c = cell.config
    assert c["source"] == row["source_url"]
    assert (c["layers"], c["vocab_rows"], c["experts_held"]) == (
        8, 200192, 128)
    assert set(c["reduced"]) == {"layers", "weights"}
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    dense = attention + 4 * 2048 + 3 * 2048 * 6144
    expert = (attention + 4 * 2048 + 2048 * 128 + 128
              + 129 * 3 * 2048 * 1024)
    assert (attention, dense, expert) == (27_263_232, 65_020_160,
                                          839_131_520)
    assert reference_trinity.num_params(c) == c["parameters"] == (
        2 * dense + 6 * expert + 2 * 200192 * 2048 + 2048) == 5_984_817_920
    assert c["parameter_bytes"] == 2 * c["parameters"]
    assert reference_trinity.pattern(c) == "wD wD wE fE wE wE wE fE"
    assert reference_trinity.cache_bytes(c) == c["cache_bytes"] == {
        "rings": 20 * 6 * 2048 * 1024 * 2, "pages": 160 * 2 * 2048 * 1024 * 2}
    serve = c["serve"]
    assert serve["length_buckets"] == [64, 128, 256]
    assert serve["token_budget"] == 256 and serve["top_k"] == 10
    held = serve["context_cache"]
    assert held == {"slots": 20, "pages": 160, "tokens_per_slot": 65536,
                    "register_chunk": 2048}
    assert held["register_chunk"] == c["sliding_window"]
    assert "FOUR pipeline stages" in c["deployment"]
    assert "DEPARTURE" in c["deployment"] and "sixteen" in c["training"]
    assert {"attention_gate", "head_norms", "rotary", "norms", "mup",
            "router", "initializers"} <= set(c["assumed"])
    assert c["init_gain"] == {"q_norm": 2.0}
    assert c["init_std"]["w_down"] == 0.01 and c["init_std"]["router_bias"] > 0


def test_the_mix_is_two_classes_drawn_once_for_every_seed(cell):
    t = cell.traffic
    files, modules = t["context_classes"]
    assert (files["contexts"], modules["contexts"]) == (16, 4)
    assert (files["share"], modules["share"]) == (0.35, 0.65)
    assert files["length"] == {"distribution": "lognormal", "median": 3072,
                               "sigma": 0.4, "min": 1024, "max": 8192}
    assert modules["length"] == {"distribution": "lognormal",
                                 "median": 49152, "sigma": 0.15,
                                 "min": 32768, "max": 65536}
    assert (t["checked_requests"], t["checked_contexts"],
            t["checked_modules"]) == (12, 4, 2)
    assert t["program_args"] == ["--serve_deadline_ms", "30000"]
    pool = serve_lm_paged_ctx.make_pool(cell.config, t)
    again = serve_lm_paged_ctx.make_pool(cell.config, t)
    assert pool["context_of"] == again["context_of"]
    lengths = [len(c) for c in pool["contexts"]]
    assert pool["class_of"] == [0] * 16 + [1] * 4
    assert all(1024 <= n <= 8192 for n in lengths[:16])
    assert all(32768 <= n <= 65536 for n in lengths[16:])
    pages = sum(-(-n // 2048) for n in lengths)
    held = cell.config["serve"]["context_cache"]
    # the pool holds what the contexts need and a tenth more
    assert pages * 1.1 <= held["pages"] and len(lengths) == held["slots"]
    asked = np.asarray(pool["context_of"][:t["request_pool"]])
    assert 0.62 < (asked >= 16).mean() < 0.68
    # the class's first context the most asked (Zipf)
    counts = np.bincount(asked, minlength=20)
    assert counts[16] == counts[16:].max() and counts[0] == counts[:16].max()
    # the same instants and questions for every seed, in another order
    a, b = (loadgen.schedule(seed, 20.0, t) for seed in (1, 2**31 + 5))
    assert [x["due_s"] for x in a] == [x["due_s"] for x in b]
    assert sorted(x["body_index"] for x in a) == sorted(
        x["body_index"] for x in b) == list(range(len(a)))
    assert [x["body_index"] for x in a] != [x["body_index"] for x in b]
    picked = serve_lm_paged_ctx.pick_checked(3, a, pool, t)
    classes = [pool["class_of"][pool["context_of"][a[i]["body_index"]]]
               for i in picked]
    assert len(picked) == 12 and classes == [1, 1] + [0] * 10
    contexts = {pool["context_of"][a[i]["body_index"]] for i in picked}
    assert len(contexts) >= 4


def test_the_floors_are_the_hand_counts(cell):
    c = cell.config
    # one row of 100 question tokens behind 50,000 cached ones
    counts = roofline_trinity.row_counts(50_000, 100, 2048)
    assert counts == {"full_keys": 50_100,
                      "full_pairs": 100 * 50_000 + 5050,
                      "window_keys": 2047 + 100, "window_pairs": 100 * 2048}
    # and behind 1,000: the window is not full until the 1,048th key
    short = roofline_trinity.row_counts(1000, 100, 2048)
    assert short["window_pairs"] == short["full_pairs"] == 100 * 1000 + 5050
    assert short["window_keys"] == 1100
    full = roofline_trinity.attend_floor(
        c, 100, counts["full_keys"], counts["full_pairs"], "TPU v5 lite")
    assert full["flops"] == 4 * 128 * 32 * counts["full_pairs"]
    assert full["bytes"] == 50_100 * 2048 + 100 * 32 * 128 * 4
    assert full["bound"] == "flops"
    window = roofline_trinity.attend_floor(
        c, 100, counts["window_keys"], counts["window_pairs"], "TPU v5 lite")
    assert window["bytes"] == 2147 * 2048 + 100 * 32 * 128 * 4
    step = roofline_trinity.score_step_floor(
        c, 1, 100, {"keys": counts["window_keys"],
                    "pairs": counts["window_pairs"]},
        {"keys": counts["full_keys"], "pairs": counts["full_pairs"]},
        800, 110, "TPU v5 lite")
    every = (8 * (3 * 2048 * 4096 + 2 * 2048 * 512) + 2 * 3 * 2048 * 6144
             + 6 * (2048 * 128 + 3 * 2048 * 1024))
    assert step["bytes"] == (
        every * 2 + 200192 * 2048 * 2 + 100 * 2048 * 2
        + 6 * window["bytes"] + 2 * full["bytes"]
        + 6 * (110 * 3 * 2048 * 1024 * 2 + 800 * 2048 * 6))
    assert step["flops"] == (
        2 * every * 100 + 2 * 200192 * 2048 + 6 * window["flops"]
        + 2 * full["flops"] + 6 * 2 * 3 * 800 * 2048 * 1024)
    assert step["bound"] == "bytes"
