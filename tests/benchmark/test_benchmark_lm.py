"""The language-model serve cell of the benchmark: its files load and say
what ISSUE 26 asked for, the runner kind `serve_lm` rehearses end to end
on the CPU at toy widths (new files and entries only), a tree without
the model gets no result at once, the reference's weights and its
lower-precision control behave, and the floors never pass the time a
chip at its peaks would take."""

import json
import os
import sys

import numpy as np
import pytest

from bench_testlib import ROOT, has_result_line, make_toy_root

from benchmarks import common, loadgen  # noqa: E402
from benchmarks import reference_lm, roofline_lm  # noqa: E402

CELL = "nemotron3-super-ep4.serve_score_open"
TINY = dict(
    hidden_size=64, pattern="MEM*E", vocab_size=512, vocab_rows=128,
    mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=32,
    conv_kernel=4, chunk_size=128, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=16, experts_held=4, expert_first=4,
    num_experts_per_tok=4, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=5.0,
    norm_eps=1e-5, layer_norm_epsilon=1e-5, n_routed_experts_published=16,
    serve={"length_buckets": [128, 256], "token_budget": 512, "top_k": 10})


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    dest = make_toy_root(str(tmp_path_factory.mktemp("lm") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-lm"), "configs", "toy-lm.json")
    with open(os.path.join(home, "traffic", "serve_score_open.json")) as f:
        mix = json.load(f)
    dump(dict(mix, name="toy_score", rate_per_s=12.0, request_pool=80,
              warm_requests=2, checked_requests=4, generator_threads=4,
              length={"distribution": "lognormal", "median": 90,
                      "sigma": 0.6, "min": 8, "max": 512}),
         "traffic", "toy_score.json")
    dump({"limits": {"served_top_logit_gap": 0.05,
                     "served_score_gap": 0.05,
                     "served_score_gap_median": 0.05}},
         "limits", "toy-lm.score.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-lm", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-lm.json"})
    bench["workloads"].append({"name": "toy-lm.score", "config": "toy-lm",
                               "traffic": "toy_score", "chips": 1,
                               "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-lm.score")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    from benchmarks.runners import serve_lm
    cell = common.Cell(toy_root, "toy-lm.score")
    return cell, serve_lm.run(cell, 2_600_000_123, 2.0, trace=False,
                              require_tpu=False, emit=False)


def test_lm_rehearsal_is_correct_and_serves_every_request(rehearsal):
    cell, result = rehearsal
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 24
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_score_gap"]["value"] < 0.05
    assert names["compiled_inside_window"]["value"] == 0
    assert result["values"]["request_p50_ms"] > 0
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))


def test_lm_rehearsal_router_facts_and_fill(rehearsal):
    """The program's counters give the runner what the new per-layer
    metrics read."""
    from code2vec_tpu import obs
    from benchmarks.runners import serve_lm
    window = common.RegistryWindow(obs.default_registry())
    window.close()          # from the process's start to now
    facts = serve_lm.router_facts(window)
    assert 0.0 <= facts["tokens_without_local_expert_pct"] <= 100.0
    assert facts["experts_hit_per_step_layer"] <= 4
    assert facts["assignments_per_step_layer"] <= 4 * facts["tokens_per_step"]
    fill = window.histogram("serving_batch_tokens_fill_ratio")
    assert fill and 0.0 < fill[0] / fill[1] <= 1.0
    load = window.histogram("moe_expert_load_max_over_mean")
    assert load and load[0] / load[1] >= 1.0


def test_an_altered_answer_reads_not_correct(rehearsal):
    from benchmarks.runners import serve_lm
    cell, _ = rehearsal
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in (40, 130)]
    ref = reference_lm.forward(7, cell.config, seqs)
    ids, logits = reference_lm.own_answers(ref["logits"], 10)
    served = {"sequences": seqs, "malformed": 0, "ids": ids,
              "logits": logits, "routing": ref["chosen_last"]}
    limits = cell.limits()
    sound = serve_lm.check_answers(cell, 7, served, limits)
    assert all(c["ok"] for c in sound)
    swapped = dict(served, ids=ids[:, ::-1].copy())
    bad = serve_lm.check_answers(cell, 7, swapped, limits)
    assert not all(c["ok"] for c in bad)
    none = serve_lm.check_answers(cell, 7, dict(served, sequences=[]), limits)
    assert not all(c["ok"] for c in none)


def test_a_tree_without_the_model_gets_no_result_at_once(toy_root,
                                                        monkeypatch):
    from benchmarks.runners import serve_lm
    monkeypatch.setitem(sys.modules, "code2vec_tpu.lm_facade", None)
    cell = common.Cell(toy_root, "toy-lm.score")
    with pytest.raises(common.NoResult) as e:
        serve_lm.run(cell, 1, 1.0, trace=False, require_tpu=False)
    assert e.value.code == 3


def test_the_runner_refuses_a_cpu_with_no_metric_line():
    import subprocess
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and not has_result_line(out.stdout)
    assert "needs 1 TPU chip" in out.stderr


def test_the_cells_files_say_what_was_asked():
    cell = common.Cell(ROOT, CELL)
    c, t = cell.config, cell.traffic
    assert cell.chips == 1 and cell.runner == "serve_lm"
    assert (c["pattern"], c["experts_held"], c["vocab_rows"]) == (
        "MEMEMEMEM*E", 128, 32768)
    # the published keys as published (the cut lives in keys of its own)
    published = {"hidden_size": 4096, "num_hidden_layers": 88,
                 "n_routed_experts": 512, "num_experts_per_tok": 22,
                 "vocab_size": 131072, "moe_intermediate_size": 2688,
                 "moe_latent_size": 1024, "mamba_num_heads": 128,
                 "moe_shared_expert_intermediate_size": 5376,
                 "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
                 "chunk_size": 128, "conv_kernel": 4, "head_dim": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "routed_scaling_factor": 5, "num_nextn_predict_layers": 1}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["hybrid_override_pattern"].count("M") == 40
    assert c["pattern"] in c["hybrid_override_pattern"]
    assert sorted(c["reduced"]) == sorted(
        next(e for e in cell.bench["configs"]
             if e["name"] == "nemotron3-super-ep4")["reduced"])
    assert t["length"] == {"distribution": "lognormal", "median": 1024,
                           "sigma": 0.8, "min": 128, "max": 8192}
    assert (t["id_zipf"], t["top_k"], t["request_pool"], t["warm_requests"],
            t["checked_requests"]) == (1.05, 10, 1024, 16, 32)
    assert t["rate_per_s"] == int(t["rate_per_s"])
    n = len(loadgen.schedule(3, 20.0 + 5.0, t))
    assert n <= t["request_pool"]
    assert {m["name"] for m in cell.end_to_end()} == {"request_p50_ms",
                                                      "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert {"lm_step_device_ms.serve", "moe_experts_roofline.serve",
            "ssd_scan_roofline.serve", "batch_tokens_fill_pct.serve",
            "expert_load_max_over_mean.serve",
            "tokens_without_local_expert_pct.serve", "compile_s.setup",
            "restore_s.setup", "compiles_in_window.serve"} <= mine
    assert not {"vocab_load_s.setup", "state_init_s.setup",
                "extract_mean_ms.serve", "serve_step_device_ms.serve"} & mine
    for name in mine:
        assert cell.layer_metric_spec(name)["reader"]
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}


def test_the_traffic_is_the_stated_distribution():
    from benchmarks.runners import serve_lm
    t = common.Cell(ROOT, CELL).traffic
    lengths = serve_lm.request_lengths(np.random.default_rng(1), 200_000, t)
    assert lengths.min() == 128 and lengths.max() == 8192
    assert abs(np.median(lengths) - 1024) < 15
    assert 1340 < lengths.mean() < 1440
    assert 0.003 < (lengths == 8192).mean() < 0.008


def test_program_and_reference_name_the_same_leaves():
    from code2vec_tpu.models import hybrid_lm
    c = common.Cell(ROOT, CELL).config
    program = hybrid_lm.leaf_specs(hybrid_lm.LMConfig.from_file(
        os.path.join(ROOT, "benchmarks", "configs",
                     "nemotron3-super-ep4.json")))
    assert [(n, tuple(s), d) for n, s, d, _ in reference_lm.all_leaves(c)] \
        == [(leaf.name, tuple(leaf.shape), leaf.dtype) for leaf in program]


def test_seeded_leaves_are_a_function_of_seed_name_and_index():
    c = dict(TINY)
    big = 2 ** 31 + 12345
    a = reference_lm.make_leaf(big, c, "layers.01.w1", (4, 32, 48),
                               "bfloat16", "normal")
    again = reference_lm.make_leaf(big, c, "layers.01.w1", (4, 32, 48),
                                   "bfloat16", "normal")
    assert (np.asarray(a, np.float32) == np.asarray(again, np.float32)).all()
    other = reference_lm.make_leaf(big + 1, c, "layers.01.w1", (4, 32, 48),
                                   "bfloat16", "normal")
    assert (np.asarray(a, np.float32) != np.asarray(other, np.float32)).any()
    # a share's experts are rows of one whole layer's leaf
    whole = reference_lm.make_leaf(big, dict(c, expert_first=0),
                                   "layers.01.w1", (16, 32, 48), "bfloat16",
                                   "normal")
    assert (np.asarray(whole, np.float32)[4:8]
            == np.asarray(a, np.float32)).all()
    flat = np.asarray(whole, np.float32).ravel()
    assert abs(flat.std() - 0.02) < 0.001 and abs(flat.mean()) < 0.001
    dt = reference_lm.make_leaf(big, c, "layers.00.dt_bias", (4096,),
                                "float32", "dt_bias")
    soft = np.log1p(np.exp(np.asarray(dt)))
    assert 0.00099 < soft.min() and soft.max() < 0.1001


def test_the_control_is_further_from_the_reference_than_it_is_from_itself():
    c = dict(TINY)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in (60, 200)]
    ref = reference_lm.forward(11, c, seqs)
    low = reference_lm.forward(11, c, seqs, lower=True)
    same = reference_lm.served_gap(ref["logits"], *reference_lm.own_answers(
        ref["logits"], 10))
    assert same == {"top_gap": 0.0, "score_gap": 0.0,
                    "score_gap_median": 0.0}
    gap = reference_lm.served_gap(ref["logits"], *reference_lm.own_answers(
        low["logits"], 10))
    assert gap["score_gap"] >= gap["score_gap_median"] > 1e-3
    assert 0.0 <= reference_lm.same_expert_sets(
        ref["chosen_last"], low["chosen_last"]) <= 1.0


@pytest.mark.parametrize("tokens,per_token,hit", [
    (128, 0.2, 20), (1024, 5.5, 128), (8192, 5.5, 128), (8192, 22.0, 128)])
def test_floors_never_pass_the_time_of_a_chip_at_its_peaks(tokens, per_token,
                                                           hit):
    """A synthetic trace whose scopes take exactly what a chip at both
    published peaks at once would take: the shares read at most 100."""
    from benchmarks.runners import serve_lm
    from benchmarks import trace_scopes
    cell = common.Cell(ROOT, CELL)
    kind = "TPU v5 lite"
    assign = tokens * per_token
    moe = roofline_lm.moe_experts_floor(cell.config, tokens, assign, hit,
                                        kind)
    ssd = roofline_lm.ssd_scan_floor(cell.config, tokens, kind)
    for floor in (moe, ssd):
        assert floor["seconds"] == max(floor["seconds_by_flops"],
                                       floor["seconds_by_bytes"]) > 0
    runs = 7

    class Window:
        _open = {}
        _close = {("moe_tokens_routed_total", ()): (tokens * 5.0 * runs, None),
                  ("moe_tokens_without_local_expert_total", ()): (0.0, None),
                  ("moe_local_assignments_total", ()): (assign * 5 * runs,
                                                        None),
                  ("moe_experts_hit_total", ()): (hit * 5.0 * runs, None)}

        def histogram(self, name, labels=None):
            return (1.0, 5 * runs)
    at_peaks = {"runs": runs, "seconds": {
        "moe_experts": runs * 5 * (moe["seconds_by_flops"]
                                   + moe["seconds_by_bytes"]),
        "ssd_scan": runs * 5 * (ssd["seconds_by_flops"]
                                + ssd["seconds_by_bytes"])}}
    import unittest.mock
    with unittest.mock.patch.object(trace_scopes, "scope_seconds",
                                    return_value=at_peaks):
        facts = serve_lm.roofline_facts(cell, kind, "unused", Window())
    assert 0 < facts["moe_experts_roofline"] <= 100.0
    assert 0 < facts["ssd_scan_roofline"] <= 100.0
    with unittest.mock.patch.object(trace_scopes, "scope_seconds",
                                    return_value=None):
        assert serve_lm.roofline_facts(cell, kind, "unused", Window()) == {}


def test_forward_flops_are_the_issues_estimate():
    c = common.Cell(ROOT, CELL).config
    by_kind = roofline_lm.forward_flops_per_token(c, 700)
    total = sum(by_kind.values())
    assert 2.0e9 < total < 2.1e9
    assert 0.50 < by_kind["M"] / total < 0.58
    assert 0.38 < by_kind["E"] / total < 0.44


def test_score_answers_well_formed():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import importlib
    lm_gen = importlib.import_module("benchmarks.loadgen_lm")
    good = {"tokens": 3, "top": [{"id": 1, "logit": 0.5, "probability": 0.1},
                                 {"id": 2, "logit": 0.1, "probability": 0.05}]}
    assert lm_gen.well_formed(good) == 2
    assert lm_gen.well_formed({"methods": []}) == -1
    assert lm_gen.well_formed(dict(good, top=good["top"] * 2)) == -1
    bad = {"tokens": 3, "top": [{"id": 1, "logit": float("nan"),
                                 "probability": 0.1}]}
    assert lm_gen.well_formed(bad) == -1
    assert loadgen.well_formed(good) == -1      # the shim patches only
    #                                             its own process
