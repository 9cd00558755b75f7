"""The session-turns cell of the benchmark: its files load and say what
ISSUE 45 asked for, the runner kind `serve_lm_session_ctx` rehearses end
to end on the CPU at toy widths (new files and entries only: sessions
registered, turns chained by the ids the answers carry, every session's
tokens laid end to end, the checked turns read from ONE reference
forward a session), a tree whose program lacks the model gets no result
at once, the instants, sessions and lengths are the same for every seed
and keep the 250 ms rule, the reference's weights, its lower-precision
control and the nine faults behave, and the floors are the hand
counts."""

import json
import os

import numpy as np
import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common  # noqa: E402
from benchmarks import reference_lm, reference_solar, roofline_solar  # noqa: E402
from benchmarks.runners import serve_lm_session_ctx as runner  # noqa: E402

CELL = "solar-open2-ep8.serve_score_session_turns"
# the per-layer metrics this cell brings as FILES; their BENCHMARK.json
# entries wait for a `benchmark` PR (PERF.md section 7 says why)
BROUGHT = ("delta_rule_roofline.serve", "session_attend_roofline.serve",
           "held_experts_roofline.serve", "extend_step_mfu.serve",
           "delta_share_of_step_pct.serve", "session_pool_fill_pct.serve",
           "extend_tokens_per_s.serve")
P = 8
TINY = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=8, layers=4,
    gqa_layers=[0, 4], gqa_interval=3, vocab_size=256, vocab_rows=128,
    max_position_embeddings=4096, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, use_rope=False, use_gqa_gate=True,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    intermediate_size=96, moe_intermediate_size=32,
    # FOUR experts: the facade counts the experts hit on process-wide
    # series that tests/benchmark/test_benchmark_lm.py reads whole and
    # holds to its own toy's four
    n_routed_experts=4, experts_held=4, expert_first=0,
    num_experts_per_tok=2, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, first_k_dense_replace=0,
    tie_word_embeddings=False, rms_norm_eps=1e-5, rope_theta=10000,
    partial_rotary_factor=1,
    init_std={"default": 0.02, "router_bias": 0.01, "conv_w": 0.5},
    init_spread={"a_log": [1.0, 16.0], "dt_bias": [0.001, 0.1]},
    serve={"length_buckets": [16], "token_budget": 32, "top_k": 10,
           "context_cache": {"slots": 5, "pages": 48, "tokens_per_slot": 128,
                             "register_chunk": P}})
TOY_LIMITS = {"served_top_logit_gap": 0.3, "served_score_gap": 0.5,
              "served_score_gap_median": 0.1}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    from code2vec_tpu.models import delta_moe_lm
    dest = make_toy_root(str(tmp_path_factory.mktemp("solar") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-solar"), "configs", "toy-solar.json")
    mix = common.load_json(os.path.join(home, "traffic",
                                        "serve_score_session_turns.json"))
    dump(dict(mix, name="toy_turns", rate_per_s=4.0, request_pool=40,
              warm_requests=3, sessions=4, checked_turns=4,
              checked_sessions=2, checked_last=2, reference_tokens=400,
              generator_threads=8,
              session_length={"distribution": "lognormal", "median": 30,
                              "sigma": 0.4, "min": 12, "max": 60},
              length={"distribution": "lognormal", "median": 7,
                      "sigma": 0.4, "min": 3, "max": 16}),
         "traffic", "toy_turns.json")
    dump({"limits": TOY_LIMITS}, "limits", "toy-solar.turns.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-solar", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-solar.json"})
    bench["workloads"].append({"name": "toy-solar.turns",
                               "config": "toy-solar", "traffic": "toy_turns",
                               "chips": 1, "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-solar.turns")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # toy sequences of ~60 tokens padded to the cell's 16,384 would be
    # all padding: the reference's time on the CPU, nothing else
    kept = (reference_solar.PAD_TO, reference_solar.QUERY_BLOCK,
            reference_solar.TOKEN_BLOCK, reference_solar.SEGMENTS,
            delta_moe_lm.CHUNK)
    (reference_solar.PAD_TO, reference_solar.QUERY_BLOCK,
     reference_solar.TOKEN_BLOCK, reference_solar.SEGMENTS,
     delta_moe_lm.CHUNK) = 32, 8, 16, 2, P
    yield dest
    (reference_solar.PAD_TO, reference_solar.QUERY_BLOCK,
     reference_solar.TOKEN_BLOCK, reference_solar.SEGMENTS,
     delta_moe_lm.CHUNK) = kept


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    cell = common.Cell(toy_root, "toy-solar.turns")
    return cell, runner.run(cell, 2_600_000_123, 3.0, trace=False,
                            require_tpu=False, emit=False)


def test_session_rehearsal_is_correct_and_keeps_every_turn(rehearsal):
    cell, result = rehearsal
    assert result["correct"], [c for c in result["checks"] if not c["ok"]]
    assert result["failed"] == 0 and result["attempted"] == 12
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_turns_checked"]["value"] == 4
    assert names["served_sessions_checked"]["value"] == 2
    assert names["served_last_turns_checked"]["value"] == 2
    assert names["served_questions_checked"]["value"] == 2
    for zero in ("compiled_inside_window", "sessions_evicted",
                 "turns_refused_or_lost", "ids_named_twice",
                 "answers_malformed"):
        assert names[zero]["value"] == 0, zero
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))
    results = common.load_json(os.path.join(cell.work, "results.json"))
    plan = common.load_json(os.path.join(cell.work, "plan.json"))
    # every turn named the id its session's turn before answered with
    now = list(plan["sessions"])
    for r, req in zip(results, plan["requests"]):
        assert r["named"] == now[req["session"]] and r["status"] == 200
        assert r["context"] not in now
        now[req["session"]] = r["context"]


def test_a_turn_that_comes_due_early_waits_and_is_timed_from_its_due_instant(
        rehearsal):
    """The generator alone against a stub server: the second turn of a
    session is due while the first is still being answered."""
    import http.server
    import threading
    import time
    from benchmarks import loadgen_sessions
    seen = []

    class Stub(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])))
            seen.append((time.perf_counter(), body["context"],
                         body.get("keep", False)))
            time.sleep(0.3)
            answer = {"tokens": len(body["ids"]), "top": [
                {"id": 1, "logit": 0.5, "probability": 0.5}]}
            if body.get("keep"):
                answer["context"] = body["context"] + "+"
            raw = json.dumps(answer).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

        def log_message(self, *args):
            pass
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    turn = {"ids": [1, 2], "top_k": 1, "keep_body": False}
    plan = {"port": server.server_address[1], "deadline_ms": 5000.0,
            "threads": 4, "sessions": ["a", "b"], "requests": [
                dict(turn, due_s=0.0, session=0, keep=True),
                dict(turn, due_s=0.1, session=0, keep=True),
                dict(turn, due_s=0.1, session=1, keep=True),
                dict(turn, due_s=0.8, session=0, keep=False)]}
    results = loadgen_sessions.run(plan, time.time() + 0.05)
    server.shutdown()
    assert [r["named"] for r in results] == ["a", "a+", "b", "a++"]
    assert [r["context"] for r in results] == ["a+", "a++", "b+", None]
    assert all(r["ok"] for r in results)
    # the early one: sent some 0.2 s late, and that wait is in its time
    assert results[1]["late_ms"] > 150 and results[1]["latency_ms"] > 450
    assert results[2]["late_ms"] < 100 and results[2]["latency_ms"] < 450
    assert [keep for _, _, keep in seen] == [True, True, True, False] \
        or sorted(keep for _, _, keep in seen) == [False, True, True, True]


def _served(cell, seed):
    """Two short sessions with kept turns, served by the program in this
    process, as `served_answers` hands them to the checks."""
    serving = runner.ServingSessions(cell, seed, require_tpu=False)
    try:
        arrivals = runner.turn_schedule(seed, 3.0, cell.traffic)
        drove = serving.drive(arrivals)
        picked = runner.pick_checked(seed, serving, drove["results"],
                                     cell.traffic)
        questions = {}
        for j, s in enumerate(list(picked)[:2]):
            asked = serving.pool["blocks"][-1 - j][:5]
            questions[s] = (asked, serving.ask(s, asked, keep=False))
        return serving, runner.served_answers(serving, drove["results"],
                                              picked, questions)
    finally:
        serving.close()


@pytest.fixture(scope="module")
def served(rehearsal):
    cell, _ = rehearsal
    return _served(cell, 2_600_000_321)


def test_an_altered_answer_or_too_few_turns_reads_not_correct(rehearsal,
                                                               served):
    cell, _ = rehearsal
    _, sound = served
    checks = runner.check_answers(cell, 2_600_000_321, sound, TOY_LIMITS)
    assert all(c["ok"] for c in checks), [c for c in checks if not c["ok"]]
    wrong = dict(sound, logits=sound["logits"] + np.linspace(
        0.0, 0.5, sound["logits"].shape[1], dtype=np.float32))
    failed = [c["name"] for c in runner.check_answers(
        cell, 2_600_000_321, wrong, TOY_LIMITS) if not c["ok"]]
    assert "served_score_gap" in failed
    few = [c["name"] for c in runner.check_answers(
        cell, 2_600_000_321, dict(sound, turns=1, last_turns=0), TOY_LIMITS)
        if not c["ok"]]
    assert few == ["served_turns_checked", "served_last_turns_checked"]


def test_the_control_and_every_fault_are_other_answers(rehearsal, served):
    """At toy widths: the lower-precision control's own answers and the
    served answers against each fault's reference lie farther from the
    sound reference than the served answers do."""
    from benchmarks import control_solar
    cell, _ = rehearsal
    serving, sound = served
    seed = 2_600_000_321
    ref = reference_solar.forward(seed, cell.config, sound["sequences"],
                                  sound["read_at"])
    base = reference_lm.served_gap(ref["logits"], sound["ids"],
                                   sound["logits"])
    low = reference_solar.forward(seed, cell.config, sound["sequences"],
                                  sound["read_at"], lower=True)
    ids, logits = reference_lm.own_answers(low["logits"], 10)
    control = reference_lm.served_gap(ref["logits"], ids, logits)
    assert control["score_gap"] > 2 * base["score_gap"]
    cut = control_solar.by_read(sound, serving.pool, 20)
    assert len(cut["sequences"]) == len(sound["ids"])
    assert all(len(s) == at + 1 for s, at in zip(
        cut["sequences"], [a for reads in sound["read_at"] for a in reads]))
    for fault in reference_solar.FAULTS:
        bad = reference_solar.forward(seed, cell.config, cut["sequences"],
                                      fault=fault, starts=cut["starts"],
                                      others=cut["others"])
        got = reference_lm.served_gap(bad["logits"], sound["ids"],
                                      sound["logits"])
        assert got["score_gap"] > 2 * base["score_gap"], (fault, got, base)
    # and the cut itself is sound: read at each sequence's end, no fault
    again = reference_solar.forward(seed, cell.config, cut["sequences"])
    np.testing.assert_allclose(again["logits"], ref["logits"], atol=2e-5)


def test_the_benchmarks_reference_is_the_packages(rehearsal):
    """The benchmark's copy of the reference against the package's, on
    the benchmark's own weights; and the program's leaves are the
    configuration file's."""
    import jax.numpy as jnp
    from code2vec_tpu.models import delta_moe_lm, delta_moe_lm_reference
    cell, _ = rehearsal
    cfg = delta_moe_lm.LMConfig.from_dict(cell.config)
    seed = 77
    params = {name: reference_solar.make_leaf(seed, cell.config, name,
                                              shape, dtype, init)
              for name, shape, dtype, init
              in reference_solar.all_leaves(cell.config)}
    assert [(n, s, d) for n, s, d, _ in reference_solar.all_leaves(
        cell.config)] == [(leaf.name, tuple(leaf.shape), leaf.dtype)
                          for leaf in delta_moe_lm.leaf_specs(cfg)]
    a = np.exp(np.asarray(params["layers.01.a_log"]))
    assert a[0] == pytest.approx(1.0) and a[-1] == pytest.approx(16.0)
    dt = np.asarray(jnp.logaddexp(params["layers.01.dt_bias"], 0.0))
    assert dt[0] == pytest.approx(1e-3, rel=1e-3)
    assert dt[15] == pytest.approx(0.1, rel=1e-3) and dt[16] == dt[0]
    sequence = np.random.default_rng(3).integers(0, 128, 45).astype(np.int32)
    got = reference_solar.forward(seed, cell.config, [sequence],
                                  [[20, 44]])
    for row, at in enumerate((20, 44)):
        want, chosen = delta_moe_lm_reference.logits(cfg, params,
                                                     sequence[:at + 1])
        np.testing.assert_allclose(got["logits"][row], want, atol=2e-5)
        assert (np.sort(got["chosen_last"][row], -1)
                == np.sort(np.asarray(chosen[:, -1]), -1)).all()
    assert reference_solar.pattern(cell.config) == cfg.pattern


def test_a_tree_without_the_model_gets_no_result_at_once(toy_root,
                                                         monkeypatch):
    from code2vec_tpu import lm_facade
    cell = common.Cell(toy_root, "toy-solar.turns")
    monkeypatch.delitem(lm_facade.MODEL_MODULES, "solar_open2")
    with pytest.raises(common.NoResult) as e:
        runner.run(cell, 1, 1.0, trace=False, require_tpu=False)
    assert e.value.code == 3


# ------------------------------------------------------ the cell's own files

@pytest.fixture(scope="module")
def cell():
    return common.Cell(ROOT, CELL)


def test_the_cell_its_files_and_limits_load(cell):
    assert cell.runner == "serve_lm_session_ctx" and cell.chips == 1
    assert cell.run_module() is runner
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}
    assert [m["name"] for m in cell.end_to_end()] == ["request_p50_ms",
                                                      "setup_s"]
    # held with `in`, never ==: a later PR that lists one more cell under
    # a metric breaks nothing here
    mine = {m["name"] for m in cell.per_layer()}
    assert {"batch_tokens_fill_pct.serve", "expert_load_max_over_mean.serve",
            "batch_device_ms.serve", "dispatcher_busy_pct.serve",
            "compiles_in_window.serve", "request_p95_ms.serve"} <= mine
    for m in cell.bench["per_layer"]:
        if m["name"] in mine:
            assert CELL in m["workloads"]
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "solar-open2-ep8")
    assert entry["reduced"] == ["layers", "experts_held", "vocab_rows",
                                "weights"]
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 1


@pytest.mark.parametrize("name", BROUGHT)
def test_a_brought_metric_has_its_file_and_the_runner_feeds_it(cell, name):
    from benchmarks import readers
    spec = cell.layer_metric_spec(name)
    assert spec["name"] == name and spec["moves"] == "request_p50_ms"
    assert spec["reader"] in readers.KINDS and spec["what"]
    assert spec["unit"] == ("tokens/s" if name.startswith("extend_tokens")
                            else "%")
    if spec["reader"] == "run_fact":
        import inspect
        key = spec["args"]["key"]
        assert key in inspect.getsource(runner), key
        # a program that lacks the spans and counters (the parent) gives
        # the reader nothing, and the line leaves the metric out
        assert readers.run_fact(readers.Measured(
            cell, "TPU v5 lite", None, 20.0), key) is None


def test_the_configuration_keeps_every_published_key(cell):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"Solar-Open2-250B"' in line)
    c = cell.config
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert c[key] == value, key
    assert (c["layers"], c["experts_held"], c["expert_first"],
            c["vocab_rows"]) == (4, 40, 0, 24576)
    assert set(c["reduced"]) == {"layers", "experts_held", "vocab_rows",
                                 "weights"}
    for group in ("assumed", "published", "precision", "deployment"):
        assert c[group], group
    assert reference_solar.pattern(c) == "G K K K"
    assert reference_solar.num_params(c) == c["parameters"] == 3_308_353_344
    assert c["parameter_bytes"] == 2 * c["parameters"] == 6_616_706_688
    got = reference_solar.cache_bytes(c)
    assert got == {k: c["cache_bytes"][k] for k in ("states", "pages")}
    assert got["states"] == 24 * 13_025_280
    assert got["pages"] == 416 * 8_388_608
    held = c["serve"]["context_cache"]
    assert held["tokens_per_slot"] == 96 * held["register_chunk"] == 196608
    assert c["serve"]["length_buckets"] == [256, 512, 1024]
    assert c["serve"]["token_budget"] == 1024


def test_the_program_reads_the_file_and_counts_the_same(cell):
    from code2vec_tpu.models import delta_moe_lm, lm_common
    cfg = delta_moe_lm.LMConfig.from_dict(cell.config)
    specs = delta_moe_lm.leaf_specs(cfg)
    assert lm_common.count_leaves(specs) == 3_308_353_344
    assert [(leaf.name, tuple(leaf.shape), leaf.dtype) for leaf in specs] \
        == [(n, s, d) for n, s, d, _ in reference_solar.all_leaves(
            cell.config)]
    assert cfg.pattern == "G K K K" and cfg.conv_channels == 24576


def test_the_turns_are_the_same_for_every_seed_and_keep_the_gap(cell):
    traffic = cell.traffic
    a = runner.turn_schedule(11, 20.0, traffic)
    b = runner.turn_schedule(4_300_000_017, 20.0, traffic)
    assert len(a) == round(traffic["rate_per_s"] * 20.0)
    for key in ("due_s", "session", "length"):
        assert [x[key] for x in a] == [x[key] for x in b], key
    assert [x["body_index"] for x in a] != [x["body_index"] for x in b]
    assert sorted(x["body_index"] for x in a) == list(range(len(a)))
    last = {}
    for x in a:
        if x["session"] in last:
            assert x["due_s"] - last[x["session"]] >= 0.25
        last[x["session"]] = x["due_s"]
        assert 128 <= x["length"] <= 1024
    # Zipf over the sessions: the first is the most asked
    counts = np.bincount([x["session"] for x in a], minlength=16)
    assert counts[0] == counts.max() and len(last) >= 8
    # at a rate no sixteen sessions could carry, the schedule says so
    with pytest.raises(ValueError, match="no session is free"):
        runner.turn_schedule(1, 2.0, dict(traffic, rate_per_s=500.0,
                                          request_pool=4096))


def test_the_mix_fits_the_pool_with_room_for_a_windows_growth(cell):
    pool = runner.make_pool(cell.config, cell.traffic)
    again = runner.make_pool(cell.config, cell.traffic)
    lengths = [len(s) for s in pool["sessions"]]
    assert len(lengths) == 16 and lengths == [len(s)
                                              for s in again["sessions"]]
    assert min(lengths) >= 16384 and max(lengths) <= 131072
    assert (pool["blocks"] == again["blocks"]).all()
    assert pool["blocks"].max() < 24576
    held = cell.config["serve"]["context_cache"]
    pages = sum(-(-n // held["register_chunk"]) for n in lengths)
    turns = runner.turn_schedule(1, 20.0, cell.traffic)
    grown = (sum(x["length"] for x in turns)
             + cell.traffic["warm_requests"]
             * cell.traffic["length"]["median"])
    # every session may cross one boundary more than its tokens fill
    worst = pages + grown // held["register_chunk"] + 2 * 16
    assert worst <= 0.95 * held["pages"], (pages, worst)
    assert len(lengths) <= held["slots"]


def test_the_floors_are_the_hand_counts(cell):
    c = cell.config
    assert roofline_solar.turn_counts(1000, 3) == {
        "keys": 1003, "pairs": 3 * 1000 + 6}
    # one K layer's delta rule, one row of 512 tokens
    terms = roofline_solar.delta_rule_terms(c, 1, 512)
    assert sum(t["flops"] for t in terms) == 7 * 64 * 128 * 128 * 512
    assert sum(t["bytes"] for t in terms) == (
        2 * 64 * 128 * 128 * 4 + 2 * 3 * 24576 * 2
        + 512 * (5 * 8192 + 64) * 4)
    floor = roofline_solar.delta_rule_floor(c, 1, 512, "TPU v5 lite")
    assert floor["bound"] == "bytes"
    assert floor["seconds"] == pytest.approx(
        (8_388_608 + 294_912 + 84_017_152) / 819e9)
    matrices = roofline_solar.mixer_matrices(c)
    assert matrices["G"] == 109_051_904 - 0
    assert matrices["K"] == 137_732_288 - (98_304 + 64 + 8_192 + 128)
    # the whole step: every matrix a token passes, read once
    step = roofline_solar.extend_step_terms(c, 1, 512, 33_280, 512 * 32_768
                                            + 512 * 513 // 2, 1365, 40)
    every = (109_051_904 + 3 * (137_732_288 - 106_688)
             + 4 * (1_310_720 + 15_728_640))
    assert step[0]["bytes"] == 2 * every
    assert step[1]["flops"] == 2 * every * 512
    assert step[2] == {"term": step[2]["term"],
                       "flops": 2 * 24576 * 4096, "bytes": 24576 * 4096 * 2}
    hit = [t for t in step if "expert layers" in t["term"]]
    assert sum(t["bytes"] for t in hit) == 4 * (
        40 * 3 * 4096 * 1280 * 2 + 1365 * 4096 * 6)
    whole = roofline_solar.extend_step_floor(
        c, 1, 512, 33_280, 512 * 32_768 + 512 * 513 // 2, 1365, 40,
        "TPU v5 lite")
    assert 5e-3 < whole["seconds"] < 9e-3 and whole["bound"] == "bytes"
