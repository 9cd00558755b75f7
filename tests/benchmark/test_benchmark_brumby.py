"""The rerank-burst cell of the benchmark: its files load and say what
ISSUE 39 asked for, the runner kind `serve_lm_state_ctx` rehearses end
to end on the CPU at toy widths (new files and entries only), a tree
whose program lacks the model gets no result at once, the burst
schedule sends sixteen distinct contexts a burst at the same instants
for every seed, the reference's weights, its lower-precision control
and the six faults behave, the recorded chip readings fall on the right
side of the cell's limits, and the floors are the hand counts."""

import json
import os

import numpy as np
import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common  # noqa: E402
from benchmarks import reference_brumby, reference_lm, roofline_brumby  # noqa: E402
from benchmarks.runners import serve_lm_ctx, serve_lm_state_ctx  # noqa: E402

CELL = "brumby-14b-pp8.serve_score_rerank_burst"
BROUGHT = ("retention_read_roofline.serve",
           "retention_share_of_step_pct.serve", "score_step_mfu.serve",
           "batch_rows_mean.serve")
TINY = dict(
    model_type="brumby", hidden_size=64, num_hidden_layers=4, layers=2,
    vocab_size=512, vocab_rows=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e6,
    max_position_embeddings=512, intermediate_size=96, rms_norm_eps=1e-6,
    retention_chunk=16, gate_memory_tokens=[20, 200],
    init_std={"default": 0.02},
    serve={"length_buckets": [16, 32], "token_budget": 64, "top_k": 10,
           "context_cache": {"slots": 6, "tokens_per_slot": 448,
                             "register_chunk": 64}})
TOY_LIMITS = {"served_top_logit_gap": 0.05, "served_score_gap": 0.05,
              "served_score_gap_median": 0.02}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    dest = make_toy_root(str(tmp_path_factory.mktemp("brumby") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-brumby"), "configs", "toy-brumby.json")
    mix = common.load_json(os.path.join(home, "traffic",
                                        "serve_score_rerank_burst.json"))
    dump(dict(mix, name="toy_burst", rate_per_s=3.0, request_pool=40,
              burst_rows=4, warm_requests=2, checked_requests=6,
              checked_contexts=3, contexts=6, generator_threads=8,
              context_length={"distribution": "lognormal", "median": 150,
                              "sigma": 0.4, "min": 70, "max": 448},
              length={"distribution": "lognormal", "median": 12,
                      "sigma": 0.3, "min": 6, "max": 32}),
         "traffic", "toy_burst.json")
    dump({"limits": TOY_LIMITS}, "limits", "toy-brumby.burst.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-brumby", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-brumby.json"})
    bench["workloads"].append({"name": "toy-brumby.burst",
                               "config": "toy-brumby",
                               "traffic": "toy_burst", "chips": 1,
                               "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-brumby.burst")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # toy sequences of ~200 tokens padded to the cell's 2,048 would be
    # 90 % padding: the reference's time on the CPU, nothing else
    kept = (reference_brumby.PAD_TO, reference_brumby.QUERY_BLOCK,
            reference_brumby.LOWER_CHUNK)
    (reference_brumby.PAD_TO, reference_brumby.QUERY_BLOCK,
     reference_brumby.LOWER_CHUNK) = 64, 16, 16
    yield dest
    (reference_brumby.PAD_TO, reference_brumby.QUERY_BLOCK,
     reference_brumby.LOWER_CHUNK) = kept


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    cell = common.Cell(toy_root, "toy-brumby.burst")
    return cell, serve_lm_state_ctx.run(
        cell, 2_600_000_123, 3.0, trace=False, require_tpu=False, emit=False)


def test_state_ctx_rehearsal_is_correct_and_serves_every_request(rehearsal):
    cell, result = rehearsal
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 9 * 4
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_requests_checked"]["value"] == 6
    assert names["served_contexts_checked"]["value"] >= 3
    assert names["served_score_gap_median"]["value"] < 0.004
    assert names["compiled_inside_window"]["value"] == 0
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))
    ids = common.load_json(os.path.join(cell.work, "data",
                                        "context_ids.json"))
    assert len(set(ids)) == 6
    # one body a (question, context) pair SENT: nine bursts of four and
    # the two warm requests, not the pool's 40 x 6
    bodies = [f for f in os.listdir(os.path.join(cell.work, "data"))
              if f[0].isdigit()]
    assert len(bodies) == 9 * 4 + 2


def test_state_ctx_counters_feed_the_new_metrics(rehearsal):
    """The program's series give the readers what the shared and the new
    per-layer metrics read; without a trace the three device shares are
    left out."""
    from code2vec_tpu import obs
    from benchmarks import readers
    cell, _ = rehearsal
    window = common.RegistryWindow(obs.default_registry())
    window.close()          # from the process's start to now
    rows = window.histogram("serving_batch_rows")
    assert rows and rows[0] >= 9 * 4 and 1.0 <= rows[0] / rows[1] <= 4.0
    read = serve_lm_state_ctx.serve_lm.counter_delta(
        window, "retention_states_read_total")
    nbytes = serve_lm_state_ctx.serve_lm.counter_delta(
        window, "retention_state_bytes_read_total")
    assert read >= 9 * 4 * 2 and read % 2 == 0
    assert nbytes == read // 2 * window.gauge("state_cache_slot_bytes")
    assert window.gauge("state_cache_slot_bytes") == 2 * 2 * 17 * 136 * 4
    assert window.gauge("latent_cache_fill_ratio") == 1.0   # slots held
    total, count = serve_lm_ctx.registry_total("context_register_seconds")
    assert count >= 6 and total > 0
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", window, window_s=3.0,
        facts={"context_register_ms": 1e3 * total / count}))
    assert got["batch_rows_mean.serve"] == rows[0] / rows[1]
    assert got["batch_fill_pct.serve"] > 0
    assert not {"retention_read_roofline.serve", "score_step_mfu.serve",
                "retention_share_of_step_pct.serve",
                "ctx_score_step_device_ms.serve"} & set(got)
    assert set(got) <= {m["name"] for m in cell.per_layer()}
    assert serve_lm_state_ctx.retention_facts(
        cell, "TPU v5 lite", os.path.join(cell.work, "no_trace"), window,
        [], serve_lm_ctx.make_pool(cell.config, cell.traffic)) == {}


def _served(cell, seed, n=3):
    pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
    seqs, contexts = [], []
    for i in range(n):
        c = (2 * i) % len(pool["contexts"])
        seqs.append(np.concatenate([pool["contexts"][c],
                                    pool["questions"][i]]))
        contexts.append(c)
    ref = reference_brumby.forward(seed, cell.config, seqs)
    ids, logits = reference_lm.own_answers(ref["logits"], 10)
    return pool, ref, {
        "sequences": seqs, "contexts": contexts, "malformed": 0, "ids": ids,
        "logits": logits, "routing": np.zeros((n, 0), np.int32)}


def test_an_altered_answer_or_context_reads_not_correct(rehearsal):
    cell, _ = rehearsal
    limits = cell.limits()
    pool, _, served = _served(cell, 7)
    few = dict(cell.traffic, checked_requests=3, checked_contexts=1)
    cell.traffic, kept = few, cell.traffic
    try:
        sound = serve_lm_state_ctx.check_answers(cell, 7, served, limits)
        assert all(c["ok"] for c in sound), sound
        swapped = dict(served, ids=served["ids"][:, ::-1].copy())
        assert not all(c["ok"] for c in serve_lm_state_ctx.check_answers(
            cell, 7, swapped, limits))
    finally:
        cell.traffic = kept
    short = serve_lm_state_ctx.check_answers(cell, 7, served, limits)
    assert not {c["name"]: c for c in short}["served_requests_checked"]["ok"]


def test_the_control_and_every_fault_are_other_answers(rehearsal):
    """At toy widths: the control's own answers and each of the six
    faults move the logits past what the sound comparison reads; the
    reference against itself does not, nor do its four causal stretches
    against the whole square."""
    import jax
    from benchmarks.control_brumby import FAULTS, fault_runs
    cell, _ = rehearsal
    pool, ref, served = _served(cell, 11)
    own = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    assert own["score_gap"] < 1e-5 and own["top_gap"] == 0.0
    low = reference_brumby.forward(11, cell.config, served["sequences"],
                                   lower=True)
    gap = reference_lm.served_gap(
        ref["logits"], *reference_lm.own_answers(low["logits"], 10))
    # the int8 control read 0.012-0.014 at these widths, the sound
    # rehearsal under 0.004
    assert gap["score_gap"] > 0.008
    runs = fault_runs(pool, served["sequences"], served["contexts"], 64)
    assert sorted(runs) == sorted(FAULTS) and len(FAULTS) == 6
    for fault in FAULTS:
        bad = reference_brumby.forward(11, cell.config, **runs[fault])
        moved = reference_lm.served_gap(bad["logits"], served["ids"],
                                        served["logits"])
        # the smallest, a context one token short, read 0.074
        assert moved["score_gap"] > 0.04, (fault, moved)
    # no key masked is the sound run
    same = reference_brumby.forward(
        11, cell.config, served["sequences"], first_key=[0, 0, 0])
    np.testing.assert_allclose(same["logits"], ref["logits"], atol=1e-6)
    kept = reference_brumby.SEGMENTS
    reference_brumby.SEGMENTS = 1
    jax.clear_caches()
    try:
        whole = reference_brumby.forward(11, cell.config,
                                         served["sequences"])
    finally:
        reference_brumby.SEGMENTS = kept
        jax.clear_caches()
    np.testing.assert_allclose(whole["logits"], ref["logits"], atol=1e-5)


def test_the_benchmarks_reference_is_the_packages(rehearsal):
    """Two plain forms written apart: the benchmark's own (key blocks,
    layer by layer) and the package's (`retention_lm_reference`), over
    the seed's weights."""
    from code2vec_tpu.models import retention_lm, retention_lm_reference
    cell, _ = rehearsal
    cfg = retention_lm.LMConfig.from_dict(cell.config)
    params = {name: reference_brumby.make_leaf(3, cell.config, name, shape,
                                               dtype, init)
              for name, shape, dtype, init in reference_brumby.all_leaves(
                  cell.config)}
    sequence = np.random.RandomState(1).randint(0, 128, (150,)).astype(
        np.int32)
    np.testing.assert_allclose(
        reference_brumby.forward(3, cell.config, [sequence])["logits"][0],
        retention_lm_reference.logits(cfg, params, sequence), atol=2e-5)


def test_a_tree_without_the_model_gets_no_result_at_once(toy_root,
                                                         monkeypatch):
    from code2vec_tpu import lm_facade
    cell = common.Cell(toy_root, "toy-brumby.burst")
    monkeypatch.delitem(lm_facade.MODEL_MODULES, "brumby")
    with pytest.raises(common.NoResult) as e:
        serve_lm_state_ctx.run(cell, 1, 1.0, trace=False,
                               require_tpu=False)
    assert e.value.code == 3


# ------------------------------------------------------ the cell's own files

@pytest.fixture(scope="module")
def cell():
    return common.Cell(ROOT, CELL)


def test_the_cell_its_files_and_limits_load(cell):
    assert cell.runner == "serve_lm_state_ctx" and cell.chips == 1
    assert cell.run_module() is serve_lm_state_ctx
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}
    assert [m["name"] for m in cell.end_to_end()] == ["request_p50_ms",
                                                      "setup_s"]
    mine = {m["name"] for m in cell.per_layer()}
    listed = {m["name"]: m for m in cell.bench["per_layer"]}
    for name in BROUGHT:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "request_p50_ms"
    # held with <=, never ==: a later PR that lists the cell under one
    # more metric breaks nothing here
    assert set(BROUGHT) | {
        "batch_wait_mean_ms.serve", "device_phase_mean_ms.serve",
        "generator_late_p95_ms.serve", "request_p95_ms.serve",
        "restore_s.setup", "compile_s.setup", "batch_device_ms.serve",
        "batch_render_ms.serve", "batch_fill_pct.serve",
        "dispatcher_busy_pct.serve", "compiles_in_window.serve",
        "batch_tokens_fill_pct.serve"} <= mine
    # a dense model with a state cache: no router, no latents a token
    assert not [n for n in mine if n.startswith(("moe_", "expert_", "mla_"))]
    assert "latent_cache_fill_pct.serve" not in mine
    from benchmarks import readers
    for name in mine:
        spec = cell.layer_metric_spec(name)
        assert spec["reader"] in readers.KINDS and spec["name"] == name
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "brumby-14b-pp8")
    assert entry["reduced"] == ["layers", "weights"]
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200
    assert [w["name"] for w in cell.bench["workloads"]].count(CELL) == 1
    four = [w for w in cell.bench["workloads"] if w["chips"] == 4]
    assert len(cell.bench["workloads"]) >= 9 and len(four) == 1


def test_the_configuration_keeps_every_published_key(cell):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Brumby-14B-Base")
    for key, value in row["config"].items():
        assert cell.config[key] == value, key
    c = cell.config
    assert c["source"] == row["source_url"]
    assert (c["layers"], c["vocab_rows"]) == (5, 151936)
    assert set(c["reduced"]) == {"layers", "weights"}
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8   # retention
             + 2 * 128 + 2 * 5120 + 3 * 5120 * 17408)         # norms, MLP
    assert layer == 330_352_904
    assert reference_brumby.num_params(c) == c["parameters"] == (
        5 * layer + 2 * 151936 * 5120 + 5120) == 3_207_594_280
    assert 6.41e9 < c["parameter_bytes"] < 6.42e9
    assert reference_brumby.state_bytes(c) == c["state_bytes_per_context"] \
        == 5 * 8 * 129 * 8256 * 4 == 170_403_840
    assert reference_brumby.cache_bytes(c) == c["state_cache_bytes"] \
        == 5_452_922_880
    assert c["state_features"] == 8256 <= 9216
    serve = c["serve"]
    assert serve["length_buckets"] == [16, 32]
    assert serve["token_budget"] == 256 and serve["top_k"] == 10
    held = serve["context_cache"]
    assert (held["slots"], held["register_chunk"]) == (32, 2048)
    # the admission limit: the model's positions less the longest bucket
    assert held["tokens_per_slot"] == c["max_position_embeddings"] - 256
    assert "eight" in c["deployment"].lower() and "head" in c["deployment"]
    assert "DEPARTURE" in c["deployment"]
    assert {"power", "gate", "head_norms", "rotary", "scale", "eps",
            "state_precision", "initializers", "traffic"} <= set(c["assumed"])
    assert "int8" in c["precision"]["lower_precision_control"]
    assert "bfloat16" in c["precision"]["lower_precision_control"]
    assert c["why_batch"] and c["published"]["layers"] == 40


def test_reference_leaves_are_the_programs(cell):
    from code2vec_tpu.models import retention_lm as lm
    have = [(leaf.name, tuple(leaf.shape), leaf.dtype)
            for leaf in lm.leaf_specs(lm.LMConfig.from_dict(cell.config))]
    assert [w[:3] for w in reference_brumby.all_leaves(cell.config)] == have
    bias = np.asarray(reference_brumby.make_leaf(
        5, cell.config, "layers.01.bg", (8,), "float32", "gate_bias"))
    gate = 1.0 / (1.0 + np.exp(-bias))
    np.testing.assert_allclose(1.0 / (1.0 - gate)[[0, -1]], [1000.0, 10000.0],
                               rtol=1e-3)
    # the program's own initialiser gives the same bias
    from code2vec_tpu.models import lm_common
    np.testing.assert_allclose(lm_common.gate_bias(8, 1000.0, 10000.0), bias,
                               rtol=1e-5)
    one = np.asarray(reference_brumby.make_leaf(
        5, cell.config, "layers.00.wo", (128, 256), "bfloat16", "normal"),
        np.float32)
    again = np.asarray(reference_brumby.make_leaf(
        5, cell.config, "layers.00.wo", (128, 256), "bfloat16", "normal"),
        np.float32)
    assert 0.017 < one.std() < 0.023 and (one == again).all()


def test_the_burst_schedule(cell):
    """Sixteen DISTINCT contexts a burst, all due at one instant; every
    seed the same instants and contexts, the questions in another order;
    most questions ride the 16 bucket; every context fits the admission
    limit."""
    from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
    traffic = cell.traffic
    assert traffic["burst_rows"] == 16 and traffic["contexts"] == 32
    one = serve_lm_state_ctx.burst_schedule(3, 20.0, traffic)
    two = serve_lm_state_ctx.burst_schedule(4, 20.0, traffic)
    bursts = int(round(traffic["rate_per_s"] * 20.0))
    assert len(one) == len(two) == 16 * bursts
    for k in range(bursts):
        mine = one[16 * k:16 * k + 16]
        assert len({a["due_s"] for a in mine}) == 1
        assert len({a["question"] for a in mine}) == 1
        assert len({a["context"] for a in mine}) == 16
        assert [a["context"] for a in mine] == [
            a["context"] for a in two[16 * k:16 * k + 16]]
        assert mine[0]["due_s"] == two[16 * k]["due_s"]
    assert len({a["body_index"] for a in one}) == len(one)   # none repeated
    assert sorted({a["question"] for a in one}) == sorted(
        {a["question"] for a in two}) == list(range(bursts))
    assert [a["question"] for a in one] != [a["question"] for a in two]
    asked = np.bincount([a["context"] for a in
                         serve_lm_state_ctx.burst_schedule(
                             3, 200.0, dict(traffic, rate_per_s=10.0))],
                        minlength=32)
    assert asked[0] > asked[8] > asked[31] > 0      # Zipf, without replacement
    pool = serve_lm_ctx.make_pool(cell.config, traffic)
    serve = cell.config["serve"]
    buckets = parse_buckets(serve["length_buckets"], serve["token_budget"])
    assert buckets == (16, 32, 256)
    lengths = np.asarray(pool["lengths"])
    share = np.mean([bucket_for(n, buckets) == 16 for n in lengths])
    assert 0.78 < share < 0.88 and 11 <= np.median(lengths) <= 13
    assert lengths.min() >= 6 and lengths.max() <= 32
    contexts = [len(c) for c in pool["contexts"]]
    assert len(contexts) == 32 and min(contexts) >= 8192
    assert max(contexts) <= serve["context_cache"]["tokens_per_slot"]
    assert 480_000 < sum(contexts) < 640_000
    picked = serve_lm_state_ctx.pick_checked(3, one, pool, traffic)
    on = [one[i]["context"] for i in picked]
    assert len(picked) == len(set(picked)) == 8 and len(set(on)) >= 4
    longest = max({a["context"] for a in one},
                  key=lambda c: len(pool["contexts"][c]))
    assert on[0] == longest


def test_recorded_readings_fall_on_the_right_side_of_the_limits(cell):
    """The limits file records the chip readings it was set from: every
    sound reading passes all three limits; the lower-precision control
    and each of the six faults fail at least one."""
    limits = cell.limits()
    recorded = common.load_json(os.path.join(
        cell.home, "limits", CELL + ".json"))["readings"]

    def fails(reading):
        return [n for n in limits if reading[n] > limits[n]]
    assert len(recorded["sound"]) >= 12
    for reading in recorded["sound"]:
        assert not fails(reading), reading
    for kind in ("control", "wrong_slot", "stale_length", "last_chunk_alone",
                 "gates_ignored", "normaliser_dropped", "power_one"):
        assert recorded[kind], kind
        for reading in recorded[kind]:
            assert fails(reading), (kind, reading)


def test_the_traffic_file_records_the_sweep_and_the_sets(cell):
    text = cell.traffic["rate_set_from"]
    assert "sweep_rate_brumby.py" in text and "knee" in text.lower()
    assert "set A" in text and "set B" in text
    assert "bursts/s sustained" in text
    assert cell.traffic["rate_per_s"] == 6.0      # 0.3 of the knee, 20


def test_the_floors_are_the_hand_counts(cell):
    """Sixteen rows of 12 question tokens on sixteen states."""
    c = cell.config
    rows, tokens = 16, 16 * 12
    pairs = 16 * (12 * 13 // 2)
    read = roofline_brumby.retention_read_terms(c, rows, tokens, pairs)
    assert read[0]["bytes"] == 16 * 8 * 129 * 8256 * 4 == 545_292_288
    assert read[1]["flops"] == 2 * 8256 * 129 * 40 * tokens
    assert read[2]["flops"] == 2 * (128 + 129) * 40 * pairs
    floor = roofline_brumby.retention_read_floor(c, rows, tokens, pairs,
                                                 "TPU v5 lite")
    assert floor["bound"] == "bytes"
    assert abs(floor["seconds"] - (545_292_288 + tokens * 96 * 128 * 2)
               / 819e9) < 1e-9
    assert roofline_brumby.dense_layer_params(c) == (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408)
    step = roofline_brumby.score_step_floor(c, rows, rows, tokens, pairs,
                                            "TPU v5 lite")
    weights = 5 * roofline_brumby.dense_layer_params(c) * 2
    head = 151936 * 5120 * 2
    assert step["bytes"] == (weights + head + tokens * 5120 * 2
                             + 5 * (545_292_288 + tokens * 96 * 128 * 2))
    # three streams of one order: states 2.73, layer weights 3.30, head
    # 1.56 GB; bytes bound, 9-10 ms
    assert step["bound"] == "bytes" and 9e-3 < step["seconds"] < 10e-3
    # one row on one context: the state is a thirtieth of the bytes
    one = roofline_brumby.score_step_floor(c, 1, 1, 12, 78, "TPU v5 lite")
    assert 5 * 8 * 129 * 8256 * 4 / one["bytes"] < 0.04
