"""The long-context selecting-attention cell of the benchmark: its files
load and say what ISSUE 34 asked for, the runner kind
`serve_lm_sparse_ctx` rehearses end to end on the CPU at toy widths (new
files and entries only), a tree whose program lacks the model gets no
result at once, the reference's weights, its lower-precision control and
the four faults behave, the recorded chip readings fall on the right
side of the cell's limits, the traffic's lengths fall where the mix
says, and the floors are the hand counts."""

import json
import os

import numpy as np
import pytest

from bench_testlib import ROOT, make_toy_root

from benchmarks import common, loadgen  # noqa: E402
from benchmarks import reference_keye, reference_lm, roofline_keye  # noqa: E402
from benchmarks.runners import serve_lm_ctx, serve_lm_sparse_ctx  # noqa: E402

CELL = "keye-vl2-pp8.serve_score_longctx_open"
# the per-layer metrics the cell reads beyond the shared serve ones: three
# the runner kind `serve_lm_sparse_ctx` brought (PR 34), four of PR 31's
BROUGHT = ("index_select_roofline.serve", "sparse_attend_roofline.serve",
           "keys_selected_pct.serve", "moe_gated_experts_roofline.serve",
           "ctx_score_step_device_ms.serve", "latent_cache_fill_pct.serve",
           "context_register_ms.setup")
TINY = dict(
    model_type="KeyeVL2", hidden_size=64, num_hidden_layers=4, layers=2,
    vocab_size=512, vocab_rows=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 4, 2], "rope_type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "topk": 16, "q_chunk_size": 512,
               "kv_chunk_size": 512},
    moe_intermediate_size=48, num_experts=16, num_local_experts=16,
    experts_held=16, expert_first=0, num_experts_per_tok=4,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    rms_norm_eps=1e-6,
    init_std={"default": 0.02, "w_down": 0.01}, init_gain={"q_norm": 2.0},
    serve={"length_buckets": [16, 32], "token_budget": 64, "top_k": 10,
           "context_cache": {"slots": 4, "tokens_per_slot": 256,
                             "register_chunk": 64}})
TOY_LIMITS = {"served_top_logit_gap": 0.2, "served_score_gap": 0.3,
              "served_score_gap_median": 0.1}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark copied, plus a toy configuration, mix and cell of the
    new runner kind: new files and new entries alone."""
    dest = make_toy_root(str(tmp_path_factory.mktemp("keye") / "root"))
    home = os.path.join(dest, "benchmarks")

    def dump(obj, *parts):
        path = os.path.join(home, *parts)
        assert not os.path.exists(path), f"{path} would be edited"
        with open(path, "w") as f:
            json.dump(obj, f)
    dump(dict(TINY, name="toy-keye"), "configs", "toy-keye.json")
    mix = common.load_json(os.path.join(home, "traffic",
                                        "serve_score_longctx_open.json"))
    # its own corpus: at toy widths one key of 16 kept otherwise moves a
    # row's logits by a step, and which draw stays inside the toy limits
    # is the draw's luck, not the cell's
    dump(dict(mix, name="toy_longctx", rate_per_s=12.0, request_pool=80,
              corpus_seed=20261002,
              warm_requests=2, checked_requests=6, checked_contexts=3,
              contexts=4, generator_threads=4,
              context_length={"distribution": "lognormal", "median": 150,
                              "sigma": 0.3, "min": 70, "max": 256},
              length={"distribution": "lognormal", "median": 20,
                      "sigma": 0.5, "min": 3, "max": 64}),
         "traffic", "toy_longctx.json")
    dump({"limits": TOY_LIMITS}, "limits", "toy-keye.longctx.json")
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-keye", "source": "test",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy-keye.json"})
    bench["workloads"].append({"name": "toy-keye.longctx",
                               "config": "toy-keye",
                               "traffic": "toy_longctx", "chips": 1,
                               "why": "toy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-keye.longctx")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # toy sequences of ~200 tokens padded to the cell's 4,096 would be
    # 95 % padding: the reference's time on the CPU, nothing else
    # (and query blocks of 32, so that the four causal stretches of a
    # padded length are live: 4 x 32 divides every multiple of 128)
    kept = reference_keye.PAD_TO, reference_keye.QUERY_BLOCK
    reference_keye.PAD_TO, reference_keye.QUERY_BLOCK = 128, 32
    yield dest
    reference_keye.PAD_TO, reference_keye.QUERY_BLOCK = kept


@pytest.fixture(scope="module")
def rehearsal(toy_root):
    cell = common.Cell(toy_root, "toy-keye.longctx")
    return cell, serve_lm_sparse_ctx.run(
        cell, 2_600_000_123, 2.0, trace=False, require_tpu=False, emit=False)


def test_sparse_ctx_rehearsal_is_correct_and_serves_every_request(rehearsal,
                                                                  capfd):
    cell, result = rehearsal
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 24
    names = {c["name"]: c for c in result["checks"]}
    assert names["served_requests_checked"]["value"] == 6
    assert names["served_contexts_checked"]["value"] >= 3
    assert names["served_score_gap_median"]["value"] < 0.1
    assert names["compiled_inside_window"]["value"] == 0
    assert os.path.isdir(os.path.join(cell.work, "checkpoint", "saved"))
    ids = common.load_json(os.path.join(cell.work, "data",
                                        "context_ids.json"))
    assert len(set(ids)) == 4
    # only the checked requests asked for their kept keys
    asking = [f for f in os.listdir(os.path.join(cell.work, "data"))
              if f.endswith(".sel.json")]
    assert len(asking) == 6


def test_sparse_ctx_counters_feed_the_new_metrics(rehearsal):
    """The program's counters give the runner what the new per-layer
    metrics read; without a trace the two rooflines are left out."""
    from code2vec_tpu import obs
    from benchmarks import readers
    cell, _ = rehearsal
    window = common.RegistryWindow(obs.default_registry())
    window.close()          # from the process's start to now
    facts = serve_lm_sparse_ctx.selection_facts(window)
    assert facts["latents_per_step"] > facts["tokens_per_step"] > 0
    assert facts["index_pairs_per_step"] >= 2 * facts["latents_per_step"]
    assert facts["selected_pairs_per_step"] <= 2 * 16 * facts[
        "tokens_per_step"]
    assert 0.0 < facts["keys_selected_pct"] < 100.0
    total, count = serve_lm_ctx.registry_total("context_register_seconds")
    assert count >= 4 and total > 0
    got = readers.read_all(readers.Measured(
        cell, "TPU v5 lite", window, window_s=2.0,
        facts={"context_register_ms": 1e3 * total / count,
               "keys_selected_pct": facts["keys_selected_pct"]}))
    assert 0.0 < got["latent_cache_fill_pct.serve"] <= 100.0
    assert got["context_register_ms.setup"] > 0
    assert got["keys_selected_pct.serve"] == facts["keys_selected_pct"]
    assert not {"index_select_roofline.serve", "sparse_attend_roofline.serve",
                "ctx_score_step_device_ms.serve",
                "moe_gated_experts_roofline.serve"} & set(got)
    assert set(got) <= {m["name"] for m in cell.per_layer()}
    assert serve_lm_sparse_ctx.roofline_facts(
        cell, "TPU v5 lite", os.path.join(cell.work, "no_trace"), window
    ) == {"keys_selected_pct": facts["keys_selected_pct"]}


def _served(cell, seed, n=3):
    pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
    seqs, contexts = [], []
    for i in range(n):
        c = pool["context_of"][i]
        seqs.append(np.concatenate([pool["contexts"][c],
                                    pool["questions"][i]]))
        contexts.append(c)
    ref = reference_keye.forward(seed, cell.config, seqs)
    ids, logits = reference_lm.own_answers(ref["logits"], 10)
    return pool, ref, {
        "sequences": seqs, "contexts": contexts, "malformed": 0, "ids": ids,
        "logits": logits, "routing": ref["chosen_last"],
        "selected": [[np.flatnonzero(m).tolist() for m in kept]
                     for kept in ref["selected_last"]]}


def test_an_altered_answer_or_context_reads_not_correct(rehearsal, capfd):
    cell, _ = rehearsal
    limits = cell.limits()
    pool, _, served = _served(cell, 7)
    few = dict(cell.traffic, checked_requests=3, checked_contexts=1)
    cell.traffic, kept = few, cell.traffic
    try:
        sound = serve_lm_sparse_ctx.check_answers(cell, 7, served, limits)
        assert all(c["ok"] for c in sound), sound
        assert "selected_sets_overlap_share: 1.0" in capfd.readouterr().out
        swapped = dict(served, ids=served["ids"][:, ::-1].copy())
        assert not all(c["ok"] for c in serve_lm_sparse_ctx.check_answers(
            cell, 7, swapped, limits))
        moved, short, _ = _fault_sequences(pool, served)
        for sequences in (moved, short):
            assert not all(c["ok"] for c in
                           serve_lm_sparse_ctx.check_answers(
                               cell, 7, dict(served, sequences=sequences),
                               limits))
    finally:
        cell.traffic = kept
    short = serve_lm_sparse_ctx.check_answers(cell, 7, served, limits)
    assert not {c["name"]: c for c in short}["served_requests_checked"]["ok"]


def _fault_sequences(pool, served):
    from benchmarks.control_keye import fault_sequences
    return fault_sequences(pool, served["sequences"], served["contexts"])


def test_the_control_and_the_selection_faults_are_other_answers(rehearsal,
                                                                monkeypatch):
    """At toy widths: the control's own answers, the selection ignored and
    the selection made from another context's index keys each move the
    logits; the reference against itself does not."""
    cell, _ = rehearsal
    pool, ref, served = _served(cell, 11, n=3)
    own = reference_lm.served_gap(ref["logits"], served["ids"],
                                  served["logits"])
    assert own["score_gap"] < 1e-5 and own["top_gap"] == 0.0
    low = reference_keye.forward(11, cell.config, served["sequences"],
                                 lower=True)
    gap = reference_lm.served_gap(
        ref["logits"], *reference_lm.own_answers(low["logits"], 10))
    assert gap["score_gap"] > 0.05
    _, _, foreign = _fault_sequences(pool, served)
    assert [len(f) for f in foreign] == [len(s) for s in
                                         served["sequences"]]
    for kwargs in (dict(fault="dense"),
                   dict(fault="foreign_index", other=foreign)):
        bad = reference_keye.forward(11, cell.config, served["sequences"],
                                     **kwargs)
        moved = reference_lm.served_gap(bad["logits"], served["ids"],
                                        served["logits"])
        assert moved["score_gap"] > 0.02, kwargs
        if kwargs["fault"] == "dense":
            assert all(k.sum(-1).min() > 16 for k in bad["selected_last"])
        else:
            assert reference_keye.selected_overlap(
                served["selected"], bad["selected_last"]) < 0.9
    # the four causal stretches are the whole square
    import jax
    monkeypatch.setattr(reference_keye, "SEGMENTS", 1)
    jax.clear_caches()
    whole = reference_keye.forward(11, cell.config, served["sequences"])
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_allclose(whole["logits"], ref["logits"], atol=1e-5)
    assert all((a == b).all() for a, b in zip(whole["selected_last"],
                                              ref["selected_last"]))
    # the foreign index keys of the SAME context are the sound run
    same = reference_keye.forward(11, cell.config, served["sequences"],
                                  fault="foreign_index",
                                  other=served["sequences"])
    np.testing.assert_allclose(same["logits"], ref["logits"], atol=1e-5)


def test_a_tree_without_the_model_gets_no_result_at_once(toy_root,
                                                         monkeypatch):
    from code2vec_tpu import lm_facade
    cell = common.Cell(toy_root, "toy-keye.longctx")
    monkeypatch.delitem(lm_facade.MODEL_MODULES, "KeyeVL2")
    with pytest.raises(common.NoResult) as e:
        serve_lm_sparse_ctx.run(cell, 1, 1.0, trace=False,
                                require_tpu=False)
    assert e.value.code == 3


# ------------------------------------------------------ the cell's own files

@pytest.fixture(scope="module")
def cell():
    return common.Cell(ROOT, CELL)


def test_the_cell_its_files_and_limits_load(cell):
    assert cell.runner == "serve_lm_sparse_ctx" and cell.chips == 1
    assert cell.run_module() is serve_lm_sparse_ctx
    assert set(cell.limits()) == {"served_top_logit_gap", "served_score_gap",
                                  "served_score_gap_median"}
    assert [m["name"] for m in cell.end_to_end()] == ["request_p50_ms",
                                                      "setup_s"]
    mine = {m["name"] for m in cell.per_layer()}
    # every metric the runner kind brought is listed for its cell (PR 36
    # appended the entries; each is found by its name)
    new = set(BROUGHT)
    assert len(new) == 7 and new <= mine
    listed = {m["name"]: m for m in cell.bench["per_layer"]}
    for name in ("index_select_roofline.serve",
                 "sparse_attend_roofline.serve", "keys_selected_pct.serve"):
        assert listed[name]["workloads"] == [CELL]
    assert CELL not in listed["batches_cut_idle_pct.serve"]["workloads"]
    assert "batches_cut_idle_pct.serve" not in mine
    assert mine - new == {
        "batch_wait_mean_ms.serve", "device_phase_mean_ms.serve",
        "generator_late_p95_ms.serve", "request_p95_ms.serve",
        "restore_s.setup", "compile_s.setup", "batch_device_ms.serve",
        "batch_render_ms.serve", "batch_fill_pct.serve",
        "dispatcher_busy_pct.serve", "compiles_in_window.serve",
        "batch_tokens_fill_pct.serve", "expert_load_max_over_mean.serve"}
    from benchmarks import readers
    for name in mine:
        spec = cell.layer_metric_spec(name)
        assert spec["reader"] in readers.KINDS and spec["name"] == name
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "keye-vl2-pp8")
    assert entry["reduced"] == ["layers", "vision_tower", "weights"]
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200
    # found by its name: a later PR's cell behind it breaks nothing
    assert [w["name"] for w in cell.bench["workloads"]].count(CELL) == 1
    assert cell.entry["chips"] == 1


def test_the_configuration_keeps_every_published_key(cell):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    for key, value in row["config"].items():
        assert cell.config[key] == value, key
    c = cell.config
    assert c["source"] == row["source_url"]
    assert (c["layers"], c["experts_held"], c["expert_first"],
            c["vocab_rows"]) == (6, 128, 0, 151936)
    assert set(c["reduced"]) == {"layers", "vision_tower", "weights"}
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128       # attention
             + 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128      # indexer
             + 2 * 2048 + 2048 * 128 + 128 * 3 * 2048 * 768)  # norms, MoE
    assert layer == 625_381_760
    assert reference_keye.num_params(c) == c["parameters"] == (
        6 * layer + 2 * 151936 * 2048 + 2048) == 4_374_622_464
    assert c["parameter_bytes"] == 2 * c["parameters"]
    assert reference_keye.cache_bytes(c) == 8 * 40960 * 6 * 2176 \
        == 4_278_190_080
    serve = c["serve"]
    assert serve["length_buckets"] == [64, 128, 256]
    assert serve["token_budget"] == 256 and serve["top_k"] == 10
    assert serve["context_cache"]["register_chunk"] <= 2048
    assert "eight" in c["deployment"].lower() and "head" in c["deployment"]
    assert {"head_norms", "rotary", "indexer", "initializers",
            "q_chunk_size_kv_chunk_size", "traffic"} <= set(c["assumed"])
    assert "int8" in c["precision"]["lower_precision_control"]


def test_reference_leaves_are_the_programs(cell):
    from code2vec_tpu.models import sparse_gqa_moe_lm as lm
    have = [(leaf.name, tuple(leaf.shape), leaf.dtype)
            for leaf in lm.leaf_specs(lm.LMConfig.from_dict(cell.config))]
    assert [w[:3] for w in reference_keye.all_leaves(cell.config)] == have
    gain = reference_keye.make_leaf(5, cell.config, "layers.01.q_norm",
                                    (128,), "float32", "ones")
    plain = reference_keye.make_leaf(5, cell.config, "layers.01.k_norm",
                                     (128,), "float32", "ones")
    bias = reference_keye.make_leaf(5, cell.config, "layers.01.idx_k_bias",
                                    (64,), "float32", "zeros")
    assert float(gain.min()) == 2.0 and float(plain.max()) == 1.0
    assert not np.asarray(bias).any()
    down = np.asarray(reference_keye.make_leaf(
        5, cell.config, "layers.00.w_down", (2, 64, 256), "bfloat16",
        "normal"), np.float32)
    other = np.asarray(reference_keye.make_leaf(
        5, cell.config, "layers.00.wo", (128, 256), "bfloat16", "normal"),
        np.float32)
    assert 0.008 < down.std() < 0.012 and 0.017 < other.std() < 0.023
    again = np.asarray(reference_keye.make_leaf(
        5, cell.config, "layers.00.w_down", (2, 64, 256), "bfloat16",
        "normal"), np.float32)
    assert (down == again).all()


def test_the_mix_draws_the_lengths_it_states(cell):
    """Three quarters of the questions ride the 128 bucket, the median in
    its middle; every context fits one slot and is far longer than the
    2,048 keys a query keeps; contexts by Zipf(1.0); at least 150
    requests a 20 s window at the cell's rate."""
    from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
    pool = serve_lm_ctx.make_pool(cell.config, cell.traffic)
    serve = cell.config["serve"]
    buckets = parse_buckets(serve["length_buckets"], serve["token_budget"])
    assert buckets == (64, 128, 256)
    lengths = np.asarray(pool["lengths"])
    share = {b: float(np.mean([bucket_for(n, buckets) == b
                               for n in lengths])) for b in buckets}
    assert 0.70 < share[128] < 0.80
    assert 90 <= np.median(lengths) <= 102
    assert lengths.min() >= 32 and lengths.max() <= 256
    contexts = [len(c) for c in pool["contexts"]]
    assert len(contexts) == 8 and min(contexts) >= 24576
    assert max(contexts) <= serve["context_cache"]["tokens_per_slot"]
    assert 0.7 < sum(contexts) / (8 * 40960) < 0.9
    asked = np.bincount(pool["context_of"], minlength=8) / len(lengths)
    assert 0.33 < asked[0] < 0.41 and asked[0] > asked[1] > asked[7]
    arrivals = loadgen.schedule(3, 20.0, cell.traffic)
    assert len(arrivals) >= 150
    picked = serve_lm_ctx.pick_checked(3, arrivals, pool, cell.traffic)
    on = [pool["context_of"][arrivals[i]["body_index"]] for i in picked]
    assert len(picked) == len(set(picked)) == 8 and len(set(on)) >= 4


def test_recorded_readings_fall_on_the_right_side_of_the_limits(cell):
    """The limits file records the chip readings it was set from: every
    sound reading passes all three limits; the lower-precision control
    and each of the four faults fail at least one."""
    limits = cell.limits()
    recorded = common.load_json(os.path.join(
        cell.home, "limits", CELL + ".json"))["readings"]

    def fails(reading):
        return [n for n in limits if reading[n] > limits[n]]
    assert len(recorded["sound"]) >= 12
    for reading in recorded["sound"]:
        assert not fails(reading), reading
    for kind in ("control", "dense", "foreign_index", "wrong_slot",
                 "stale_length"):
        assert recorded[kind], kind
        for reading in recorded[kind]:
            assert fails(reading), (kind, reading)


def test_the_floors_are_the_hand_counts(cell):
    """One row of 96 question tokens on 32,768 cached ones."""
    c = cell.config
    q, held = 96, 32768
    pairs = q * held + q * (q + 1) // 2
    index = roofline_keye.index_select_terms(c, q, held + q, pairs)
    assert index[0]["flops"] == 2 * 16 * 64 * pairs
    assert index[1]["bytes"] == (held + q) * 64 * 2
    floor = roofline_keye.index_select_floor(c, q, held + q, pairs,
                                             "TPU v5 lite")
    assert floor["bound"] == "flops"
    assert abs(floor["seconds"] - 2048 * pairs / 197e12) < 1e-9
    kept = q * 2048
    attend = roofline_keye.sparse_attend_terms(c, q, held + q, kept)
    assert attend[0]["flops"] == 4 * 32 * 128 * kept
    # the slot read once (67 MB) is fewer bytes than 2,048 rows a query
    assert attend[1]["bytes"] == attend[1]["masked_bytes"] == (
        (held + q) * 2048) < attend[1]["gathered_bytes"] == kept * 2048
    floor = roofline_keye.sparse_attend_floor(c, q, held + q, kept,
                                              "TPU v5 lite")
    assert floor["bound"] == "bytes" and 80e-6 < floor["seconds"] < 90e-6
    # a single query: gathering its 2,048 rows is the cheaper form
    one = roofline_keye.sparse_attend_terms(c, 1, held + 1, 2048)
    assert one[1]["bytes"] == one[1]["gathered_bytes"] == 2048 * 2048
    # no more flops than dense attention over the visible pairs would take
    assert attend[0]["flops"] < 4 * 32 * 128 * pairs / 10
