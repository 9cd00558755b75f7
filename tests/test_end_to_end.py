"""End-to-end slice on a tiny synthetic dataset: preprocess -> vocab ->
train (loss decreases) -> evaluate (model memorizes) -> save/load -> predict.
This is BASELINE.json config #1's shape (java-small, CPU-runnable) in
miniature."""

import os
import pickle
import random

import numpy as np
import pytest

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.model_facade import Code2VecModel
from code2vec_tpu.vocab import VocabType


def _make_synthetic_dataset(tmp_path, n_rows=96, max_contexts=8, seed=0):
    """Learnable synthetic data: target determined by which tokens appear."""
    rng = random.Random(seed)
    # NB: targets must match the legality filter ^[a-zA-Z|]+$
    # (common.py:122-124) or every prediction is filtered out.
    letters = ["alpha", "beta", "gamma", "delta"]
    tokens = [f"tok{i}" for i in range(12)]
    paths = [f"path{i}" for i in range(6)]
    targets = [f"name|{letters[i]}" for i in range(4)]
    rows = []
    for _ in range(n_rows):
        t = rng.randrange(len(targets))
        contexts = []
        for _ in range(rng.randint(3, max_contexts)):
            # token identity leaks the target -> memorizable
            tok = tokens[t * 3 + rng.randrange(3)]
            contexts.append(f"{tok},{rng.choice(paths)},{tok}")
        pad = " " * (max_contexts - len(contexts))
        rows.append(f"{targets[t]} " + " ".join(contexts) + pad)

    token_counts = {w: 10 for w in tokens}
    path_counts = {p: 10 for p in paths}
    target_counts = {t: 10 for t in targets}

    prefix = str(tmp_path / "synthetic")
    with open(prefix + ".train.c2v", "w") as f:
        f.write("\n".join(rows) + "\n")
    with open(prefix + ".val.c2v", "w") as f:
        f.write("\n".join(rows[:32]) + "\n")
    with open(prefix + ".dict.c2v", "wb") as f:
        pickle.dump(token_counts, f)
        pickle.dump(path_counts, f)
        pickle.dump(target_counts, f)
        pickle.dump(len(rows), f)
    return prefix


@pytest.mark.parametrize("use_packed", [True, False])
def test_train_eval_save_load_predict(tmp_path, use_packed):
    prefix = _make_synthetic_dataset(tmp_path)
    save_path = str(tmp_path / "model" / "saved_model")
    config = Config(
        train_data_path_prefix=prefix,
        test_data_path=prefix + ".val.c2v",
        model_save_path=save_path,
        max_contexts=8,
        train_batch_size=16, test_batch_size=16,
        num_train_epochs=30,
        num_batches_to_log_progress=1000,
        compute_dtype="float32",
        use_packed_data=use_packed,
        shuffle_buffer_size=64,
        save_every_epochs=1000,  # don't checkpoint mid-test
        verbose_mode=0,
    )
    model = Code2VecModel(config)
    model.train()

    results = model.evaluate()
    # memorizable dataset: near-perfect top-1 after 30 epochs
    assert results.topk_acc[0] > 0.9, str(results)
    assert results.subtoken_f1 > 0.9, str(results)

    # w2v export
    w2v_path = str(tmp_path / "tokens.w2v")
    model.save_word2vec_format(w2v_path, VocabType.Token)
    with open(w2v_path) as f:
        header = f.readline().split()
    assert int(header[0]) == model.vocabs.token_vocab.size
    assert int(header[1]) == config.token_embeddings_size

    # load into a fresh model and check eval matches; also exercise the
    # code-vector export — by default the sharded retrieval store
    # format (retrieval/store.py; --vectors_text restores the
    # reference's text layout, pinned in tests/test_retrieval.py)
    load_config = Config(
        model_load_path=save_path,
        test_data_path=prefix + ".val.c2v",
        max_contexts=8, test_batch_size=16,
        compute_dtype="float32",
        use_packed_data=use_packed,
        export_code_vectors=True,
        verbose_mode=0,
    )
    loaded = Code2VecModel(load_config)
    results2 = loaded.evaluate()
    np.testing.assert_allclose(results2.topk_acc, results.topk_acc, atol=1e-6)
    vectors_path = load_config.test_data_path + ".vectors"
    assert os.path.exists(vectors_path)
    from code2vec_tpu.retrieval.store import VectorStore
    store = VectorStore.open(vectors_path)
    assert store.rows == load_config.num_test_examples
    assert store.dim == 3 * load_config.token_embeddings_size
    assert store.fingerprint == loaded.model_fingerprint()
    assert np.isfinite(store.load()).all()

    # predict on a raw line (no filtering)
    line = "unknownname tok0,path0,tok0 tok1,path1,tok1" + " " * 6
    preds = loaded.predict([line])
    assert len(preds) == 1
    assert preds[0].original_name == "unknownname"
    # k is clamped to the target vocab size (reference:
    # tensorflow_model.py:298-299)
    assert len(preds[0].topk_predicted_words) == min(
        config.top_k_words_considered_during_prediction,
        loaded.vocabs.target_vocab.size)
    assert abs(sum(preds[0].topk_predicted_words_scores) - 1.0) < 1e-5
    assert ("tok0", "path0", "tok0") in preds[0].attention_per_context
    # name|alpha should be the top prediction for tok0/tok1 contexts
    assert preds[0].topk_predicted_words[0] == "name|alpha"


def test_startup_phases_are_each_timed_once(tmp_path):
    """`startup_phase_seconds{phase}`: a `Code2VecModel` start times its
    dictionary load and its initial state once each, the trainer its
    first step once (call until the result is ready), and `restore`
    exists only under --load, where the initial state is still built."""
    prefix = _make_synthetic_dataset(tmp_path, n_rows=48)
    save_path = str(tmp_path / "model" / "saved_model")
    common = dict(max_contexts=8, compute_dtype="float32", verbose_mode=0)

    def phases():
        names = [e["name"] for e in tracer.chrome_trace()["traceEvents"]
                 if e["name"].startswith("startup.")]
        return {n[len("startup."):]: names.count(n) for n in set(names)}

    def seconds(phase):
        return obs.gauge("startup_phase_seconds", phase=phase).value

    tracer = obs.default_tracer()
    tracer.clear()
    tracer.enable()
    try:
        obs.gauge("startup_phase_seconds", phase="restore").set(0.0)
        model = Code2VecModel(Config(
            train_data_path_prefix=prefix, model_save_path=save_path,
            train_batch_size=16, num_train_epochs=2,
            num_batches_to_log_progress=1000, save_every_epochs=1000,
            **common))
        assert phases() == {"vocab_load": 1, "state_init": 1}
        model.train()
        assert phases() == {"vocab_load": 1, "state_init": 1,
                            "first_step": 1}
        assert seconds("restore") == 0.0
        for phase in ("vocab_load", "state_init", "first_step"):
            assert seconds(phase) > 0.0, phase
        Code2VecModel(Config(model_load_path=save_path,
                             test_data_path=prefix + ".val.c2v",
                             test_batch_size=16, **common))
        assert phases() == {"vocab_load": 2, "state_init": 2,
                            "first_step": 1, "restore": 1}
        assert seconds("restore") > 0.0
    finally:
        tracer.disable()
        tracer.clear()


def test_release_roundtrip(tmp_path):
    prefix = _make_synthetic_dataset(tmp_path, n_rows=32)
    save_path = str(tmp_path / "model" / "m")
    config = Config(
        train_data_path_prefix=prefix, model_save_path=save_path,
        max_contexts=8, train_batch_size=16, num_train_epochs=2,
        compute_dtype="float32", verbose_mode=0, save_every_epochs=1000,
        num_batches_to_log_progress=1000)
    model = Code2VecModel(config)
    model.train()

    release_config = Config(
        model_load_path=save_path, release=True, max_contexts=8,
        compute_dtype="float32", verbose_mode=0)
    releaser = Code2VecModel(release_config)
    assert releaser.evaluate() is None  # release mode returns None
    released_path = save_path + ".release"
    assert os.path.isdir(released_path)

    # released artifact loads (without optimizer state)
    from code2vec_tpu.training.checkpoint import load_model_meta
    assert load_model_meta(released_path)["released"] is True
    load_config = Config(
        model_load_path=released_path, test_data_path=prefix + ".val.c2v",
        max_contexts=8, test_batch_size=16, compute_dtype="float32",
        verbose_mode=0)
    loaded = Code2VecModel(load_config)
    results = loaded.evaluate()
    assert results is not None


def test_repl_pipeline_on_input_java(tmp_path):
    """The interactive REPL's loop body, non-interactively: native
    extractor bridge over the shipped Input.java -> model.predict ->
    parse_prediction_results (predictions + attention display rows).
    reference flow: interactive_predict.py:39-72."""
    import os
    import subprocess
    from code2vec_tpu.serving.extractor_bridge import PathExtractor
    from code2vec_tpu.serving.interactive import parse_prediction_results

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    binary = os.path.join(repo_root, "cpp", "build", "c2v-extract")
    if not os.path.exists(binary):
        rc = subprocess.run(["make", "-C", os.path.join(repo_root, "cpp")],
                            capture_output=True, text=True)
        assert rc.returncode == 0, rc.stderr

    prefix = _make_synthetic_dataset(tmp_path)
    config = Config(
        train_data_path_prefix=prefix,
        max_contexts=8, train_batch_size=16, test_batch_size=16,
        num_train_epochs=1, compute_dtype="float32",
        num_batches_to_log_progress=1000, shuffle_buffer_size=64,
        save_every_epochs=1000)
    model = Code2VecModel(config)
    model.train()

    extractor = PathExtractor(config, max_path_length=8, max_path_width=2)
    lines, hash_to_string = extractor.extract_paths(
        os.path.join(repo_root, "Input.java"))
    assert lines, "no methods extracted from Input.java"

    raw = model.predict(lines)
    oov = model.vocabs.target_vocab.special_words.oov
    methods = parse_prediction_results(raw, hash_to_string, oov, topk=5)
    assert len(methods) == len(lines)
    m = methods[0]
    # the shipped Input.java defines `sumValues` (subtokens sum|values)
    assert m.original_name == "sum|values"
    assert m.predictions, "no top-k predictions surfaced"
    assert all(0.0 <= p["probability"] <= 1.0 for p in m.predictions)
    # attention rows must display READABLE paths (hash inverted)
    assert m.attention_paths
    for att in m.attention_paths:
        assert att["path"].startswith("("), att  # node-string form
        assert 0.0 <= att["score"] <= 1.0
