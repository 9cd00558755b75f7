"""Parity tests: native C++ data core (cpp/src/dataloader.cc) vs the
pure-Python reference path in data/reader.py.

Both implement the reference pipeline semantics
(path_context_reader.py:184-228): empty field = PAD, unknown word = OOV,
context valid iff any part != PAD.
"""

import os
import subprocess

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import native, packed
from code2vec_tpu.data import reader as reader_mod
from code2vec_tpu.data.reader import EstimatorAction
from code2vec_tpu.vocab import Code2VecVocabs, Vocab, VocabType, special_words_for

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def built_library():
    if native.load_library() is None:
        rc = subprocess.run(["make", "-C", os.path.join(REPO_ROOT, "cpp")],
                            capture_output=True, text=True)
        assert rc.returncode == 0, rc.stderr
        native._lib_checked = False  # re-probe after building
    assert native.load_library() is not None


@pytest.fixture()
def vocabs():
    def build(vocab_type, words):
        return Vocab(vocab_type, words,
                     special_words_for(vocab_type, separate_oov_and_pad=False))
    return Code2VecVocabs(
        token_vocab=build(VocabType.Token, ["foo", "bar", "baz", "n"]),
        path_vocab=build(VocabType.Path, ["111", "222", "-333"]),
        target_vocab=build(VocabType.Target, ["get|x", "set|y"]),
    )


LINES = [
    "get|x foo,111,bar bar,222,baz n,-333,foo",
    "set|y foo,111,foo",
    "unknown|target foo,111,bar",          # OOV target
    "get|x zzz,999,qqq",                   # all-OOV context: still valid
    "get|x ,,",                            # all-empty context: invalid
    "get|x",                               # no contexts at all
    "",                                    # empty line
    "get|x foo,111,bar  bar,222,baz",      # double space: empty field skipped
    "get|x malformed_no_commas",
    "get|x a,b,c,d,e extra,222,parts",     # >3 comma parts ignored
    "set|y foo,111,bar\n",                 # trailing newline kept by caller
    "\n",                                  # blank line (must still be a row)
]


def _python_parse(lines, vocabs, m, action):
    """Force the pure-Python path regardless of the native library."""
    lib = native._lib
    native._lib = None
    try:
        return reader_mod.parse_context_lines(lines, vocabs, m, action)
    finally:
        native._lib = lib


def test_parse_parity_all_fields(vocabs):
    m = 4
    action = EstimatorAction.Evaluate
    py = _python_parse(LINES, vocabs, m, action)
    nat = reader_mod.parse_context_lines(LINES, vocabs, m, action)
    np.testing.assert_array_equal(py.source_token_indices,
                                  nat.source_token_indices)
    np.testing.assert_array_equal(py.path_indices, nat.path_indices)
    np.testing.assert_array_equal(py.target_token_indices,
                                  nat.target_token_indices)
    np.testing.assert_array_equal(py.context_valid_mask,
                                  nat.context_valid_mask)
    np.testing.assert_array_equal(py.target_index, nat.target_index)
    assert py.target_strings == nat.target_strings


def test_parse_parity_fuzz(vocabs):
    rng = np.random.default_rng(0)
    tokens = ["foo", "bar", "baz", "n", "zzz", ""]
    paths = ["111", "222", "-333", "999", ""]
    targets = ["get|x", "set|y", "nope", ""]
    lines = []
    for _ in range(300):
        n_ctx = int(rng.integers(0, 8))
        parts = [str(rng.choice(targets))]
        for _ in range(n_ctx):
            parts.append(",".join([str(rng.choice(tokens)),
                                   str(rng.choice(paths)),
                                   str(rng.choice(tokens))]))
        lines.append(" ".join(parts))
    m = 5
    action = EstimatorAction.Train
    py = _python_parse(lines, vocabs, m, action)
    nat = reader_mod.parse_context_lines(lines, vocabs, m, action)
    for field in ("source_token_indices", "path_indices",
                  "target_token_indices", "context_valid_mask",
                  "target_index"):
        np.testing.assert_array_equal(getattr(py, field), getattr(nat, field),
                                      err_msg=field)


def test_native_pack_matches_python_pack(tmp_path, vocabs):
    c2v = tmp_path / "data.test.c2v"
    c2v.write_text("\n".join(LINES) + "\n")
    m = 4
    native_out = packed.pack_c2v(str(c2v), vocabs, m,
                                 out_path=str(tmp_path / "native.c2vb"))
    lib = native._lib
    native._lib = None
    try:
        python_out = packed.pack_c2v(str(c2v), vocabs, m,
                                     out_path=str(tmp_path / "python.c2vb"))
    finally:
        native._lib = lib
    with open(native_out, "rb") as f:
        native_bytes = f.read()
    with open(python_out, "rb") as f:
        python_bytes = f.read()
    assert native_bytes == python_bytes
    with open(native_out + ".targets") as f:
        native_targets = f.read()
    with open(python_out + ".targets") as f:
        python_targets = f.read()
    assert native_targets == python_targets


def test_from_tables_and_parse_rows_match_vocab_tables(vocabs):
    """The worker-side table constructor (raw bytes->id dicts, no vocab
    object) and the interleaved-row parse entry point must agree with
    the vocab-built tables + separate-array parse."""
    m = 4
    ref = native.NativeTables(vocabs)
    worker = native.NativeTables.from_tables(
        {w.encode(): i for w, i in vocabs.token_vocab.word_to_index.items()},
        {w.encode(): i for w, i in vocabs.path_vocab.word_to_index.items()},
        {w.encode(): i for w, i in vocabs.target_vocab.word_to_index.items()},
        token_pad=vocabs.token_vocab.pad_index,
        token_oov=vocabs.token_vocab.oov_index,
        path_pad=vocabs.path_vocab.pad_index,
        path_oov=vocabs.path_vocab.oov_index,
        target_oov=vocabs.target_vocab.oov_index)
    lines = [ln.rstrip("\n") for ln in LINES]
    blob = ("\n".join(lines) + "\n").encode()
    n = len(lines)
    src, pth, tgt, label, _mask = ref.parse_blob(blob, n, m)
    rec = worker.parse_rows_blob(blob, n, m)
    np.testing.assert_array_equal(rec[:, 0], label)
    np.testing.assert_array_equal(rec[:, 1:1 + m], src)
    np.testing.assert_array_equal(rec[:, 1 + m:1 + 2 * m], pth)
    np.testing.assert_array_equal(rec[:, 1 + 2 * m:], tgt)


def test_native_histogram_range_matches_python(tmp_path):
    """`c2v_histogram_range` (the map step of the multiprocess histogram
    build) must reproduce the Python serial loop exactly, including the
    skip rules for empty names/fields and non-3-piece contexts."""
    from code2vec_tpu.data import preprocess as pp
    raw = tmp_path / "raw.txt"
    raw.write_text(
        "get|x foo,111,bar foo,111,bar bar,222,baz\n"
        "\n"                                  # blank line skipped
        " t,1,t\n"                            # empty name: line skipped
        "set|y  foo,111,foo ,, a,b\n"         # empty field, 3-empty, 2-piece
        "get|x a,b,c,d e,111,f\n"             # 4-piece skipped, 3-piece kept
        "solo\n"
        "last f,222,g")                       # unterminated final line
    serial = pp.build_histograms(str(raw))
    assert native.load_library() is not None
    sharded = pp.build_histograms(str(raw), num_workers=2)
    assert tuple(sharded) == tuple(serial)


def test_fused_pack_native_matches_python(tmp_path, vocabs):
    """pack_raw with the native worker core vs the pure-Python memo path:
    identical `.c2vb` bytes and sidecar (sampling engaged)."""
    raw = tmp_path / "raw.txt"
    rng = np.random.default_rng(3)
    tokens = ["foo", "bar", "baz", "n", "zzz"]
    paths = ["111", "222", "-333", "999"]
    with open(raw, "w") as f:
        for i in range(200):
            k = int(rng.integers(1, 9))  # m=4 -> plenty over budget
            ctxs = [",".join([str(rng.choice(tokens)), str(rng.choice(paths)),
                              str(rng.choice(tokens))]) for _ in range(k)]
            f.write(f"get|x {' '.join(ctxs)}\n")
    w2c = {"foo": 5, "bar": 4, "baz": 3, "n": 2}
    p2c = {"111": 5, "222": 4, "-333": 3}
    native_out = str(tmp_path / "native.c2vb")
    packed.pack_raw(str(raw), native_out, vocabs, w2c, p2c, 4, seed=11,
                    num_workers=1)
    lib = native._lib
    native._lib = None
    try:
        python_out = str(tmp_path / "python.c2vb")
        packed.pack_raw(str(raw), python_out, vocabs, w2c, p2c, 4, seed=11,
                        num_workers=1)
    finally:
        native._lib = lib
    with open(native_out, "rb") as a, open(python_out, "rb") as b:
        assert a.read() == b.read()
    with open(native_out + ".targets", "rb") as a, \
            open(python_out + ".targets", "rb") as b:
        assert a.read() == b.read()


def test_packed_dataset_roundtrip_native(tmp_path, vocabs):
    c2v = tmp_path / "data.train.c2v"
    c2v.write_text("\n".join(LINES) + "\n")
    out = packed.pack_c2v(str(c2v), vocabs, 4)
    ds = packed.PackedDataset(out, vocabs)
    batches = list(ds.iter_batches(2, EstimatorAction.Train, num_epochs=1))
    # valid train rows: known target AND >=1 valid context
    total = sum(b.num_valid for b in batches)
    assert total == 4  # lines 0,1,7,9 survive; ragged tail dropped -> pairs
