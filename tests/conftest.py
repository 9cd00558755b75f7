"""Test harness: run JAX on CPU with 8 virtual devices so DP/TP/CP sharding
is exercised without TPU hardware (SURVEY.md §4 implication)."""

import os

# Forced (not setdefault): tests exercise sharding on 8 virtual CPU
# devices whatever the ambient environment points JAX at. The
# environment carries both settings into the subprocesses tests spawn;
# the config updates cover this process (safe: the backend initializes
# lazily).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import pytest  # noqa: E402

from code2vec_tpu.vocab import (  # noqa: E402
    Code2VecVocabs, WordFreqDicts,
)


@pytest.fixture
def tiny_vocabs() -> Code2VecVocabs:
    """Small deterministic vocabs used across tests."""
    freq = WordFreqDicts(
        token_to_count={"foo": 10, "bar": 8, "baz": 5, "qux": 2},
        path_to_count={"P1": 9, "P2": 7, "P3": 3},
        target_to_count={"get|name": 6, "set|value": 4, "run": 2},
        num_train_examples=100,
    )
    return Code2VecVocabs.create_from_freq_dicts(
        freq, max_token_vocab_size=10, max_path_vocab_size=10,
        max_target_vocab_size=10)


@pytest.fixture
def tiny_config(tmp_path):
    from code2vec_tpu.config import Config
    return Config(
        train_data_path_prefix=str(tmp_path / "data"),
        max_contexts=4,
        train_batch_size=2,
        test_batch_size=2,
        num_train_epochs=1,
        shuffle_buffer_size=8,
        seed=0,
    )


# A benchmark test file that reads a series SINCE THE PROCESS STARTED, and
# the prefix of the series it reads so.
_READ_WHOLE = {"test_benchmark_lm": "moe_",
               "test_benchmark_trinity": "score_"}


@pytest.fixture(autouse=True, scope="module")
def _series_from_zero_for_the_files_that_read_them_whole(request):
    """tests/benchmark/test_benchmark_lm.py holds the expert router's
    series (`moe_*`) SINCE THE PROCESS STARTED to its own toy's four
    experts a (step, layer), and reads exactly 4.0 alone. Under `--dist
    loadfile` a worker runs many files in one process, files with more
    tests first: tests/test_latent_moe_lm.py and test_sparse_gqa_moe_lm.py
    serve toys of sixteen experts (13.9 and 13.0 hit a step and layer)
    and are queued before it, so it fails (5.0 <= 4) whenever the
    scheduler hands it to a worker that ran either; every new test file
    moves that draw. tests/benchmark/test_benchmark_trinity.py reads
    `score_pages_needed_total` the same way and holds it to a multiple
    of its toy's TWO full layers: test_benchmark_solar.py (one full
    layer) stands three files before it, behind a file of no seconds, so
    the worker that ends that file first takes both (465 % 2, seen at
    PR 46; one draw in three). Those files may be edited only by a PR of
    kind `benchmark`: until one makes them read the series over their
    own rehearsal, they alone start from zero. No other file's series
    move."""
    prefix = _READ_WHOLE.get(request.module.__name__.rpartition(".")[2])
    if prefix:
        from code2vec_tpu import obs
        obs.default_registry().reset(prefix)
