"""The train runner without a chip, and the float32 reference it is held
to: a rehearsal of the runner's control flow at toy widths on the CPU
over ten seeds (it reads `correct` true and prints no device metric),
the same with the timed step broken underneath (`correct` false), the
reference against itself (blocking, weights), and that the lower
precision and a dropped part of the batch fail the limits the benchmark
runs with.

This file is also ONE UNIT of the tier-1 run (`-n 6 --dist loadfile`
hands out whole files, most tests first): it holds 19 or 20 tests and
its long tests come first, so that the worker that takes it stays busy
for three quarters of a minute. See bench_testlib.py, "Why three files".
"""

import json
import os

import numpy as np
import pytest

from bench_testlib import ROOT, has_result_line, make_toy_root

from benchmarks import common, reference
from benchmarks.runners import train

DIMS = reference.Dims(3000, 2000, 1500, 16, 16)
# the driver's seeds are large: more than 32 signed bits hold
SEEDS = [2 ** 31 + 21 + 104729 * i for i in range(10)]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("bench") / "copy"))


@pytest.mark.parametrize("seed", SEEDS)
def test_train_rehearsal_reads_correct_and_prints_no_device_metric(
        toy_root, capsys, seed):
    cell = common.Cell(toy_root, "toy-nodrop.train")
    got = train.run(cell, seed, 0.5, False, require_tpu=False, emit=False)
    assert got["correct"], got["checks"]
    assert got["steps"] > 0 and got["device"]["platform"] == "cpu"
    assert [c["name"] for c in got["checks"]] == [
        "fed_rows_foreign_or_repeated", "loss_gap_step1", "loss_gap_step2",
        "loss_gap_step3", "first_grad_norm_gap", "param_change_norm_gap"]
    assert not has_result_line(capsys.readouterr().out)


def test_train_with_the_step_broken_underneath_reads_not_correct(
        toy_root, monkeypatch):
    """The timed path returns its state unchanged from the second step
    on (the step runs, its state is thrown away): the run goes through,
    `correct` comes out false."""
    import jax

    class Broken(train.Program):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            step, calls = self.train_step, [0]

            def unchanged(state, *rest):
                calls[0] += 1
                if calls[0] == 1:
                    return step(state, *rest)
                _, loss = step(jax.tree.map(lambda x: x + 0, state), *rest)
                return state, loss
            self.train_step = unchanged

    monkeypatch.setattr(train, "Program", Broken)
    cell = common.Cell(toy_root, "toy-nodrop.train")
    got = train.run(cell, 2 ** 31 + 22, 0.5, False, require_tpu=False,
                    emit=False)
    assert not got["correct"]
    failed = [c["name"] for c in got["checks"] if not c["ok"]]
    assert "param_change_norm_gap" in failed


def limits():
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "train.default.json")) as f:
        return json.load(f)["limits"]


def batches(rows=64, contexts=20, n=3, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        count = rng.integers(2, contexts + 1, rows)
        mask = (np.arange(contexts)[None] < count[:, None])
        ids = lambda hi: (rng.integers(1, hi, (rows, contexts))  # noqa: E731
                          * mask).astype(np.int32)
        out.append({"src": ids(3000), "pth": ids(2000), "tgt": ids(3000),
                    "mask": mask.astype(np.float32),
                    "labels": rng.integers(1, 1500, rows).astype(np.int32)})
    return out


def test_weights_are_a_pure_function_of_seed_leaf_and_index():
    import jax.numpy as jnp
    big = 2 ** 31 + 12345          # more than 32 signed bits hold
    a = reference.make_params(big, DIMS)
    b = reference.make_params(big, DIMS)
    other = reference.make_params(big + 1, DIMS)
    limit = reference.init_limits(DIMS)
    for name, (rows, cols) in DIMS.shapes().items():
        assert a[name].shape == (rows, cols) and a[name].dtype == jnp.float32
        assert np.array_equal(a[name], b[name])
        assert not np.array_equal(a[name], other[name])
        assert float(jnp.max(jnp.abs(a[name]))) <= limit[name]
        assert abs(float(jnp.mean(a[name]))) < 0.1 * limit[name]
    rows = jnp.array([5, 17, 2999])
    words = jnp.asarray(reference.seed_words(big, DIMS))
    part = reference.hash_uniform(words[0], rows, 16, limit["token_embedding"])
    assert np.array_equal(part, np.asarray(a["token_embedding"])[[5, 17, 2999]])
    assert DIMS.num_params() == sum(int(v.size) for v in a.values())


def test_reference_does_not_depend_on_its_blocking_without_dropout():
    data = batches()
    a = reference.follow_steps(7, DIMS, data, keep=1.0, block_rows=64)
    b = reference.follow_steps(7, DIMS, data, keep=1.0, block_rows=16)
    assert a.losses == pytest.approx(b.losses, rel=1e-5)
    assert reference.worst_leaf_gap(b.grad_norms, a.grad_norms)[0] < 1e-4
    assert reference.worst_leaf_gap(b.delta_norms, a.delta_norms)[0] < 1e-4
    assert a.losses[0] == pytest.approx(np.log(1500), rel=2e-3)


@pytest.mark.parametrize("lower", reference.LOWER)
def test_the_lower_precision_fails_the_limits(lower):
    """The control kept as a test, whole and by its halves. Parameters
    held in bfloat16 move the parameters' change past its limit; int8
    matmul operands alone do NOT fail a train limit (whole-batch norms
    absorb them; the serve cells' served_score_gap holds the matmul
    precision, PERF.md section 2), and the test says so."""
    data = batches()
    sound = reference.follow_steps(7, DIMS, data, keep=1.0, block_rows=64)
    low = reference.follow_steps(7, DIMS, data, keep=1.0, lower=lower,
                                 block_rows=64)
    checks = train.compare(low, sound, limits())
    failed = [c["name"] for c in checks if not c["ok"]]
    if lower == "operands":
        assert failed == []
    else:
        assert "param_change_norm_gap" in failed
    own = train.compare(sound, sound, limits())
    assert all(c["ok"] for c in own)


@pytest.mark.parametrize("fault", ["quarter_of_the_batch_left_out",
                                   "state_unchanged"])
def test_a_dropped_term_fails_the_limits(fault):
    data = batches()
    sound = reference.follow_steps(7, DIMS, data, keep=1.0, block_rows=64)
    if fault == "state_unchanged":
        broken = reference.Followed(sound.losses, sound.grad_norms,
                                    {k: 0.0 for k in sound.delta_norms})
        want = "param_change_norm_gap"
    else:
        # three quarters of the rows, still divided by the whole batch:
        # what a step that loses one chip's share computes
        part = [{k: v[:48] for k, v in b.items()} for b in data]
        kept = reference.follow_steps(7, DIMS, part, keep=1.0, block_rows=48)
        broken = reference.Followed(
            [x * 0.75 for x in kept.losses],
            {k: v * 0.75 for k, v in kept.grad_norms.items()},
            kept.delta_norms)
        want = "loss_gap_step1"
    failed = [c["name"] for c in train.compare(broken, sound, limits())
              if not c["ok"]]
    assert want in failed, failed


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = reference.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, leaf = reference.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5}, ref)
    assert leaf == "c" and gap == pytest.approx(0.5)      # against median 1.0
    assert reference.worst_leaf_gap({"a": float("nan"), "b": 2.0, "c": 0.0},
                                    ref)[0] == float("inf")


def test_served_gap_reads_zero_for_the_reference_itself_and_more_below():
    import jax.numpy as jnp
    params = reference.make_params(3, DIMS)
    b = batches(rows=16, n=1, seed=3)[0]
    logits, _, _ = reference.forward(params, b["src"], b["pth"], b["tgt"],
                                     b["mask"])
    logits = logits.at[:, 0].set(-jnp.inf)
    order = np.argsort(-np.asarray(logits), axis=1)[:, :10].astype(np.int32)
    top = np.take_along_axis(np.asarray(logits), order, axis=1)
    logp = top - np.log(np.exp(top - top[:, :1]).sum(1, keepdims=True))
    for half in reference.LOWER:
        got = reference.served_gap(params, b["src"], b["pth"], b["tgt"],
                                   b["mask"], order, logp.astype(np.float32),
                                   control=half)
        assert got["top_gap"] == 0.0 and got["score_gap"] < 1e-4
        assert got["control_score_gap"] > 10 * max(got["score_gap"], 1e-5)
    swapped = order[:, ::-1].copy()
    worse = reference.served_gap(params, b["src"], b["pth"], b["tgt"],
                                 b["mask"], swapped, logp[:, ::-1].copy())
    assert worse["top_gap"] > 0.01
