"""The train head with a VJP of its own (ops/head_ce.py), held against
`jax.value_and_grad` of the form it replaced: the einsum's logits under
optax's cross-entropy. In float32 the two are the same sums in another
order; under bfloat16 operands the rounding of the code vectors'
gradient's operand falls on `exp(l - m)` where autodiff's falls on the
probabilities less the one-hot, so the gradients agree as vectors to a
few units of bfloat16's 2**-9. The TPU kernel of pass B runs here
through the Pallas interpreter."""

import functools

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from code2vec_tpu.ops import head_ce
from code2vec_tpu.ops.head_ce import head_cross_entropy
from code2vec_tpu.parallel.mesh import AXIS_DATA, MeshPlan, make_mesh

B, D, V = 16, 24, 200
# loss; gradients as vectors (norm of the difference over the norm)
TOLERANCE = {"float32": (1e-6, 1e-6), "bfloat16": (1e-6, 5e-3)}


def _optax_form(x, table, labels, weights, real_rows, dtype):
    logits = jnp.einsum("bd,vd->bv", x.astype(dtype), table.astype(dtype),
                        preferred_element_type=jnp.float32)
    if real_rows < table.shape[0]:
        logits = jnp.where(jnp.arange(table.shape[0])[None, :] < real_rows,
                           logits, -jnp.inf)
    return jnp.sum(weights * optax.softmax_cross_entropy_with_integer_labels(
        logits, labels))


def _random(rng):
    return dict(
        x=rng.normal(size=(B, D)).astype(np.float32),
        table=(rng.normal(size=(V, D)) * 0.4).astype(np.float32),
        labels=rng.integers(0, V, B).astype(np.int32),
        weights=np.full((B,), 1.0 / B, np.float32), real_rows=V)


def _padded_columns(rng):
    case = _random(rng)
    case["real_rows"] = V - 7
    case["labels"] = rng.integers(0, V - 7, B).astype(np.int32)
    return case


def _zero_weight_rows(rng):
    case = _random(rng)
    case["weights"][[0, 5, B - 1]] = 0.0
    return case


def _logits_of_80(rng):
    """Every row's label logit is +80, another column's -80 and a third
    ties the label at +80 (so the gradients are not all rounding):
    without the shift by the row max the exponentials leave float32."""
    case = _random(rng)
    table = rng.choice([-1.0, 1.0], size=(V, D)).astype(np.float32)
    case["labels"] = np.arange(B, dtype=np.int32) * 3
    table[case["labels"] + 1] = -table[case["labels"]]
    tie = table[case["labels"]].copy()
    tie[:, 0] *= -1.0
    tie[:, 1] *= 3.0
    table[case["labels"] + 2] = tie
    case["table"] = table
    case["x"] = table[case["labels"]] * (80.0 / D)
    return case


def _labels_at_the_edges(rng):
    case = _random(rng)
    case["labels"] = np.where(np.arange(B) % 2 == 0, 0, V - 1).astype(
        np.int32)
    return case


CASES = {"random": _random, "padded_columns": _padded_columns,
         "zero_weight_rows": _zero_weight_rows,
         "logits_of_80": _logits_of_80,
         "labels_at_the_edges": _labels_at_the_edges}


def _case(name):
    case = CASES[name](np.random.default_rng(sorted(CASES).index(name)))
    real_rows = case.pop("real_rows")
    return {k: jnp.asarray(v) for k, v in case.items()}, real_rows


def _value_and_grads(fn, case, real_rows, dtype, **kwargs):
    return jax.value_and_grad(
        lambda x, table: fn(x, table, case["labels"], case["weights"],
                            real_rows, dtype, **kwargs),
        argnums=(0, 1))(case["x"], case["table"])


def _assert_close(got, want, dtype, loss_atol=0.0):
    loss_tol, grad_tol = TOLERANCE[jnp.dtype(dtype).name]
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=loss_tol,
                               atol=loss_atol)
    for name, a, b in zip(("code vectors", "table"), got[1], want[1]):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.isfinite(a).all(), name
        assert (np.linalg.norm(a - b)
                <= grad_tol * np.linalg.norm(b)), (name, np.abs(a - b).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_loss_and_gradients_are_autodiffs_of_the_optax_form(name, dtype):
    case, real_rows = _case(name)
    dtype = jnp.dtype(dtype)
    got = _value_and_grads(head_cross_entropy, case, real_rows, dtype)
    # a loss between logits of 80 is good to their last bit, not its own
    _assert_close(got, _value_and_grads(_optax_form, case, real_rows, dtype),
                  dtype, loss_atol=80 * 2.0 ** -23 * (name == "logits_of_80"))
    code_ct, table_ct = (np.asarray(g) for g in got[1])
    if name == "padded_columns":
        # a padded column has probability 0: nothing reaches its row
        assert not table_ct[real_rows:].any()
        assert table_ct[:real_rows].any()
    if name == "zero_weight_rows":
        dead = np.asarray(case["weights"]) == 0
        assert not code_ct[dead].any() and code_ct[~dead].all(axis=1).any()
        live = {k: v[~dead] if v.shape[:1] == (B,) else v
                for k, v in case.items()}
        _assert_close(got[:1] + ((code_ct[~dead], table_ct),),
                      _value_and_grads(_optax_form, live, real_rows, dtype),
                      dtype)
    if name == "logits_of_80":
        logits = np.asarray(case["x"]) @ np.asarray(case["table"]).T
        np.testing.assert_allclose([logits.max(), logits.min()],
                                   [80.0, -80.0], rtol=1e-6)
        # the label and its tie share the probability, the rest have none
        np.testing.assert_allclose(float(got[0]), np.log(2.0), rtol=1e-2)


# ------------------------------------------------------ pass B's kernel

@pytest.fixture
def toy_tiles(monkeypatch):
    monkeypatch.setattr(head_ce, "TILE", 128)
    monkeypatch.setattr(head_ce, "ROWS", 8)


def _kernel_case(columns, rows=16, width=128):
    rng = np.random.default_rng(columns)
    logits = jnp.asarray(rng.normal(size=(rows, columns)) * 4, jnp.float32)
    table = jnp.asarray(rng.normal(size=(columns, width)), jnp.float32)
    return logits, jnp.max(logits, axis=-1), table


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("columns", [
    128,    # one tile
    384,    # whole tiles
    300,    # a ragged last tile: what lies past column 300 is masked
])
def test_the_kernel_gives_the_plain_ops_sums(toy_tiles, columns, dtype):
    logits, row_max, table = _kernel_case(columns)
    want = head_ce._exp_sums_plain(logits, row_max, table, jnp.dtype(dtype))
    got = head_ce._exp_sums_pallas(logits, row_max, table, jnp.dtype(dtype),
                                   interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                                   atol=2e-6 * float(jnp.abs(b).max()))


def _interpreted(logits, row_max, table, compute_dtype):
    return head_ce._exp_sums_pallas(logits, row_max, table, compute_dtype,
                                    interpret=True)


@pytest.mark.parametrize("rows,columns,width,kernel", [
    (16, 300, 128, True),
    (8, 300, 128, True),        # one block of rows
    (12, 300, 128, False),      # rows that are not whole blocks
    (16, 300, 24, False),       # a width that is not whole lanes
    (16, 100, 128, False),      # fewer target rows than a tile
])
def test_shapes_the_blocks_do_not_divide_take_the_plain_ops(
        toy_tiles, rows, columns, width, kernel):
    logits, row_max, table = _kernel_case(columns, rows, width)
    jaxpr = str(jax.make_jaxpr(functools.partial(
        head_ce._exp_sums_on_a_chip, compute_dtype=jnp.bfloat16))(
            logits, row_max, table))
    assert ("pallas_call" in jaxpr) is kernel


# ------------------------------------------------------- jit and meshes

def test_a_donated_state_gives_the_same_update():
    """As the train step holds it: the table donated, its update made
    from the head's gradient inside one jit."""
    case, real_rows = _case("random")

    def update(table, x):
        loss, (code_ct, table_ct) = jax.value_and_grad(
            lambda x, t: head_cross_entropy(
                x, t, case["labels"], case["weights"], real_rows,
                jnp.float32), argnums=(0, 1))(x, table)
        return table - 0.1 * table_ct, loss, code_ct
    want = update(case["table"], case["x"])
    got = jax.jit(update, donate_argnums=0)(jnp.array(case["table"]),
                                            case["x"])
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-8)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("columns", [300, 301, 1003])
@pytest.mark.parametrize("chips", [2, 4, 8])
def test_four_chips_over_the_rows_give_one_chips_loss_and_gradients(
        monkeypatch, toy_tiles, chips, columns, form, dtype):
    """A data mesh of `chips`: the batch's rows arrive sharded and the
    table whole on every chip, and the chips split the TARGET rows for
    the head. What crosses them: every chip's code vectors, labels and
    weights (an all-gather), the row max (`pmax`), the row sums, `E @
    W` and the label's logit (`psum`s of `(B,)` and `(B, D)`), and in
    the backward one all-gather of the finished shards of the table's
    gradient; no table-shaped sum. The last chip's shard is ragged (301
    and 1,003 rows leave 1 and 3 modulo 2, 4 and 8; 300 leaves 4 modulo
    8) and holds the table's own padded rows (`real_rows < V`) and two
    of the labels; every fifth row has weight 0. The code vectors'
    cotangent comes back sharded over `data` as they went in."""
    if form == "kernel":
        monkeypatch.setattr(head_ce, "_exp_sums_on_a_chip", _interpreted)
    rng = np.random.default_rng(3)
    real_rows = columns - 5
    labels = rng.integers(0, real_rows, 32).astype(np.int32)
    labels[[1, 30]] = real_rows - 1, real_rows - 2
    case = dict(
        x=rng.normal(size=(32, 128)).astype(np.float32),
        table=(rng.normal(size=(columns, 128)) * 0.2).astype(np.float32),
        labels=labels,
        weights=np.where(np.arange(32) % 5 == 0, 0, 1 / 32).astype(
            np.float32))
    shard = -(-columns // chips)
    assert (real_rows - 2) // shard == chips - 1    # in the last shard
    dtype = jnp.dtype(dtype)
    want = _value_and_grads(_optax_form, case, real_rows, dtype)
    mesh = make_mesh(MeshPlan(dp=chips, tp=1, cp=1))
    assert head_ce.target_shards(mesh) == chips
    rows = NamedSharding(mesh, P(AXIS_DATA))
    placed = dict(
        x=jax.device_put(case["x"], NamedSharding(mesh, P(AXIS_DATA, None))),
        table=jax.device_put(case["table"], NamedSharding(mesh, P())),
        labels=jax.device_put(case["labels"], rows),
        weights=jax.device_put(case["weights"], rows))
    got = jax.jit(lambda c: _value_and_grads(
        head_cross_entropy, c, real_rows, dtype, mesh=mesh))(placed)
    assert got[1][0].sharding.spec[0] == AXIS_DATA
    assert got[1][1].sharding.is_fully_replicated
    assert got[1][1].shape == case["table"].shape
    assert not np.asarray(got[1][1])[real_rows:].any()
    _assert_close(got, want, dtype)


def test_a_mesh_that_shards_the_table_keeps_the_plain_ops(monkeypatch):
    """Target rows over `model`: GSPMD partitions the plain ops; the
    kernel would have to be a collective."""
    def refuse(*args, **kwargs):
        raise AssertionError("pass B's kernel under a sharded table")
    monkeypatch.setattr(head_ce, "_exp_sums_on_a_chip", refuse)
    case, real_rows = _case("random")
    mesh = make_mesh(MeshPlan(dp=2, tp=2, cp=1))
    placed = dict(case, table=jax.device_put(
        case["table"], NamedSharding(mesh, P("model", None))))
    got = jax.jit(lambda c: _value_and_grads(
        head_cross_entropy, c, real_rows, jnp.float32, mesh=mesh))(placed)
    _assert_close(got, _value_and_grads(_optax_form, case, real_rows,
                                        jnp.float32), jnp.float32)
