"""The train head: the target-table classifier and its cross-entropy,
with a VJP of its own.

The float32 logits of a train batch are the largest array of the step
(`(1024, 261245)`: 1.07 GB) and the head is bound by the passes over
them, not by its matmuls. Under autodiff of `logits @ table^T` + optax's
cross-entropy there are four: the forward writes them (with the row
max), a second pass reads them for the sum of exponentials, a third
for the code vectors' gradient, a fourth for the table's. Here three:

- pass A: `logits = x @ W^T` (operands in the compute dtype, float32
  out) and the row max `m`; padded target columns read `-inf`.
- pass B, ONE read of the logits: over `E = exp(logits - m)` both the
  row sums `S` and `EW = E @ W` `(B, D)`. `S` gives the loss, and the
  code vectors' gradient is `w (EW / S - W[label])`: `S` is a scalar a
  row and leaves the matmul, so the backward needs no pass of its own.
- pass C, in the backward: `dW = ((E / S - onehot) w)^T @ x`, which the
  compiler keeps inside the table's Adam update.

XLA gives a reduce over a matmul's OPERAND a pass of its own, so on a
TPU pass B is a Pallas kernel (`_exp_sums_kernel`: a grid over tiles of
target rows, the two accumulators resident in VMEM); everywhere else,
and for shapes the kernel's blocks do not divide, it is the two plain
ops. The kernel is no collective: where a mesh shards the batch's rows
and nothing else it runs chip by chip under `shard_map`; a mesh that
shards the table keeps the plain ops, which GSPMD partitions.

Same operand precision as the autodiff form (operands in the compute
dtype, float32 accumulation, float32 logits and reductions); the
rounding of pass B's operand falls on `E` where autodiff's falls on
`(E / S - onehot) w`, and the one-hot term is an exact row of `W` in
the compute dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from code2vec_tpu.parallel.mesh import AXIS_DATA

# Pass B's kernel: target rows a grid step, and the most batch rows a
# block holds (more rows take more blocks, each reading the table once).
# Chosen once on the chip (PERF.md, PR 35): from 256 to 2,048 target
# rows the kernel is bound by the bytes it reads.
TILE = 512
ROWS = 1024
_LANES = 128


def head_cross_entropy(code_vectors: jax.Array, table: jax.Array,
                       labels: jax.Array, weights: jax.Array,
                       real_rows: int, compute_dtype,
                       mesh: Optional[Mesh] = None) -> jax.Array:
    """`sum_b weights[b] * CE(code_vectors[b] @ table^T, labels[b])`, a
    float32 scalar, over the first `real_rows` rows of `table` `(V, D)`
    (the rest are padding and get probability 0). `code_vectors` `(B,
    D)` and `table` are cast to `compute_dtype` for the matmuls;
    `weights` `(B,)` float32 is `valid / B` for the train loss (a row of
    weight 0 adds nothing to the loss or to either gradient).
    Differentiable in `code_vectors` and `table` alone. `mesh` is the
    one the caller's arrays are sharded over under GSPMD, if any.

    The profiler's op view shows every op, forward and backward, under
    `logits_ce`: the name is set inside the VJP's bodies, and the outer
    scope keeps `jvp(...)` off it (as ops/encode_live.py)."""
    with jax.named_scope("head_ce"):
        return _head(code_vectors, table, labels, weights, real_rows,
                     compute_dtype, mesh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head(code_vectors, table, labels, weights, real_rows, compute_dtype,
          mesh):
    loss, _ = _head_fwd(code_vectors, table, labels, weights, real_rows,
                        compute_dtype, mesh)
    return loss


def _head_fwd(code_vectors, table, labels, weights, real_rows,
              compute_dtype, mesh):
    with jax.named_scope("logits_ce"):
        logits = jnp.einsum("bd,vd->bv", code_vectors.astype(compute_dtype),
                            table.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
        if real_rows < table.shape[0]:
            col = jnp.arange(table.shape[0])
            logits = jnp.where(col[None, :] < real_rows, logits, -jnp.inf)
        row_max = jnp.max(logits, axis=-1)
        sum_exp, exp_rows = _exp_sums(logits, row_max, table, compute_dtype,
                                      mesh)
        label_logit = jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]
        # optax's order: log sum exp(l - m) - (l[label] - m)
        loss = jnp.sum(weights * (jnp.log(sum_exp)
                                  - (label_logit - row_max)))
    return loss, (code_vectors, table, labels, weights, logits, row_max,
                  sum_exp, exp_rows)


def _head_bwd(real_rows, compute_dtype, mesh, residuals, loss_ct):
    (code_vectors, table, labels, weights, logits, row_max, sum_exp,
     exp_rows) = residuals
    with jax.named_scope("logits_ce"):
        scale = loss_ct * weights                           # (B,)
        label_rows = jnp.take(table, labels, axis=0).astype(
            compute_dtype).astype(jnp.float32)
        code_ct = scale[:, None] * (exp_rows / sum_exp[:, None] - label_rows)
        prob = jnp.exp(logits - row_max[:, None]) / sum_exp[:, None]
        col = jnp.arange(table.shape[0], dtype=labels.dtype)
        logits_ct = scale[:, None] * jnp.where(
            col[None, :] == labels[:, None], prob - 1.0, prob)
        table_ct = jnp.einsum("bv,bd->vd", logits_ct.astype(compute_dtype),
                              code_vectors.astype(compute_dtype),
                              preferred_element_type=jnp.float32)
    # each rounded to the compute dtype first, as autodiff rounds the
    # cotangent of an operand that was cast to it (on a data mesh the
    # table's then leaves its all-reduce at half width)
    return (code_ct.astype(compute_dtype).astype(code_vectors.dtype),
            table_ct.astype(compute_dtype).astype(table.dtype), None, None)


_head.defvjp(_head_fwd, _head_bwd)


# ---------------------------------------------------------------- pass B

def _exp_sums(logits, row_max, table, compute_dtype, mesh):
    """`(sum_v E, E @ table)` over `E = exp(logits - row_max)`: `(B,)`
    and `(B, D)` float32."""
    local = functools.partial(_exp_sums_on_a_chip,
                              compute_dtype=compute_dtype)
    if mesh is None:
        return local(logits, row_max, table)
    if mesh.devices.size != mesh.shape[AXIS_DATA]:
        return _exp_sums_plain(logits, row_max, table, compute_dtype)
    rows = P(AXIS_DATA)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(AXIS_DATA, None), rows, P()),
        out_specs=(rows, P(AXIS_DATA, None)), check_vma=False)(
            logits, row_max, table)


def _exp_sums_on_a_chip(logits, row_max, table, compute_dtype):
    batch, width = logits.shape[0], table.shape[1]
    rows = min(batch, ROWS)
    if batch % rows or rows % 8 or width % _LANES or table.shape[0] < TILE:
        return _exp_sums_plain(logits, row_max, table, compute_dtype)
    return jax.lax.platform_dependent(
        logits, row_max, table,
        tpu=functools.partial(_exp_sums_pallas, compute_dtype=compute_dtype),
        default=functools.partial(_exp_sums_plain,
                                  compute_dtype=compute_dtype))


def _exp_sums_plain(logits, row_max, table, compute_dtype):
    exp = jnp.exp(logits - row_max[:, None])
    return jnp.sum(exp, axis=-1), jnp.einsum(
        "bv,vd->bd", exp.astype(compute_dtype), table.astype(compute_dtype),
        preferred_element_type=jnp.float32)


def _exp_sums_kernel(logits_ref, max_ref, table_ref, sums_ref, rows_ref, *,
                     columns: int, compute_dtype):
    """One block of batch rows against one tile of target rows: `E =
    exp(logits - max)`, its lane-wise partial row sums added into
    `sums_ref` `(rows, 128)` and `E @ table_tile` into `rows_ref` `(rows,
    D)`, both resident over the tiles. What the last tile holds past
    column `columns` is whatever the memory held: zeroed on both
    operands."""
    from jax.experimental import pallas as pl
    step = pl.program_id(1)
    tile = logits_ref.shape[1]

    @pl.when(step == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        rows_ref[...] = jnp.zeros_like(rows_ref)

    def accumulate(ragged: bool):
        weight = table_ref[...]
        exp = jnp.exp(logits_ref[...] - max_ref[...])
        if ragged:
            at = step * tile
            weight = jnp.where(at + jax.lax.broadcasted_iota(
                jnp.int32, weight.shape, 0) < columns, weight, 0.0)
            exp = jnp.where(at + jax.lax.broadcasted_iota(
                jnp.int32, exp.shape, 1) < columns, exp, 0.0)
        sums_ref[...] += sum(
            exp[:, lane:lane + _LANES] for lane in range(0, tile, _LANES))
        rows_ref[...] += jnp.dot(
            exp.astype(compute_dtype), weight.astype(compute_dtype),
            preferred_element_type=jnp.float32)

    if columns % tile == 0:
        accumulate(False)
    else:
        last = pl.num_programs(1) - 1
        pl.when(step < last)(functools.partial(accumulate, False))
        pl.when(step == last)(functools.partial(accumulate, True))


def _exp_sums_pallas(logits, row_max, table, compute_dtype,
                     interpret: bool = False):
    # imported where a step is traced around the kernel: 0.8 s that a
    # process which only serves, or only imports the program, never pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, columns = logits.shape
    rows, width = min(batch, ROWS), table.shape[1]
    sums, exp_rows = pl.pallas_call(
        functools.partial(_exp_sums_kernel, columns=columns,
                          compute_dtype=compute_dtype),
        grid=(batch // rows, pl.cdiv(columns, TILE)),
        in_specs=[pl.BlockSpec((rows, TILE), lambda i, j: (i, j)),
                  pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((TILE, width), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((rows, _LANES), lambda i, j: (i, 0)),
                   pl.BlockSpec((rows, width), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((batch, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((batch, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="head_exp_sums", interpret=interpret,
    )(logits, row_max[:, None], table)
    return jnp.sum(sums, axis=-1), exp_rows
