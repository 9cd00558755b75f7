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
ops.

Where a mesh shards the batch's rows and nothing else, its chips split
the TARGET rows between them for the head (`target_shards`): under one
`shard_map` over `data` each chip gathers every chip's code vectors
(`(4096, 384)`: 6.3 MB), takes its `ceil(V / n)` rows of the replicated
table and runs the three passes over them against all the rows. The
row max, the row sums, `E @ W` and the label's logit cross the chips as
`(B,)` and `(B, D)` reductions. Its `dW` is then the WHOLE sum for its
rows: it is rounded to the compute dtype where one chip rounds it, and
one all-gather of the shards in that dtype makes the table's cotangent
on every chip. No table-shaped partial sum is left for GSPMD to
all-reduce in float32 (`java14m.train_dp4`: 150 MB into a chip where the
all-reduce moved 602 MB). The kernel is no collective: a mesh that
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

from code2vec_tpu.ops.sharded import tp_label_logit
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


def target_shards(mesh: Optional[Mesh]) -> int:
    """How many chips share the head's target rows: every chip of a mesh
    that shards the batch's rows and nothing else, 1 anywhere else. Read
    off the mesh alone."""
    return 1 if _split_axis(mesh) is None else mesh.devices.size


def _split_axis(mesh: Optional[Mesh]) -> Optional[str]:
    if mesh is None or mesh.devices.size != mesh.shape[AXIS_DATA]:
        return None
    return AXIS_DATA


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head(code_vectors, table, labels, weights, real_rows, compute_dtype,
          mesh):
    loss, _ = _head_fwd(code_vectors, table, labels, weights, real_rows,
                        compute_dtype, mesh)
    return loss


# The residuals under `shard_map` over `data`: every row's code vector,
# the table, every row's label and weight whole on every chip, the
# logits by target columns, then the row max, the row sums and `E @ W`,
# whole.
_RESIDUAL_SPECS = (P(), P(), P(), P(), P(None, AXIS_DATA), P(), P(), P())


def _head_fwd(code_vectors, table, labels, weights, real_rows,
              compute_dtype, mesh):
    axis = _split_axis(mesh)
    forward = functools.partial(
        _forward, real_rows=real_rows, compute_dtype=compute_dtype,
        axis=axis,
        # a mesh that is not split here leaves the head to GSPMD
        exp_sums=(_exp_sums_on_a_chip if mesh is None or axis
                  else _exp_sums_plain))
    if axis:
        rows = P(axis)
        forward = jax.shard_map(
            forward, mesh=mesh, in_specs=(P(axis, None), P(), rows, rows),
            out_specs=(P(), _RESIDUAL_SPECS), check_vma=False)
    return forward(code_vectors, table, labels, weights)


def _head_bwd(real_rows, compute_dtype, mesh, residuals, loss_ct):
    axis = _split_axis(mesh)
    backward = functools.partial(_backward, compute_dtype=compute_dtype,
                                 axis=axis)
    if axis:
        backward = jax.shard_map(
            backward, mesh=mesh, in_specs=_RESIDUAL_SPECS + (P(),),
            out_specs=(P(axis, None), P()), check_vma=False)
    return backward(*residuals, loss_ct) + (None, None)


_head.defvjp(_head_fwd, _head_bwd)


def _forward(code_vectors, table, labels, weights, *, real_rows,
             compute_dtype, axis, exp_sums):
    """Passes A and B and the loss over the target rows this chip holds:
    all of them (`axis` None), or its shard of them against the rows of
    every chip of `axis`."""
    with jax.named_scope("logits_ce"):
        code_vectors, labels, weights = (
            _across(_all_rows, x, axis)
            for x in (code_vectors, labels, weights))
        shard = _own_target_rows(table, compute_dtype, axis)
        logits = jnp.einsum("bd,vd->bv", code_vectors.astype(compute_dtype),
                            shard.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
        if real_rows < shard.shape[0] * _chips(axis):
            col = _own_columns(shard.shape[0], jnp.int32, axis)
            logits = jnp.where(col[None, :] < real_rows, logits, -jnp.inf)
        row_max = _across(jax.lax.pmax, jnp.max(logits, axis=-1), axis)
        sum_exp, exp_rows = _across(
            jax.lax.psum, exp_sums(logits, row_max, shard, compute_dtype),
            axis)
        label_logit = _label_logits(logits, labels, axis)
        # optax's order: log sum exp(l - m) - (l[label] - m)
        loss = jnp.sum(weights * (jnp.log(sum_exp)
                                  - (label_logit - row_max)))
    return loss, (code_vectors, table, labels, weights, logits, row_max,
                  sum_exp, exp_rows)


def _backward(code_vectors, table, labels, weights, logits, row_max, sum_exp,
              exp_rows, loss_ct, *, compute_dtype, axis):
    """Pass C over this chip's target rows, and the code vectors'
    cotangent for its own rows of the batch."""
    with jax.named_scope("logits_ce"):
        scale = loss_ct * weights                           # (B,)
        own_labels, own_scale, own_sums, own_rows = (
            _own_rows(x, axis) for x in (labels, scale, sum_exp, exp_rows))
        label_rows = jnp.take(table, own_labels, axis=0).astype(
            compute_dtype).astype(jnp.float32)
        code_ct = own_scale[:, None] * (own_rows / own_sums[:, None]
                                        - label_rows)
        prob = jnp.exp(logits - row_max[:, None]) / sum_exp[:, None]
        col = _own_columns(logits.shape[1], labels.dtype, axis)
        logits_ct = scale[:, None] * jnp.where(
            col[None, :] == labels[:, None], prob - 1.0, prob)
        table_ct = jnp.einsum("bv,bd->vd", logits_ct.astype(compute_dtype),
                              code_vectors.astype(compute_dtype),
                              preferred_element_type=jnp.float32)
        # each rounded to the compute dtype first, as autodiff rounds the
        # cotangent of an operand that was cast to it: the table's is
        # whole by then, and crosses the chips at that width
        table_ct = _all_target_rows(table_ct.astype(compute_dtype),
                                    table.shape[0], axis)
        code_ct = code_ct.astype(compute_dtype)
        if axis is not None:
            # the two leave together, so the shards are gathered before
            # the encoder's backward starts: left to the scheduler, pass
            # C and the all-gather stood between the halves of the token
            # table's asynchronous all-reduce (training/step.py), and
            # that step never ended on the chips (PR 38)
            code_ct, table_ct = jax.lax.optimization_barrier(
                (code_ct, table_ct))
    return code_ct.astype(code_vectors.dtype), table_ct.astype(table.dtype)


# ------------------------------------------ one chip's part of the whole
# Each is the identity, or the whole, where no `axis` splits the head.

def _across(collective, x, axis):
    return x if axis is None else collective(x, axis)


_all_rows = functools.partial(jax.lax.all_gather, tiled=True)


def _chips(axis) -> int:
    return 1 if axis is None else jax.lax.axis_size(axis)


def _own_rows(x, axis):
    """This chip's rows of an array that holds every chip's."""
    if axis is None:
        return x
    rows = x.shape[0] // _chips(axis)
    return jax.lax.dynamic_slice_in_dim(
        x, jax.lax.axis_index(axis) * rows, rows)


def _own_target_rows(table, compute_dtype, axis):
    """This chip's `ceil(V / n)` rows of the whole table in the compute
    dtype, the last chip's filled up with zero rows (one copy: the pad,
    the slice and the cast)."""
    if axis is None:
        return table
    rows = -(-table.shape[0] // _chips(axis))
    padded = jnp.pad(table, ((0, rows * _chips(axis) - table.shape[0]),
                             (0, 0)))
    return jax.lax.dynamic_slice_in_dim(
        padded, jax.lax.axis_index(axis) * rows, rows).astype(compute_dtype)


def _all_target_rows(shard, rows: int, axis):
    """The chips' shards put together, less the last one's filling."""
    return shard if axis is None else _all_rows(shard, axis)[:rows]


def _own_columns(width: int, dtype, axis):
    """The target rows behind this chip's `width` columns of logits."""
    col = jnp.arange(width, dtype=dtype)
    return col if axis is None else col + jax.lax.axis_index(axis) * width


def _label_logits(logits, labels, axis):
    """Each row's logit at its label: a take where the chip holds every
    column, else a take on the chip that holds the label's, summed over
    the chips."""
    if axis is None:
        return jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return tp_label_logit(logits, labels, axis)


# ---------------------------------------------------------------- pass B

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
