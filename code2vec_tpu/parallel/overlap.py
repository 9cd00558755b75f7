"""Bucketed async gradient all-reduce: overlap communication with the
optimizer apply.

The unbucketed GSPMD train step is ONE XLA program: backward, the
data-parallel gradient all-reduce and the full Adam sweep run as a
single dispatch, and the all-reduce of the LAST gradient serializes
ahead of the ENTIRE optimizer apply. The apply is a pure streaming
pass over every parameter and moment — bound by HBM bandwidth, not by
compute — so the lever is keeping the interconnect busy while it runs
(neither is measured on the current machine).

This module splits the step into 1 + K dispatches:

1. **backward** — per-shard forward/backward under `shard_map` with NO
   gradient reduce: each device keeps its local partial gradients
   (declared replicated with the replication check off — the standard
   "unreduced array" spelling). Only the scalar loss is psummed (exact
   global loss, one element).
2. **K bucket steps** — the gradient leaves are partitioned into
   size-bounded buckets ordered by approximate backward-completion
   order (classifier first — its gradient exists first in the backward
   pass). Each bucket is its own jitted dispatch: psum the bucket's
   partial gradients over the data axis, then apply the optimizer to
   exactly that parameter subtree (donated, so params/moments update in
   place). The K dispatches are enqueued back to back; on device,
   bucket i's all-reduce overlaps bucket i-1's (bandwidth-bound) Adam
   apply, and the host never sits behind one monolithic step chain.

Semantics: the per-bucket optimizer is the SAME optax transformation
`state.make_optimizer` built (applied to a subtree — Adam is
elementwise, and every bucket's count advances identically), and the
psum of per-shard partials is the same sum the in-program all-reduce
computes. Loss/params parity with the unbucketed step is pinned in
tests/test_overlap.py (bit-equal single-device; documented float
tolerance across the reduction-order change on a mesh). Dropout under
a mesh folds in the data-axis index (the manual-kernel path's
discipline) — same distribution, different draw than the unbucketed
GSPMD step's single global mask.

Scope: dense optimizer, on either backward flavor — the GSPMD
tp = cp = 1 path, or the manual-kernel tp/cp path (the per-shard
backward then runs the explicit tp_ops forward and the per-leaf
reducers psum each gradient over exactly the mesh axes its spec
leaves replicated, which for a tp-sharded table skips the sharded
axis — the same storage-replication transpose rule the monolithic
manual step applies). The sparse path exchanges rows instead of
tables and stays monolithic. Works with mesh=None too (pure
pipelining of apply dispatches — the measurable win is on 2+ hosts,
experiments/overlap_bench.py).

`config.overlap_in_backward` goes one step further: instead of one
whole-model backward followed by K bucket dispatches, the backward
itself is split per bucket (grad w.r.t. only that bucket's leaves —
one extra forward per bucket), and bucket i's reduce+apply is
dispatched BEFORE bucket i+1's backward. On device the bucket-i psum
rides the interconnect while bucket i+1's backward occupies the
compute units — true in-backward completion, at the cost of the
recomputed forwards. Whether that trades profitably is
hardware-dependent; experiments/input_bench.py measures it and
BENCH_INPUT.md records the verdict either way.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from code2vec_tpu import obs
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.parallel.mesh import AXIS_DATA

# Approximate backward-completion order of the param leaves: the
# classifier matmul is the LAST forward op, so its gradient is the
# first one backward finishes; the input-side gathers come last.
# Unknown leaves (future params) sort after these, alphabetically.
_BACKWARD_ORDER = ("target_embedding", "attention", "transform",
                   "path_embedding", "token_embedding")


def plan_buckets(params, bucket_bytes: int) -> List[List[str]]:
    """Partition param-leaf names into contiguous buckets of at most
    `bucket_bytes` (a leaf larger than the budget gets its own
    bucket), in backward-completion order."""
    names = sorted(params, key=lambda n: (
        _BACKWARD_ORDER.index(n) if n in _BACKWARD_ORDER
        else len(_BACKWARD_ORDER), n))
    buckets: List[List[str]] = []
    current: List[str] = []
    current_bytes = 0
    for name in names:
        nbytes = int(np.prod(params[name].shape)) * 4  # grads are f32
        if current and current_bytes + nbytes > bucket_bytes:
            buckets.append(current)
            current, current_bytes = [], 0
        current.append(name)
        current_bytes += nbytes
        if current_bytes >= bucket_bytes:
            buckets.append(current)
            current, current_bytes = [], 0
    if current:
        buckets.append(current)
    return buckets


def _adam_core(opt_state):
    """The ScaleByAdamState slice of a dense optax state, or None when
    the structure is not the one `state.make_optimizer` builds (the
    builder then refuses loudly rather than mis-slicing)."""
    if not isinstance(opt_state, (tuple, list)) or not opt_state:
        return None
    core = opt_state[0]
    if not (hasattr(core, "mu") and hasattr(core, "nu")
            and hasattr(core, "count") and isinstance(core.mu, dict)):
        return None
    return core


def build_overlap_train_step(builder, example_state) -> Callable:
    """(state, *batch_arrays, rng) -> (state, loss) host composite of
    1 backward + K bucket dispatches. `builder` is the
    TrainStepBuilder; `example_state` fixes tree structure/shapes."""
    config = builder.config
    module = builder.module
    optimizer = builder.optimizer
    mesh = builder.mesh
    params = example_state.params
    core = _adam_core(example_state.opt_state)
    if core is None or set(core.mu) != set(params):
        raise ValueError(
            "overlap_grad_allreduce needs the dense optax Adam state "
            "state.make_optimizer builds (ScaleByAdamState over the "
            "param dict); got "
            f"{type(example_state.opt_state).__name__}.")
    opt_rest_len = len(example_state.opt_state) - 1

    bucket_bytes = int(float(config.overlap_bucket_mb) * (1 << 20))
    buckets = plan_buckets(params, bucket_bytes)
    param_specs = mesh_lib.param_specs(params)
    manual = bool(getattr(builder, "manual", False))
    in_backward = bool(getattr(config, "overlap_in_backward", False))
    batch_specs = tuple(
        mesh_lib.batch_specs()[name] for name in (
            "source_token_indices", "path_indices",
            "target_token_indices", "context_valid_mask",
            "target_index", "example_valid"))

    # ------------------------------------------------------- backward

    def local_loss_fn(p, src, pth, tgt, mask, labels, valid, dropout_rng,
                      global_batch: int):
        logits, _, _ = module.apply(
            {"params": p}, src, pth, tgt, mask,
            deterministic=False, rngs={"dropout": dropout_rng})
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        ce = ce * valid.astype(jnp.float32)
        # local sum / GLOBAL batch: per-shard partial grads then SUM to
        # exactly the unbucketed step's sum-CE / batch_size loss
        return jnp.sum(ce) / global_batch

    # make_loss_fn(batch..., rng, step) -> (loss_of_params, finish):
    # `loss_of_params(p)` is the per-shard loss whose gradient is this
    # shard's PARTIAL gradient, `finish(local)` turns the per-shard
    # scalar into the exact global loss. One factory per backward
    # flavor; both the whole-model backward and the per-bucket
    # in-backward variant trace through it.
    if manual:
        def make_loss_fn(src, pth, tgt, mask, labels, valid, rng, step):
            # per-shard dropout folding (data/ctx axis indexes) happens
            # inside _manual_rows_to_code — same draw as the monolithic
            # manual step
            dropout_rng = jax.random.fold_in(rng, step)

            def loss_of_params(p):
                code_vectors, _ = builder._manual_encode(
                    p, src, pth, tgt, mask,
                    deterministic=False, dropout_rng=dropout_rng)
                loss, _ = builder._manual_ce(p, code_vectors, labels,
                                             valid)
                return loss

            # _manual_ce already psums the scalar over the data axis
            return loss_of_params, (lambda local: local)
    elif mesh is not None:
        dp = dict(zip(mesh.axis_names, mesh.devices.shape))[AXIS_DATA]

        def make_loss_fn(src, pth, tgt, mask, labels, valid, rng, step):
            # distinct dropout per data shard (the manual path's
            # discipline); tp = cp = 1 so no other axes draw
            dropout_rng = jax.random.fold_in(
                jax.random.fold_in(rng, step),
                jax.lax.axis_index(AXIS_DATA))

            def loss_of_params(p):
                return local_loss_fn(p, src, pth, tgt, mask, labels,
                                     valid, dropout_rng,
                                     labels.shape[0] * dp)

            return loss_of_params, (
                lambda local: jax.lax.psum(local, AXIS_DATA))
    else:
        def make_loss_fn(src, pth, tgt, mask, labels, valid, rng, step):
            dropout_rng = jax.random.fold_in(rng, step)

            def loss_of_params(p):
                return local_loss_fn(p, src, pth, tgt, mask, labels,
                                     valid, dropout_rng, labels.shape[0])

            return loss_of_params, (lambda local: local)

    def full_backward(p, src, pth, tgt, mask, labels, valid, rng, step):
        loss_fn, finish = make_loss_fn(src, pth, tgt, mask, labels,
                                       valid, rng, step)
        local, grads = jax.value_and_grad(loss_fn)(p)
        # grads stay UNREDUCED (each shard's partial); only the scalar
        # loss is finished here
        return grads, finish(local)

    if in_backward:
        backward = None  # replaced by the per-bucket backwards below
    elif mesh is None:
        backward = jax.jit(full_backward)
    else:
        sharded = jax.shard_map(
            full_backward, mesh=mesh,
            in_specs=(param_specs,) + batch_specs + (P(), P()),
            out_specs=(param_specs, P()),
            check_vma=False)
        backward = jax.jit(sharded)

    def make_bucket_backward(names: Sequence[str], with_loss: bool):
        """Backward restricted to one bucket's leaves: grad w.r.t. only
        those params (the rest are constants — no grad computed for
        them), at the cost of re-running the forward. Only bucket 0
        returns the loss; all buckets share the identical dropout draw,
        so the per-bucket grads are pieces of ONE consistent whole-model
        gradient."""
        sub_specs = {k: param_specs[k] for k in names}

        def bucket_backward(p, src, pth, tgt, mask, labels, valid,
                            rng, step):
            loss_fn, finish = make_loss_fn(src, pth, tgt, mask, labels,
                                           valid, rng, step)

            def sub_loss(p_sub):
                return loss_fn({**p, **p_sub})

            p_sub = {k: p[k] for k in names}
            if with_loss:
                local, g_sub = jax.value_and_grad(sub_loss)(p_sub)
                return g_sub, finish(local)
            return jax.grad(sub_loss)(p_sub)

        if mesh is None:
            return jax.jit(bucket_backward)
        sharded = jax.shard_map(
            bucket_backward, mesh=mesh,
            in_specs=(param_specs,) + batch_specs + (P(), P()),
            out_specs=(sub_specs, P()) if with_loss else sub_specs,
            check_vma=False)
        return jax.jit(sharded)

    # --------------------------------------------------- bucket steps

    def make_bucket_fn(names: Sequence[str]):
        specs = {k: param_specs[k] for k in names}
        reducer = None
        if mesh is not None:
            def reduce(gs):
                out = {}
                for k, g in gs.items():
                    axes = mesh_lib.replicated_axes_for_spec(specs[k])
                    out[k] = jax.lax.psum(g, axes) if axes else g
                return out

            reducer = jax.shard_map(reduce, mesh=mesh, in_specs=(specs,),
                                    out_specs=specs, check_vma=False)

        def bucket_step(p_sub, mu_sub, nu_sub, count, rest, g_sub):
            # `count` and `rest` are NOT donated: every bucket reads
            # the same shared count buffer (each computes the identical
            # incremented value), where mu/nu/param/grad leaves belong
            # to exactly one bucket and alias in place.
            if reducer is not None:
                g_sub = reducer(g_sub)
            opt_sub = (adam_type(count=count, mu=mu_sub, nu=nu_sub),
                       ) + tuple(rest)
            updates, new_opt = optimizer.update(g_sub, opt_sub, p_sub)
            return optax.apply_updates(p_sub, updates), new_opt

        # params/mu/nu donate (updated in place); grads are NOT listed:
        # there is no same-shaped output left for them once the params
        # aliased, and XLA's unusable-donation warning would fire every
        # compile. In-backward mode must NOT donate the params: every
        # per-bucket backward re-reads the FULL original param dict, and
        # bucket i's apply is dispatched before bucket i+1's backward —
        # donating bucket i's params would invalidate buffers the later
        # backwards still consume (transient cost: one params copy).
        donate = (1, 2) if in_backward else (0, 1, 2)
        return jax.jit(bucket_step, donate_argnums=donate)

    adam_type = type(core)
    bucket_fns = [make_bucket_fn(names) for names in buckets]
    bucket_backwards = ([make_bucket_backward(names, with_loss=(i == 0))
                         for i, names in enumerate(buckets)]
                        if in_backward else None)

    h_bucket = obs.histogram(
        "train_overlap_bucket_dispatch_seconds",
        "host-side dispatch of one bucketed all-reduce+apply step")

    def train_step(state, src, pth, tgt, mask, labels, valid, rng):
        import time as _time
        if in_backward:
            grads, loss = None, None
        else:
            grads, loss = backward(state.params, src, pth, tgt, mask,
                                   labels, valid, rng, state.step)
        adam = state.opt_state[0]
        rest = tuple(state.opt_state[1:])
        new_params = {}
        new_mu = {}
        new_nu = {}
        new_count = None
        new_rest = rest
        for i, (fn, names) in enumerate(zip(bucket_fns, buckets)):
            t0 = _time.perf_counter()
            if in_backward:
                # bucket i's reduce+apply is enqueued before bucket
                # i+1's backward: the psum rides the interconnect while
                # the next backward occupies the compute units
                if i == 0:
                    g_sub, loss = bucket_backwards[0](
                        state.params, src, pth, tgt, mask, labels,
                        valid, rng, state.step)
                else:
                    g_sub = bucket_backwards[i](
                        state.params, src, pth, tgt, mask, labels,
                        valid, rng, state.step)
            else:
                g_sub = {k: grads[k] for k in names}
            p_sub = {k: state.params[k] for k in names}
            p_out, opt_out = fn(p_sub,
                                {k: adam.mu[k] for k in names},
                                {k: adam.nu[k] for k in names},
                                adam.count, rest, g_sub)
            new_params.update(p_out)
            new_mu.update(opt_out[0].mu)
            new_nu.update(opt_out[0].nu)
            new_count = opt_out[0].count  # identical across buckets
            new_rest = tuple(opt_out[1:])
            h_bucket.observe(_time.perf_counter() - t0)
        opt_state = (adam_type(count=new_count, mu=new_mu, nu=new_nu),
                     ) + new_rest
        if opt_rest_len != len(new_rest):  # structural invariant
            raise AssertionError("bucket optimizer changed state arity")
        from code2vec_tpu.training.state import TrainState
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=opt_state), loss

    n_leaves = len(params)
    if mesh is None:
        flavor = "single-device (apply pipelining only)"
    elif manual:
        flavor = "manual-kernel tp/cp (per-leaf replicated-axes psum)"
    else:
        flavor = "data-parallel psum per bucket"
    train_step.overlap_buckets = len(buckets)
    train_step.overlap_in_backward = in_backward
    train_step.overlap_description = (
        f"{len(buckets)} gradient bucket(s) over {n_leaves} leaves "
        f"(<= {config.overlap_bucket_mb:g} MB each, backward-completion "
        f"order {[list(b) for b in buckets]}), {flavor}"
        + (", in-backward per-bucket completion" if in_backward else ""))
    obs.gauge("train_overlap_buckets",
              "gradient buckets of the overlapped train step "
              "(0/absent = unbucketed single-program step)"
              ).set(len(buckets))
    return train_step
