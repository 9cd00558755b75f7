"""Training state: params + Adam state, with mesh-sharded initialization.

The reference's trainable state is four TF variables plus Adam slots
managed by the session (tensorflow_model.py:204-231); here it's an
explicit pytree initialized directly into its target sharding via
jit(out_shardings=...) so a pod-scale model never materializes unsharded
on one host.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from code2vec_tpu.models.code2vec import Code2VecModule
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.training.sparse_adam import HybridOptState, init_slots


@flax.struct.dataclass
class TrainState:
    step: jax.Array         # scalar int32
    params: Any             # flax param dict
    opt_state: Any          # optax state, or HybridOptState (sparse mode)


# Tables updated by the touched-rows sparse Adam path
# (training/sparse_adam.py) when config.use_sparse_embedding_update.
# target_embedding stays dense: its gradient flows through the full
# softmax, so every row is touched every step.
SPARSE_PARAM_NAMES = ("token_embedding", "path_embedding")


def split_sparse_dense(params):
    """Partition a flax param dict into (sparse tables, dense rest)."""
    sparse = {k: v for k, v in params.items() if k in SPARSE_PARAM_NAMES}
    dense = {k: v for k, v in params.items() if k not in SPARSE_PARAM_NAMES}
    return sparse, dense


def uses_sparse_update(config) -> bool:
    return bool(config is not None
                and getattr(config, "use_sparse_embedding_update", False))


def _scale_by_adam_nu_dtype(b1: float, b2: float, eps: float,
                            mu_dtype, nu_dtype) -> optax.GradientTransformation:
    """optax.scale_by_adam with a storage dtype for the SECOND moment as
    well (optax only exposes mu_dtype). Math is performed in the
    gradient's dtype (f32 here); only storage is cast — exactly how
    optax handles mu. Used when config.adam_nu_dtype != float32."""
    mu_dtype, nu_dtype = jnp.dtype(mu_dtype), jnp.dtype(nu_dtype)

    def init_fn(params):
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mu_dtype),
                            params),
            nu=jax.tree.map(lambda p: jnp.zeros_like(p, dtype=nu_dtype),
                            params))

    def update_fn(updates, state, params=None):
        del params
        count = optax.safe_increment(state.count)
        mu = jax.tree.map(
            lambda g, m: b1 * m.astype(g.dtype) + (1.0 - b1) * g,
            updates, state.mu)
        nu = jax.tree.map(
            lambda g, n: b2 * n.astype(g.dtype) + (1.0 - b2) * (g * g),
            updates, state.nu)
        b1c = 1.0 - b1 ** count.astype(jnp.float32)
        b2c = 1.0 - b2 ** count.astype(jnp.float32)
        new_updates = jax.tree.map(
            lambda m, n: (m / b1c) / (jnp.sqrt(n / b2c) + eps), mu, nu)
        return new_updates, optax.ScaleByAdamState(
            count=count,
            mu=jax.tree.map(lambda m: m.astype(mu_dtype), mu),
            nu=jax.tree.map(lambda n: n.astype(nu_dtype), nu))

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(config) -> optax.GradientTransformation:
    # reference uses tf.compat.v1.train.AdamOptimizer() defaults
    # (tensorflow_model.py:231): lr 1e-3, b1 .9, b2 .999, eps 1e-8.
    # mu/nu storage dtypes are throughput knobs (config.adam_mu_dtype /
    # config.adam_nu_dtype); plain optax.adam whenever nu stays f32, so
    # the default path is bit-identical to stock optax.
    nu_dtype = jnp.dtype(getattr(config, "adam_nu_dtype", "float32"))
    if nu_dtype == jnp.float32:
        return optax.adam(
            learning_rate=config.learning_rate,
            b1=config.adam_beta1, b2=config.adam_beta2, eps=config.adam_eps,
            mu_dtype=jnp.dtype(config.adam_mu_dtype))
    return optax.chain(
        _scale_by_adam_nu_dtype(
            b1=config.adam_beta1, b2=config.adam_beta2, eps=config.adam_eps,
            mu_dtype=jnp.dtype(config.adam_mu_dtype), nu_dtype=nu_dtype),
        optax.scale(-config.learning_rate))


def dropout_rng(config, salt: int = 2) -> jax.Array:
    """Per-run dropout key using the configured PRNG implementation (the
    hardware `rbg` generator by default — see config.dropout_prng_impl)."""
    return jax.random.key(config.seed + salt, impl=config.dropout_prng_impl)


def init_params(module: Code2VecModule, rng: jax.Array):
    """Initialize the param dict with throwaway token shapes (params do not
    depend on batch shapes)."""
    dummy = jnp.zeros((1, 1), dtype=jnp.int32)
    dummy_mask = jnp.zeros((1, 1), dtype=jnp.float32)
    variables = module.init({"params": rng}, dummy, dummy, dummy, dummy_mask)
    return variables["params"]


def state_spec_tree(state: Any):
    """PartitionSpec tree for a TrainState (params + optimizer slots follow
    the same layout; the Adam counter and `step` are replicated)."""
    return mesh_lib.tree_param_specs(state)


def create_train_state(
    module: Code2VecModule,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    mesh: Optional[Mesh] = None,
    config=None,
) -> TrainState:
    """Build a TrainState; with a mesh, every leaf is created directly into
    its NamedSharding (no host-side full materialization).

    With `config.use_sparse_embedding_update`, `optimizer` covers only the
    dense subtree and the token/path tables get RowAdamSlots."""
    sparse = uses_sparse_update(config)
    mu_dtype = (jnp.dtype(config.adam_mu_dtype) if sparse else None)

    def init_fn(rng):
        params = init_params(module, rng)
        if sparse:
            sparse_params, dense_params = split_sparse_dense(params)
            opt_state = HybridOptState(
                dense=optimizer.init(dense_params),
                slots={name: init_slots(table, mu_dtype)
                       for name, table in sparse_params.items()})
        else:
            opt_state = optimizer.init(params)
        return TrainState(
            step=jnp.zeros((), dtype=jnp.int32),
            params=params,
            opt_state=opt_state)

    if mesh is None:
        return jax.jit(init_fn)(rng)

    abstract = jax.eval_shape(init_fn, rng)
    shardings = mesh_lib.shardings(mesh, state_spec_tree(abstract))
    return jax.jit(init_fn, out_shardings=shardings)(rng)


def num_params(state: TrainState) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(state.params))
