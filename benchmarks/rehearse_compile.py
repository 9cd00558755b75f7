#!/usr/bin/env python3
"""Compile the train step of a cell at its real widths for a TPU v5e that
is described, not attached, so that a program that does not fit or does
not partition costs no chip time. Nothing runs; no number printed here is
a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [cell ...]

Default cells: the ctx500 step on one chip and the dp=4 step on the 2x2
host. Prints XLA's memory analysis per device and the collectives the
compiler put in.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEFAULT_CELLS = ("java14m-ctx500.train_hostfed", "java14m.train_dp4")


def compile_cell(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks import common
    from benchmarks.runners.train import program_argv
    from code2vec_tpu.cli import config_from_args
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.parallel import mesh as mesh_lib
    from code2vec_tpu.training.state import (
        TrainState, init_params, make_optimizer, state_spec_tree)
    from code2vec_tpu.training.step import TrainStepBuilder, _batch_spec_tuple

    cell = common.Cell(ROOT, name)
    cfg = cell.config
    config = config_from_args(program_argv(cell, "unused", 0))
    for key, value in cfg.get("program_overrides", {}).items():
        setattr(config, key, value)
    config.verify()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = None
    if config.mesh_size > 1:
        mesh = mesh_lib.make_mesh(mesh_lib.MeshPlan.from_config(config),
                                  devices=topo.devices)
    dims = ModelDims(token_vocab_size=cfg["token_rows"],
                     path_vocab_size=cfg["path_rows"],
                     target_vocab_size=cfg["target_rows"],
                     token_dim=cfg["token_dim"], path_dim=cfg["path_dim"])
    module = Code2VecModule(dims=dims,
                            dropout_keep_rate=config.dropout_keep_rate,
                            compute_dtype=jnp.dtype(config.compute_dtype))
    optimizer = make_optimizer(config)

    def init(rng):
        params = init_params(module, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))
    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    rows, m = cfg["batch_rows_per_chip"] * cell.chips, cfg["max_contexts"]
    batch = [((rows, m), jnp.int32)] * 3 + [((rows, m), jnp.float32),
                                            ((rows,), jnp.int32),
                                            ((rows,), jnp.bool_)]
    key = jax.eval_shape(lambda: jax.random.key(
        0, impl=config.dropout_prng_impl))
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda sds, _spec: jax.ShapeDtypeStruct(  # noqa: E731
            sds.shape, sds.dtype, sharding=one)
    else:
        place = lambda sds, spec: jax.ShapeDtypeStruct(  # noqa: E731
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec))
    state = jax.tree.map(place, abstract, state_spec_tree(abstract),
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    args = [place(jax.ShapeDtypeStruct(s, d), spec)
            for (s, d), spec in zip(batch, _batch_spec_tuple())]
    rng = place(key, P())
    step = TrainStepBuilder(module, optimizer, config,
                            mesh=mesh).make_train_step(abstract)
    compiled = step.lower(state, *args, rng).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    collectives = sorted(set(re.findall(
        r"\b(all-reduce|reduce-scatter|all-gather|all-to-all|"
        r"collective-permute)(?:-start)?\b", text)))
    out = {"cell": name, "chips": cell.chips,
           "argument_bytes": mem.argument_size_in_bytes,
           "output_bytes": mem.output_size_in_bytes,
           "temp_bytes": mem.temp_size_in_bytes,
           "alias_bytes": mem.alias_size_in_bytes,
           "collectives": collectives}
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    out["per_device_bytes"] = total
    out["fits_16e9"] = total < 16e9
    return out


def main(argv) -> int:
    ok = True
    for name in (argv or DEFAULT_CELLS):
        facts = compile_cell(name)
        print(facts, flush=True)
        ok = ok and facts["fits_16e9"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
