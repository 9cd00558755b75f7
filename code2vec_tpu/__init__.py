"""code2vec_tpu: a TPU-native (JAX/XLA/Flax/pjit) framework for learning
distributed representations of code from bags of AST path-contexts.

Capability parity target: km-Poonacha/code2vec (see /root/repo/SURVEY.md).
The architecture is TPU-first — host-side integer data pipeline, a single
Flax model (instead of the reference's dual TF1/Keras backends,
reference: code2vec.py:7-13), pjit/shard_map sharding over a
``jax.sharding.Mesh`` for data/model/context parallelism, Optax Adam,
Orbax checkpoints — not a translation of the reference's TF graphs.
"""

__version__ = "0.1.0"

import os as _os

if not _os.environ.get("C2V_HOST_WORKER"):
    import jax as _jax

    # Sharding-invariant PRNG: the sharded kernels assume a dropout pattern
    # that is bit-identical whether the batch lives on one device or a mesh.
    _jax.config.update("jax_threefry_partitionable", True)
# C2V_HOST_WORKER marks spawned multiprocessing children of the offline
# data pipeline (data/preprocess.py _worker_pool): pure host-side
# split/lookup/pack code that must not pay a jax import (seconds + 100s
# of MB per worker). Such workers never touch jax, so skipping the
# flag-pinning import above is safe.

from code2vec_tpu.config import Config  # noqa: F401
