"""Where the process runs and where its compiled programs are kept.

Two facts every process that compiles must settle before its first
jit, and say out loud: which device it is on (the CPU tier-1 tests and
a chip run execute the same entry points; only the log can tell a
reader which happened), and which directory holds the persistent
compilation cache.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The persistent compilation cache directory: the environment's
    `JAX_COMPILATION_CACHE_DIR` untouched when set, else ONE fixed
    git-ignored directory inside the checkout. The path is part of the
    cache's key — a directory that moves never hits — so it is never
    derived from a tempfile, pid or clock."""
    return os.environ.get(_CACHE_ENV) or os.path.join(_REPO_ROOT,
                                                      ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Call before the first compile in every process that compiles
    (trainer, serving replica, pipeline stage child, bench.py). With
    `JAX_COMPILATION_CACHE_DIR` set JAX already reads it and no other
    directory is set here. A process pinned to the CPU platform keeps
    JAX's default (no cache) unless the environment names one: XLA:CPU
    logs a machine-feature error on every cache hit and its compiles
    are short. Never called in `C2V_HOST_WORKER` children (they must
    not import jax). Returns the directory in use, or None."""
    if _MIN_COMPILE_ENV not in os.environ:
        # JAX's default skips programs that compiled in under 1 s; the
        # per-bucket serving steps can be that quick, and a warm
        # replica should compile nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(_CACHE_ENV):
        return compile_cache_dir()
    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()


def device_summary(tree: Any = None) -> Dict[str, Any]:
    """Platform, device kind and count as JAX reports them, the number
    of distinct devices holding `tree`'s arrays (None: not asked), and
    the largest `peak_bytes_in_use` and `peak_bytes_reserved` over
    local devices (None where the backend keeps no memory statistics,
    e.g. the CPU). On the TPU runtime the first counts live arrays only
    and the second is what compiled programs reserved for their
    temporaries (seen on the v5e, PR 21): peak device memory is their
    sum."""
    devices = jax.devices()
    holders: Optional[int] = None
    first = devices[0]
    if tree is not None:
        held = set()
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array):
                held.update(leaf.devices())
        if held:
            holders = len(held)
            first = min(held, key=lambda d: d.id)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]

    def peak(key: str) -> Optional[int]:
        values = [s[key] for s in stats if key in s]
        return max(values) if values else None

    return {"platform": first.platform, "device_kind": first.device_kind,
            "n_devices": len(devices), "holders": holders,
            "peak_bytes_in_use": peak("peak_bytes_in_use"),
            "peak_bytes_reserved": peak("peak_bytes_reserved")}


def shard_layout(array: jax.Array) -> str:
    """How one array is spread: `shard (rows, dim) on N device(s)`."""
    sharding = array.sharding
    return (f"shard {sharding.shard_shape(array.shape)} on "
            f"{len(sharding.device_set)} device(s)")


def describe_devices(tree: Any = None) -> str:
    """One greppable log fragment of `device_summary` (chip_smoke.py
    parses it): `device: platform=tpu kind="TPU v5 lite" devices=1
    params_on=1 peak_bytes_in_use=123 peak_bytes_reserved=45`."""
    s = device_summary(tree)
    text = (f'device: platform={s["platform"]} kind="{s["device_kind"]}" '
            f'devices={s["n_devices"]}')
    if s["holders"] is not None:
        text += f' params_on={s["holders"]}'
    for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
        text += f' {key}={"n/a" if s[key] is None else s[key]}'
    return text
