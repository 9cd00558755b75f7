"""Tensor-parallel collective kernels used inside shard_map.

The reference computes everything whole on one device (embedding tables
tensorflow_model.py:204-219; full-vocab logits :225). At pod scale the
three tables (~385M params, BASELINE.md) are row-sharded over the `model`
mesh axis; these kernels implement the sharded compute with explicit XLA
collectives:

- `tp_embedding_lookup`: masked local gather + psum (the vocab-parallel
  embedding pattern — each shard gathers rows it owns, others contribute
  zeros).
- `tp_softmax_ce`: cross-entropy over row-sharded logits via
  pmax/psum-logsumexp, without ever materializing the full (B, V) logits
  on one device.
- `tp_top_k`: local top-k + all_gather + re-top-k, returning global ids.

All functions assume they run inside shard_map with `axis_name` bound.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _shard_offset(num_rows_local: int, axis_name: str) -> jax.Array:
    return jax.lax.axis_index(axis_name) * num_rows_local


def tp_embedding_lookup(table_shard: jax.Array, ids: jax.Array,
                        axis_name: str) -> jax.Array:
    """Gather rows of a row-sharded table by global ids: (..., dim) f32.

    Each shard translates global ids to local ones, gathers in-range rows,
    zeroes the rest, and a psum over `axis_name` reconstructs the full
    lookup (out-of-range shards contribute 0).
    """
    rows_local = table_shard.shape[0]
    offset = _shard_offset(rows_local, axis_name)
    local_ids = ids - offset
    in_range = (local_ids >= 0) & (local_ids < rows_local)
    safe_ids = jnp.clip(local_ids, 0, rows_local - 1)
    gathered = jnp.take(table_shard, safe_ids, axis=0)
    gathered = jnp.where(in_range[..., None], gathered, 0.0)
    return jax.lax.psum(gathered, axis_name)


def tp_logits(code_vectors: jax.Array, target_table_shard: jax.Array,
              compute_dtype=jnp.bfloat16) -> jax.Array:
    """Local logits slice (B, V_local) for a row-sharded classifier."""
    return jnp.einsum(
        "bd,vd->bv", code_vectors.astype(compute_dtype),
        target_table_shard.astype(compute_dtype),
        preferred_element_type=jnp.float32)


def tp_softmax_ce(local_logits: jax.Array, labels: jax.Array,
                  axis_name: str) -> jax.Array:
    """Sparse softmax cross-entropy over row-sharded logits: (B,) f32.

    Numerics identical to an unsharded logsumexp: global max via pmax,
    global sum-exp and the label's logit via psum (the label row lives on
    exactly one shard).
    """
    # Max shift is stabilization only — its gradient cancels exactly in
    # logsumexp (d/dm [log Σexp(x-m) + m] = 0), and pmax has no AD rule.
    local_max = jax.lax.stop_gradient(jnp.max(local_logits, axis=-1))  # (B,)
    global_max = jax.lax.pmax(local_max, axis_name)
    sumexp = jnp.sum(jnp.exp(local_logits - global_max[:, None]), axis=-1)
    global_sumexp = jax.lax.psum(sumexp, axis_name)               # (B,)

    label_logit = tp_label_logit(local_logits, labels, axis_name)  # (B,)
    return jnp.log(global_sumexp) + global_max - label_logit


def tp_label_logit(local_logits: jax.Array, labels: jax.Array,
                   axis_name: str) -> jax.Array:
    """Each row's logit at its (global) label from row-sharded logits:
    (B,) f32. The label's column lives on exactly one shard: a take
    there, 0 elsewhere, summed over `axis_name`."""
    v_local = local_logits.shape[-1]
    local_labels = labels - _shard_offset(v_local, axis_name)
    in_range = (local_labels >= 0) & (local_labels < v_local)
    safe = jnp.clip(local_labels, 0, v_local - 1)
    label_logit_local = jnp.take_along_axis(
        local_logits, safe[:, None], axis=-1)[:, 0]
    return jax.lax.psum(
        jnp.where(in_range, label_logit_local, 0.0), axis_name)


def tp_log_softmax_at_topk(local_logits, axis_name: str):
    """Global (max, logsumexp) pair for normalizing scores of sharded
    logits; returned per example so callers can normalize any slice."""
    local_max = jax.lax.stop_gradient(jnp.max(local_logits, axis=-1))
    global_max = jax.lax.pmax(local_max, axis_name)
    sumexp = jnp.sum(jnp.exp(local_logits - global_max[:, None]), axis=-1)
    global_sumexp = jax.lax.psum(sumexp, axis_name)
    return global_max, jnp.log(global_sumexp) + global_max


def tp_top_k(local_logits: jax.Array, k: int,
             axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """Top-k over row-sharded logits -> (values (B, k), global ids (B, k)).

    Communication is O(B * k * tp) instead of all-gathering the full
    (B, V) logits (1 GB/batch at the reference's 261K-target vocab,
    batch 1024 — SURVEY.md §7 'hard parts').
    """
    v_local = local_logits.shape[-1]
    offset = _shard_offset(v_local, axis_name)
    k_local = min(k, v_local)
    values, idx = jax.lax.top_k(local_logits, k_local)           # (B, k_local)
    global_idx = idx + offset
    all_values = jax.lax.all_gather(values, axis_name, axis=1)    # (B, tp, k_local)
    all_idx = jax.lax.all_gather(global_idx, axis_name, axis=1)
    b = all_values.shape[0]
    flat_vals = all_values.reshape(b, -1)
    flat_idx = all_idx.reshape(b, -1)
    top_vals, pos = jax.lax.top_k(flat_vals, k)                   # (B, k)
    top_idx = jnp.take_along_axis(flat_idx, pos, axis=1)
    return top_vals, top_idx
