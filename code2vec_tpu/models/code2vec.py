"""The code2vec model as a single Flax module.

One TPU-first implementation replaces the reference's two parallel
backends (TF1 session graphs tensorflow_model.py:196-308 and tf.keras
keras_model.py:37-95). Architecture (identical math):

  token/path embedding gathers -> concat (B, M, 3d) -> dropout(0.25)
  -> tanh(. @ TRANSFORM) -> masked single-query attention -> code vector
  -> logits = code_vector @ TARGET_EMB^T  (~261K-way classifier)

Parameter shapes and initializers follow tensorflow_model.py:204-219 and
:248-253: embeddings use variance_scaling(1.0, fan_out, uniform);
TRANSFORM/ATTENTION use TF's get_variable default (glorot_uniform).
Parameters are float32; matmuls run in `compute_dtype` (bfloat16 on the
MXU) with float32 accumulation.

`jax.named_scope`s name the step's parts for the profiler's op view:
`embed_gather`, `transform`, `attention` (ops/attention.py), `logits_ce`
(the classifier here for eval and predict; in the GSPMD train steps the
classifier with its loss and BOTH directions, ops/head_ce.py); a
backward op carries its forward scope as `transpose(jvp(<scope>))`.
Scopes are metadata: the compiled program does not change
(tests/test_model.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from code2vec_tpu.ops.attention import masked_single_query_attention


@dataclasses.dataclass(frozen=True)
class ModelDims:
    token_vocab_size: int
    path_vocab_size: int
    target_vocab_size: int
    token_dim: int = 128
    path_dim: int = 128
    # Real (unpadded) target vocab size. Table rows may be padded up to a
    # multiple of the tensor-parallel degree so row shards are equal-sized
    # under shard_map; padded classifier columns must never win, so logits
    # for ids >= real_target_vocab_size are masked to -inf.
    real_target_vocab_size: int = 0
    # Highest special-word (PAD/OOV) index in the target vocab. Eval rows
    # whose label is <= this floor have no real in-vocab target, so their
    # CE term is excluded from the reported eval loss (train rows are
    # already filtered by the reader; the reference's eval loop reports no
    # loss at all, tensorflow_model.py:155-182, so the convention here is
    # chosen to keep eval loss comparable to train loss).
    target_oov_floor: int = 0

    def __post_init__(self):
        if self.real_target_vocab_size == 0:
            object.__setattr__(self, "real_target_vocab_size",
                               self.target_vocab_size)

    @property
    def context_dim(self) -> int:
        return self.path_dim + 2 * self.token_dim

    @property
    def code_dim(self) -> int:
        return self.context_dim

    @property
    def has_padded_targets(self) -> bool:
        return self.real_target_vocab_size < self.target_vocab_size

    def padded_to(self, tp: int) -> "ModelDims":
        """Round table row counts up to a multiple of `tp` (equal row
        shards for the manual tensor-parallel kernels)."""
        def up(n):
            return ((n + tp - 1) // tp) * tp
        return dataclasses.replace(
            self,
            token_vocab_size=up(self.token_vocab_size),
            path_vocab_size=up(self.path_vocab_size),
            target_vocab_size=up(self.target_vocab_size),
            real_target_vocab_size=self.real_target_vocab_size,
        )

    @classmethod
    def from_config_and_vocabs(cls, config, vocabs) -> "ModelDims":
        tv = vocabs.target_vocab
        dims = cls(
            token_vocab_size=vocabs.token_vocab.size,
            path_vocab_size=vocabs.path_vocab.size,
            target_vocab_size=tv.size,
            token_dim=config.token_embeddings_size,
            path_dim=config.path_embeddings_size,
            target_oov_floor=max(tv.pad_index, tv.oov_index),
        )
        if config.tp > 1:
            dims = dims.padded_to(config.tp)
        return dims


def _embedding_init():
    # reference: tensorflow_model.py:208 — variance_scaling(scale=1.0,
    # mode='fan_out', distribution='uniform').
    return nn.initializers.variance_scaling(1.0, "fan_out", "uniform")


class Code2VecModule(nn.Module):
    dims: ModelDims
    dropout_keep_rate: float = 0.75
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Mesh axis name the context dimension is sharded over (context/sequence
    # parallelism); None under plain jit/GSPMD.
    context_axis_name: Optional[str] = None

    def setup(self):
        d = self.dims
        self.token_embedding = self.param(
            "token_embedding", _embedding_init(),
            (d.token_vocab_size, d.token_dim), jnp.float32)
        self.path_embedding = self.param(
            "path_embedding", _embedding_init(),
            (d.path_vocab_size, d.path_dim), jnp.float32)
        self.target_embedding = self.param(
            "target_embedding", _embedding_init(),
            (d.target_vocab_size, d.code_dim), jnp.float32)
        self.transform = self.param(
            "transform", nn.initializers.glorot_uniform(),
            (d.context_dim, d.code_dim), jnp.float32)
        self.attention = self.param(
            "attention", nn.initializers.glorot_uniform(),
            (d.code_dim, 1), jnp.float32)

    def transform_contexts(
        self,
        source_token_indices: jax.Array,   # (B, M) int32
        path_indices: jax.Array,           # (B, M) int32
        target_token_indices: jax.Array,   # (B, M) int32
        deterministic: bool = True,
    ) -> jax.Array:
        """Embed, concat, dropout, tanh-transform: (B, M, code_dim).

        reference: tensorflow_model.py:237-251.
        """
        with jax.named_scope("embed_gather"):
            src = jnp.take(self.token_embedding, source_token_indices,
                           axis=0)
            pth = jnp.take(self.path_embedding, path_indices, axis=0)
            tgt = jnp.take(self.token_embedding, target_token_indices,
                           axis=0)
        return self.transform_gathered(src, pth, tgt,
                                       deterministic=deterministic)

    @jax.named_scope("transform")
    def transform_gathered(
        self,
        source_rows: jax.Array,            # (B, M, token_dim) f32
        path_rows: jax.Array,              # (B, M, path_dim) f32
        target_rows: jax.Array,            # (B, M, token_dim) f32
        deterministic: bool = True,
    ) -> jax.Array:
        """Concat, dropout, tanh-transform pre-gathered embedding rows.

        Entry point for the sparse-optimizer train step
        (training/step.py): gathers happen *outside* the differentiated
        function so gradients arrive per-row instead of as dense
        table-shaped scatters (training/sparse_adam.py).
        """
        ctx = jnp.concatenate([source_rows, path_rows, target_rows],
                              axis=-1)                       # (B, M, 3d)
        # Cast to the compute dtype *before* dropout: the masked/scaled
        # (B, M, 3d) intermediate (and its backward) then moves through
        # HBM at half width. The 1/keep scale in bfloat16 differs from
        # f32 scaling below dropout's own noise floor; with
        # compute_dtype=float32 this is exactly the reference math
        # (tensorflow_model.py:244-245, keep=0.75).
        ctx = ctx.astype(self.compute_dtype)
        if not deterministic:
            keep = self.dropout_keep_rate
            rng = self.make_rng("dropout")
            mask = jax.random.bernoulli(rng, p=keep, shape=ctx.shape)
            ctx = jnp.where(mask, ctx / jnp.asarray(keep, ctx.dtype),
                            jnp.zeros((), ctx.dtype))
        transformed = jnp.tanh(
            jnp.einsum("bmc,cd->bmd", ctx, self.transform.astype(self.compute_dtype),
                       preferred_element_type=jnp.float32))
        return transformed.astype(self.compute_dtype)

    def encode(
        self,
        source_token_indices: jax.Array,
        path_indices: jax.Array,
        target_token_indices: jax.Array,
        context_valid_mask: jax.Array,     # (B, M) float
        deterministic: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Code vectors (B, code_dim) float32 + attention weights (B, M)."""
        transformed = self.transform_contexts(
            source_token_indices, path_indices, target_token_indices,
            deterministic=deterministic)
        code_vectors, attention = masked_single_query_attention(
            transformed, self.attention[:, 0], context_valid_mask,
            axis_name=self.context_axis_name)
        return code_vectors.astype(jnp.float32), attention

    @jax.named_scope("logits_ce")
    def logits_from_code_vectors(self, code_vectors: jax.Array) -> jax.Array:
        """(B, target_vocab) float32 — the replicated (non-TP) classifier.

        reference: tensorflow_model.py:225, :296. The tensor-parallel
        variant lives in ops/sharded.py and consumes `target_embedding`
        row-sharded.
        """
        logits = jnp.einsum(
            "bd,vd->bv", code_vectors.astype(self.compute_dtype),
            self.target_embedding.astype(self.compute_dtype),
            preferred_element_type=jnp.float32)
        if self.dims.has_padded_targets:
            col = jnp.arange(self.dims.target_vocab_size)
            logits = jnp.where(col[None, :] < self.dims.real_target_vocab_size,
                               logits, -jnp.inf)
        return logits

    def encode_from_rows(self, source_rows, path_rows, target_rows,
                         context_valid_mask, deterministic: bool = True):
        """`encode` from pre-gathered embedding rows (sparse-update
        train path): (code_vectors f32, attention)."""
        transformed = self.transform_gathered(
            source_rows, path_rows, target_rows, deterministic=deterministic)
        code_vectors, attention = masked_single_query_attention(
            transformed, self.attention[:, 0], context_valid_mask,
            axis_name=self.context_axis_name)
        return code_vectors.astype(jnp.float32), attention

    def __call__(self, source_token_indices, path_indices, target_token_indices,
                 context_valid_mask, deterministic: bool = True):
        code_vectors, attention = self.encode(
            source_token_indices, path_indices, target_token_indices,
            context_valid_mask, deterministic=deterministic)
        logits = self.logits_from_code_vectors(code_vectors)
        return logits, code_vectors, attention
