"""The facade of a model of `models/` other than code2vec, served for
scoring: what `code2vec.py serve --model_config FILE` builds and
`PredictionServer` drives.

The server's contract with a model (serving/server.py reads nothing
else): `config`, `model_fingerprint()`, `context_buckets`,
`served_endpoints`, `uses_extractor`, `batcher_options()`, one batched
call (`predict` for code2vec's extractor lines, `score_batch` here),
`warmup()`, `predict_compile_count()`, `describe_devices()`,
`smoke_schema()`.

A request is a token sequence; the answer is the top-k of the
next-token logits at its last position, over the vocabulary rows held.
A batch is `(rows, length)` padded on the right: `length` one of a few
buckets, `rows` a power of two, `rows x length <= serve_token_budget`.
One jitted step a shape, every shape compiled by `warmup()`.

`--load` restores a parameters-only artifact leaf by leaf straight into
place (training/checkpoint.py `restore_params`); without it the
parameters are initialised from `--seed`, and `--save` writes them.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.model_facade import _H_FILL, _stage
from code2vec_tpu.models import hybrid_lm
from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
from code2vec_tpu.training import checkpoint as ckpt_mod
from code2vec_tpu.utils.device import describe_devices

_H_TOKEN_FILL = obs.histogram(
    "serving_batch_tokens_fill_ratio",
    "real tokens over rows x padded length of one scoring step",
    buckets=(0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))
_H_EXPERT_LOAD = obs.histogram(
    "moe_expert_load_max_over_mean",
    "per scoring step and expert layer, over the experts held: the "
    "busiest expert's tokens over the mean (the router's own counts, "
    "fetched with the answer); 1 is perfectly even",
    buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0, 32.0, 128.0))
_C_ROUTED = obs.counter(
    "moe_tokens_routed_total",
    "(real token, expert layer) pairs the router handled")
_C_UNSERVED = obs.counter(
    "moe_tokens_without_local_expert_total",
    "(real token, expert layer) pairs none of whose chosen experts is "
    "held here: the layer adds only its shared expert for them")


_C_ASSIGNED = obs.counter(
    "moe_local_assignments_total",
    "(real token, chosen expert) pairs whose expert is held here: the "
    "rows the grouped matmuls work on")
_C_EXPERTS_HIT = obs.counter(
    "moe_experts_hit_total",
    "held experts that got at least one token, summed over scoring "
    "steps and expert layers: whose weights a step had to read")


class ScoreRequest(NamedTuple):
    ids: np.ndarray         # (length,) int32
    top_k: int


class ScoreResult(NamedTuple):
    token_ids: np.ndarray       # (top_k,) int32, over the rows held
    logits: np.ndarray          # (top_k,) float32
    probabilities: np.ndarray   # (top_k,) softmax over the rows held
    tokens: int
    routing_last: np.ndarray    # (expert layers, k): the router's choice
    #                             at the last position


def row_counts(bucket: int, budget: int) -> Tuple[int, ...]:
    """The row shapes of one length bucket: powers of two up to what the
    token budget holds."""
    out, rows = [], 1
    while rows * bucket <= budget:
        out.append(rows)
        rows *= 2
    return tuple(out)


class ScoringModel:
    served_endpoints = ("score",)
    uses_extractor = False

    def __init__(self, config: Config):
        self.config = config
        config.verify()
        self.log = config.log
        with open(config.model_config) as f:
            raw = json.load(f)
        self.lm = hybrid_lm.LMConfig.from_dict(raw, config.model_config)
        serve = raw.get("serve", {})
        self.token_budget = int(config.serve_token_budget)
        self.top_k = int(config.top_k_words_considered_during_prediction)
        self._buckets = parse_buckets(
            serve.get("length_buckets", ()), self.token_budget)
        self.log(f"Creating scoring model from {config.model_config}: "
                 f"pattern {self.lm.pattern}, experts "
                 f"[{self.lm.expert_first}, "
                 f"{self.lm.expert_first + self.lm.experts_held}) of "
                 f"{self.lm.n_routed_experts}, vocabulary rows "
                 f"{self.lm.vocab_rows} of {self.lm.vocab_size}")
        if config.is_loading:
            config.model_load_path = ckpt_mod.resolve_load_path(
                config.model_load_path, log=self.log)
            with obs.startup_phase("restore"):
                self.params = ckpt_mod.restore_params(
                    config.model_load_path,
                    hybrid_lm.abstract_params(self.lm))
            self.log(f"Loaded model weights from {config.model_load_path}")
        else:
            with obs.startup_phase("state_init"):
                self.params = jax.block_until_ready(
                    hybrid_lm.init_params(self.lm, config.seed))
        self._predict_steps: Dict[Tuple[int, int], object] = {}
        self._fingerprint: Optional[str] = None
        self.log(f"Model created: {hybrid_lm.num_params(self.lm):,} "
                 f"parameters; {self.describe_devices()}")

    # ------------------------------------------------------ the contract

    @property
    def context_buckets(self) -> Tuple[int, ...]:
        """Padded lengths, ascending; the last is the token budget."""
        return self._buckets

    def batcher_options(self) -> Dict:
        return {"bucket_of": lambda r: bucket_for(len(r.ids), self._buckets),
                "max_batch_tokens": self.token_budget}

    def shapes(self) -> List[Tuple[int, int]]:
        return [(rows, b) for b in self._buckets
                for rows in row_counts(b, self.token_budget)]

    def describe_devices(self) -> str:
        return describe_devices(self.params)

    def predict_compile_count(self) -> int:
        return len(self._predict_steps)

    def model_fingerprint(self) -> str:
        """The configuration, where the weights came from and a few of
        their values."""
        if self._fingerprint is None:
            probe = np.asarray(self.params["final_norm"][:8], np.float32)
            head = np.asarray(self.params["head"][:2, :8], np.float32)
            self._fingerprint = hashlib.sha256(repr((
                self.lm, self.config.model_load_path, self.config.seed,
                probe.tobytes(), head.tobytes())).encode()).hexdigest()[:16]
        return self._fingerprint

    def set_params(self, params: Dict[str, jax.Array]) -> None:
        """Other weights in place of the held ones (the caller frees
        those first where the device cannot hold two sets)."""
        self.params = params
        self._fingerprint = None

    def save(self, model_save_path: Optional[str] = None) -> str:
        path = ckpt_mod.save_params(
            model_save_path or self.config.model_save_path, self.params,
            {"model_config": os.path.basename(self.config.model_config),
             "pattern": self.lm.pattern, "seed": self.config.seed})
        self.log(f"Saved {len(self.params)} parameter leaves to {path}")
        return path

    def smoke_schema(self) -> dict:
        [r] = self.score_batch([ScoreRequest(np.zeros((4,), np.int32),
                                             self.top_k)])
        return {"topk": len(r.token_ids), "code_vector_size": 0,
                "scores_finite": bool(np.isfinite(r.logits).all())}

    # ------------------------------------------------------------ scoring

    def _step(self, rows: int, length: int):
        key = (rows, length)
        step = self._predict_steps.get(key)
        if step is None:
            cfg, k = self.lm, self.top_k
            block = min(4096, cfg.vocab_rows)

            def lm_score_step(params, ids, lengths):
                return hybrid_lm.lm_score_step(cfg, k, block, params, ids,
                                               lengths)
            step = self._predict_steps[key] = jax.jit(lm_score_step)
            self.log(f"Compiling scoring step for shape (rows={rows}, "
                     f"length={length}) [{len(self._predict_steps)} of "
                     f"{len(self.shapes())}]")
        return step

    def warmup(self, rows: Optional[int] = None) -> None:
        """Compile and run every (rows, length) shape once, so that no
        request pays a compile out of its deadline."""
        for n, length in self.shapes():
            out = self._step(n, length)(
                self.params, np.zeros((n, length), np.int32),
                np.ones((n,), np.int32))
            jax.block_until_ready(out.topk_values)

    def validate(self, ids: Sequence[int], top_k: int) -> ScoreRequest:
        """A request's ids as an array, or ValueError saying what is
        wrong with them."""
        try:
            arr = np.asarray(ids, dtype=np.int64)
        except (TypeError, ValueError, OverflowError):
            raise ValueError("ids must be a list of integers")
        if arr.ndim != 1 or not 1 <= arr.size <= self.token_budget:
            raise ValueError(f"ids must hold 1 to {self.token_budget} "
                             f"token ids")
        if arr.min() < 0 or arr.max() >= self.lm.vocab_rows:
            raise ValueError(f"token ids must lie in "
                             f"[0, {self.lm.vocab_rows})")
        if not 1 <= int(top_k) <= self.top_k:
            raise ValueError(f"top_k must lie in [1, {self.top_k}]")
        return ScoreRequest(arr.astype(np.int32), int(top_k))

    def score_batch(self, requests: Sequence[ScoreRequest]
                    ) -> List[ScoreResult]:
        """One result a request, in order. The batcher hands over what
        fits one step; a longer list is cut into steps here."""
        out: List[ScoreResult] = []
        pending = list(requests)
        while pending:
            take, deepest = 0, 0
            for r in pending:
                b = bucket_for(len(r.ids), self._buckets)
                if take and (take + 1) * max(deepest, b) > self.token_budget:
                    break
                take, deepest = take + 1, max(deepest, b)
            out.extend(self._score_step(pending[:take], deepest))
            pending = pending[take:]
        return out

    def _score_step(self, requests: Sequence[ScoreRequest], length: int
                    ) -> List[ScoreResult]:
        n = len(requests)
        with _stage("assemble"):
            rows = next(c for c in row_counts(length, self.token_budget)
                        if c >= n)
            ids = np.zeros((rows, length), np.int32)
            lengths = np.zeros((rows,), np.int32)
            for i, r in enumerate(requests):
                ids[i, :len(r.ids)] = r.ids
                lengths[i] = len(r.ids)
            _H_FILL["rows"].observe(n / rows)
            _H_TOKEN_FILL.observe(float(lengths.sum()) / (rows * length))
        with _stage("device"):
            got = self._step(rows, length)(self.params, ids, lengths)
            values, indices, lse, stats = jax.device_get(
                (got.topk_values, got.topk_indices, got.lse, got.stats))
        with _stage("render"):
            self._observe_router(stats)
            results = []
            for i, r in enumerate(requests):
                k = r.top_k
                results.append(ScoreResult(
                    indices[i, :k], values[i, :k],
                    np.exp(values[i, :k] - lse[i]), int(lengths[i]),
                    stats.chosen_last[i]))
            return results

    @staticmethod
    def _observe_router(stats) -> None:
        real = int(stats.real_tokens)
        for load, unserved in zip(stats.load, stats.unserved_tokens):
            total = int(load.sum())
            if total:
                _H_EXPERT_LOAD.observe(float(load.max()) * len(load) / total)
            _C_ROUTED.inc(real)
            _C_UNSERVED.inc(int(unserved))
            _C_ASSIGNED.inc(total)
            _C_EXPERTS_HIT.inc(int((load > 0).sum()))
