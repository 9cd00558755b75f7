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

The model's module is chosen by the configuration file's own
`model_type` (`MODEL_MODULES`); what a module gives the facade is
`LMConfig.from_dict`, `leaf_specs` and `lm_score_step`. A module that
also has `init_cache` and `ctx_register_step` can keep CONTEXTS on the
device, where the file's `serve.context_cache` gives the slots. The
cache is the module's own: a tuple with one entry a layer, each entry an
array or a tuple of arrays (two kinds of state a token); the facade
holds it, hands it to the steps, donates it to registration and counts
its bytes, and reads nothing inside it. A module says what a
slot IS with its `CACHE_KIND`, and `CACHE_KINDS` below is the ONE place
that says what each kind means: rows of the cache a token (the
default), one state of fixed size whatever the context's length
(`"state"`, models/retention_lm.py: registration then CARRIES the
slot's state from chunk to chunk, a chunk that starts at 0 starting from
zeros so that a reused slot never leaks what it held; `tokens_per_slot`
is only the longest context admitted, and the gauges read slots held
over slots), either of them with a list of PAGES of a pool beside it
(`"paged"`, models/window_moe_lm.py; `"state+pages"`,
models/delta_moe_lm.py). A context is registered once (`register_context`: chunk by chunk under
one compiled shape, into a free or the least recently used slot,
serving/context_cache.py), and a request may then name it: its tokens
are scored as the continuation of the context's. The cache arrays are
read by scoring steps and updated in place (donated) by registration
chunks; `_cache_lock` makes "look the rows' slots up, dispatch the
step" one act against "dispatch a chunk, take the new arrays", so a step
never holds arrays a chunk gave away, and a slot taken after a step's
lookup is overwritten only behind that step on the device.

`--load` restores a parameters-only artifact leaf by leaf straight into
place (training/checkpoint.py `restore_params`); without it the
parameters are initialised from `--seed`, and `--save` writes them.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.config import Config
from code2vec_tpu.model_facade import (
    _H_FILL, _device_part, _stage, head_sorted_columns_gauge,
)
from code2vec_tpu.models import (
    delta_moe_lm, hybrid_lm, latent_moe_lm, lm_common, retention_lm,
    sparse_gqa_moe_lm, window_moe_lm,
)
from code2vec_tpu.ops.sparse_attn import unpack_bits
from code2vec_tpu.ops.topk import sorted_columns
from code2vec_tpu.serving.batcher import bucket_for, parse_buckets
from code2vec_tpu.serving.context_cache import (
    ContextSlots, PoolTooSmall, TooLong, chunks, context_id, extended_id,
)
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


_H_REGISTER = obs.histogram(
    "context_register_seconds",
    "wall time of one context registration, all its chunks",
    buckets=(0.01, 0.03, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0))
_H_REGISTER_CHUNK = obs.histogram(
    "context_register_chunk_seconds",
    "wall time of one registration chunk, dispatch to ready",
    buckets=(0.003, 0.01, 0.03, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0))
_C_UNKNOWN = obs.counter(
    "score_unknown_context_total",
    "score requests that named a context that is unknown or evicted")

_C_KEYS = obs.counter(
    "score_latents_read_total",
    "latents a scoring step's rows attend in one layer: each row's "
    "cached context tokens and its own real tokens")
_C_PAIRS = obs.counter(
    "score_attended_pairs_total",
    "(query, visible key) pairs of a scoring step's rows in one layer: "
    "q x cached + q (q + 1) / 2 a row of q real tokens")

_C_INDEX_PAIRS = obs.counter(
    "score_index_pairs_scored_total",
    "(query, visible key) pairs the indexer of a selecting attention "
    "scored, summed over a scoring step's rows, real queries and layers")
_C_KEYS_VISIBLE = obs.counter(
    "score_keys_visible_total",
    "keys a real query may see, summed over a scoring step's rows, "
    "queries and layers (what the selection chooses among)")
_C_KEYS_SELECTED = obs.counter(
    "score_keys_selected_total",
    "keys the selection kept (the step's own count, fetched with the "
    "answer), summed over a scoring step's rows, real queries and layers")

_C_SCORE_STATES = obs.counter(
    "score_states_read_total",
    "(row, layer) recurrent states a scoring or extending step's rows "
    "read from their contexts' cache slots, whatever the module")
_C_SCORE_STATE_BYTES = obs.counter(
    "score_state_bytes_read_total",
    "bytes of the states (and what a slot keeps beside them) that a "
    "scoring or extending step's rows read from the cache, every layer")
_C_STATES = obs.counter(
    "retention_states_read_total",
    "(row, layer) retention states a scoring step's rows read from "
    "their contexts' cache slots")
_C_STATE_BYTES = obs.counter(
    "retention_state_bytes_read_total",
    "bytes of the retention states a scoring step's rows read from the "
    "cache, every layer (the states as held: float32)")
_G_SLOT_BYTES = obs.gauge(
    "state_cache_slot_bytes",
    "bytes one context holds in a cache of fixed-size states, every "
    "layer; 0 for a cache of token rows")

_C_WINDOW_KEYS = obs.counter(
    "score_window_keys_read_total",
    "cached keys of the rings that a scoring step's rows may see (a "
    "row's context tokens, at most the window less one), times the "
    "window layers")
_C_FULL_KEYS = obs.counter(
    "score_full_keys_read_total",
    "cached keys of the pages that a scoring step's rows may see (a "
    "row's context tokens), times the full layers")
_C_PAGES_NEEDED = obs.counter(
    "score_pages_needed_total",
    "pages a scoring step's rows hold, each row its own, times the full "
    "layers")
_C_PAGES_VISITED = obs.counter(
    "score_pages_visited_total",
    "pages a scoring step's loop walked: its real rows times the longest "
    "page list among them, times the full layers (a row rides the "
    "longest row's trips)")

_C_TURNS = obs.counter(
    "context_extend_turns_total",
    "kept turns: score requests with `keep` whose step extended their "
    "context")
_C_TURN_TOKENS = obs.counter(
    "context_extend_tokens_total",
    "tokens by which kept turns extended their contexts")
_C_TURN_PAGES = obs.counter(
    "context_extend_pages_appended_total",
    "pages kept turns took from the pool as their contexts crossed page "
    "boundaries")
_C_TURN_WAITED = obs.counter(
    "context_extend_waited_total",
    "kept rows held back for the next step because their context had a "
    "kept row in the step before it")
_H_EXTEND = obs.histogram(
    "context_extend_seconds",
    "the book's part of one step of kept turns: lookups, pages taken, "
    "the new ids put in the old ones' place (the device's part is "
    "serving_predict_device_seconds)",
    buckets=(1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 0.01, 0.03, 0.1))

# the configuration file's `model_type` -> the module that runs it
MODEL_MODULES = {"nemotron_h": hybrid_lm, "glm4_moe_lite": latent_moe_lm,
                 "KeyeVL2": sparse_gqa_moe_lm, "brumby": retention_lm,
                 "afmoe": window_moe_lm, "solar_open2": delta_moe_lm}


class SlotKind(NamedTuple):
    """What a context holds in a module's cache."""
    fixed_size: bool    # its slot is ONE state whatever the tokens behind
    #                     it; else rows (or a ring) of the cache, a token
    pages: bool         # and every token in pages of a pool beside it
    longest: str        # what bounds a context's length, for the 4xx


_BY_POSITIONS = "the model's positions less the longest question"
# what a module's `CACHE_KIND` may say, and the ONE place that says what
# each means (`ScoringModel.slot` is the held module's)
CACHE_KINDS = {
    "tokens": SlotKind(False, False, "a cache slot's tokens"),
    "state": SlotKind(True, False, _BY_POSITIONS),
    "paged": SlotKind(False, True, _BY_POSITIONS),
    "state+pages": SlotKind(True, True, _BY_POSITIONS),
}


def cache_kind(module) -> str:
    kind = getattr(module, "CACHE_KIND", "tokens")
    if kind not in CACHE_KINDS:
        raise ValueError(f"{module.__name__}: CACHE_KIND {kind!r} is none "
                         f"of {', '.join(CACHE_KINDS)}")
    return kind


def selected_positions(words: np.ndarray, capacity: int, held: int
                       ) -> np.ndarray:
    """A row's `StepStats.selected_last` of one layer as positions of
    the sequence context ++ question, ascending: the packed row counts
    the slot's `capacity` positions, then the row's own tokens, which
    stand behind the `held` the context has."""
    at = unpack_bits(words)
    return np.where(at < capacity, at, at - capacity + held).astype(np.int32)


class UnknownContext(LookupError):
    """A request named a context that is not (or no longer) held."""


class ScoreRequest(NamedTuple):
    ids: np.ndarray         # (length,) int32
    top_k: int
    context: Optional[str] = None   # a registered context's id
    keep: bool = False      # the context is left extended by `ids`


class ScoreResult(NamedTuple):
    token_ids: np.ndarray       # (top_k,) int32, over the rows held
    logits: np.ndarray          # (top_k,) float32
    probabilities: np.ndarray   # (top_k,) softmax over the rows held
    tokens: int
    routing_last: np.ndarray    # (expert layers, k): the router's choice
    #                             at the last position; (0, 0) for a
    #                             model with no expert layer, never absent
    context_tokens: int = 0     # tokens of the context read before them
    unknown_context: Optional[str] = None   # set INSTEAD of an answer:
    #                             the context went between the request's
    #                             admission and its step
    selected_last: Optional[np.ndarray] = None  # (layers, words) uint32:
    #                             the keys the last position attended,
    #                             packed (`selected_positions`); None for
    #                             a model whose attention selects nothing
    kept_as: Optional[str] = None   # a kept turn: the id its context has
    #                             now (the one the request named is gone)
    refused: Optional[str] = None   # set INSTEAD of an answer: why a kept
    #                             turn could not be held (which limit)


def row_counts(bucket: int, budget: int) -> Tuple[int, ...]:
    """The row shapes of one length bucket: powers of two up to what the
    token budget holds."""
    out, rows = [], 1
    while rows * bucket <= budget:
        out.append(rows)
        rows *= 2
    return tuple(out)


class _Turns:
    """The book's part of one step of kept turns, under the cache's
    lock: before the step is dispatched each row's context is looked up
    and the free pages its new tokens need are taken (`take`); once it
    is dispatched the longer contexts take the old ones' places under
    their new ids (`commit`); a step that failed gives the pages back
    (`undo`)."""

    def __init__(self, book: ContextSlots):
        self.book = book
        self.rows: Dict[int, Tuple] = {}    # row -> (old id, its tokens,
        #                                     new id, its tokens, its
        #                                     pages, those of them taken)
        self.kept: Dict[int, str] = {}
        self.refused: Dict[int, str] = {}

    def take(self, row: int, context: str, ids: np.ndarray):
        """What the row's step reads and writes: the context as held,
        its page list grown by the pages taken; None for a context that
        is gone; the reason (a str) for a turn that cannot be held."""
        with obs.span("context.extend", hist=_H_EXTEND):
            try:
                found = self.book.extend_pages(context, len(ids))
            except (TooLong, PoolTooSmall) as e:
                self.refused[row] = str(e)
                return self.refused[row]
            if found is None:
                return None
            held, taken = found
            pages = held.pages + taken
            self.rows[row] = (context, held.tokens,
                              extended_id(context, ids),
                              held.tokens + len(ids), pages, taken)
            return held._replace(pages=pages)

    def commit(self) -> Dict[int, str]:
        """-> {row: its context's id} for rows whose context went
        between `take` and now (a registration evicted it): the step
        wrote into a slot that is no longer theirs, behind which the
        new owner's registration writes."""
        gone = {}
        with obs.span("context.extend", hist=_H_EXTEND):
            for row, (old, was, new, tokens, pages, taken
                      ) in self.rows.items():
                if self.book.replace(old, new, tokens, pages):
                    self.kept[row] = new
                    _C_TURNS.inc()
                    _C_TURN_TOKENS.inc(tokens - was)
                    _C_TURN_PAGES.inc(len(taken))
                else:
                    self.book.release(None, taken)
                    gone[row] = old
        return gone

    def undo(self) -> None:
        for *_, taken in self.rows.values():
            self.book.release(None, taken)


class ScoringModel:
    served_endpoints = ("score",)
    uses_extractor = False

    def __init__(self, config: Config):
        self.config = config
        config.verify()
        self.log = config.log
        with open(config.model_config) as f:
            raw = json.load(f)
        kind = raw.get("model_type", "nemotron_h")
        if kind not in MODEL_MODULES:
            raise ValueError(
                f"{config.model_config}: model_type {kind!r} is none of "
                f"{', '.join(MODEL_MODULES)}")
        self.module = MODEL_MODULES[kind]
        self.model_name = self.module.__name__.rsplit(".", 1)[-1]
        self.lm = self.module.LMConfig.from_dict(raw, config.model_config)
        specs = self.module.leaf_specs(self.lm)
        serve = raw.get("serve", {})
        self.token_budget = int(config.serve_token_budget)
        self.top_k = int(config.top_k_words_considered_during_prediction)
        self._buckets = parse_buckets(
            serve.get("length_buckets", ()), self.token_budget)
        experts = "no expert layer"
        if getattr(self.lm, "n_routed_experts", 0):
            experts = (f"experts [{self.lm.expert_first}, "
                       f"{self.lm.expert_first + self.lm.experts_held}) of "
                       f"{self.lm.n_routed_experts}")
        self.log(f"Creating scoring model from {config.model_config}: "
                 f"pattern {self.lm.pattern}, {experts}, vocabulary rows "
                 f"{self.lm.vocab_rows} of {self.lm.vocab_size}")
        if config.is_loading:
            config.model_load_path = ckpt_mod.resolve_load_path(
                config.model_load_path, log=self.log)
            with obs.startup_phase("restore"):
                self.params = ckpt_mod.restore_params(
                    config.model_load_path,
                    lm_common.abstract_leaves(specs))
            self.log(f"Loaded model weights from {config.model_load_path}")
        else:
            with obs.startup_phase("state_init"):
                self.params = jax.block_until_ready(
                    lm_common.init_leaves(self.lm, specs, config.seed))
        if hasattr(self.module, "ATTEND_FORM"):
            self._c_attend_form = obs.counter(
                "sparse_attend_steps_total",
                "scoring steps by the form of attention over the selected "
                "keys that their shape picked",
                form=self.module.ATTEND_FORM)
        self._predict_steps: Dict[Tuple[int, int], object] = {}
        self._fingerprint: Optional[str] = None
        self.contexts: Optional[ContextSlots] = None
        self.slot = CACHE_KINDS["tokens"]   # what a context holds
        self.extends = hasattr(self.module, "ctx_extend_step")
        held = serve.get("context_cache")
        if held and hasattr(self.module, "init_cache"):
            slot = self.slot = CACHE_KINDS[cache_kind(self.module)]
            self.register_chunk = int(held["register_chunk"])
            self.contexts = ContextSlots(
                held["slots"], held["tokens_per_slot"],
                fixed_size=slot.fixed_size,
                pages=held["pages"] if slot.pages else 0,
                page_tokens=self.register_chunk)
            if slot.fixed_size or slot.pages:
                most = self.lm.max_position_embeddings - self._buckets[-1]
                if self.contexts.capacity > most:
                    raise ValueError(
                        f"{config.model_config}: tokens_per_slot admits "
                        f"contexts past {slot.longest} ({most})")
            if (not slot.fixed_size
                    and self.contexts.capacity % self.register_chunk):
                raise ValueError(
                    f"{config.model_config}: tokens_per_slot must be a "
                    f"multiple of register_chunk")
            # a row's page list is as long as the longest context
            self.list_pages = (self.contexts.pages_for(self.contexts.capacity)
                               if slot.pages else 0)
            self.cache = self.module.init_cache(
                self.lm, self.contexts.slots,
                *((self.contexts.pages, self.register_chunk) if slot.pages
                  else (self.contexts.capacity,)))
            self._cache_lock = threading.Lock()
            self._register_lock = threading.Lock()
            self._register_step = None
            self.served_endpoints = ("score", "contexts")
            arrays = jax.tree.leaves(self.cache)
            pool = [a for a in arrays if slot.pages
                    and a.shape[0] == self.contexts.pages]
            total = sum(a.nbytes for a in arrays)
            pooled = sum(a.nbytes for a in pool)
            # a slot's share of every array but the pool's (its leading
            # dimension counts the slots, and whatever spare ones the
            # module keeps)
            self.slot_bytes = sum(a.nbytes // a.shape[0] for a in arrays
                                  if all(a is not p for p in pool))
            _G_SLOT_BYTES.set(self.slot_bytes if slot.fixed_size else 0)
            what = (f"one state of {self.slot_bytes:,} bytes a context"
                    if slot.fixed_size else
                    f"{self.register_chunk if slot.pages else self.contexts.capacity}"
                    f" tokens each")
            self.log(
                f"Context cache: {self.contexts.slots} "
                f"{'slots' if slot.fixed_size or not slot.pages else 'ring slots'}"
                f", {what} ({total - pooled:,} bytes)"
                + (f", and a pool of {self.contexts.pages} pages of "
                   f"{self.register_chunk} tokens ({pooled:,} bytes)"
                   if slot.pages else "")
                + f"; pattern {self.lm.pattern}; contexts of up to "
                  f"{self.contexts.capacity} tokens"
                + (f" ({self.list_pages} pages)" if slot.pages else "")
                + f"; registration in chunks of {self.register_chunk}"
                + ("; a scored turn can be kept" if self.extends else ""))
        self.log(f"Model created: {lm_common.count_leaves(specs):,} "
                 f"parameters; {self.describe_devices()}")

    # the names the older modules' tests read; `slot` says both
    state_cache = property(lambda self: self.slot.fixed_size)
    paged_cache = property(lambda self: self.slot.pages)

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
        if self.contexts is not None:
            # latents of the old weights answer nothing: every context
            # goes, and has to be registered again
            with self._register_lock, self._cache_lock:
                self.contexts = self.contexts.fresh()

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

    def _step(self, rows: int, length: int, keep: bool = False):
        """The jitted step of one shape: scoring, or with `keep` the
        module's extending step (the cache donated; -> (cache,
        outputs))."""
        key = (rows, length) + ((True,) if keep else ())
        step = self._predict_steps.get(key)
        if step is None:
            cfg, k, module = self.lm, self.top_k, self.module
            block = min(4096, cfg.vocab_rows)
            head_sorted_columns_gauge("score").set(
                sorted_columns(rows, block, min(k, cfg.vocab_rows)))

            if keep:
                def ctx_extend_step(params, cache, ids, lengths, slot, held,
                                    pages):
                    return module.ctx_extend_step(
                        cfg, k, block, params, cache, ids, lengths, slot,
                        held, pages)
                step = jax.jit(ctx_extend_step, donate_argnums=(1,))
            elif self.contexts is None:
                def lm_score_step(params, ids, lengths):
                    return module.lm_score_step(cfg, k, block, params, ids,
                                                lengths)
                step = jax.jit(lm_score_step)
            else:
                # `more`: a paged cache's page lists, a row
                def ctx_score_step(params, ids, lengths, cache, slot, held,
                                   *more):
                    return module.lm_score_step(cfg, k, block, params, ids,
                                                lengths, cache, slot, held,
                                                *more)
                step = jax.jit(ctx_score_step)
            self._predict_steps[key] = step
            # with kept turns: every shape twice, and registration's
            programs = len(self.shapes()) * (1 + self.extends) + self.extends
            self.log(f"Compiling {'extending' if keep else 'scoring'} step "
                     f"for shape (rows={rows}, length={length}) "
                     f"[{len(self._predict_steps)} of {programs}]")
        return step

    def _run_step(self, rows: int, length: int, ids: np.ndarray,
                  lengths: np.ndarray, contexts: Sequence[Optional[str]] = (),
                  keep: bool = False):
        """Dispatch one scoring step, row i after context `contexts[i]`;
        the answer is fetched by the caller. -> (the step's outputs, the
        tokens each row's context holds, {row: its context's id} for
        the rows whose context is gone: they run as padding, `lengths`
        zeroed IN PLACE for them). With a cache the slots are looked up
        and the step dispatched under the cache's lock (module
        docstring). With `keep` the step EXTENDS the rows' contexts
        (`_Turns` books it, under the same lock) and two more come
        back: {row: the id its context has now} and {row: why the turn
        could not be held} (such a row too runs as padding). The first
        parts of the device stage (`serving_predict_device_seconds`):
        the arguments are put on the device here, not inside the call,
        so that each part is timed."""
        gone: Dict[int, str] = {}
        cached = self.contexts is not None
        with contextlib.ExitStack() as locked:
            if cached:
                with _device_part("lookup"):
                    locked.enter_context(self._cache_lock)
                    slot = np.zeros((rows,), np.int32)
                    held = np.zeros((rows,), np.int32)
                    more = ()
                    if self.slot.pages:
                        more = (np.zeros((rows, self.list_pages), np.int32),)
                    turns = _Turns(self.contexts) if keep else None
                    for i, context in enumerate(contexts):
                        if context is None:
                            continue
                        found = (turns.take(i, context, ids[i, :lengths[i]])
                                 if keep else self.contexts.lookup(context))
                        if found is None:
                            gone[i], lengths[i] = context, 0
                            continue
                        if isinstance(found, str):      # the turn refused
                            lengths[i] = 0
                            continue
                        slot[i], held[i] = found.slot, found.tokens
                        if self.slot.pages:
                            more[0][i, :len(found.pages)] = found.pages
            with _device_part("put"):
                if not cached:
                    held = np.zeros((rows,), np.int32)
                on_device = jax.device_put(
                    (ids, lengths, slot, held) + more if cached
                    else (ids, lengths))
            with _device_part("enqueue"):
                if keep:
                    try:
                        self.cache, out = self._step(rows, length, True)(
                            self.params, self.cache, *on_device)
                    except BaseException:
                        turns.undo()
                        raise
                    gone.update(turns.commit())
                    return out, held, gone, turns.kept, turns.refused
                out = self._step(rows, length)(
                    self.params, *on_device[:2],
                    *((self.cache,) if cached else ()), *on_device[2:])
                del on_device   # the inputs go here, not between two parts
        return out, held, gone

    def warmup(self, rows: Optional[int] = None) -> None:
        """Compile and run every (rows, length) shape once (and the one
        registration chunk), so that no request pays a compile out of
        its deadline."""
        for n, length in self.shapes():
            out, _, _ = self._run_step(
                n, length, np.zeros((n, length), np.int32),
                np.ones((n,), np.int32))
            jax.block_until_ready(out.topk_values)
        if self.extends and self.contexts is not None:
            # rows of no real token: each writes back what it read
            for n, length in self.shapes():
                jax.block_until_ready(self._extend_rows(
                    n, length, np.zeros((n, length), np.int32),
                    np.zeros((n,), np.int32)).topk_values)
            self._register_chunk(
                np.zeros((self.register_chunk,), np.int32), 0, 0, 0, ())
        elif self.contexts is not None:
            # a chunk of no real token into slot 0: what it writes there
            # lies behind the length of whatever the slot holds (a state
            # takes nothing from it and comes back as it was)
            # (a paged cache keeps what lies past a chunk's real tokens:
            # a chunk of none writes nothing)
            self._register_chunk(
                np.zeros((self.register_chunk,), np.int32), 0, 0,
                0 if self.slot.pages
                else self.contexts.capacity - self.register_chunk,
                () if self.slot.pages else None)

    def _token_ids(self, ids: Sequence[int], most: int) -> np.ndarray:
        try:
            arr = np.asarray(ids, dtype=np.int64)
        except (TypeError, ValueError, OverflowError):
            raise ValueError("ids must be a list of integers")
        if arr.ndim != 1 or not 1 <= arr.size <= most:
            raise ValueError(f"ids must hold 1 to {most} token ids")
        if arr.min() < 0 or arr.max() >= self.lm.vocab_rows:
            raise ValueError(f"token ids must lie in "
                             f"[0, {self.lm.vocab_rows})")
        return arr.astype(np.int32)

    def validate(self, ids: Sequence[int], top_k: int,
                 context: Optional[str] = None, keep: bool = False
                 ) -> ScoreRequest:
        """A request's ids as an array, or ValueError saying what is
        wrong with them; UnknownContext for a context that is not
        held."""
        arr = self._token_ids(ids, self.token_budget)
        if not 1 <= int(top_k) <= self.top_k:
            raise ValueError(f"top_k must lie in [1, {self.top_k}]")
        if keep and not (self.extends and self.contexts is not None):
            raise ValueError(
                f"keep: {self.model_name} cannot extend a registered "
                f"context in place; register the longer context (POST "
                f"/contexts)")
        if keep and context is None:
            raise ValueError("keep extends a registered context: name it "
                             "(context)")
        if context is not None:
            if self.contexts is None:
                raise ValueError("this model keeps no contexts: send the "
                                 "whole sequence as ids")
            if self.contexts.lookup(str(context)) is None:
                _C_UNKNOWN.inc()
                raise UnknownContext(
                    f"context {context!r} is unknown or evicted: register "
                    f"it again (POST /contexts)")
            context = str(context)
        return ScoreRequest(arr, int(top_k), context, bool(keep))

    # ----------------------------------------------------------- contexts

    def _extend_rows(self, rows: int, length: int, ids: np.ndarray,
                     lengths: np.ndarray, slot: Optional[np.ndarray] = None,
                     held: Optional[np.ndarray] = None,
                     pages: Optional[np.ndarray] = None):
        """Dispatch one extending step on rows whose slots, lengths held
        and page lists the caller knows (zeros where None), under the
        cache's lock; the book is the caller's. -> the step's outputs
        (`self.cache` is the extended cache)."""
        zeros = np.zeros((rows,), np.int32)
        if pages is None:
            pages = np.zeros((rows, self.list_pages), np.int32)
        with self._cache_lock:
            self.cache, out = self._step(rows, length, True)(
                self.params, self.cache, ids, lengths,
                zeros if slot is None else slot,
                zeros if held is None else held, pages)
        return out

    def _register_chunk(self, ids: np.ndarray, real: int, slot: int,
                        start: int, pages: Optional[Sequence[int]] = None
                        ) -> None:
        """`pages`: with a paged cache, the context's page list."""
        if self.extends:
            # the empty context extended: the extending step at one row
            listed = np.zeros((1, self.list_pages), np.int32)
            listed[0, :len(pages)] = pages
            with obs.span("context.register.chunk", hist=_H_REGISTER_CHUNK):
                self._extend_rows(
                    1, self.register_chunk, ids[None, :],
                    np.array([real], np.int32), np.array([slot], np.int32),
                    np.array([start], np.int32), listed)
                jax.block_until_ready(self.cache)
            return
        if self._register_step is None:
            cfg, module = self.lm, self.module

            def ctx_register_step(params, cache, ids, length, slot, start,
                                  *more):
                return module.ctx_register_step(cfg, params, cache, ids,
                                                length, slot, start, *more)
            self._register_step = jax.jit(ctx_register_step,
                                          donate_argnums=(1,))
        more = ()
        if pages is not None:
            more = (np.zeros((self.list_pages,), np.int32),)
            more[0][:len(pages)] = pages
        with obs.span("context.register.chunk", hist=_H_REGISTER_CHUNK):
            with self._cache_lock:
                self.cache = self._register_step(
                    self.params, self.cache, ids, np.int32(real),
                    np.int32(slot), np.int32(start), *more)
            jax.block_until_ready(self.cache)

    def register_context(self, ids: Sequence[int]) -> Dict:
        """Run a context's tokens through the model once and keep their
        latents (or the state behind them) in a cache slot. -> {"context":
        its id, "tokens",
        "evicted": the id that lost the slot, or None, "held": whether
        it was there already}. Scoring steps run between the chunks."""
        if self.contexts is None:
            raise ValueError("this model keeps no contexts")
        try:
            arr = self._token_ids(ids, self.contexts.capacity)
        except ValueError as e:
            if "ids must hold" not in str(e):
                raise
            raise ValueError(f"{e} (the longest context admitted: "
                             f"{self.slot.longest})")
        context = context_id(arr)
        with self._register_lock, obs.span("context.register",
                                           hist=_H_REGISTER):
            if self.contexts.lookup(context) is not None:
                return {"context": context, "tokens": int(arr.size),
                        "evicted": None, "held": True}
            pages, also = None, {}
            if self.slot.pages:
                try:
                    slot, pages, gone = self.contexts.acquire_pages(arr.size)
                except PoolTooSmall as e:
                    raise ValueError(f"{e} (the page pool)")
                evicted = gone[0] if gone else None
                also = {"evicted_contexts": gone}
            else:
                slot, evicted = self.contexts.acquire()
            try:
                for start, real in chunks(arr.size, self.register_chunk):
                    part = np.zeros((self.register_chunk,), np.int32)
                    part[:real] = arr[start:start + real]
                    self._register_chunk(part, real, slot, start, pages)
            except BaseException:
                self.contexts.release(slot, pages or ())
                raise
            self.contexts.commit(slot, context, arr.size, pages or ())
        return {"context": context, "tokens": int(arr.size),
                "evicted": evicted, "held": False, **also}

    def selected_positions(self, result: ScoreResult) -> List[np.ndarray]:
        """By layer, the positions of context ++ question that the
        request's last position attended."""
        return [selected_positions(words, self.contexts.capacity,
                                   result.context_tokens)
                for words in result.selected_last]

    def score_batch(self, requests: Sequence[ScoreRequest]
                    ) -> List[ScoreResult]:
        """One result a request, in order. The batcher hands over what
        fits one step; a longer list is cut into steps here. A step
        either keeps all its rows or none, and never two kept rows of
        one context: the second waits for the next step, where the id
        it names is gone (the first one's turn replaced it)."""
        out: List[ScoreResult] = []
        pending = list(requests)
        while pending:
            take, deepest, kept = 0, 0, set()
            for r in pending:
                b = bucket_for(len(r.ids), self._buckets)
                if take and ((take + 1) * max(deepest, b) > self.token_budget
                             or r.keep != pending[0].keep):
                    break
                if r.keep and r.context in kept:
                    _C_TURN_WAITED.inc()
                    break
                take, deepest = take + 1, max(deepest, b)
                kept.add(r.context)
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
            contexts = [r.context for r in requests]
            keep = bool(requests[0].keep)
        with _stage("device"):
            got, held, gone, *turns = self._run_step(
                rows, length, ids, lengths, contexts, keep)
            kept, refused = turns or ({}, {})
            with _device_part("wait"):
                answer = jax.block_until_ready(
                    (got.topk_values, got.topk_indices, got.lse, got.stats))
            with _device_part("fetch"):
                values, indices, lse, stats = jax.device_get(answer)
        _H_TOKEN_FILL.observe(float(lengths.sum()) / (rows * length))
        _C_UNKNOWN.inc(len(gone))
        if self.slot.fixed_size:
            # a row reads its context's state, the same bytes whatever
            # the context's length: nothing here counts tokens
            reading = int(((held > 0) & (lengths > 0)).sum())
            layers = getattr(self.lm, "state_layers", self.lm.layers)
            _C_SCORE_STATES.inc(reading * layers)
            _C_SCORE_STATE_BYTES.inc(reading * self.slot_bytes)
            if not self.slot.pages:
                _C_STATES.inc(reading * layers)
                _C_STATE_BYTES.inc(reading * self.slot_bytes)
        if self.slot.pages:
            self._count_paged(held, lengths)
        elif self.contexts is not None and not self.slot.fixed_size:
            q = lengths.astype(np.int64)
            pairs = int((q * held + q * (q + 1) // 2).sum())
            _C_KEYS.inc(int((held + q).sum()))
            _C_PAIRS.inc(pairs)
            if stats.selected_keys is not None:
                # every visible key of a query is index-scored, so the
                # two counts are one number while the indexer prunes none
                layers = len(stats.selected_keys)
                _C_INDEX_PAIRS.inc(pairs * layers)
                _C_KEYS_VISIBLE.inc(pairs * layers)
                _C_KEYS_SELECTED.inc(int(stats.selected_keys.sum()))
                self._c_attend_form.inc()
        with _stage("render"):
            self._observe_router(stats)
            results = []
            for i, r in enumerate(requests):
                k = r.top_k
                results.append(ScoreResult(
                    indices[i, :k], values[i, :k],
                    np.exp(values[i, :k] - lse[i]), int(lengths[i]),
                    stats.chosen_last[i], int(held[i]), gone.get(i),
                    None if stats.selected_last is None
                    else stats.selected_last[i], kept.get(i),
                    refused.get(i)))
            return results

    def _count_paged(self, held: np.ndarray, lengths: np.ndarray) -> None:
        """What a step on a paged cache reads, from its rows' lengths:
        the ring rows and the page tokens its real rows may see, the
        pages they hold and the pages the full layers' loop walks (every
        real row rides the longest list's trips)."""
        window = getattr(self.lm, "window_layers", 0)
        full = self.lm.full_layers
        seen = held[lengths > 0].astype(np.int64)
        if not seen.size:
            return
        needed = -(-seen // self.register_chunk)
        _C_WINDOW_KEYS.inc(int(np.minimum(
            seen, self.register_chunk - 1).sum()) * window)
        _C_FULL_KEYS.inc(int(seen.sum()) * full)
        _C_PAGES_NEEDED.inc(int(needed.sum()) * full)
        _C_PAGES_VISITED.inc(int(needed.max()) * seen.size * full)

    @staticmethod
    def _observe_router(stats) -> None:
        """A model with no expert layer hands over arrays of zero
        layers: nothing is observed and the `moe_*` series stay where
        they were."""
        real = int(stats.real_tokens)
        for load, unserved in zip(stats.load, stats.unserved_tokens):
            total = int(load.sum())
            if total:
                _H_EXPERT_LOAD.observe(float(load.max()) * len(load) / total)
            _C_ROUTED.inc(real)
            _C_UNSERVED.inc(int(unserved))
            _C_ASSIGNED.inc(total)
            _C_EXPERTS_HIT.inc(int((load > 0).sum()))
