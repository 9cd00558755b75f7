"""Host-side streaming reader for `.c2v` path-context files.

TPU-first redesign of the reference's in-graph tf.data pipeline
(reference: path_context_reader.py:119-228): strings never reach the
device. The host tokenizes, looks up vocab ids, pads and masks into fixed
`(B, MAX_CONTEXTS)` int32 arrays; XLA only ever sees integers. Row
semantics are reproduced exactly:

- a context is valid iff any of its three parts is not PAD
  (reference: path_context_reader.py:209-214);
- training rows are dropped when the target is OOV/PAD or no context is
  valid; eval rows only when no context is valid; predict rows never
  (reference: path_context_reader.py:153-177, 100);
- missing trailing fields behave like padding contexts (the reference's
  CsvDataset record_defaults, path_context_reader.py:82-83).

Shuffling uses a bounded reservoir-style buffer like tf.data's
`shuffle(buffer_size)` (reference: path_context_reader.py:139), and the
file can be sharded across hosts (`shard_index`/`num_shards`) for
multi-host TPU pods — each host reads a disjoint subset of rows.
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
import os
import random
import struct
from typing import Iterable, Iterator, List, Optional, Sequence

import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.vocab import Code2VecVocabs

# Handles cached at module scope: _parse_chunk is the reader's hot path
# (called from the worker pool threads; the registry's metrics are
# thread-safe, the lookup lock is what we avoid per chunk).
_H_PARSE = obs.histogram(
    "data_parse_seconds",
    "parse+filter of one reader chunk (parse_chunk_lines raw lines)")
_C_ROWS_READ = obs.counter("data_rows_read_total",
                           "raw .c2v lines parsed")
_C_ROWS_DROPPED = obs.counter(
    "data_rows_dropped_total",
    "parsed rows removed by the reference row filter (OOV target / no "
    "valid context)")


@dataclasses.dataclass(frozen=True)
class EpochEnd:
    """Marker yielded between epochs when a batch stream is constructed
    with `yield_epoch_markers=True`.

    This is the data-pass boundary itself — the trainer drives per-epoch
    checkpointing/evaluation off these markers instead of a raw-line
    `train_steps_per_epoch` estimate, so the schedule cannot drift when
    rows are filtered out (the reference counts raw lines,
    config.py:165-167, and its step math is therefore approximate).
    `epoch` is 1-based: the marker follows the epoch's last batch.
    """
    epoch: int


class EstimatorAction(enum.Enum):
    Train = "train"
    Evaluate = "evaluate"
    Predict = "predict"

    @property
    def is_train(self) -> bool:
        return self is EstimatorAction.Train

    @property
    def is_evaluate(self) -> bool:
        return self is EstimatorAction.Evaluate

    @property
    def is_predict(self) -> bool:
        return self is EstimatorAction.Predict


@dataclasses.dataclass
class RowBatch:
    """One batch of model inputs (host numpy; device transfer elsewhere).

    `example_valid` marks rows that are real examples (the final batch of an
    eval epoch is padded up to the fixed batch size so shapes stay static
    under jit; metrics must ignore padded rows).
    """
    source_token_indices: np.ndarray   # (B, M) int32
    path_indices: np.ndarray           # (B, M) int32
    target_token_indices: np.ndarray   # (B, M) int32
    context_valid_mask: np.ndarray     # (B, M) float32
    target_index: np.ndarray           # (B,) int32
    example_valid: np.ndarray          # (B,) bool
    target_strings: Optional[List[str]] = None      # (B,) for eval/predict
    # Raw string triples, only materialized for predict (attention display).
    source_strings: Optional[np.ndarray] = None     # (B, M) object
    path_strings: Optional[np.ndarray] = None       # (B, M) object
    target_token_strings: Optional[np.ndarray] = None  # (B, M) object

    @property
    def num_valid(self) -> int:
        return int(self.example_valid.sum())

    def model_inputs(self):
        return (self.source_token_indices, self.path_indices,
                self.target_token_indices, self.context_valid_mask)


def parse_context_lines(
    lines: Sequence[str],
    vocabs: Code2VecVocabs,
    max_contexts: int,
    estimator_action: EstimatorAction,
    keep_strings: bool = False,
) -> RowBatch:
    """Parse raw `.c2v` lines into a RowBatch (unfiltered).

    Reference row parse: path_context_reader.py:184-228.
    """
    n = len(lines)
    m = max_contexts
    keep = keep_strings or estimator_action.is_predict
    if not keep:
        # Hot path: the native C++ core does split+lookup+mask when built
        # (identical semantics; tests/test_native_dataloader.py pins it).
        from code2vec_tpu.data import native
        tables = native.tables_for(vocabs)
        parsed = tables.parse_lines(lines, m) if tables is not None else None
        if parsed is not None:
            src, pth, tgt, label, mask = parsed
            return RowBatch(
                source_token_indices=src,
                path_indices=pth,
                target_token_indices=tgt,
                context_valid_mask=mask,
                target_index=label,
                example_valid=np.ones((n,), dtype=bool),
                # only evaluation reads the raw targets; training must not
                # pay a per-line Python loop after the C call
                target_strings=(
                    [line.split(" ", 1)[0].rstrip("\n") for line in lines]
                    if estimator_action.is_evaluate else None),
            )
    token_w2i = vocabs.token_vocab.word_to_index
    path_w2i = vocabs.path_vocab.word_to_index
    token_oov = vocabs.token_vocab.oov_index
    path_oov = vocabs.path_vocab.oov_index
    token_pad = vocabs.token_vocab.pad_index
    path_pad = vocabs.path_vocab.pad_index

    src = np.full((n, m), token_pad, dtype=np.int32)
    pth = np.full((n, m), path_pad, dtype=np.int32)
    tgt = np.full((n, m), token_pad, dtype=np.int32)
    target_index = np.empty((n,), dtype=np.int32)
    if keep:
        src_s = np.full((n, m), "", dtype=object)
        pth_s = np.full((n, m), "", dtype=object)
        tgt_s = np.full((n, m), "", dtype=object)
    target_strings: List[str] = []

    target_lookup = vocabs.target_vocab.lookup_index
    for i, line in enumerate(lines):
        parts = line.rstrip("\n").split(" ")
        target_str = parts[0] if parts else ""
        target_strings.append(target_str)
        target_index[i] = target_lookup(target_str)
        row_contexts = parts[1:m + 1]
        for j, ctx in enumerate(row_contexts):
            if not ctx:
                continue
            pieces = ctx.split(",")
            # Malformed contexts (< 3 fields) behave like the reference's
            # sparse->dense fill: missing parts are PAD
            # (path_context_reader.py:190-196).
            a = pieces[0] if len(pieces) > 0 else ""
            b = pieces[1] if len(pieces) > 1 else ""
            c = pieces[2] if len(pieces) > 2 else ""
            src[i, j] = token_w2i.get(a, token_pad if a == "" else token_oov)
            pth[i, j] = path_w2i.get(b, path_pad if b == "" else path_oov)
            tgt[i, j] = token_w2i.get(c, token_pad if c == "" else token_oov)
            if keep:
                src_s[i, j], pth_s[i, j], tgt_s[i, j] = a, b, c

    # Context valid iff any part is not PAD (reference:
    # path_context_reader.py:209-214). Note that in the joined PAD/OOV
    # scheme an all-OOV context is treated as invalid — intentionally
    # identical to the reference.
    mask = ((src != token_pad) | (tgt != token_pad) | (pth != path_pad))
    context_valid_mask = mask.astype(np.float32)

    return RowBatch(
        source_token_indices=src,
        path_indices=pth,
        target_token_indices=tgt,
        context_valid_mask=context_valid_mask,
        target_index=target_index,
        example_valid=np.ones((n,), dtype=bool),
        target_strings=target_strings,
        source_strings=src_s if keep else None,
        path_strings=pth_s if keep else None,
        target_token_strings=tgt_s if keep else None,
    )


def row_filter_mask(batch: RowBatch, vocabs: Code2VecVocabs,
                    estimator_action: EstimatorAction) -> np.ndarray:
    """Vectorized reference row filter (path_context_reader.py:153-177)."""
    any_valid = batch.context_valid_mask.any(axis=1)
    if estimator_action.is_train:
        target_known = batch.target_index > vocabs.target_vocab.oov_index
        return any_valid & target_known
    return any_valid


def _select_rows(batch: RowBatch, idx: np.ndarray) -> RowBatch:
    def sel(x):
        if x is None:
            return None
        if isinstance(x, list):
            return [x[i] for i in idx]
        return x[idx]
    return RowBatch(**{f.name: sel(getattr(batch, f.name))
                       for f in dataclasses.fields(RowBatch)})


def invalid_batch(batch_size: int, max_contexts: int) -> RowBatch:
    """A batch of nothing: every row invalid, every context masked.

    Multi-host eval pads short hosts' streams with these so all hosts
    run the same number of collective eval steps
    (parallel/distributed.py lockstep_eval_stream), and the predict
    warm-up runs every compiled shape on one; index 0 is the pad row in
    every vocab, matching `_pad_rows`' fill."""
    return RowBatch(
        source_token_indices=np.zeros((batch_size, max_contexts), np.int32),
        path_indices=np.zeros((batch_size, max_contexts), np.int32),
        target_token_indices=np.zeros((batch_size, max_contexts), np.int32),
        context_valid_mask=np.zeros((batch_size, max_contexts), np.float32),
        target_index=np.zeros((batch_size,), np.int32),
        example_valid=np.zeros((batch_size,), bool),
        target_strings=[""] * batch_size,
    )


def slice_contexts(batch: RowBatch, m: int) -> RowBatch:
    """Truncate the context axis to the first `m` columns (bucketed
    predict: serving/batcher.py picks the smallest configured bucket
    that still holds every VALID context of the batch, so the slice
    never drops a real context — only padding columns)."""
    if batch.source_token_indices.shape[1] <= m:
        return batch

    def cut(x):
        return None if x is None else x[:, :m]

    return RowBatch(
        source_token_indices=cut(batch.source_token_indices),
        path_indices=cut(batch.path_indices),
        target_token_indices=cut(batch.target_token_indices),
        context_valid_mask=cut(batch.context_valid_mask),
        target_index=batch.target_index,
        example_valid=batch.example_valid,
        target_strings=batch.target_strings,
        source_strings=cut(batch.source_strings),
        path_strings=cut(batch.path_strings),
        target_token_strings=cut(batch.target_token_strings),
    )


def _pad_rows(batch: RowBatch, batch_size: int) -> RowBatch:
    """Pad with invalid rows up to `batch_size` (static shapes under jit)."""
    n = batch.target_index.shape[0]
    if n == batch_size:
        return batch
    pad = batch_size - n

    def pad_arr(x, fill=0):
        if x is None:
            return None
        if isinstance(x, list):
            return x + [""] * pad
        shape = (pad,) + x.shape[1:]
        return np.concatenate([x, np.full(shape, fill, dtype=x.dtype)], axis=0)

    out = RowBatch(
        source_token_indices=pad_arr(batch.source_token_indices),
        path_indices=pad_arr(batch.path_indices),
        target_token_indices=pad_arr(batch.target_token_indices),
        context_valid_mask=pad_arr(batch.context_valid_mask),
        target_index=pad_arr(batch.target_index),
        example_valid=np.concatenate([batch.example_valid,
                                      np.zeros((pad,), dtype=bool)]),
        target_strings=pad_arr(batch.target_strings),
        source_strings=pad_arr(batch.source_strings, fill=""),
        path_strings=pad_arr(batch.path_strings, fill=""),
        target_token_strings=pad_arr(batch.target_token_strings, fill=""),
    )
    return out


def _iter_file_lines(path: str, shard_index: int, num_shards: int,
                     buffer_size: int = 16 * 1024 * 1024) -> Iterator[str]:
    # buffer_size plays the role of the reference's CsvDataset buffer
    # (config.csv_buffer_size; reference: path_context_reader.py:122-125).
    with open(path, "r", buffering=buffer_size) as f:
        for i, line in enumerate(f):
            if num_shards > 1 and i % num_shards != shard_index:
                continue
            yield line


def _epoch_shuffle_rng(seed: int, epoch: int) -> random.Random:
    """Shuffle RNG for one absolute epoch index: a stable blake2b hash
    of (seed, epoch), NOT a tuple seed (tuple seeding routes through
    hash(), which PYTHONHASHSEED randomizes across processes). Keyed
    per epoch so a resumed run shuffles epoch e exactly like an
    uninterrupted run would — the text-reader counterpart of the packed
    dataset's elastic epoch-keyed permutation."""
    digest = hashlib.blake2b(struct.pack("<qq", seed, epoch),
                             digest_size=16).digest()
    return random.Random(int.from_bytes(digest, "little"))


class PathContextReader:
    """Streaming batched reader with reference-equivalent semantics.

    Yields `RowBatch`es of exactly `batch_size` rows. In training the final
    partial batch (across all epochs) is dropped — static shapes are worth
    far more on TPU than the reference's single ragged tail batch
    (path_context_reader.py:148 allows a ragged final batch; the deviation
    is at most one batch per run). In evaluation the tail is padded and
    marked invalid instead so every example is scored.
    """

    def __init__(self, vocabs: Code2VecVocabs, config,
                 estimator_action: EstimatorAction,
                 data_path: Optional[str] = None,
                 shard_index: int = 0, num_shards: int = 1,
                 repeat_endlessly: bool = False,
                 parse_chunk_lines: int = 4096,
                 batch_size: Optional[int] = None,
                 num_epochs: Optional[int] = None,
                 yield_epoch_markers: bool = False,
                 start_epoch: int = 0,
                 skip_rows: int = 0):
        self.vocabs = vocabs
        self.config = config
        self.estimator_action = estimator_action
        self.data_path = data_path if data_path is not None else \
            config.data_path(is_evaluating=estimator_action.is_evaluate)
        self.shard_index = shard_index
        self.num_shards = num_shards
        self.repeat_endlessly = repeat_endlessly
        self.parse_chunk_lines = parse_chunk_lines
        # per-host batch override for multi-host runs
        self.batch_size_override = batch_size
        # epoch-count override (resume trains only the remaining budget)
        self.num_epochs_override = num_epochs
        # Emit EpochEnd markers at file-pass boundaries (training only).
        # With a bounded shuffle buffer the boundary is smeared by up to
        # `shuffle_buffer_size` lines — the same smear the reference's
        # `.repeat(epochs).shuffle(buffer)` pipeline has
        # (path_context_reader.py:134-139).
        self.yield_epoch_markers = yield_epoch_markers
        # Absolute index of the first epoch this reader will stream
        # (resumed runs pass their completed-epoch count): the shuffle
        # RNG is keyed per absolute epoch, so the resumed pass orders
        # its lines exactly as an uninterrupted run would have.
        self.start_epoch = start_epoch
        # Resume data cursor (training only): drop this host's share of
        # the first epoch's already-consumed POST-FILTER rows from the
        # epoch-keyed shuffled order — the text-reader counterpart of
        # PackedDataset.iter_batches(skip_rows=...), obeying the same
        # cursor laws (the resumed stream is exactly the uninterrupted
        # stream minus its first skip_rows rows; later epochs are
        # untouched). The facade rounds the cursor down to a global
        # batch multiple before it gets here.
        self.skip_rows = skip_rows

    # ------------------------------------------------------------------

    def process_input_rows(self, lines: Sequence[str]) -> RowBatch:
        """Single-shot parse used by predict (no filtering; reference:
        path_context_reader.py:96-107)."""
        return parse_context_lines(
            lines, self.vocabs, self.config.max_contexts,
            self.estimator_action, keep_strings=True)

    def __iter__(self) -> Iterator[RowBatch]:
        batch_size = self.batch_size_override or self.config.batch_size(
            is_evaluating=self.estimator_action.is_evaluate)
        if self.estimator_action.is_train:
            if self.repeat_endlessly:
                epochs = None
            elif self.num_epochs_override is not None:
                epochs = self.num_epochs_override
            else:
                epochs = self.config.num_train_epochs
            line_iter = self._shuffled_lines(epochs)
            yield from self._batched(
                line_iter, batch_size,
                skip_rows=self.skip_rows // max(self.num_shards, 1))
            return
        line_iter = _iter_file_lines(self.data_path, self.shard_index,
                                     self.num_shards,
                                     self.config.csv_buffer_size)
        yield from self._batched(line_iter, batch_size)

    # ------------------------------------------------------------------

    def _shuffled_lines(self, epochs: Optional[int]) -> Iterator:
        """Repeat + bounded shuffle buffer (reference semantics of
        `.repeat(epochs).shuffle(buffer)`, path_context_reader.py:134-139).
        Yields an EpochEnd marker after every file pass."""
        buf: List[str] = []
        buf_size = self.config.shuffle_buffer_size
        epoch = 0
        while epochs is None or epoch < epochs:
            rng = _epoch_shuffle_rng(self.config.seed,
                                     self.start_epoch + epoch)
            for line in _iter_file_lines(self.data_path, self.shard_index,
                                         self.num_shards,
                                         self.config.csv_buffer_size):
                if len(buf) < buf_size:
                    buf.append(line)
                    continue
                j = rng.randrange(buf_size)
                out, buf[j] = buf[j], line
                yield out
            epoch += 1
            if epochs is not None and epoch == epochs:
                # drain the buffer before the final marker
                rng.shuffle(buf)
                yield from buf
                buf = []
            yield EpochEnd(epoch)

    def _parse_chunk(self, chunk: List[str]) -> RowBatch:
        with obs.span("data_parse_chunk", hist=_H_PARSE):
            raw = parse_context_lines(chunk, self.vocabs,
                                      self.config.max_contexts,
                                      self.estimator_action)
            keep = row_filter_mask(raw, self.vocabs, self.estimator_action)
            out = _select_rows(raw, np.nonzero(keep)[0])
        _C_ROWS_READ.inc(len(chunk))
        _C_ROWS_DROPPED.inc(len(chunk) - out.target_index.shape[0])
        return out

    def _parsed_chunks(self, line_iter: Iterator) -> Iterator:
        """Yield filtered RowBatch chunks (and EpochEnd markers, in order)
        with up to `config.reader_num_workers` chunks parsed concurrently —
        the role of the reference's `num_parallel_calls=reader_num_workers`
        dataset map (path_context_reader.py:141-142). The native split+
        lookup core releases the GIL, so worker threads scale the hot
        parse; EpochEnd markers act as ordering barriers."""
        import collections
        from concurrent.futures import ThreadPoolExecutor

        workers = max(1, self.config.reader_num_workers)
        chunk: List[str] = []
        with ThreadPoolExecutor(max_workers=workers) as pool:
            inflight: collections.deque = collections.deque()
            for line in line_iter:
                if isinstance(line, EpochEnd):
                    # flush the partial chunk so every line of the pass is
                    # emitted before its boundary marker
                    if chunk:
                        inflight.append(pool.submit(self._parse_chunk, chunk))
                        chunk = []
                    while inflight:
                        yield inflight.popleft().result()
                    yield line
                    continue
                chunk.append(line)
                if len(chunk) >= self.parse_chunk_lines:
                    inflight.append(pool.submit(self._parse_chunk, chunk))
                    chunk = []
                    while len(inflight) > workers:
                        yield inflight.popleft().result()
            if chunk:
                inflight.append(pool.submit(self._parse_chunk, chunk))
            while inflight:
                yield inflight.popleft().result()

    def _batched(self, line_iter: Iterator, batch_size: int,
                 skip_rows: int = 0) -> Iterator[RowBatch]:
        pending: List[RowBatch] = []
        pending_rows = 0
        # Cursor resume: discard the first `skip_rows` POST-FILTER rows
        # of the stream — they are the rows the interrupted epoch
        # already consumed, in exactly this (epoch-keyed, deterministic)
        # order. Applies to the FIRST streamed epoch only; the boundary
        # marker clears any leftover skip (a stale over-long cursor
        # must not eat into the next epoch's rows).
        remaining_skip = max(int(skip_rows), 0)

        def pop_batches() -> Iterator[RowBatch]:
            nonlocal pending, pending_rows
            while pending_rows >= batch_size:
                merged = _concat_batches(pending)
                pending = []
                pending_rows = 0
                n = merged.target_index.shape[0]
                for start in range(0, n - batch_size + 1, batch_size):
                    yield _select_rows(merged, np.arange(start, start + batch_size))
                tail = n % batch_size
                if tail:
                    pending = [_select_rows(merged, np.arange(n - tail, n))]
                    pending_rows = tail

        for item in self._parsed_chunks(line_iter):
            if isinstance(item, EpochEnd):
                remaining_skip = 0
                yield from pop_batches()
                if self.yield_epoch_markers:
                    yield item
                continue
            if remaining_skip:
                n = item.target_index.shape[0]
                if n <= remaining_skip:
                    remaining_skip -= n
                    continue
                item = _select_rows(item,
                                    np.arange(remaining_skip, n))
                remaining_skip = 0
            if item.target_index.shape[0]:
                pending.append(item)
                pending_rows += item.target_index.shape[0]
            yield from pop_batches()
        yield from pop_batches()
        if pending_rows:
            merged = _concat_batches(pending)
            if self.estimator_action.is_train:
                return  # drop ragged tail (see class docstring)
            yield _pad_rows(merged, batch_size)


def _concat_batches(batches: List[RowBatch]) -> RowBatch:
    if len(batches) == 1:
        return batches[0]

    def cat(name):
        vals = [getattr(b, name) for b in batches]
        if vals[0] is None:
            return None
        if isinstance(vals[0], list):
            return [x for v in vals for x in v]
        return np.concatenate(vals, axis=0)

    return RowBatch(**{f.name: cat(f.name) for f in dataclasses.fields(RowBatch)})
