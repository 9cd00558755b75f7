"""Evaluation loop: device top-k + host metrics + per-example audit log.

reference flow: tensorflow_model.py:114-194 — iterate the eval reader,
fetch (top_words, scores, original_names, code_vectors), update topk/
subtoken metrics, append per-example outcomes to `log.txt`, optionally
dump code vectors to `<test>.vectors`.

TPU redesign: the jitted eval step returns top-k *indices* over the
(possibly row-sharded) logits; strings only exist host-side. Batches are
padded to fixed size with invalid rows (reader) and masked here.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from code2vec_tpu import obs
from code2vec_tpu.evaluation.metrics import (
    ModelEvaluationResults, SubtokensEvaluationMetric, TargetWordTables,
    TopKAccuracyEvaluationMetric, batch_prediction_info,
)
from code2vec_tpu.training.step import device_put_batch
from code2vec_tpu.utils.device import describe_devices


class Evaluator:
    def __init__(self, config, vocabs, eval_step: Callable, mesh=None,
                 log_path: str = "log.txt"):
        self.config = config
        self.vocabs = vocabs
        self.eval_step = eval_step
        self.mesh = mesh
        self.log_path = log_path
        self.tables = TargetWordTables(vocabs.target_vocab)

    def _host_rows(self, arr) -> np.ndarray:
        """Rows of a data-sharded eval output that THIS host computed.
        Single-process: the whole array. Multi-host: the eval step's
        outputs are global arrays sharded over `data`; each process can
        only address (and only needs) the rows of its own data shard —
        the same rows it contributed via `global_batch_arrays`."""
        if jax.process_count() == 1 or not hasattr(arr, "addressable_shards"):
            return np.asarray(arr)
        blocks = {}  # row-start -> shard data (dedup tp/cp replicas)
        for s in arr.addressable_shards:
            blocks.setdefault(s.index[0].start or 0, s.data)
        return np.concatenate(
            [np.asarray(blocks[k]) for k in sorted(blocks)], axis=0)

    def evaluate(self, params, batches: Iterable,
                 code_vectors_path: Optional[str] = None,
                 code_vectors_sink: Optional[Callable] = None,
                 prefetch: bool = True) -> ModelEvaluationResults:
        """Pipelined evaluation: a worker thread parses/packs batches
        (DevicePrefetcher, same division of labor as the trainer), and
        the host-side metric update for batch N runs while the device
        executes batch N+1 — the first host fetch of N's outputs then
        mostly finds them already computed. `prefetch=False` keeps the
        strictly serial order (parse -> transfer -> step -> metrics per
        batch); both paths produce identical results (pinned by
        tests), the pipelined one just overlaps host and device work."""
        with obs.span("evaluate",
                      hist=obs.histogram("eval_seconds",
                                         "one full evaluation pass")):
            results = self._evaluate_inner(params, batches,
                                           code_vectors_path,
                                           code_vectors_sink, prefetch)
        obs.counter("eval_runs_total", "completed evaluation passes").inc()
        self.config.log(f"Evaluation pass done; {describe_devices(params)}")
        # Last-eval quality gauges: the same scalars the TB eval/ tags
        # carry, visible to a Prometheus scrape between TB flushes.
        for name, value in results.tb_scalars():
            obs.gauge(f"eval_{name}", "latest evaluation result").set(value)
        return results

    def _evaluate_inner(self, params, batches: Iterable,
                        code_vectors_path: Optional[str],
                        code_vectors_sink: Optional[Callable],
                        prefetch: bool) -> ModelEvaluationResults:
        config = self.config
        topk_metric = TopKAccuracyEvaluationMetric(
            config.top_k_words_considered_during_prediction, self.tables)
        subtoken_metric = SubtokensEvaluationMetric(self.tables)
        loss_sum = 0.0
        # CE is summed on device over rows with a real in-vocab target
        # (the eval step excludes OOV/PAD labels); this mirrors that mask
        # host-side so the mean divides by the same row count.
        oov_floor = max(self.vocabs.target_vocab.pad_index,
                        self.vocabs.target_vocab.oov_index)
        loss_rows = 0
        total_predictions = 0
        total_batches = 0
        start_time = time.time()

        vectors_file = open(code_vectors_path, "w") if code_vectors_path else None
        log_file = open(self.log_path, "w") if self.log_path else None

        def consume(batch, out):
            """Host-side bookkeeping for one completed step's outputs."""
            nonlocal loss_sum, loss_rows, total_predictions, total_batches
            topk_indices = self._host_rows(out.topk_indices)
            valid = np.asarray(batch.example_valid)
            names = batch.target_strings
            if names is None:
                # Fall back to vocab words (train-filtered data only has
                # in-vocab targets, so this is lossless there).
                names = [self.vocabs.target_vocab.lookup_word(int(i))
                         for i in batch.target_index]
            names = [n for n, v in zip(names, valid) if v]
            rows = topk_indices[valid]
            # one vectorized pass shared by both metrics and the log
            info = batch_prediction_info(self.tables, names, rows)
            topk_metric.update_batch_from_indices(names, rows, info=info)
            subtoken_metric.update_batch_from_indices(names, rows, info=info)
            loss_sum += float(out.loss_sum)
            loss_rows += int(np.sum(
                valid & (np.asarray(batch.target_index) > oov_floor)))
            total_predictions += len(names)
            total_batches += 1
            if log_file is not None:
                self._log_predictions(log_file, names, info)
            if vectors_file is not None:
                code_vectors = self._host_rows(out.code_vectors)[valid]
                for vec in code_vectors:
                    vectors_file.write(" ".join(map(str, vec)) + "\n")
            if code_vectors_sink is not None:
                # structured export (retrieval vector store): valid
                # rows' vectors + their method ids, in eval order
                code_vectors_sink(
                    self._host_rows(out.code_vectors)[valid], names)
            if total_batches % config.num_batches_to_log_progress == 0:
                elapsed = time.time() - start_time
                config.log(f"Evaluated {total_predictions} examples... "
                           f"({total_predictions / max(elapsed, 1e-9):.0f} "
                           f"samples/sec)")

        try:
            if prefetch:
                from code2vec_tpu.utils.prefetch import DevicePrefetcher
                stream = DevicePrefetcher(batches, self.mesh,
                                          depth=config.prefetch_batches,
                                          keep_host_batch=True)
                pending = None
                for arrays, batch in stream:
                    out = self.eval_step(params, *arrays)  # async dispatch
                    if pending is not None:
                        consume(*pending)  # overlaps the in-flight step
                    pending = (batch, out)
                if pending is not None:
                    consume(*pending)
            else:
                for batch in batches:
                    arrays = device_put_batch(batch, self.mesh)
                    out = self.eval_step(params, *arrays)
                    consume(batch, out)
            if log_file is not None:
                log_file.write(str(topk_metric.topk_correct_predictions) + "\n")
        finally:
            if vectors_file is not None:
                vectors_file.close()
            if log_file is not None:
                log_file.close()

        # Multi-host: each process scored its own rows of each global
        # batch; sum the raw counters across hosts so the reported metrics
        # are global ratios of global counts (parallel/distributed.py).
        # `loss_sum` is NOT reduced: the eval step psums CE over the whole
        # global batch and replicates it, so every host already holds the
        # global total. `loss_rows` is a host-local count, so it is.
        if jax.process_count() > 1:
            from code2vec_tpu.parallel import distributed
            packed = np.concatenate([
                [loss_rows,
                 topk_metric.nr_predictions,
                 subtoken_metric.nr_true_positives,
                 subtoken_metric.nr_false_positives,
                 subtoken_metric.nr_false_negatives],
                topk_metric.nr_correct_predictions,
            ])
            packed = distributed.allreduce_host_scalars(packed)
            (loss_rows, topk_metric.nr_predictions,
             subtoken_metric.nr_true_positives,
             subtoken_metric.nr_false_positives,
             subtoken_metric.nr_false_negatives) = packed[:5]
            topk_metric.nr_correct_predictions = packed[5:]

        obs.counter("eval_examples_total",
                    "examples scored across evaluation passes "
                    "(host-local rows)").inc(total_predictions)
        return ModelEvaluationResults(
            topk_acc=topk_metric.topk_correct_predictions,
            subtoken_precision=subtoken_metric.precision,
            subtoken_recall=subtoken_metric.recall,
            subtoken_f1=subtoken_metric.f1,
            loss=loss_sum / max(loss_rows, 1))

    def _log_predictions(self, log_file, names, info) -> None:
        # reference: tensorflow_model.py:410-421
        for name, rank, idx in zip(names, info.match_rank, info.match_idx):
            if rank >= 0:
                if rank == 0:
                    log_file.write(f"Original: {name}, predicted 1st: "
                                   f"{self.tables.word(int(idx))}\n")
                else:
                    log_file.write("\t\t predicted correctly at rank: "
                                   f"{rank + 1}\n")
            else:
                log_file.write(f"No results for predicting: {name}\n")
