"""The one feed path: a background thread reads and packs batches into a
bounded queue while the device steps, and the consumer transfers each as
it takes it (the reference gets this from tf.data's internal C++
threads, path_context_reader.py:150). The trainer and the evaluator both
iterate a `DevicePrefetcher`; its contract is pinned in
tests/test_prefetch.py."""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional

from code2vec_tpu import obs
from code2vec_tpu.data.reader import EpochEnd
from code2vec_tpu.training.step import (
    device_put_batch, fused_path_applies, pack_batch_host,
)

# Module-scope handles: these fire once per batch on the worker and
# consumer threads (registry metrics are thread-safe).
_H_PACK = obs.histogram(
    "prefetch_pack_seconds",
    "host packing of one batch's fused transfer buffer (worker thread)")
_H_READ = obs.histogram(
    "prefetch_read_seconds",
    "the worker's next() on the reader for one batch: packed-corpus "
    "slice, shuffle, filter (worker thread)")
_H_BUSY = obs.histogram(
    "prefetch_busy_seconds",
    "read + pack (+ the trainer's per-batch counters) of one batch: "
    "what the one worker thread does apart "
    "from waiting on a full queue; its sum over wall time is the "
    "feed's busy share (at 1 the step starts to wait)")
_H_DEVICE_PUT = obs.histogram(
    "prefetch_device_put_seconds",
    "host-side cost of dispatching one batch's device transfer "
    "(consumer thread; the transfer itself is async)")


class DevicePrefetcher:
    """Wraps a RowBatch iterable; yields (device_arrays, host_batch) with
    up to `depth` batches prepared ahead of consumption. EpochEnd markers
    from the underlying iterable are passed through in order (bare, not
    wrapped in a tuple).

    Division of labor: the worker thread runs only HOST work — iterating
    the reader (parse/filter) and packing the fused transfer buffer
    (pack_batch_host, pure numpy). The device transfer + jitted unpack
    happen on the consumer thread at yield time; transfers dispatch
    asynchronously, so the consumer is not stalled — and keeping every
    runtime interaction on one thread avoids serializing the consumer's
    step dispatches against a second thread's transfer calls inside the
    runtime client (2-3x worse real-data throughput with device_put on
    the worker thread in a pre-round measurement; not re-measured on
    the current machine)."""

    _SENTINEL = object()

    def __init__(self, batches: Iterable, mesh, depth: int = 4,
                 keep_host_batch: bool = False,
                 observe: Optional[Callable] = None):
        self.batches = batches
        # called with every host batch on the worker thread (counters
        # that read the batch itself); its time counts as busy
        self.observe = observe
        self.mesh = mesh
        self.depth = max(1, depth)
        self.keep_host_batch = keep_host_batch
        self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)

    def _put(self, item) -> bool:
        """Bounded put that gives up when the consumer has stopped, so an
        abandoned iteration never wedges this thread on a full queue
        (pinning the upstream reader's files for the process lifetime)."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            pack = fused_path_applies(self.mesh)
            batches = iter(self.batches)
            while True:
                try:
                    with obs.span("prefetch_read") as read:
                        batch = next(batches)
                except StopIteration:
                    break
                if isinstance(batch, EpochEnd):
                    item = batch
                else:
                    # an epoch marker's read is no batch's: only these
                    # count (one observation a batch)
                    _H_READ.observe(read.seconds)
                    busy = read.seconds
                    if self.observe is not None:
                        with obs.span("prefetch_observe") as observing:
                            self.observe(batch)
                        busy += observing.seconds
                    packed = None
                    if pack:
                        # the packed buffer is all the consumer needs
                        # unless it asked for the host batch too —
                        # don't pin both
                        with obs.span("prefetch_pack",
                                      hist=_H_PACK) as packing:
                            packed = pack_batch_host(batch)
                        busy += packing.seconds
                    _H_BUSY.observe(busy)
                    item = (batch if self.keep_host_batch or not pack
                            else None, packed)
                if not self._put(item):
                    return
        except BaseException as e:  # propagate to consumer
            self._error = e
        finally:
            self._put(self._SENTINEL)

    def __iter__(self) -> Iterator:
        self._thread.start()
        try:
            while True:
                item = self._queue.get()
                if item is self._SENTINEL:
                    if self._error is not None:
                        raise self._error
                    return
                if isinstance(item, EpochEnd):
                    yield item
                    continue
                batch, packed = item
                with obs.span("prefetch_device_put", hist=_H_DEVICE_PUT):
                    arrays = device_put_batch(batch, self.mesh,
                                              packed=packed)
                yield arrays, (batch if self.keep_host_batch else None)
        finally:
            # consumer stopped (normally, by exception, or abandoned):
            # release the worker so it can exit and drop the reader
            self._stop.set()
