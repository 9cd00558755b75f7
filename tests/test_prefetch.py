"""The one feed path's contract (utils/prefetch.py `DevicePrefetcher`):
what the trainer and the evaluator rely on when they iterate it. The
worker's timing histograms are pinned in tests/test_obs.py
(`test_prefetch_worker_times_its_read_and_its_busy_share`)."""

import threading
import time

import numpy as np
import pytest

from code2vec_tpu.data.reader import EpochEnd, RowBatch
from code2vec_tpu.utils.prefetch import DevicePrefetcher


def _batch(tag, n=2, m=4):
    """A batch whose every label is `tag`: it says which one came out."""
    return RowBatch(
        source_token_indices=np.ones((n, m), np.int32),
        path_indices=np.ones((n, m), np.int32),
        target_token_indices=np.ones((n, m), np.int32),
        context_valid_mask=np.ones((n, m), np.float32),
        target_index=np.full((n,), tag, np.int32),
        example_valid=np.ones((n,), bool))


def _tag(item):
    arrays, _ = item
    return int(np.asarray(arrays[4])[0])


def _wait_for(condition, seconds=1.0):
    deadline = time.monotonic() + seconds
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.005)
    return condition()


def test_batches_come_in_order_with_epoch_markers_in_place():
    stream = [_batch(0), _batch(1), EpochEnd(1), _batch(2), EpochEnd(2)]
    got = list(DevicePrefetcher(iter(stream), mesh=None, depth=2))
    assert [g if isinstance(g, EpochEnd) else _tag(g) for g in got] == [
        0, 1, EpochEnd(1), 2, EpochEnd(2)]
    arrays, _ = got[0]
    assert len(arrays) == 6             # the model's six arrays, on device
    assert arrays[3].dtype == np.float32 and arrays[5].dtype == bool


def test_a_readers_exception_reaches_the_consumer_after_its_batches():
    def reader():
        yield _batch(0)
        yield _batch(1)
        raise OSError("shard went away")

    got = []
    with pytest.raises(OSError, match="shard went away"):
        for item in DevicePrefetcher(reader(), mesh=None, depth=4):
            got.append(_tag(item))
    assert got == [0, 1]


def test_an_abandoned_iteration_releases_the_worker():
    """The consumer stops after one batch with the queue full: the
    worker's bounded put gives up and the thread ends within a second
    (it no longer holds the reader)."""
    def endless():
        n = 0
        while True:
            yield _batch(n)
            n += 1

    prefetcher = DevicePrefetcher(endless(), mesh=None, depth=1)
    stream = iter(prefetcher)
    assert _tag(next(stream)) == 0
    assert _wait_for(prefetcher._queue.full)
    stream.close()                      # what a `break` does
    prefetcher._thread.join(timeout=1.0)
    assert not prefetcher._thread.is_alive()
    assert prefetcher._put(_batch(99)) is False


@pytest.mark.parametrize("keep", [False, True])
def test_the_host_batch_comes_along_only_when_asked_for(keep):
    batches = [_batch(0), _batch(1)]
    got = list(DevicePrefetcher(iter(batches), mesh=None,
                                keep_host_batch=keep))
    assert [_tag(g) for g in got] == [0, 1]
    hosts = [host for _, host in got]
    if keep:
        assert hosts[0] is batches[0] and hosts[1] is batches[1]
    else:
        assert hosts == [None, None]


def test_observe_sees_every_batch_once_and_no_epoch_marker():
    seen = []
    stream = [_batch(0), EpochEnd(1), _batch(1), _batch(2), EpochEnd(2)]
    got = list(DevicePrefetcher(
        iter(stream), mesh=None,
        observe=lambda batch: seen.append((threading.current_thread(),
                                           int(batch.target_index[0])))))
    assert len(got) == 5
    assert [tag for _, tag in seen] == [0, 1, 2]
    assert all(thread is not threading.current_thread()
               for thread, _ in seen)   # on the worker, not the consumer


@pytest.mark.parametrize("depth", [1, 3])
def test_the_worker_runs_ahead_by_depth_and_no_further(depth):
    """`depth` batches wait in the queue and one more in the worker's
    hands, blocked on the full queue: the reader is never asked for
    more than that beyond what the consumer took."""
    read = []

    def reader():
        for n in range(depth + 6):
            read.append(n)
            yield _batch(n)

    taken = 0
    for item in DevicePrefetcher(reader(), mesh=None, depth=depth):
        assert _tag(item) == taken
        taken += 1
        ahead = min(taken + depth + 1, depth + 6)
        assert _wait_for(lambda: len(read) == ahead)
        time.sleep(0.02)                # a stalled worker stays stalled
        assert len(read) == ahead
    assert taken == depth + 6
