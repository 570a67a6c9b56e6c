"""Resumable, sharded batch iterators.

Parity surface (reference /root/reference/unicore/data/iterators.py): the
``EpochBatchIterator`` contract — multi-epoch iteration with per-epoch
shuffle, per-host shards padded to equal length, mid-epoch ``state_dict``
resume with proportional position rescaling when the iterator length
changed, grad-accumulation grouping, and background prefetch with a
bottleneck warning.  Implementation original to this framework:

- No torch DataLoader: batches are fetched + collated by a thread pool
  (numpy releases the GIL for the heavy copies) and double-buffered by
  :class:`BufferedIterator`, overlapping host collation with device step
  time the way the reference's worker processes + pinned buffers do.
- ``num_shards`` = number of *hosts* (JAX processes); the per-device split
  happens later via the trainer's global-batch assembly, so there is no
  per-device iterator to desync.
- Epoch planning (shuffle + shard) is one pure function; the iterator
  classes are pull-based (``__next__``) rather than generator-wrapped.
"""

import contextlib
import itertools
import logging
import math
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from unicore_tpu.telemetry import spans

from . import data_utils

logger = logging.getLogger(__name__)

# queue sentinel: the producer thread finished cleanly
_DONE = object()

# Depth of active skip() fast-forwards on the consumer side.  While > 0,
# BufferedIterator's --data-stall-timeout budget is RELAXED (x10, below):
# in steady state the prefetch buffer amortizes per-batch latency
# variance (an occasionally-slow batch never starves the consumer, whose
# pulls return instantly from the buffer), but a tight skip loop drains
# the buffer and exposes raw per-batch production latency to the stall
# clock — a budget tuned to steady-state pulls would false-trip on a
# healthy pipeline.  The budget is relaxed rather than suspended so a
# producer that wedges outright MID-SKIP (dead mount, stuck LMDB read)
# still becomes a diagnosed DataStallError, never an unbounded hang.
# The normal budget re-arms on the first pull after the skip.
# Consumer-side only (one training thread): a plain counter suffices.
_stall_relaxed = 0
_SKIP_STALL_BUDGET_MULTIPLIER = 10.0


@contextlib.contextmanager
def relaxed_stall_watchdog():
    """Relax the BufferedIterator stall budget (x10) for the enclosed
    fast-forward (re-entrant)."""
    global _stall_relaxed
    _stall_relaxed += 1
    try:
        yield
    finally:
        _stall_relaxed -= 1


class CountingIterator(object):
    """Pull-based wrapper that tracks how many items were consumed.

    ``n`` counts consumed items (resuming iterators start it at their
    offset); ``total`` bounds the expected length.  Pulling past ``total``
    while the source still produces raises, because it means the resume
    arithmetic and the actual stream disagree.
    """

    def __init__(self, iterable, start=None, total=None):
        self.iterable = iterable
        self._itr = iter(iterable)
        self.n = getattr(iterable, "n", 0) if start is None else start
        self.total = self.n + len(iterable) if total is None else total

    def __len__(self):
        return self.total

    def __iter__(self):
        return self

    def __next__(self):
        x = next(self._itr)  # StopIteration ends the epoch
        if self.n >= self.total:
            raise RuntimeError(
                "Mismatch between actual and expected iterable length. "
                "This may be caused by resuming training from a checkpoint "
                "using a different number of workers or update_freq."
            )
        self.n += 1
        return x

    def has_next(self):
        return self.n < self.total

    def skip(self, num_to_skip):
        """Consume and discard ``num_to_skip`` items.  The data-stall
        budget is relaxed (x10) for the duration: fast-forwarding (resume
        offsets, the health sentinel's post-rewind skip-ahead) waits on
        raw per-batch production with no prefetch buffer to amortize it,
        which must not read as a stalled pipeline — while a producer that
        truly wedges mid-skip still raises instead of hanging."""
        with relaxed_stall_watchdog():
            for _ in itertools.islice(self, num_to_skip):
                pass
        return self

    def take(self, n):
        """Cap the iterator at ``n`` items, propagating to the source."""
        self.total = min(self.total, n)
        if hasattr(self.iterable, "take"):
            self.iterable.take(n)
        return self


class EpochBatchIterating(object):
    """Protocol for epoch-based iterators (resume + epoch bookkeeping)."""

    def __len__(self) -> int:
        raise NotImplementedError

    @property
    def next_epoch_idx(self):
        raise NotImplementedError

    def next_epoch_itr(self, shuffle=True, fix_batches_to_gpus=False,
                       set_dataset_epoch=True):
        raise NotImplementedError

    def end_of_epoch(self) -> bool:
        raise NotImplementedError

    @property
    def iterations_in_epoch(self) -> int:
        raise NotImplementedError

    def state_dict(self):
        raise NotImplementedError

    def load_state_dict(self, state_dict):
        raise NotImplementedError

    @property
    def first_batch(self):
        return "DUMMY"


class EpochBatchIterator(EpochBatchIterating):
    """Multi-epoch iterator over a dataset with host-sharding and resume.

    Constructor args mirror the reference (iterators.py:167-230) minus
    torch-specific knobs; ``num_shards``/``shard_id`` are the JAX process
    count/index.
    """

    def __init__(
        self,
        dataset,
        collate_fn,
        batch_sampler,
        seed=1,
        num_shards=1,
        shard_id=0,
        num_workers=0,
        epoch=1,
        buffer_size=0,
        timeout=0,
        disable_shuffling=False,
        stall_timeout=0.0,
    ):
        self.dataset = dataset
        self.collate_fn = collate_fn
        self.batch_sampler = batch_sampler
        self._frozen_batches = (
            None if callable(batch_sampler) else tuple(batch_sampler)
        )
        self.seed = seed
        self.num_shards = num_shards
        self.shard_id = shard_id
        self.num_workers = num_workers
        # capped: an oversized prefetch buffer just hoards host RAM
        self.buffer_size = min(buffer_size, 20)
        self.timeout = timeout
        self.disable_shuffling = disable_shuffling
        self.stall_timeout = stall_timeout

        self.epoch = max(epoch, 1)  # epochs are 1-based
        self.shuffle = not disable_shuffling
        self._cur_epoch_itr = None
        self._next_epoch_itr = None
        self._supports_prefetch = getattr(dataset, "supports_prefetch", False)
        # When a device prefetcher (data/prefetch.py) reads ahead of the
        # training thread, the raw iterator position runs AHEAD of what was
        # actually trained; the prefetcher installs itself here so
        # state_dict()/end_of_epoch() report the CONSUMED position and a
        # mid-epoch checkpoint resume never skips the buffered updates.
        self.position_source = None

    @property
    def frozen_batches(self):
        if self._frozen_batches is None:
            self._frozen_batches = tuple(
                self.batch_sampler(self.dataset, self.epoch)
            )
        return self._frozen_batches

    @property
    def first_batch(self):
        if len(self.frozen_batches) == 0:
            raise Exception(
                "The dataset is empty. This could indicate "
                "that all elements in the dataset have been skipped. "
                "Try increasing the max number of allowed tokens or using "
                "a larger dataset."
            )
        if getattr(self.dataset, "supports_fetch_outside_dataloader", True):
            return self.collate_fn(
                [self.dataset[i] for i in self.frozen_batches[0]]
            )
        return "DUMMY"

    def __len__(self):
        return int(math.ceil(len(self.frozen_batches) / float(self.num_shards)))

    @property
    def n(self):
        return self.iterations_in_epoch

    @property
    def next_epoch_idx(self):
        """The epoch the next ``next_epoch_itr`` call will serve."""
        if self._next_epoch_itr is not None:
            return self.epoch  # a resumed mid-epoch iterator is pending
        if self._cur_epoch_itr is not None and self.end_of_epoch():
            return self.epoch + 1
        return self.epoch

    def next_epoch_itr(self, shuffle=True, fix_batches_to_gpus=False,
                       set_dataset_epoch=True):
        if self.disable_shuffling:
            shuffle = False
        self.position_source = None  # stale prefetcher from the last epoch
        self.epoch = self.next_epoch_idx
        if set_dataset_epoch and hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(self.epoch)
        if self._next_epoch_itr is not None:
            # hand over the iterator prepared by load_state_dict
            self._cur_epoch_itr, self._next_epoch_itr = self._next_epoch_itr, None
        else:
            if callable(self.batch_sampler):
                self._frozen_batches = None  # re-plan batches for this epoch
            self._cur_epoch_itr = self._get_iterator_for_epoch(
                self.epoch, shuffle, fix_batches_to_gpus=fix_batches_to_gpus
            )
        self.shuffle = shuffle
        return self._cur_epoch_itr

    def end_of_epoch(self) -> bool:
        if self.position_source is not None:
            return self.position_source.end_of_epoch()
        return not self._cur_epoch_itr.has_next()

    @property
    def iterations_in_epoch(self):
        if self.position_source is not None:
            return self.position_source.iterations_in_epoch
        for itr in (self._cur_epoch_itr, self._next_epoch_itr):
            if itr is not None:
                return itr.n
        return 0

    def state_dict(self):
        """Position snapshot; an exhausted epoch serializes as the start of
        the next one."""
        if self.end_of_epoch():
            return {
                "epoch": self.epoch + 1,
                "iterations_in_epoch": 0,
                "shuffle": self.shuffle,
                "len": len(self),
            }
        return {
            "epoch": self.epoch,
            "iterations_in_epoch": self.iterations_in_epoch,
            "shuffle": self.shuffle,
            "len": len(self),
        }

    def load_state_dict(self, state_dict):
        self.epoch = state_dict["epoch"]
        offset = state_dict.get("iterations_in_epoch", 0)
        if offset == 0:
            self._next_epoch_itr = None
            return
        saved_len = state_dict.get("len")
        if saved_len is not None and saved_len != len(self):
            # host count or update_freq changed since the checkpoint: keep
            # the same fraction of the epoch consumed
            rescaled = int(offset * len(self) / saved_len)
            logger.info(
                "Iterator size changed (update_freq / host count?); "
                f"rescaling itr_pos {offset} -> {rescaled} for consistency"
            )
            offset = rescaled
        self._next_epoch_itr = self._get_iterator_for_epoch(
            self.epoch,
            shuffle=state_dict.get("shuffle", True),
            offset=offset,
        )
        if self._next_epoch_itr is None:
            raise RuntimeError(
                "Cannot resume training due to dataloader mismatch. You can "
                "relaunch training with `--reset-dataloader` and it should "
                "work."
            )

    # -- epoch planning ------------------------------------------------------

    def _plan_shard(self, epoch, shuffle, fix_batches_to_gpus):
        """This host's padded batch list for ``epoch``.

        Order is deterministic in (seed, epoch).  ``fix_batches_to_gpus``
        only matters for prefetch-capable datasets (matching the
        reference): the shard split happens before shuffling, so each host
        keeps (and prefetches) the same batches every epoch, and the
        shuffle is per-host-seeded.
        """

        def reshuffled(batches, seed):
            batches = list(batches)
            with data_utils.numpy_seed(seed):
                np.random.shuffle(batches)
            return batches

        fix_to_host = fix_batches_to_gpus and self._supports_prefetch
        batches = self.frozen_batches
        if shuffle and not fix_to_host:
            batches = reshuffled(batches, self.seed + epoch)
        shard = list(
            ShardedIterator(
                batches, self.num_shards, self.shard_id, fill_value=[]
            )
        )
        if self._supports_prefetch:
            self.dataset.prefetch([i for b in shard for i in b])
        if shuffle and fix_to_host:
            shard = reshuffled(shard, self.seed + epoch + self.shard_id)
        return shard

    def _get_iterator_for_epoch(self, epoch, shuffle, fix_batches_to_gpus=False,
                                offset=0):
        shard = self._plan_shard(epoch, shuffle, fix_batches_to_gpus)
        if offset > 0 and offset >= len(shard):
            return None  # position beyond the epoch: caller decides
        itr = _MapLoaderIterator(
            self.dataset,
            self.collate_fn,
            shard[offset:],
            num_workers=self.num_workers,
        )
        if self.buffer_size > 0:
            itr = BufferedIterator(
                self.buffer_size,
                itr,
                stall_timeout=self.stall_timeout,
                context=(
                    f"dataset {type(self.dataset).__name__}, epoch {epoch}, "
                    f"shard {self.shard_id}/{self.num_shards}"
                ),
            )
        return CountingIterator(itr, start=offset, total=len(shard))



class _MapLoaderIterator(object):
    """Fetch+collate loop replacing torch DataLoader.

    ``num_workers`` threads prefetch upcoming batches concurrently while
    preserving order; numpy copies release the GIL so this overlaps with the
    main thread's device dispatch.
    """

    def __init__(self, dataset, collate_fn, batch_sampler, num_workers=0):
        self.dataset = dataset
        self.collate_fn = collate_fn
        self.batch_sampler = batch_sampler
        self.num_workers = num_workers

    def __len__(self):
        return len(self.batch_sampler)

    def _load(self, batch):
        if len(batch) == 0:
            return {}
        # on the thread that builds the batch: a worker, else the buffer's
        # pump thread, else the consumer
        with spans.annotation("data_produce"):
            return self.collate_fn([self.dataset[int(i)] for i in batch])

    def __iter__(self):
        if self.num_workers <= 0:
            for batch in self.batch_sampler:
                yield self._load(batch)
            return
        # keep ~2 batches in flight per worker, yielding strictly in order
        with ThreadPoolExecutor(max_workers=self.num_workers) as pool:
            pending = []
            source = iter(self.batch_sampler)
            for batch in itertools.islice(source, self.num_workers * 2):
                pending.append(pool.submit(self._load, batch))
            while pending:
                head = pending.pop(0)
                nxt = next(source, None)
                if nxt is not None:
                    pending.append(pool.submit(self._load, nxt))
                yield head.result()


class GroupedIterator(CountingIterator):
    """Chunks of ``chunk_size`` consecutive batches — the gradient-
    accumulation grouping (reference iterators.py:406-435)."""

    def __init__(self, iterable, chunk_size):
        def chunks():
            src = iter(iterable)
            while True:
                block = list(itertools.islice(src, chunk_size))
                if not block:
                    return
                yield block

        super().__init__(
            chunks(),
            start=int(math.ceil(getattr(iterable, "n", 0) / float(chunk_size))),
            total=int(math.ceil(len(iterable) / float(chunk_size))),
        )
        self.chunk_size = chunk_size


class ShardedIterator(CountingIterator):
    """Round-robin shard of an iterable, padded with ``fill_value`` so every
    shard has the same length (reference iterators.py:438-468)."""

    def __init__(self, iterable, num_shards, shard_id, fill_value=None):
        if not 0 <= shard_id < num_shards:
            raise ValueError("shard_id must be between 0 and num_shards")
        padded_len = int(math.ceil(len(iterable) / float(num_shards)))

        def sharded():
            count = 0
            for i, item in enumerate(iterable):
                if i % num_shards == shard_id:
                    count += 1
                    yield item
            while count < padded_len:
                count += 1
                yield fill_value

        super().__init__(
            sharded(),
            start=int(math.ceil(getattr(iterable, "n", 0) / float(num_shards))),
            total=padded_len,
        )


class DataStallError(RuntimeError):
    """The prefetch producer delivered nothing for ``--data-stall-timeout``
    seconds — the data pipeline is wedged (dead filesystem mount, deadlocked
    loader, unreachable remote store), not merely slow."""


class BufferedIterator(object):
    """Producer-thread prefetch of up to ``size`` ready batches.

    The producer pushes batches (or its terminating exception) into a
    bounded queue; the consumer warns — at most every 15 minutes, and only
    after the first 5 minutes of a run — when the buffer runs near empty,
    which indicates the data pipeline can't keep up with the device
    (reference iterators.py:471-554's bottleneck warning).

    ``stall_timeout`` (seconds, 0 = off; ``--data-stall-timeout``)
    escalates starvation into a diagnosis: when the producer delivers
    NOTHING for that long, ``__next__`` raises :class:`DataStallError`
    naming the dataset/epoch ``context`` and the position instead of
    warning forever while the run silently makes no progress.
    """

    _RUNTIME_BEFORE_WARN = 5 * 60
    _WARN_EVERY = 15 * 60

    def __init__(self, size, iterable, stall_timeout=0.0, context=None):
        self._queue = queue.Queue(size)
        self._iterable = iterable
        self._producer = None
        self._exhausted = False
        self._started = time.time()
        self._last_warn = None
        self._stall_timeout = float(stall_timeout or 0.0)
        self._context = context
        self._delivered = 0
        self.total = len(iterable)

    def _start_producer(self):
        def pump():
            try:
                sent = 0
                for item in self._iterable:
                    self._queue.put(item)
                    sent += 1
                    if self.total is not None and sent >= self.total:
                        break
                self._queue.put(_DONE)
            except Exception as e:
                self._queue.put(e)

        self._producer = threading.Thread(
            target=pump, name="buffered-iterator-producer", daemon=True
        )
        self._producer.start()

    def __len__(self):
        return self.total

    def __iter__(self):
        return self

    def take(self, n):
        self.total = min(self.total, n)
        if hasattr(self._iterable, "take"):
            self._iterable.take(n)
        return self

    def _maybe_warn_starved(self):
        if self._queue.qsize() >= min(2, max(1, self._queue.maxsize // 2)):
            return
        now = time.time()
        if now - self._started <= self._RUNTIME_BEFORE_WARN:
            return
        if self._last_warn is not None and now - self._last_warn <= self._WARN_EVERY:
            return
        logger.debug(
            "Data loading buffer is empty or nearly empty. This may "
            "indicate a data loading bottleneck, and increasing the "
            "number of workers (--num-workers) may help."
        )
        self._last_warn = now

    def _get_with_stall_watchdog(self, budget):
        """Block for the next item, but never past ``budget`` seconds of
        total producer silence."""
        deadline = time.time() + budget
        while True:
            remaining = deadline - time.time()
            if remaining <= 0:
                where = f" of {self._context}" if self._context else ""
                alive = (
                    self._producer is not None and self._producer.is_alive()
                )
                relaxed = (
                    " (relaxed x10 budget: this happened DURING a skip "
                    "fast-forward)"
                    if budget > self._stall_timeout
                    else ""
                )
                from unicore_tpu import telemetry

                telemetry.emit(
                    "data-stall", budget=round(budget, 1),
                    position=self._delivered, total=self.total,
                    context=str(self._context) if self._context else None,
                    producer_alive=alive,
                )
                raise DataStallError(
                    f"data pipeline stalled: the prefetch producer delivered "
                    f"nothing for {budget:.0f}s "
                    f"(--data-stall-timeout){relaxed} at position "
                    f"{self._delivered}/{self.total}{where}; producer thread "
                    f"{'is still alive but wedged' if alive else 'has DIED'}."
                    "  Check the dataset storage (mount, LMDB file, remote "
                    "store) — a merely-slow pipeline logs the starvation "
                    "warning instead of tripping this."
                )
            try:
                return self._queue.get(True, timeout=min(5.0, remaining))
            except queue.Empty:
                continue

    def __next__(self):
        # exhaustion must be sticky: a grouped/sliced consumer pulls once
        # more after the final partial chunk, and blocking on the drained
        # queue then would deadlock the epoch boundary
        if self._exhausted:
            raise StopIteration()
        if self._producer is None:
            self._start_producer()
        self._maybe_warn_starved()
        # ``depth``: the ready batches found waiting, i.e. the room the
        # data layer has left (0 = the consumer is about to block)
        with spans.annotation("data_next", depth=self._queue.qsize()):
            if self._stall_timeout > 0:
                budget = self._stall_timeout * (
                    _SKIP_STALL_BUDGET_MULTIPLIER if _stall_relaxed else 1.0
                )
                item = self._get_with_stall_watchdog(budget)
            else:
                item = self._queue.get(True)
        if isinstance(item, Exception):
            raise item
        if item is _DONE:
            self._exhausted = True
            raise StopIteration()
        self._delivered += 1
        return item
