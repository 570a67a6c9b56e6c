"""Double-buffered device prefetcher: hide the host from the hot loop.

The synchronous trainer pays a serial host tax on the training thread for
every update: dtype narrowing, ``np.stack`` over the micro-batches, a
pickled slot-plan all-gather, and the blocking host->device transfer —
all while the devices sit idle between dispatches.  This module overlaps
that work with device compute: while update N runs, a producer thread has
already planned, narrowed, stacked, and transferred update N+1, so the
training thread's per-update work is exactly one jitted dispatch.

Correctness constraints (and how they are met):

- **Collective/program ordering.**  In a multi-process run every host
  must enqueue the same device computations in the same order.  The
  producer thread therefore never issues a device collective: the
  slot-plan exchange runs over the *distributed coordination service's
  key-value store* (a TCP side channel keyed by ``(epoch, update)``), so
  it cannot interleave with the training thread's jit dispatches,
  fingerprint gathers, or checkpoint barriers.  Producer-side device
  work is limited to per-host transfers (``device_put`` /
  ``make_array_from_process_local_data``), which involve no cross-host
  matching.
- **Plan semantics in update order.**  The plan (slot modes), the
  batch-geometry signatures, and the piggybacked graceful-stop flags are
  *carried on each item* and noted into the consistency guard by the
  training thread at consumption time — so the guard's fingerprint and
  the collectively-agreed stop decision see exactly the same values in
  exactly the same update order as the synchronous path (bit-for-bit).
  One semantic widening: stop flags are sampled when the producer BUILDS
  an item, so a SIGTERM lands in the agreed decision up to queue depth +
  1 updates late (synchronous: at most 1) — still on every host at the
  same update.
- **Deterministic fallback.**  Whether an update is prefetched or falls
  back to the synchronous path is a pure function of host-identical
  state: the item index (the first item of every epoch is synchronous —
  it initializes TrainState and caches the globally-consistent dummy
  batch on the training thread) and the agreed slot modes (any
  ``gather``/``dummy`` slot means every host falls back together).
  ``--fault-inject`` geometry/seed perturbation disables prefetch
  outright (the chaos hooks must see raw host batches).

Single-host runs skip the plan exchange entirely; the producer just
narrows/stacks/transfers.
"""

import base64
import itertools
import logging
import pickle
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, List, Optional

logger = logging.getLogger(__name__)

# queue sentinel: the producer finished the epoch cleanly
_DONE = object()

# drop our own plan keys this many updates behind the producer: any peer
# lagging further has long since stalled its own pipeline (queue depth
# bounds host skew), and its blocking get then times out with a diagnosis
# instead of reading a deleted key
_KV_RETAIN_UPDATES = 256


class PrefetchError(RuntimeError):
    """The producer thread died or a plan exchange timed out."""


@dataclass
class PreparedUpdate:
    """One fully device-resident update, built off the training thread.

    ``data`` depends on ``kind``: the prepared global batch (``single``),
    the stacked micro-batch tree for the fused scan (``scan``), or the
    list of per-slot ``(prepared batch, weight)`` pairs (``micro``)."""

    kind: str
    data: Any
    weight: float
    raw_samples: List[Any]  # host refs: NaN localization / OOM report
    sigs: Any
    modes: Optional[List[str]]
    stop_flags: Optional[List[Any]]
    seq: int
    n_batches: int
    prefetch_wall: float = 0.0


@dataclass
class RawUpdate:
    """Conservative fallback: raw micro-batches plus the already-agreed
    plan (when multi-host), consumed by the trainer's synchronous path."""

    samples: List[Any]
    sigs: Any
    modes: Optional[List[str]]
    stop_flags: Optional[List[Any]]
    seq: int
    n_batches: int
    reason: str = ""


@dataclass
class _ProducerError:
    exc: BaseException
    tb: str = ""


class _ProducerStopped(Exception):
    """Internal: close() asked the producer to exit while it waited on a
    peer's plan key — a clean shutdown, not an error."""


def plan_slot_modes(all_sigs, data_size: int, nproc: int) -> List[str]:
    """Pure slot-mode agreement from every host's batch signatures.

    Shared by the synchronous plan (psum all-gather) and the prefetcher's
    KV exchange so both paths decide layouts identically:

    - ``shard``:  every host holds a same-shaped batch whose rows divide
      its local data-shard count — each host contributes exactly its rows
      to ONE global P('data') array;
    - ``gather``: shapes diverge / some hosts empty / rows not divisible
      (epoch tails) — hosts exchange rows and replicate the concatenation;
    - ``dummy``:  no host has data (GroupedIterator padding) — weight-0
      step on the cached, globally-consistent dummy batch.
    """
    local_shards = data_size // nproc if data_size % nproc == 0 else 0
    n_slots = len(all_sigs[0]) if all_sigs else 0
    modes = []
    for i in range(n_slots):
        slot = [host_sigs[i] for host_sigs in all_sigs]
        if all(s is None for s in slot):
            modes.append("dummy")
        elif (
            local_shards > 0
            and all(s == slot[0] for s in slot)
            and slot[0] not in (None, "unshardable")
            and all(shape[0] % local_shards == 0 for shape, _ in slot[0][1])
        ):
            modes.append("shard")
        else:
            modes.append("gather")
    return modes


def kv_client():
    """The distributed coordination service's KV store client, or None
    when this process isn't part of a ``jax.distributed`` cluster.  The
    TCP side channel lets the producer thread exchange slot plans without
    issuing device collectives (which must stay in training-thread
    program order)."""
    from unicore_tpu.utils import retry

    return retry.coordination_client()


def _encode(payload) -> str:
    return base64.b64encode(pickle.dumps(payload)).decode("ascii")


def _decode(s):
    return pickle.loads(base64.b64decode(s.encode("ascii")))


class DevicePrefetcher:
    """Wraps a :class:`~unicore_tpu.data.iterators.GroupedIterator` of
    update chunks and yields :class:`PreparedUpdate` / :class:`RawUpdate`
    items built by a producer thread, ``depth`` updates ahead.

    Exposes the iterator surface the training loop needs (``has_next``,
    ``skip``, ``take``, ``n``) and, once :meth:`attach_epoch_itr` is
    called, overrides the epoch iterator's position bookkeeping so
    mid-epoch checkpoints record the *consumed* position, not the
    producer's read-ahead position (resume must not skip the buffered
    updates).
    """

    def __init__(self, trainer, grouped_itr, epoch: int = 1, depth: int = 2,
                 plan_timeout: float = 600.0):
        import jax

        self.trainer = trainer
        self._inner = grouped_itr
        self._epoch = int(epoch)
        self._queue: "queue.Queue" = queue.Queue(max(1, depth))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._plan_timeout = float(plan_timeout or 600.0)

        self._nproc = jax.process_count()
        self._rank = jax.process_index()
        from unicore_tpu.parallel import dp_world_size

        self._data_size = dp_world_size(trainer.mesh)
        self._client = kv_client() if self._nproc > 1 else None

        # item sequence numbers key the KV plan exchange; they start at the
        # grouped iterator's (deterministic, host-identical) resume offset
        self._first_seq = int(getattr(grouped_itr, "n", 0))
        self._next_seq = self._first_seq
        self._expect = int(len(grouped_itr)) - self._first_seq

        self._consumed_items = 0
        self._consumed_batches = 0
        self._base_iterations = 0
        self._finished = False
        self._epoch_itr = None

        # consumption-side stats (read by the trainer at flush)
        self.prefetched_updates = 0
        self.fallback_updates = 0

    # -- lifecycle -------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._produce, name="device-prefetcher", daemon=True
        )
        self._thread.start()
        return self

    def close(self):
        """Stop the producer and detach; safe to call twice.  Pending
        prepared items are dropped (the data they hold is re-read from
        the checkpointed position on resume)."""
        self._stop.set()
        # drain so a producer blocked on a full queue wakes up
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            if self._thread.is_alive():
                logger.warning("device prefetcher did not stop within 30s")
        self._finished = True
        if self._epoch_itr is not None:
            if getattr(self._epoch_itr, "position_source", None) is self:
                self._epoch_itr.position_source = None
            self._epoch_itr = None

    # -- epoch-iterator position override --------------------------------

    def attach_epoch_itr(self, epoch_itr):
        """Report the CONSUMED data position to ``epoch_itr.state_dict`` —
        without this, a mid-epoch checkpoint would record the producer's
        read-ahead position and resume would silently skip up to ``depth``
        updates of data."""
        self._base_iterations = int(epoch_itr.iterations_in_epoch)
        self._epoch_itr = epoch_itr
        epoch_itr.position_source = self

    @property
    def iterations_in_epoch(self) -> int:
        return self._base_iterations + self._consumed_batches

    def end_of_epoch(self) -> bool:
        return not self.has_next()

    # -- iterator surface -------------------------------------------------

    @property
    def n(self) -> int:
        return self._first_seq + self._consumed_items

    def __len__(self):
        return self._first_seq + self._expect

    def __iter__(self):
        return self

    def has_next(self) -> bool:
        return not self._finished and self._consumed_items < self._expect

    def skip(self, num_to_skip):
        """Consume and discard ``num_to_skip`` update chunks (the health
        sentinel's post-rewind fast-forward).  Items are pulled through the
        queue so producer/consumer ordering stays intact; the data-stall
        budget is relaxed like :meth:`CountingIterator.skip`."""
        from unicore_tpu.data.iterators import relaxed_stall_watchdog

        with relaxed_stall_watchdog():
            for _ in itertools.islice(self, num_to_skip):
                pass
        return self

    def take(self, n):
        self._expect = min(self._expect, max(0, n - self._first_seq))
        # propagate to the source (the CountingIterator.take contract) so
        # the producer doesn't keep planning/transferring updates past the
        # cap until the queue backpressures
        if hasattr(self._inner, "take"):
            self._inner.take(n)
        return self

    def __next__(self):
        if self._finished or self._consumed_items >= self._expect:
            self._finished = True
            raise StopIteration()
        while True:
            try:
                item = self._queue.get(True, timeout=5.0)
                break
            except queue.Empty:
                if self._thread is not None and not self._thread.is_alive():
                    self._finished = True
                    raise PrefetchError(
                        "device prefetcher producer thread died without "
                        "delivering an item or an error"
                    )
        if item is _DONE:
            self._finished = True
            raise StopIteration()
        if isinstance(item, _ProducerError):
            self._finished = True
            if item.tb:
                # the re-raise below roots the traceback at this frame;
                # the frames that actually failed live on the producer side
                logger.error(
                    "device prefetcher producer thread failed:\n%s", item.tb
                )
            raise item.exc
        self._consumed_items += 1
        self._consumed_batches += item.n_batches
        if isinstance(item, PreparedUpdate):
            self.prefetched_updates += 1
        else:
            self.fallback_updates += 1
        return item

    # -- producer ---------------------------------------------------------

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            for samples in self._inner:
                if self._stop.is_set():
                    return
                item = self._build_item(samples, self._next_seq)
                self._next_seq += 1
                if not self._put(item):
                    return
            self._put(_DONE)
        except _ProducerStopped:
            return
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            import traceback

            self._put(_ProducerError(e, traceback.format_exc()))

    def _build_item(self, samples, seq: int):
        trainer = self.trainer
        samples = list(samples)
        n_batches = len(samples)
        sigs = [trainer._local_sig(s) for s in samples]
        modes = None
        flags = None
        if self._nproc > 1:
            rows = self._exchange_plan(seq, sigs)
            all_sigs = [row[0] for row in rows]
            flags = [row[1] for row in rows]
            modes = plan_slot_modes(all_sigs, self._data_size, self._nproc)

        # fallback decisions must be a pure function of host-identical
        # state (item index; the agreed modes) — a host-local decision
        # would desync which collectives each host runs
        reason = None
        if seq == self._first_seq:
            reason = "first update (TrainState init + dummy-batch caching)"
        elif modes is not None and any(m != "shard" for m in modes):
            reason = f"non-shard slot in agreed plan {modes}"
        elif modes is None and any(trainer._is_empty(s) for s in samples):
            reason = "empty micro-slot (single-host tail)"
        if reason is not None:
            return RawUpdate(
                samples=samples, sigs=sigs, modes=modes, stop_flags=flags,
                seq=seq, n_batches=n_batches, reason=reason,
            )
        # timer starts AFTER the plan exchange: prefetch_wall means "producer
        # build time" (narrow/stack/transfer), not "how long a peer made us
        # wait" — operators tune --num-workers off this number
        t0 = time.perf_counter()
        kind, data, weight = trainer.prepare_prefetched(samples, modes, sigs)
        return PreparedUpdate(
            kind=kind, data=data, weight=weight, raw_samples=samples,
            sigs=sigs, modes=modes, stop_flags=flags, seq=seq,
            n_batches=n_batches, prefetch_wall=time.perf_counter() - t0,
        )

    # -- KV-store slot-plan exchange --------------------------------------

    # poll interval for the interruptible KV wait: close() must never sit
    # behind a peer's full plan timeout (default 600s)
    _KV_POLL_S = 2.0

    def _kv_key(self, seq: int, rank: int) -> str:
        return f"unicore_tpu/prefetch_plan/{self._epoch}/{seq}/{rank}"

    def _abort_if_closing(self) -> None:
        if self._stop.is_set():
            raise _ProducerStopped()

    def _blocking_get(self, key: str) -> str:
        """Deadline-bounded KV wait through the shared retry surface
        (utils/retry.py — the ``unguarded-kv-wait`` lint rule pins all
        blocking KV gets there).  Polled in short slices so the producer
        observes ``close()`` within ``_KV_POLL_S`` instead of blocking
        out the whole plan timeout inside the client; while our own queue
        is full the deadline is HELD — the consumer is paused (mid-epoch
        validation, a checkpoint write, a long compile), peers pause with
        it, and a global pause must not be charged against the peer
        budget.  A genuinely dead peer still times out: the consumer
        drains the queue within ``depth`` updates and the clock starts
        for real."""
        from unicore_tpu.utils import retry

        return retry.kv_wait(
            self._client,
            key,
            timeout=self._plan_timeout,
            poll_s=self._KV_POLL_S,
            should_abort=self._abort_if_closing,
            hold_deadline=self._queue.full,
        )

    def _cleanup_previous_epoch(self):
        """Delete the PREVIOUS epoch's plan-key directory once — called
        right after the first successful exchange of this epoch, which
        proves every peer has written a key for THIS epoch and therefore
        finished reading the old one (a producer only starts epoch E after
        its host consumed epoch E-1 to the end).  Without this, every
        epoch leaks its last ``_KV_RETAIN_UPDATES`` keys per rank forever
        (the lazy in-exchange cleanup never reaches an epoch's tail).

        Deleting CURRENT-epoch keys any earlier than this is unsafe: jit
        dispatch is async, so a host's consumer can pass update N before
        the peer's producer has read that host's key for N — deletion at
        ``close()`` raced exactly that window and wedged the peer's
        exchange."""
        try:
            # coordination-service delete is recursive for directories
            self._client.key_value_delete(
                f"unicore_tpu/prefetch_plan/{self._epoch - 1}/"
            )
        except Exception:
            pass

    def _exchange_plan(self, seq: int, sigs):
        """All-gather (sigs, stop_flag) across hosts for update ``seq``
        over the coordination-service KV store.  Keys are matched by
        (epoch, update, rank), so this never conflicts with the training
        thread's device collectives regardless of thread timing."""
        from unicore_tpu.distributed import guard

        client = self._client
        payload = (sigs, guard.stop_requested())
        client.key_value_set(self._kv_key(seq, self._rank), _encode(payload))
        rows = []
        for rank in range(self._nproc):
            if rank == self._rank:
                rows.append(payload)
                continue
            try:
                raw = self._blocking_get(self._kv_key(seq, rank))
            except _ProducerStopped:
                raise
            except Exception as e:
                from unicore_tpu import telemetry

                telemetry.emit(
                    "prefetch-stall", update=int(seq), waiting_for=int(rank),
                    timeout=round(self._plan_timeout, 1),
                )
                raise PrefetchError(
                    f"slot-plan exchange for update {seq} timed out after "
                    f"{self._plan_timeout:.0f}s waiting for rank {rank} "
                    "(peer stalled, preempted, or >"
                    f"{_KV_RETAIN_UPDATES} updates behind)"
                ) from e
            try:
                rows.append(_decode(raw))
            except Exception as e:
                raise PrefetchError(
                    f"slot-plan payload from rank {rank} for update {seq} "
                    f"failed to decode — peers are desynced: {e!r}"
                ) from e
        # lazy cleanup of our own old key (peers further behind than the
        # retain window would have stalled the pipeline long before)
        old = seq - _KV_RETAIN_UPDATES
        if old >= self._first_seq:
            try:
                client.key_value_delete(self._kv_key(old, self._rank))
            except Exception:
                pass
        if seq == self._first_seq and self._epoch > 1:
            self._cleanup_previous_epoch()
        return rows
