"""Step-time spans: where does a train update actually spend its time?

The hot loop's per-update work decomposes into host phases —

* ``data_wait``       waiting on the (possibly prefetched) iterator,
* ``plan_exchange``   the multi-host slot-plan all-gather,
* ``h2d``             host->device transfer of the prepared batch,
* ``dispatch``        enqueueing the jitted step(s),

— plus the device-side phase, ``device_busy``, which the host cannot see
without a sync.  This module measures the host phases with
``perf_counter`` (always on once telemetry is configured; nanoseconds of
overhead) and the device phase with a **lag-1 sampled** probe: on a
sampled update N, one tiny replicated output leaf of the dispatched step
is retained, and at the START of update N+1 the recorder blocks on it —
by then the device has been computing N the whole time, so the block
measures N's device occupancy without ever stalling the pipeline
(the host would otherwise idle into its next dispatch anyway).

Sampling contract (``--telemetry-sample-interval N``): the probe runs on
every N-th update ONLY.  Unsampled updates make ZERO sync calls — the
``sync-transfer-in-step`` lint stays clean because the one
``block_until_ready`` lives here, outside any train_step call graph, and
``tests/test_telemetry.py`` stubs :func:`_device_sync` to prove the
zero-sync property.  ``N=0`` disables the device probe entirely (host
spans still accumulate into the ``host_blocked`` metric when a journal
is configured).

The probe resolves at the earliest idle host point — the next update's
``data_wait`` (the training thread would sit in the iterator's queue
anyway; data production lives on other threads, so the block is free).
When the sync returned instantly, the device had already gone idle
inside the gap and the measurement is only an upper bound: the journal
record carries ``upper_bound: true`` so an input-bound run can never
masquerade as device-bound.

Every site timed here also opens a ``jax.profiler.TraceAnnotation``
named ``unicore:<span>`` (:func:`annotation`), whether or not the recorder
is enabled: with no profiler capture running it costs about half a
microsecond, and in a capture (``--profile-steps``, the benchmark's
``--trace 1``) the program's own spans stand on the device events' clock,
nested by thread, so an idle gap of the device can be given to the host
phase that covers it (``benchmark/trace_scopes.py`` reads them; the names
are listed in docs/observability.md).

Sampled updates also land a ``kind="span"`` record per phase in the
event journal — the raw material ``unicore-tpu-trace`` turns into
Chrome-trace (Perfetto) slices — and feed the cross-host straggler
attribution: each host publishes its smoothed per-update wall through
the existing KV heartbeat lease, and the sampled host journals the
slowest rank by name (``kind="straggler"``).
"""

import contextlib
import logging
import time
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

#: host-side phases (order is display order in traces)
HOST_SPANS = ("data_wait", "plan_exchange", "h2d", "dispatch")
DEVICE_SPAN = "device_busy"

#: EMA horizon for the per-update step wall published via heartbeats
_STEP_WALL_EMA = 0.2


#: every annotation the program writes into a profiler capture starts so
ANNOTATION_PREFIX = "unicore:"


def annotation(name: str, **stats):
    """``jax.profiler.TraceAnnotation("unicore:<name>", **stats)``: a host
    span in the profiler's own trace (a no-op costing ~0.5 us while no
    capture runs).  ``stats`` land as the event's stats (``update=<n>``
    is the identifier the spans of one update share)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(ANNOTATION_PREFIX + name, **stats)


def mark(name: str, **stats) -> None:
    """An instant annotation: something that happened, not a phase."""
    with annotation(name, **stats):
        pass


def _device_sync(handle) -> None:
    """The ONE device sync in the spans path — module-level so the
    overhead tests can stub it and count calls."""
    handle.block_until_ready()


class SpanRecorder:
    """Per-process span accumulator (driven by the trainer + CLI loop)."""

    def __init__(self, sample_interval: int = 0):
        self.sample_interval = max(0, int(sample_interval))
        self.enabled = False
        # True between begin_update and end_update: spans recorded
        # OUTSIDE an open update (validation's plan/h2d, checkpoint
        # writes) are dropped — they are not hot-loop blockage and must
        # not poison the dispatch residual or the host_blocked total
        self._open = False
        # per-update span durations (reset each update)
        self._current: Dict[str, float] = {}
        # between-update host work attributed to the NEXT update (the
        # CLI's data_wait — recorded via between_span before train_step
        # opens the bracket)
        self._between: Dict[str, float] = {}
        self._update_started: Optional[float] = None
        # interval totals drained by trainer.flush_metrics.  The busy
        # total counts MEASURED samples only (the sync had to wait, so
        # the gap is the device's real occupancy); upper-bound samples
        # (device already idle at first look) are journaled with the
        # flag but excluded here — else a checkpoint/validation wall on
        # a sampled update would masquerade as device time
        self._totals: Dict[str, float] = {}
        self._device_busy_total = 0.0
        self._device_samples = 0  # all collected probes, incl. bounded
        # lag-1 probe state: (update, handle, dispatch_end_mono)
        self._pending_probe: Optional[tuple] = None
        # smoothed per-update wall (heartbeat straggler payload):
        # data_wait + in-step wall, EXCLUDING between-update bookkeeping
        # (a rank-local checkpoint save must not get its writer named
        # as the straggler)
        self._step_wall_ema = -1.0

    # -- configuration ----------------------------------------------------

    def configure(self, sample_interval: int) -> None:
        self.sample_interval = max(0, int(sample_interval))
        self.enabled = True

    def sampled(self, update: int) -> bool:
        return (
            self.sample_interval > 0
            and update >= 0
            and update % self.sample_interval == 0
        )

    # -- host spans -------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """Accumulate one host phase of the OPEN update (no-op when
        disabled or when no update is open — a plan exchange or transfer
        issued by validation must not count as hot-loop blockage)."""
        with annotation(name):
            if not self.enabled:
                yield
                return
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def between_span(self, name: str):
        """A between-updates phase (the CLI's data_wait), attributed to
        the NEXT update when it opens.  Entering it also collects any
        pending lag-1 device probe: the training thread is about to idle
        on the data iterator anyway (production happens on other
        threads), so blocking on the previous sampled update's output
        here costs nothing and reads the device-busy gap at the earliest
        possible host point."""
        if not self.enabled:
            with annotation(name):
                yield
            return
        self.collect_probe()
        t0 = time.perf_counter()
        try:
            with annotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            if dt > 0:
                self._between[name] = self._between.get(name, 0.0) + dt

    def add(self, name: str, seconds: float) -> None:
        if not self.enabled or not self._open or seconds <= 0:
            return
        self._current[name] = self._current.get(name, 0.0) + seconds
        self._totals[name] = self._totals.get(name, 0.0) + seconds

    def add_dispatch_residual(self, hot_block_seconds: float) -> None:
        """``dispatch`` = the hot block's wall minus the plan_exchange
        and h2d pieces already recorded for this update (those run
        inside the same block; measuring the jit call sites one by one
        would mean instrumenting four dispatch shapes)."""
        if not self.enabled:
            return
        residual = hot_block_seconds - self._current.get(
            "plan_exchange", 0.0
        ) - self._current.get("h2d", 0.0)
        self.add("dispatch", residual)

    # -- update lifecycle (called by the trainer) -------------------------

    def collect_probe(self) -> None:
        """Resolve a pending lag-1 device probe (the ONLY sync in the
        spans path; only sampled updates ever leave one pending).

        ``busy`` is dispatch-end -> sync-return.  When the sync had to
        WAIT (the device was still computing when the host looked), that
        is the device's real occupancy up to this moment.  When it
        returned instantly, the device finished somewhere inside the gap
        and ``busy`` is only an upper bound — the journal record says so
        (``upper_bound: true``) instead of letting an input-bound run
        masquerade as device-bound.  Called at the earliest idle host
        point (the data_wait between-span) and again from begin_update
        as a fallback."""
        pending = self._pending_probe
        if pending is None:
            return
        probe_update, handle, dispatched_at = pending
        self._pending_probe = None
        try:
            t0 = time.perf_counter()
            _device_sync(handle)
            sync_wait = time.perf_counter() - t0
            busy = max(0.0, time.monotonic() - dispatched_at)
            upper_bound = sync_wait < 1e-3
            self._device_samples += 1
            if not upper_bound:
                # the sync WAITED: the device was busy the whole gap —
                # only these samples feed the device_busy metric
                self._device_busy_total += busy
            from unicore_tpu.telemetry import journal

            journal.emit(
                "span", update=probe_update, name=DEVICE_SPAN,
                dur=round(busy, 6),
                # True: the device was already idle when the host first
                # looked — the real busy time is <= dur (journal-only;
                # the metric excludes these samples)
                upper_bound=upper_bound,
            )
        except Exception as err:
            logger.debug(f"device-busy probe failed: {err}")

    def begin_update(self, update: int) -> None:
        """Collect any still-pending lag-1 probe, then open update
        ``update``, folding in the between-updates work (data_wait)
        recorded since the previous update closed."""
        if not self.enabled:
            return
        self.collect_probe()
        self._update_started = time.monotonic()
        self._open = True
        for name, dt in self._between.items():
            self._current[name] = self._current.get(name, 0.0) + dt
            self._totals[name] = self._totals.get(name, 0.0) + dt
        self._between = {}

    def note_dispatched(self, update: int, handle: Any) -> None:
        """Called right after the jitted dispatch returns.  On a sampled
        update, retain ``handle`` (a small replicated output leaf — its
        readiness implies the whole step program finished) for the lag-1
        probe; unsampled updates retain NOTHING and therefore can never
        sync."""
        if not self.enabled or not self.sampled(update):
            return
        self._pending_probe = (int(update), handle, time.monotonic())

    def end_update(self, update: int) -> None:
        """Close update ``update``: fold its wall into the step-wall EMA
        and journal the host spans when sampled."""
        if not self.enabled:
            return
        self._open = False
        now = time.monotonic()
        if self._update_started is not None:
            # per-update wall = iterator wait + the in-step wall; the
            # between-update tail (validation, a checkpoint save on the
            # writer rank) is deliberately EXCLUDED — straggler
            # attribution compares sustained step rates, and naming the
            # checkpoint writer slowest after every save would be a
            # false verdict
            wall = (now - self._update_started) + self._current.get(
                "data_wait", 0.0
            )
            self._step_wall_ema = (
                wall
                if self._step_wall_ema < 0
                else (1 - _STEP_WALL_EMA) * self._step_wall_ema
                + _STEP_WALL_EMA * wall
            )
            self._update_started = None
        if self.sampled(update) and self._current:
            from unicore_tpu.telemetry import journal

            for name in HOST_SPANS:
                dur = self._current.get(name)
                if dur:
                    journal.emit(
                        "span", update=int(update), name=name,
                        dur=round(dur, 6),
                    )
        self._current = {}

    # -- interval drain (trainer.flush_metrics) ---------------------------

    def drain(self) -> Dict[str, float]:
        """Interval totals since the last drain: per-host-span seconds,
        the summed ``host_blocked``, and the sampled ``device_busy``
        seconds (plus sample count)."""
        out = dict(self._totals)
        out["host_blocked"] = sum(
            self._totals.get(k, 0.0) for k in HOST_SPANS
        )
        out[DEVICE_SPAN] = self._device_busy_total
        out["device_samples"] = float(self._device_samples)
        self._totals = {}
        self._device_busy_total = 0.0
        self._device_samples = 0
        return out

    def avg_step_wall(self) -> float:
        """Smoothed seconds per update (-1 before the first completed
        update; data_wait + in-step wall, between-update bookkeeping
        excluded) — what the heartbeat lease publishes for straggler
        attribution."""
        return self._step_wall_ema


_recorder = SpanRecorder()


def recorder() -> SpanRecorder:
    return _recorder


def reset() -> None:
    """Fresh recorder (tests)."""
    global _recorder
    _recorder = SpanRecorder()


def configure(args) -> SpanRecorder:
    _recorder.configure(
        getattr(args, "telemetry_sample_interval", 0) or 0
    )
    return _recorder


def span(name: str):
    return _recorder.span(name)


def add(name: str, seconds: float) -> None:
    _recorder.add(name, seconds)


def avg_step_wall() -> float:
    return _recorder.avg_step_wall()


def journal_straggler(update: int) -> None:
    """Sampled-update cross-host straggler attribution: read every peer's
    published step wall (the heartbeat lease's ``step_wall`` field) and
    journal the slowest rank by name.  Costs a few KV fetches per SAMPLED
    update — never a collective, never on unsampled updates."""
    if not _recorder.enabled or not _recorder.sampled(update):
        return
    from unicore_tpu.distributed import elastic
    from unicore_tpu.telemetry import journal

    runtime = elastic.active_runtime()
    if runtime is None:
        return
    walls = runtime.peer_step_walls()
    mine = _recorder.avg_step_wall()
    if mine > 0:
        walls[runtime.rank] = mine
    known = {r: w for r, w in walls.items() if w and w > 0}
    if len(known) < 2:
        return
    slowest = max(known, key=lambda r: known[r])
    fastest = min(known, key=lambda r: known[r])
    journal.emit(
        "straggler",
        update=int(update),
        slowest_rank=int(slowest),
        slowest_step_wall=round(known[slowest], 6),
        fastest_rank=int(fastest),
        fastest_step_wall=round(known[fastest], 6),
        step_walls={str(r): round(w, 6) for r, w in sorted(known.items())},
    )
