"""Continuous micro-batching inference engine.

One loop, four stages: **admit** (the bounded :class:`AdmissionQueue`
sheds overload at the door) → **batch** (bucket-affine formation, expired
requests dropped un-computed) → **dispatch** (ONE jitted XLA program per
shape bucket — the same bounded-geometry discipline the trainer earned
with ``--length-bucket`` + the persistent compile cache) → **respond**
(deadline checked one last time).

Robustness invariants, in order of importance:

* **Bounded warm-up**: every bucket's program is compiled at startup
  (``warmup()``); readiness flips true only after.  Steady state compiles
  NOTHING — a post-warm-up recompile is a geometry leak and logs a loud
  WARNING with the program count, exactly like the trainer's
  ``--compile-warmup-updates`` watchdog.
* **Bounded waits**: every blocking wait in this package is sliced and
  deadline-bounded (lint rule ``unbounded-serve-wait``).
* **Swap on a batch boundary**: hot reload hands a verified+probed
  variables tree to :meth:`request_swap`; the loop applies it BETWEEN
  batches, so no batch ever computes against half-swapped weights.
* **Drain, don't drop**: SIGTERM stops admission and flushes in-flight
  work under a deadline (:meth:`drain`); only the deadline expiring
  abandons the remainder (each abandoned request still gets a named
  response).
"""

import logging
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from unicore_tpu.checkpoint.emergency import Deadline
from unicore_tpu.distributed import chaos
from unicore_tpu.serve import request as rq
from unicore_tpu.serve.admission import AdmissionQueue
from unicore_tpu.utils import retry

logger = logging.getLogger(__name__)

#: engine phases surfaced by the readiness probe
PHASE_WARMING = "warming-up"
PHASE_SERVING = "serving"
PHASE_RELOADING = "reloading"
PHASE_DRAINING = "draining"
PHASE_STOPPED = "stopped"


def build_infer_fn(model) -> Tuple[Callable, Callable[[], int]]:
    """The jitted serving step for a ``src_tokens``-shaped model (the
    bert family): ``(variables, tokens[B, L]) -> (ids[B, L] int32,
    score[B] float32)``.

    ``score`` is the mean best-logit per row — a cheap confidence proxy
    AND the hot-reload probe's NaN canary: poisoned weights that still
    produce well-shaped int ids cannot hide from a float statistic.

    Returns ``(infer_fn, cache_size_probe)``; the probe counts compiled
    executables (same private-API discipline as the trainer's recompile
    watchdog — a jax rename disables the gauge with a warning, never
    crashes serving).
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _infer(variables, src_tokens):
        logits = model.apply(variables, src_tokens, train=False)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        score = jnp.max(logits.astype(jnp.float32), axis=-1).mean(axis=-1)
        return ids, score

    warned = [False]

    def cache_size() -> int:
        try:
            return int(_infer._cache_size())
        except Exception:
            if not warned[0]:
                warned[0] = True
                logger.warning(
                    "jit _cache_size() probe failed (jax version change?): "
                    "the serve recompile-after-warmup warning is disabled"
                )
            return -1

    return _infer, cache_size


class ServeEngine:
    """Owns the serving snapshot (model variables), the per-bucket jitted
    programs, and the admit→batch→dispatch→respond loop."""

    def __init__(
        self,
        variables,
        infer_fn: Callable,
        *,
        bucket_edges: Sequence[int],
        batch_size: int,
        pad_idx: int = 0,
        queue: Optional[AdmissionQueue] = None,
        admission_capacity: int = 256,
        cache_size_probe: Optional[Callable[[], int]] = None,
        latency_window: int = 2048,
        precision: str = "",
        quant_info: Optional[dict] = None,
        drift_probe: Optional[Callable] = None,
        drift_sample_every: int = 64,
        swap_hook: Optional[Callable] = None,
    ):
        if not bucket_edges:
            raise ValueError("bucket_edges must name at least one length")
        self.variables = variables
        self.infer_fn = infer_fn
        self.bucket_edges = tuple(sorted(int(e) for e in bucket_edges))
        self.batch_size = max(1, int(batch_size))
        self.pad_idx = int(pad_idx)
        #: precision label for /stats and the admission queue's
        #: per-(bucket, precision) service EMAs ('' = training precision)
        self.precision = str(precision)
        self.queue = queue or AdmissionQueue(
            admission_capacity,
            batch_capacity=self.batch_size,
            max_len=self.bucket_edges[-1],
            bucket_edges=self.bucket_edges,
            precision=self.precision,
        )
        #: calibration summary from quant.calibrate (mode, scale source,
        #: site count, calibration drift bound) — surfaced in /stats
        self.quant_info = quant_info
        #: optional sampled per-request logit-drift probe (quantized
        #: serving): tokens[B, L] -> per-row max |logit_q - logit_f32|.
        #: Runs every ``drift_sample_every``-th batch — a bounded shadow
        #: cost that keeps the error-bound contract observable in
        #: production, not just at calibration time.
        self._drift_probe = drift_probe
        self._drift_every = max(0, int(drift_sample_every))
        self._drift = {"samples": 0, "max_abs": 0.0, "mean_abs": 0.0,
                       "last_abs": 0.0}
        self._drift_probe_dead = False
        #: called with (variables, tag) right after a hot swap applies —
        #: the quantized CLI re-pairs its drift oracle here so sampled
        #: drift always compares the snapshot actually serving
        self._swap_hook = swap_hook
        self._cache_size_probe = cache_size_probe
        self._warm_programs = 0
        self.recompiles_after_warmup = 0
        self._phase = PHASE_WARMING
        self._ready = False
        self._stop = threading.Event()
        self._batch_seq = 0
        self.served = 0
        self.expired_at_response = 0
        self._latencies_ms: List[float] = []
        self._latency_window = int(latency_window)
        self._lock = threading.Lock()
        # hot-reload handoff: (variables, tag) applied on a batch boundary
        self._pending_swap = None
        self._swap_tag = None
        self.reloads_applied = 0
        self._thread: Optional[threading.Thread] = None
        #: the exception that killed the loop thread, if any — the CLI
        #: polls this: a server whose engine died must exit for its
        #: supervisor, never linger as a zombie with liveness green
        self.fatal_error: Optional[BaseException] = None

    # -- probes ----------------------------------------------------------

    @property
    def phase(self) -> str:
        return self._phase

    def ready(self) -> bool:
        return self._ready

    def set_ready(self, ready: bool, phase: Optional[str] = None) -> bool:
        """Readiness/phase transition; False when refused because the
        engine is already terminal.

        Draining/stopped are terminal: a hot reload (or a warm-up tail)
        that raced SIGTERM must not flip readiness back on and have a
        load balancer route traffic at a server that sheds everything.
        The lock pairs the terminal-phase check with the write — without
        it a reload thread's set_ready(True) can interleave with the
        loop thread's death transition and resurrect readiness on a dead
        engine."""
        with self._lock:
            if self._phase in (PHASE_DRAINING, PHASE_STOPPED):
                return False
            self._ready = bool(ready)
            if phase is not None:
                self._phase = phase
            return True

    # -- warm-up ---------------------------------------------------------

    def warmup(self) -> int:
        """Compile (or reload from the persistent cache) every bucket's
        program before the first real request; flips readiness true.
        Returns the number of programs compiled — the acceptance bound is
        ``<= len(bucket_edges)``."""
        if not self.set_ready(False, PHASE_WARMING):
            # already terminal (a SIGTERM beat the warm-up): compiling a
            # program per bucket on an engine that will never serve only
            # stalls the drain past its deadline
            return 0
        t0 = time.monotonic()
        for edge in self.bucket_edges:
            dummy = np.full(
                (self.batch_size, edge), self.pad_idx, dtype=np.int32
            )
            _block_on(self.infer_fn(self.variables, dummy))  # compiles
            # seed the admission queue's service estimate from a SECOND,
            # warm dispatch: timing the compiling one would inflate the
            # estimated queue delay by seconds and falsely shed the first
            # real requests as deadline-unmeetable
            tb0 = time.monotonic()
            _block_on(self.infer_fn(self.variables, dummy))
            self.queue.note_batch_service(time.monotonic() - tb0,
                                          bucket=edge)
        if self._cache_size_probe is not None:
            with self._lock:
                self._warm_programs = self._cache_size_probe()
        programs = max(self._warm_programs, 0) or len(self.bucket_edges)
        logger.info(
            f"serve warm-up complete: {programs} program(s) for "
            f"{len(self.bucket_edges)} bucket(s) "
            f"{list(self.bucket_edges)} x batch {self.batch_size} in "
            f"{time.monotonic() - t0:.1f}s; readiness -> true"
        )
        # routed through set_ready so a stop() that raced the compile
        # loop keeps the engine terminal (readiness and admission must
        # never resurrect after a terminal transition)
        if self.set_ready(True, PHASE_SERVING):
            self.queue.set_accepting(True)
        return programs

    def _watch_recompiles(self) -> None:
        if self._cache_size_probe is None or self._warm_programs <= 0:
            return
        n = self._cache_size_probe()
        # the whole read-compare-update transition holds the lock (a
        # guarded store alone couldn't stop two writers double-counting);
        # the log line stays outside it
        grew = 0
        with self._lock:
            if n > self._warm_programs:
                grew = n - self._warm_programs
                self._warm_programs = n
                self.recompiles_after_warmup += grew
        if grew:
            logger.warning(
                f"recompile after warmup: {grew} new serve program(s) "
                f"compiled at batch {self._batch_seq} ({n} total).  A "
                "request geometry escaped the bucket set — this should be "
                "impossible (admission sheds over-long requests); check "
                "bucket_edges vs the transport's validation."
            )

    # -- submission (transports + flood generator) ---------------

    def submit(self, tokens, deadline_s: float,
               request_id: Optional[str] = None) -> rq.ServeRequest:
        """Admit one request (or resolve it immediately with a named
        reason).  The caller waits on the returned request's completion
        via ``retry.bounded_wait``."""
        req = rq.ServeRequest.make(tokens, deadline_s, request_id)
        self.queue.admit(req)
        return req

    # -- hot reload ------------------------------------------------------

    def probe(self, variables) -> None:
        """Run one dummy batch through the SAME warmed program with
        candidate ``variables``; raises if the output is ill-shaped or
        the score canary is non-finite.  Shapes match warm-up, so a probe
        can never compile a new program."""
        edge = self.bucket_edges[0]
        dummy = np.full((self.batch_size, edge), self.pad_idx, dtype=np.int32)
        ids, score = self.infer_fn(variables, dummy)
        ids, score = np.asarray(ids), np.asarray(score)
        if ids.shape != (self.batch_size, edge):
            raise ValueError(
                f"probe batch produced shape {ids.shape}, "
                f"expected {(self.batch_size, edge)}"
            )
        if not np.all(np.isfinite(score)):
            raise ValueError(
                "probe batch produced non-finite scores (poisoned weights?)"
            )

    def request_swap(self, variables, tag: str) -> None:
        """Hand a verified+probed variables tree to the loop; it is
        applied on the next batch boundary (never mid-batch)."""
        with self._lock:
            self._pending_swap = variables
            self._swap_tag = tag

    def _apply_pending_swap(self) -> None:
        with self._lock:
            pending, tag = self._pending_swap, self._swap_tag
            self._pending_swap = self._swap_tag = None
        if pending is None:
            return
        self.variables = pending
        if self._swap_hook is not None:
            try:
                self._swap_hook(pending, tag)
            except Exception:
                logger.exception("swap hook failed (swap stands)")
        self.reloads_applied += 1
        logger.warning(
            f"RELOAD SWAPPED: serving snapshot replaced on batch boundary "
            f"{self._batch_seq} ({tag})"
        )
        from unicore_tpu import telemetry

        telemetry.emit(
            "serve-reload", outcome="swapped-in",
            batch=int(self._batch_seq), tag=str(tag),
        )

    # -- the loop --------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.run, name="serve-engine", daemon=True
        )
        self._thread.start()

    def run(self) -> None:
        try:
            while not self._stop.is_set():
                self._apply_pending_swap()
                self.step(timeout=0.05)
        except Exception as err:
            logger.exception("serve engine loop died")
            self.fatal_error = err
            with self._lock:
                self._ready = False
                self._phase = PHASE_STOPPED
            raise

    def healthy(self) -> bool:
        """False once the loop thread has died (or recorded a fatal) —
        distinct from liveness: the process is up, but nothing will ever
        serve another request."""
        if self.fatal_error is not None:
            return False
        return self._thread is None or self._thread.is_alive()

    def step(self, timeout: float = 0.05) -> int:
        """One loop iteration: form and dispatch at most one batch.
        Returns the number of requests served (0 if no work arrived
        within ``timeout``)."""
        batch = self.queue.take_batch(
            self.bucket_edges, timeout, max_len=self.bucket_edges[-1]
        )
        chaos.note_serve_batch(self._batch_seq)
        if batch is None:
            return 0
        reqs, padded = batch
        # the queue counted this batch in-flight at pop time (same lock),
        # so drain's "queue idle" observation can never race the span
        # between pop and the responses below; batch_done closes it
        try:
            t0 = time.monotonic()
            arr = np.full(
                (self.batch_size, padded), self.pad_idx, dtype=np.int32
            )
            for i, r in enumerate(reqs):
                arr[i, : len(r)] = r.tokens
            ids, score = self.infer_fn(self.variables, arr)
            ids, score = np.asarray(ids), np.asarray(score)
            service = time.monotonic() - t0
            self.queue.note_batch_service(service, bucket=padded)
            self._batch_seq += 1
            for i, r in enumerate(reqs):
                if r.deadline.exceeded():
                    # computed but useless: the deadline ran out during
                    # dispatch — count it honestly, never pretend success
                    self.expired_at_response += 1
                    self.queue.note_terminal_reason(rq.EXPIRED_AT_RESPONSE)
                    r.expire(rq.EXPIRED_AT_RESPONSE)
                    continue
                latency_ms = (time.monotonic() - r.arrival) * 1000.0
                r.respond(
                    rq.ServeResponse(
                        r.request_id,
                        rq.STATUS_OK,
                        output=[int(t) for t in ids[i, : len(r)]],
                        score=float(score[i]),
                        latency_ms=latency_ms,
                        bucket=padded,
                    )
                )
                self.served += 1
                with self._lock:
                    self._latencies_ms.append(latency_ms)
                    if len(self._latencies_ms) > self._latency_window:
                        del self._latencies_ms[: self._latency_window // 4]
            self._maybe_sample_drift(arr, len(reqs))
            self._watch_recompiles()
            return len(reqs)
        finally:
            self.queue.batch_done()

    def _maybe_sample_drift(self, arr, n_real: int) -> None:
        """Sampled per-request logit-drift check (quantized serving):
        every ``drift_sample_every``-th batch re-runs through the fp32
        oracle and records max |logit_q - logit_f32| per REAL request row.
        A dying probe disables itself — observability must never take the
        serving loop down."""
        if (
            self._drift_probe is None
            or self._drift_probe_dead
            or self._drift_every <= 0
            or self._batch_seq % self._drift_every != 0
        ):
            return
        try:
            per_row = np.asarray(self._drift_probe(arr), np.float32)
        except Exception:
            self._drift_probe_dead = True
            logger.exception(
                "quant drift probe died; per-request drift sampling "
                "disabled (serving continues)"
            )
            return
        rows = per_row[:n_real] if per_row.ndim else per_row.reshape(1)
        if rows.size == 0:
            return
        batch_max = float(rows.max())
        with self._lock:
            d = self._drift
            d["samples"] += int(n_real)
            d["last_abs"] = batch_max
            d["max_abs"] = max(d["max_abs"], batch_max)
            # EMA so a long run's mean tracks the CURRENT snapshot, not
            # every snapshot ever swapped in
            mean = float(rows.mean())
            d["mean_abs"] = (
                mean if d["samples"] <= n_real
                else 0.1 * mean + 0.9 * d["mean_abs"]
            )
            snapshot = dict(d)
        from unicore_tpu import telemetry

        telemetry.emit(
            "quant-path", event="drift-sample", batch=int(self._batch_seq),
            requests=int(n_real),
            max_abs_logit_drift=round(batch_max, 6),
            running_max=round(snapshot["max_abs"], 6),
        )

    # -- drain / stop ----------------------------------------------------

    def drain(self, deadline: Deadline) -> bool:
        """Graceful shutdown: stop admitting, flush everything already
        queued (plus the in-flight batch) under ``deadline``.  Returns
        True when the queue emptied in time; False means the budget ran
        out and the leftovers were resolved with named reasons."""
        self.queue.begin_drain()
        self.set_ready(False, PHASE_DRAINING)
        depth = self.queue.depth()
        logger.info(
            f"DRAIN started: {depth} queued request(s), "
            f"budget {deadline.budget if deadline.budget is not None else 'inf'}s"
        )
        try:
            retry.bounded_wait(
                self.queue.idle,
                timeout=max(0.0, deadline.remaining()),
                poll_s=0.05,
                describe="serve drain",
            )
            drained = True
        except retry.WaitTimeoutError:
            drained = False
        self.stop()
        from unicore_tpu import telemetry

        if drained:
            logger.info(
                f"DRAIN complete: in-flight work flushed in "
                f"{deadline.elapsed():.2f}s"
            )
            telemetry.emit(
                "serve-drain", outcome="complete",
                seconds=round(deadline.elapsed(), 3), queued=depth,
            )
        else:
            leftovers = self._flush_undrained()
            logger.error(
                f"DRAIN deadline exceeded: {leftovers} request(s) abandoned "
                f"after {deadline.elapsed():.2f}s (each got a terminal "
                "'draining' response)"
            )
            telemetry.emit(
                "serve-drain", outcome="deadline-exceeded",
                seconds=round(deadline.elapsed(), 3),
                abandoned=int(leftovers),
            )
        return drained

    def _flush_undrained(self) -> int:
        n = 0
        while True:
            batch = self.queue.take_batch(
                self.bucket_edges, 0.0, max_len=self.bucket_edges[-1]
            )
            if batch is None:
                break
            for r in batch[0]:
                r.shed(rq.SHED_DRAINING)
                n += 1
            self.queue.batch_done()
        return n

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            self._phase = PHASE_STOPPED
            self._ready = False
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=5.0)

    # -- stats -----------------------------------------------------------

    def latency_percentiles(self) -> dict:
        with self._lock:
            lat = list(self._latencies_ms)
        if not lat:
            return {}
        arr = np.asarray(lat)
        return {
            f"p{p}_ms": round(float(np.percentile(arr, p)), 3)
            for p in (50, 90, 99)
        }

    def update_quant_info(self, info: dict) -> None:
        """A hot swap committed a re-calibrated snapshot: /stats must
        describe the snapshot actually SERVING, so the calibration block
        is replaced and the per-request drift aggregate starts over —
        a monotonic max spanning swaps would report a long-gone
        snapshot's worst sample forever."""
        with self._lock:
            self.quant_info = dict(info)
            self._drift = {"samples": 0, "max_abs": 0.0, "mean_abs": 0.0,
                           "last_abs": 0.0}

    def stats(self) -> dict:
        quant = None
        if self.quant_info is not None:
            with self._lock:
                drift = dict(self._drift)
                quant = {**self.quant_info, "request_drift": drift}
        return {
            "phase": self._phase,
            "ready": self._ready,
            "precision": self.precision or "training",
            **({"quant": quant} if quant is not None else {}),
            "served": self.served,
            "admitted": self.queue.admitted,
            "shed": dict(self.queue.shed_counts),
            "depth": self.queue.depth(),
            "batches": self._batch_seq,
            "buckets": list(self.bucket_edges),
            "batch_size": self.batch_size,
            "estimated_delay_s": round(self.queue.estimated_delay(), 4),
            "recompiles_after_warmup": self.recompiles_after_warmup,
            "reloads_applied": self.reloads_applied,
            **self.latency_percentiles(),
        }


def _block_on(out) -> None:
    """Wait for a dispatched device computation without importing jax in
    the fake-infer test path."""
    for leaf in out if isinstance(out, (tuple, list)) else (out,):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
