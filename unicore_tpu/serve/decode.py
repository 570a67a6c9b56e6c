"""Step-level continuous batching: the incremental-decode scheduler.

The PR-7 admit→batch→dispatch loop generalizes to autoregressive
generation (docs/serving.md, "Incremental decode"):

* **prefill/decode split** — prompts run through their own bucketed
  program family (one prefill program per prompt bucket, exactly the
  encoder path's discipline), so a long-prompt dispatch can never stall
  the decode batch behind it;
* **step-level re-entry** — a sequence re-enters the scheduler's ready
  list after EVERY decode step, and batches re-form per step with
  bucket = CACHE-LENGTH bucket; a finished sequence frees its batch slot
  (and its cache pages) mid-generation instead of holding ``decode_batch``
  hostage until the longest neighbor finishes;
* **paged cache accounting** — pages come from :class:`PagedKVCache`'s
  free list; a sequence grows page-by-page, and page exhaustion preempts
  the YOUNGEST decoding sequence (least sunk cost: its pages free, the
  sequence re-queues for re-prefill over prompt + generated-so-far) —
  admission-time exhaustion sheds ``cache-oom`` at the door instead.

One compiled program per cache bucket for decode and one per prompt
bucket for prefill, both counted by the same recompile-after-warmup
watchdog the encoder engine runs: steady-state decode compiles NOTHING
(the fusion audit + tests/test_decode.py hold this bound).

Every blocking wait here is deadline-bounded (lint rule
``unbounded-serve-wait`` covers this module by path); deadlines are
enforced at admission, before every decode step, and at response;
drain/hot-reload/readiness semantics are inherited from
:class:`~unicore_tpu.serve.engine.ServeEngine` unchanged.
"""

import functools
import logging
import time
from collections import deque
from typing import List, Optional, Sequence

import numpy as np

from unicore_tpu.checkpoint.emergency import Deadline
from unicore_tpu.distributed import chaos
from unicore_tpu.platform_utils import on_tpu
from unicore_tpu.serve import request as rq
from unicore_tpu.serve.admission import AdmissionQueue
from unicore_tpu.serve.engine import (
    PHASE_DRAINING,
    PHASE_SERVING,
    PHASE_WARMING,
    ServeEngine,
    _block_on,
)
from unicore_tpu.serve.kv_cache import (
    DEFAULT_PAGE_SIZE,
    PagedKVCache,
    bucket_for,
    calibrate_kv_scales,
    gather_pages,
    quantize_kv,
    scatter_prefill,
    scatter_rows,
)
from unicore_tpu.utils import retry

logger = logging.getLogger(__name__)


class DecodeSequence:
    """One in-flight generation: its request, page ownership, and decode
    cursor.  ``pending`` is the sampled-but-not-yet-cached token; its row
    is ``next_pos`` (= prompt_len + generated - 1)."""

    __slots__ = ("req", "prompt", "out", "pages", "pending", "next_pos",
                 "bucket", "max_new", "score_sum", "steps", "seq_no")

    def __init__(self, req, prompt, pages, pending, next_pos, bucket,
                 max_new, seq_no):
        self.req = req
        self.prompt = np.asarray(prompt, np.int32)
        self.out: List[int] = []
        self.pages: List[int] = list(pages)
        self.pending = int(pending)
        self.next_pos = int(next_pos)
        self.bucket = int(bucket)
        self.max_new = int(max_new)
        self.score_sum = 0.0
        self.steps = 0
        self.seq_no = int(seq_no)

    def written_stream(self) -> np.ndarray:
        """The tokens whose K/V rows are IN the cache (prompt + every
        processed generated token; ``pending`` is not cached) — what a
        re-prefill replays after preemption."""
        if not self.out:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out, np.int32)]
        )


class DecodeEngine(ServeEngine):
    """Autoregressive serving engine: same outward surface as
    :class:`ServeEngine` (ready/phase/submit/drain/stats/hot-reload), a
    prefill+decode step loop inside."""

    #: the HTTP layer routes POST /v1/generate only at engines that
    #: declare generation support
    supports_generate = True

    def __init__(
        self,
        model,
        variables,
        *,
        bucket_edges: Sequence[int],
        decode_batch: int = 8,
        prefill_batch: Optional[int] = None,
        pad_idx: int = 0,
        eos_idx: int = 2,
        vocab_size: int = 32,
        num_pages: int = 256,
        page_size: int = DEFAULT_PAGE_SIZE,
        kv_dtype: str = "fp32",
        max_new_tokens: int = 32,
        admission_capacity: int = 256,
        latency_window: int = 2048,
        precision: str = "",
        swap_hook=None,
        decode_sample_every: int = 64,
    ):
        import jax.numpy as jnp

        if kv_dtype not in ("fp32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp32' or 'int8', got {kv_dtype!r}"
            )
        edges = tuple(sorted(int(e) for e in bucket_edges))
        if any(e % page_size for e in edges):
            raise ValueError(
                f"every cache bucket edge must be a page multiple "
                f"(page_size {page_size}), got {edges}"
            )
        prefill_batch = int(prefill_batch or decode_batch)
        queue = AdmissionQueue(
            admission_capacity,
            batch_capacity=prefill_batch,
            max_len=edges[-1],
            bucket_edges=edges,
            precision=precision,
        )
        super().__init__(
            variables,
            None,  # infer_fn: decode dispatch owns its own programs
            bucket_edges=edges,
            batch_size=decode_batch,
            pad_idx=pad_idx,
            queue=queue,
            latency_window=latency_window,
            precision=precision,
            swap_hook=swap_hook,
        )
        self.model = model
        self.prefill_batch = prefill_batch
        self.eos_idx = int(eos_idx)
        self.vocab_size = int(vocab_size)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.kv_dtype = jnp.int8 if kv_dtype == "int8" else jnp.float32
        self.max_new_tokens = int(max_new_tokens)
        self.cache: Optional[PagedKVCache] = None
        self._kv_scales = None  # (k_scale, v_scale), int8 only
        self._decode_ready: deque = deque()
        self._preempted: deque = deque()
        self._seq_counter = 0
        self._active = 0
        # decode-plane counters (all surfaced in /stats + Prometheus)
        self.tokens_generated = 0
        self.preempted_seqs = 0
        self.requeued_steps = 0
        self.prefill_batches = 0
        self.decode_steps = 0
        self._token_ms: List[float] = []
        self._decode_sample_every = max(0, int(decode_sample_every))
        self._serving_since: Optional[float] = None
        self._build_programs()

    # -- compiled program families ---------------------------------------

    def _build_programs(self) -> None:
        import jax
        import jax.numpy as jnp

        model, ps = self.model, self.page_size
        # donation keeps the pool update in-place on TPU; CPU ignores
        # donation with a per-call warning, so only request it where it
        # works
        donate = on_tpu()

        @functools.partial(
            jax.jit, donate_argnums=(3, 4) if donate else ()
        )
        def _prefill(variables, tokens, lengths, k_pool, v_pool,
                     pages, slots, scales):
            logits, (k, v) = model.apply(
                variables, tokens, method="prefill"
            )
            idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
            row = jnp.take_along_axis(
                logits, jnp.broadcast_to(
                    idx, (logits.shape[0], 1, logits.shape[2])
                ), axis=1,
            )[:, 0]
            nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
            score = jnp.max(row.astype(jnp.float32), axis=-1)
            if scales is not None:
                k = quantize_kv(k, scales[0])
                v = quantize_kv(v, scales[1])
            k_pool = scatter_prefill(k_pool, pages, slots,
                                     k.astype(k_pool.dtype))
            v_pool = scatter_prefill(v_pool, pages, slots,
                                     v.astype(v_pool.dtype))
            return nxt, score, k_pool, v_pool

        @functools.partial(
            jax.jit, donate_argnums=(4, 5) if donate else ()
        )
        def _decode(variables, tokens, positions, page_table,
                    k_pool, v_pool, scales):
            caches = (
                gather_pages(k_pool, page_table),
                gather_pages(v_pool, page_table),
            )
            logits, (k_rows, v_rows) = model.apply(
                variables, tokens, caches, positions,
                kv_scales=scales, method="decode_step",
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            score = jnp.max(logits.astype(jnp.float32), axis=-1)
            pages = jnp.take_along_axis(
                page_table, (positions // ps)[:, None], axis=1
            )[:, 0]
            slots = positions % ps
            k_pool = scatter_rows(k_pool, pages, slots,
                                  k_rows.astype(k_pool.dtype))
            v_pool = scatter_rows(v_pool, pages, slots,
                                  v_rows.astype(v_pool.dtype))
            return nxt, score, k_pool, v_pool

        @jax.jit
        def _probe(variables, tokens):
            logits = model.apply(variables, tokens, train=False)
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            score = jnp.max(logits.astype(jnp.float32), axis=-1).mean(-1)
            return ids, score

        self._prefill_fn = _prefill
        self._decode_fn = _decode
        self._probe_fn = _probe

        warned = [False]

        def cache_size() -> int:
            try:
                return int(_prefill._cache_size()) + int(
                    _decode._cache_size()
                )
            except Exception:
                if not warned[0]:
                    warned[0] = True
                    logger.warning(
                        "jit _cache_size() probe failed (jax version "
                        "change?): the decode recompile-after-warmup "
                        "warning is disabled"
                    )
                return -1

        self._cache_size_probe = cache_size

    # -- warm-up ---------------------------------------------------------

    def warmup(self) -> int:
        import jax.numpy as jnp

        if not self.set_ready(False, PHASE_WARMING):
            return 0
        t0 = time.monotonic()
        n_layers = self.model.decoder_layers
        n_heads = self.model.decoder_attention_heads
        head_dim = self.model.decoder_embed_dim // n_heads

        if self.kv_dtype == jnp.int8:
            # one eager calibration prefill over a deterministic token
            # sweep fixes the per-(layer, head, channel) scales for the
            # engine's lifetime (static scales keep every decode program
            # closed over the same constants — no recompiles on reload)
            edge = self.bucket_edges[-1]
            ids = (
                np.arange(self.prefill_batch * edge, dtype=np.int64)
                % max(2, self.vocab_size)
            ).astype(np.int32).reshape(self.prefill_batch, edge)
            _, (k, v) = self.model.apply(
                self.variables, ids, method="prefill"
            )
            self._kv_scales = calibrate_kv_scales(k, v)
            logger.info(
                "KV-CACHE int8: calibrated per-(layer, head, channel) "
                f"scales from one {self.prefill_batch}x{edge} prefill"
            )
        self.cache = PagedKVCache(
            self.num_pages, n_layers, n_heads, head_dim,
            page_size=self.page_size, dtype=self.kv_dtype,
            kv_scales=self._kv_scales,
        )
        from unicore_tpu.parallel.plan import get_global_plan

        self.cache.shard_by_plan(get_global_plan())

        sentinel = self.cache.sentinel
        for edge in self.bucket_edges:
            # prefill program for this prompt bucket: compile + one warm
            # dispatch seeding the admission queue's service EMA
            tokens = np.full((self.prefill_batch, edge), self.pad_idx,
                             np.int32)
            lengths = np.ones((self.prefill_batch,), np.int32)
            pages = np.full((self.prefill_batch, edge), sentinel, np.int32)
            slots = np.tile(
                np.arange(edge, dtype=np.int32) % self.page_size,
                (self.prefill_batch, 1),
            )
            self._dispatch_prefill_arrays(tokens, lengths, pages, slots)
            tb0 = time.monotonic()
            self._dispatch_prefill_arrays(tokens, lengths, pages, slots)
            self.queue.note_batch_service(time.monotonic() - tb0,
                                          bucket=edge)
            # decode program for this cache bucket
            dtoks = np.zeros((self.batch_size,), np.int32)
            dpos = np.zeros((self.batch_size,), np.int32)
            table = np.full(
                (self.batch_size, edge // self.page_size), sentinel,
                np.int32,
            )
            self._dispatch_decode_arrays(dtoks, dpos, table)
            self._dispatch_decode_arrays(dtoks, dpos, table)
        # the reload probe's program warms too — a hot reload must never
        # compile inside the serving loop
        self.probe(self.variables)
        if self._cache_size_probe is not None:
            with self._lock:
                self._warm_programs = self._cache_size_probe()
        programs = max(self._warm_programs, 0) or 2 * len(self.bucket_edges)
        logger.info(
            f"decode warm-up complete: {programs} program(s) "
            f"(prefill+decode) for {len(self.bucket_edges)} cache "
            f"bucket(s) {list(self.bucket_edges)} x decode batch "
            f"{self.batch_size} (kv {np.dtype(self.kv_dtype).name}, "
            f"{self.num_pages} pages x {self.page_size} rows) in "
            f"{time.monotonic() - t0:.1f}s; readiness -> true"
        )
        if self.set_ready(True, PHASE_SERVING):
            self.queue.set_accepting(True)
            self._serving_since = time.monotonic()
        return programs

    def _dispatch_prefill_arrays(self, tokens, lengths, pages, slots):
        nxt, score, k_pool, v_pool = self._prefill_fn(
            self.variables, tokens, lengths,
            self.cache.k_pool, self.cache.v_pool, pages, slots,
            self._kv_scales,
        )
        _block_on((nxt, score))
        self.cache.k_pool, self.cache.v_pool = k_pool, v_pool
        return np.asarray(nxt), np.asarray(score)

    def _dispatch_decode_arrays(self, tokens, positions, table):
        nxt, score, k_pool, v_pool = self._decode_fn(
            self.variables, tokens, positions, table,
            self.cache.k_pool, self.cache.v_pool, self._kv_scales,
        )
        _block_on((nxt, score))
        self.cache.k_pool, self.cache.v_pool = k_pool, v_pool
        return np.asarray(nxt), np.asarray(score)

    # -- probes ----------------------------------------------------------

    def probe(self, variables) -> None:
        """Full-forward canary on the smallest bucket with candidate
        weights: shape + finite-score check, never touching the live
        pools (a donation race with the loop thread would invalidate
        them)."""
        edge = self.bucket_edges[0]
        dummy = np.full((self.prefill_batch, edge), self.pad_idx, np.int32)
        ids, score = self._probe_fn(variables, dummy)
        ids, score = np.asarray(ids), np.asarray(score)
        if ids.shape != (self.prefill_batch, edge):
            raise ValueError(
                f"probe batch produced shape {ids.shape}, expected "
                f"{(self.prefill_batch, edge)}"
            )
        if not np.all(np.isfinite(score)):
            raise ValueError(
                "probe batch produced non-finite scores (poisoned weights?)"
            )

    # -- submission ------------------------------------------------------

    def submit(self, tokens, deadline_s: float,
               request_id: Optional[str] = None,
               max_new_tokens: Optional[int] = None) -> rq.ServeRequest:
        req = rq.ServeRequest.make(tokens, deadline_s, request_id)
        # generation budget rides the request (POST /v1/generate); the
        # engine clamps it to its own ceiling
        req.max_new_tokens = min(
            self.max_new_tokens,
            int(max_new_tokens) if max_new_tokens else self.max_new_tokens,
        )
        self.queue.admit(req)
        return req

    # -- the step loop ---------------------------------------------------

    def step(self, timeout: float = 0.05) -> int:
        """One scheduler iteration, decode-first: dispatch one decode
        step batch if any sequence is ready, otherwise one prefill batch
        (preempted sequences first, then admission).  Returns sequences
        FINISHED this iteration."""
        chaos.note_serve_batch(self._batch_seq)
        batch = self._take_decode_batch()
        if batch is not None:
            served = self._run_decode_step(*batch)
        else:
            served = self._run_prefill(timeout)
        self._watch_recompiles()
        return served

    # ... decode side ....................................................

    def _expire_seq(self, seq: DecodeSequence) -> None:
        self.queue.note_terminal_reason(rq.EXPIRED_IN_QUEUE)
        seq.req.expire(rq.EXPIRED_IN_QUEUE)
        self._release(seq)

    def _release(self, seq: DecodeSequence) -> None:
        if seq.pages:
            self.cache.free(seq.pages)
            seq.pages = []
        self._active -= 1

    def _shed_oom(self, req) -> None:
        self.queue.note_terminal_reason(rq.SHED_CACHE_OOM)
        req.shed(rq.SHED_CACHE_OOM)
        from unicore_tpu import telemetry

        telemetry.emit(
            "serve-shed", reason=rq.SHED_CACHE_OOM,
            request_id=req.request_id,
            occupancy=round(self.cache.occupancy(), 4),
        )

    def _preempt_youngest(self, exclude) -> bool:
        """Free the youngest ready sequence's pages and park it for
        re-prefill; False when nothing outside ``exclude`` can yield."""
        victim = None
        for s in self._decode_ready:
            if s in exclude:
                continue
            if victim is None or s.seq_no > victim.seq_no:
                victim = s
        if victim is None:
            return False
        self._decode_ready.remove(victim)
        self.cache.free(victim.pages)
        victim.pages = []
        self._preempted.append(victim)
        self.preempted_seqs += 1
        logger.warning(
            f"PREEMPT {victim.req.request_id}: cache pages exhausted — "
            f"youngest sequence yields {victim.next_pos} cached row(s) "
            f"and re-queues for re-prefill "
            f"(occupancy {self.cache.occupancy():.2f})"
        )
        return True

    def _grow(self, seq: DecodeSequence, picked) -> bool:
        """Ensure ``seq`` owns pages covering its next row, preempting
        the youngest bystander on exhaustion.  False = seq must shed."""
        needed = self.cache.pages_for(seq.next_pos + 1)
        while len(seq.pages) < needed:
            got = self.cache.alloc(1)
            if got is None:
                if not self._preempt_youngest(exclude=picked):
                    return False
                continue
            seq.pages.extend(got)
        return True

    def _take_decode_batch(self):
        """FIFO bucket-affine batch off the ready list (the admission
        queue's formation rule, re-applied per STEP so batches re-form as
        sequences finish or change cache bucket)."""
        ready = self._decode_ready
        picked: List[DecodeSequence] = []
        bucket = 0
        while ready:
            seq = ready.popleft()
            if seq.req.deadline.exceeded():
                self._expire_seq(seq)
                continue
            picked.append(seq)
            bucket = seq.bucket
            break
        if not picked:
            return None
        keep: List[DecodeSequence] = []
        while ready and len(picked) < self.batch_size:
            seq = ready.popleft()
            if seq.req.deadline.exceeded():
                self._expire_seq(seq)
                continue
            if seq.bucket == bucket:
                picked.append(seq)
            else:
                keep.append(seq)
        for s in reversed(keep):
            ready.appendleft(s)
        # page growth AFTER formation: preemption must never evict a
        # sequence picked for this very step
        live: List[DecodeSequence] = []
        for s in picked:
            if self._grow(s, picked):
                live.append(s)
            else:
                self._shed_oom(s.req)
                self._release(s)
        if not live:
            return None
        return live, bucket

    def _run_decode_step(self, seqs: List[DecodeSequence],
                         bucket: int) -> int:
        sentinel = self.cache.sentinel
        width = bucket // self.page_size
        tokens = np.zeros((self.batch_size,), np.int32)
        positions = np.zeros((self.batch_size,), np.int32)
        table = np.full((self.batch_size, width), sentinel, np.int32)
        for i, s in enumerate(seqs):
            tokens[i] = s.pending
            positions[i] = s.next_pos
            table[i, : len(s.pages)] = s.pages
        t0 = time.monotonic()
        nxt, score = self._dispatch_decode_arrays(tokens, positions, table)
        service = time.monotonic() - t0
        self._batch_seq += 1
        self.decode_steps += 1
        served = 0
        step_ms = service * 1000.0
        with self._lock:
            self._token_ms.extend([step_ms] * len(seqs))
            if len(self._token_ms) > self._latency_window:
                del self._token_ms[: self._latency_window // 4]
        for i, s in enumerate(seqs):
            tok = int(nxt[i])
            s.out.append(s.pending)  # the processed token is now cached
            s.score_sum += float(score[i])
            s.steps += 1
            self.tokens_generated += 1
            done = (
                tok == self.eos_idx
                or len(s.out) >= s.max_new
                or s.next_pos + 2 > self.bucket_edges[-1]
            )
            if done:
                self._finish(s, final=tok)
                served += 1
            else:
                s.pending = tok
                s.next_pos += 1
                s.bucket = bucket_for(s.next_pos + 1, self.bucket_edges)
                self._decode_ready.append(s)
                self.requeued_steps += 1
        self._maybe_journal_step(bucket, len(seqs), step_ms)
        return served

    def _finish(self, s: DecodeSequence, final: Optional[int]) -> None:
        out = list(s.out)
        if final is not None and final == self.eos_idx:
            out.append(final)
        latency_ms = (time.monotonic() - s.req.arrival) * 1000.0
        if s.req.deadline.exceeded():
            self.expired_at_response += 1
            self.queue.note_terminal_reason(rq.EXPIRED_AT_RESPONSE)
            s.req.expire(rq.EXPIRED_AT_RESPONSE)
        else:
            s.req.respond(rq.ServeResponse(
                s.req.request_id,
                rq.STATUS_OK,
                output=[int(t) for t in out],
                score=(s.score_sum / max(1, s.steps)),
                latency_ms=latency_ms,
                bucket=s.bucket,
            ))
            self.served += 1
            with self._lock:
                self._latencies_ms.append(latency_ms)
                if len(self._latencies_ms) > self._latency_window:
                    del self._latencies_ms[: self._latency_window // 4]
        self._release(s)

    def _maybe_journal_step(self, bucket, live, step_ms) -> None:
        if (
            self._decode_sample_every <= 0
            or self.decode_steps % self._decode_sample_every != 0
        ):
            return
        from unicore_tpu import telemetry

        telemetry.emit(
            "decode-step", step=int(self.decode_steps),
            bucket=int(bucket), live=int(live),
            service_ms=round(step_ms, 3),
            occupancy=round(self.cache.occupancy(), 4),
            tokens_generated=int(self.tokens_generated),
            preempted=int(self.preempted_seqs),
        )

    # ... prefill side ...................................................

    def _run_prefill(self, timeout: float) -> int:
        if self._preempted:
            return self._prefill_preempted()
        batch = self.queue.take_batch(
            self.bucket_edges, timeout, max_len=self.bucket_edges[-1]
        )
        if batch is None:
            return 0
        reqs, padded = batch
        try:
            admitted = []
            for r in reqs:
                pages = self.cache.alloc(self.cache.pages_for(len(r)))
                if pages is None:
                    self._shed_oom(r)
                    continue
                admitted.append((r, pages))
            if admitted:
                self._prefill_batch(
                    [(r, np.asarray(r.tokens, np.int32), pages, None)
                     for r, pages in admitted],
                    padded,
                )
        finally:
            self.queue.batch_done()
        return 0

    def _prefill_preempted(self) -> int:
        """Re-prefill preempted sequences (bucket-affine FIFO over their
        cached-stream lengths); they bypass admission — they were already
        admitted once."""
        head = self._preempted.popleft()
        stream = head.written_stream()
        padded = bucket_for(len(stream), self.bucket_edges)
        group = [(head, stream)]
        keep = []
        while self._preempted and len(group) < self.prefill_batch:
            s = self._preempted.popleft()
            st = s.written_stream()
            if bucket_for(len(st), self.bucket_edges) == padded:
                group.append((s, st))
            else:
                keep.append(s)
        for s in reversed(keep):
            self._preempted.appendleft(s)
        entries = []
        for s, st in group:
            if s.req.deadline.exceeded():
                self._expire_seq(s)
                continue
            pages = self.cache.alloc(self.cache.pages_for(len(st)))
            if pages is None:
                # still no room even for the resumption: this sequence
                # loses (bounded memory beats livelock)
                self._shed_oom(s.req)
                self._release(s)
                continue
            s.pages = pages
            entries.append((s.req, st, pages, s))
        if entries:
            self._prefill_batch(entries, padded)
        return 0

    def _prefill_batch(self, entries, padded: int) -> None:
        """Dispatch one prefill program: ``entries`` is a list of
        ``(req, stream, pages, seq-or-None)`` (seq set = resumption)."""
        sentinel = self.cache.sentinel
        B = self.prefill_batch
        tokens = np.full((B, padded), self.pad_idx, np.int32)
        lengths = np.ones((B,), np.int32)
        pages2d = np.full((B, padded), sentinel, np.int32)
        slots2d = np.tile(
            np.arange(padded, dtype=np.int32) % self.page_size, (B, 1)
        )
        for i, (req, stream, pages, _seq) in enumerate(entries):
            n = len(stream)
            tokens[i, :n] = stream
            lengths[i] = n
            pages2d[i, :n] = np.repeat(
                np.asarray(pages, np.int32),
                self.page_size,
            )[:n]
        t0 = time.monotonic()
        nxt, score = self._dispatch_prefill_arrays(
            tokens, lengths, pages2d, slots2d
        )
        self.queue.note_batch_service(time.monotonic() - t0, bucket=padded)
        self._batch_seq += 1
        self.prefill_batches += 1
        for i, (req, stream, pages, seq) in enumerate(entries):
            if seq is not None:
                # resumption: the pending token was never lost; the
                # prefill's re-sampled head token is discarded (greedy
                # decode would reproduce it anyway)
                self._decode_ready.append(seq)
                self.requeued_steps += 1
                continue
            self._seq_counter += 1
            self._active += 1
            s = DecodeSequence(
                req, stream, pages,
                pending=int(nxt[i]),
                next_pos=len(stream),
                bucket=bucket_for(
                    min(len(stream) + 1, self.bucket_edges[-1]),
                    self.bucket_edges,
                ),
                max_new=getattr(req, "max_new_tokens",
                                self.max_new_tokens),
                seq_no=self._seq_counter,
            )
            s.score_sum += float(score[i])
            s.steps += 1
            self.tokens_generated += 1
            if (
                s.pending == self.eos_idx
                or s.max_new <= 1
                or s.next_pos + 1 > self.bucket_edges[-1]
            ):
                # degenerate one-token generation: finished at prefill
                s.out.append(s.pending)
                self._finish(s, final=None)
            else:
                self._decode_ready.append(s)

    # -- drain -----------------------------------------------------------

    def _idle(self) -> bool:
        return (
            self.queue.idle()
            and not self._decode_ready
            and not self._preempted
            and self._active == 0
        )

    def drain(self, deadline: Deadline) -> bool:
        """Like the encoder engine's drain, but 'flushed' additionally
        means every in-flight GENERATION ran to completion (the loop
        keeps stepping them while the queue refuses new work)."""
        self.queue.begin_drain()
        self.set_ready(False, PHASE_DRAINING)
        depth = self.queue.depth() + len(self._decode_ready) + len(
            self._preempted
        )
        logger.info(
            f"DRAIN started: {depth} queued/decoding sequence(s), budget "
            f"{deadline.budget if deadline.budget is not None else 'inf'}s"
        )
        try:
            retry.bounded_wait(
                self._idle,
                timeout=max(0.0, deadline.remaining()),
                poll_s=0.05,
                describe="decode serve drain",
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
                f"DRAIN deadline exceeded: {leftovers} request(s) "
                f"abandoned after {deadline.elapsed():.2f}s (each got a "
                "terminal 'draining' response)"
            )
            telemetry.emit(
                "serve-drain", outcome="deadline-exceeded",
                seconds=round(deadline.elapsed(), 3),
                abandoned=int(leftovers),
            )
        return drained

    def _flush_undrained(self) -> int:
        n = super()._flush_undrained()
        for s in list(self._decode_ready) + list(self._preempted):
            s.req.shed(rq.SHED_DRAINING)
            self._release(s)
            n += 1
        self._decode_ready.clear()
        self._preempted.clear()
        return n

    # -- stats -----------------------------------------------------------

    def token_latency_percentiles(self) -> dict:
        with self._lock:
            lat = list(self._token_ms)
        if not lat:
            return {}
        arr = np.asarray(lat)
        return {
            f"token_p{p}_ms": round(float(np.percentile(arr, p)), 3)
            for p in (50, 90, 99)
        }

    def stats(self) -> dict:
        base = super().stats()
        elapsed = (
            time.monotonic() - self._serving_since
            if self._serving_since else 0.0
        )
        base.update({
            "mode": "decode",
            "kv_dtype": str(np.dtype(self.kv_dtype).name),
            "tokens_generated": self.tokens_generated,
            "tokens_per_s": round(
                self.tokens_generated / elapsed, 3
            ) if elapsed > 0 else 0.0,
            "cache_page_occupancy": round(
                self.cache.occupancy(), 4
            ) if self.cache else 0.0,
            "cache_pages_free": (
                self.cache.free_pages if self.cache else 0
            ),
            "active_sequences": self._active,
            "preempted": self.preempted_seqs,
            "requeued": self.requeued_steps,
            "prefill_batches": self.prefill_batches,
            "decode_steps": self.decode_steps,
            **self.token_latency_percentiles(),
        })
        return base
