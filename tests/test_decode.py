"""Incremental decode plane (docs/serving.md "Incremental decode"):
step-for-step parity of prefill + decode_step against the full forward
(fp32 exact, int8-KV within quantization tolerance), the decode-attention
op against its oracle, the paged KV-cache allocator's invariants
(never-partial alloc, double-free/bogus-page guards, OOM), plan legality
for cache axes, and the DecodeEngine step scheduler — FIFO bucket-affine
re-formation, preempt-youngest on page exhaustion, cache-oom shedding,
and end-to-end greedy generation with one compiled program per bucket."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.models.transformer_lm import TransformerLMModel
from unicore_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
)
from unicore_tpu.parallel.plan import (
    CACHE_HEAD_AXIS,
    ParallelPlan,
    PlanLegalityError,
    get_global_plan,
    set_global_plan,
)
from unicore_tpu.serve import request as rq
from unicore_tpu.serve.decode import DecodeEngine, DecodeSequence
from unicore_tpu.serve.kv_cache import (
    PagedKVCache,
    bucket_for,
    cache_bucket_edges,
    calibrate_kv_scales,
    gather_pages,
    quantize_kv,
    scatter_prefill,
    scatter_rows,
)

@pytest.fixture(autouse=True)
def no_inherited_plan():
    """Every ``Trainer`` sets the process-global parallel plan and leaves
    it; an engine built after one in the same worker would shard its KV
    pools by that plan (and warm a fifth program).  These tests are about
    an engine with no plan."""
    plan_was = get_global_plan()
    set_global_plan(None)
    yield
    set_global_plan(plan_was)


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------


def _tiny_model(**kw):
    cfg = dict(
        vocab_size=17,
        padding_idx=1,
        decoder_layers=2,
        decoder_embed_dim=32,
        decoder_ffn_embed_dim=64,
        decoder_attention_heads=4,
        dropout=0.0,
        emb_dropout=0.0,
        attention_dropout=0.0,
        activation_dropout=0.0,
        max_seq_len=64,
    )
    cfg.update(kw)
    return TransformerLMModel(**cfg)


@pytest.fixture(scope="module")
def tiny():
    model = _tiny_model()
    variables = model.init_params(
        jax.random.PRNGKey(0),
        {"net_input": {"src_tokens": np.ones((2, 8), np.int32)}},
    )
    return model, variables


# ---------------------------------------------------------------------------
# model layer: incremental decode == full forward
# ---------------------------------------------------------------------------


def _incremental_logits(model, variables, toks, P, kv_dtype, scales=None):
    """Prefill toks[:, :P], then decode token-by-token to the end,
    maintaining dense per-layer caches exactly like the engine's paged
    pools (quantized storage when int8).  Returns logits rows P..L-1."""
    B, L = toks.shape
    _, (k, v) = model.apply(variables, toks[:, :P], method="prefill")
    nl, _, H, _, D = k.shape
    if scales is not None:
        k = quantize_kv(k, scales[0])
        v = quantize_kv(v, scales[1])
    kc = jnp.zeros((nl, B, H, L, D), kv_dtype)
    vc = jnp.zeros((nl, B, H, L, D), kv_dtype)
    kc = kc.at[:, :, :, :P, :].set(k.astype(kv_dtype))
    vc = vc.at[:, :, :, :P, :].set(v.astype(kv_dtype))
    rows_out = []
    for t in range(P, L):
        logits_t, (kr, vr) = model.apply(
            variables,
            toks[:, t],
            (kc, vc),
            jnp.full((B,), t, jnp.int32),
            kv_scales=scales,
            method="decode_step",
        )
        kc = kc.at[:, :, :, t, :].set(kr.astype(kv_dtype))
        vc = vc.at[:, :, :, t, :].set(vr.astype(kv_dtype))
        rows_out.append(np.asarray(logits_t))
    return np.stack(rows_out, axis=1)  # (B, L - P, V)


def test_incremental_decode_matches_full_forward_fp32(tiny):
    model, variables = tiny
    rng = np.random.RandomState(0)
    B, L, P = 2, 16, 5
    toks = rng.randint(3, model.vocab_size, size=(B, L)).astype(np.int32)
    full = np.asarray(model.apply(variables, toks))
    logits_p, _ = model.apply(variables, toks[:, :P], method="prefill")
    # prefill rows are the causal forward over the prompt
    np.testing.assert_allclose(
        np.asarray(logits_p), full[:, :P], atol=1e-4, rtol=1e-4
    )
    inc = _incremental_logits(model, variables, toks, P, jnp.float32)
    np.testing.assert_allclose(inc, full[:, P:], atol=1e-4, rtol=1e-4)


def test_incremental_decode_int8_kv_within_quant_tolerance(tiny):
    model, variables = tiny
    rng = np.random.RandomState(1)
    B, L, P = 2, 16, 5
    toks = rng.randint(3, model.vocab_size, size=(B, L)).astype(np.int32)
    full = np.asarray(model.apply(variables, toks))
    _, (k, v) = model.apply(variables, toks[:, :P], method="prefill")
    scales = calibrate_kv_scales(k, v)
    inc = _incremental_logits(model, variables, toks, P, jnp.int8, scales)
    # int8 KV storage perturbs logits but must stay in the same regime
    # as the calibrated quantization error (the engine's probe gate
    # would reject anything larger)
    err = np.max(np.abs(inc - full[:, P:]))
    assert err < 0.1, f"int8-KV decode drifted {err} from the fp32 forward"


# ---------------------------------------------------------------------------
# decode-attention op vs its oracle
# ---------------------------------------------------------------------------


def test_decode_attention_masks_dead_rows():
    rng = np.random.RandomState(2)
    B, H, L, D = 3, 4, 16, 8
    q = rng.randn(B, H, D).astype(np.float32)
    kc = rng.randn(B, H, L, D).astype(np.float32)
    vc = rng.randn(B, H, L, D).astype(np.float32)
    positions = np.array([0, 7, 15], np.int32)
    out = np.asarray(decode_attention(q, kc, vc, positions))
    # oracle: per-row softmax over the live prefix only
    for b in range(B):
        live = positions[b] + 1
        s = np.einsum("hd,hld->hl", q[b], kc[b, :, :live])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hl,hld->hd", p, vc[b, :, :live])
        np.testing.assert_allclose(out[b], want, atol=1e-5, rtol=1e-5)
    # junk beyond the live prefix must not leak into the output
    kc2 = kc.copy()
    vc2 = vc.copy()
    kc2[:, :, 8:] = 1e6
    vc2[:, :, 8:] = -1e6
    pos2 = np.array([0, 7, 7], np.int32)
    a = np.asarray(decode_attention(q, kc, vc, pos2))
    b_ = np.asarray(decode_attention(q, kc2, vc2, pos2))
    np.testing.assert_allclose(a, b_, atol=1e-5)


def test_decode_attention_int8_dequant_matches_fp():
    rng = np.random.RandomState(3)
    B, H, L, D = 2, 4, 32, 8
    q = rng.randn(B, H, D).astype(np.float32)
    kf = rng.randn(B, H, L, D).astype(np.float32)
    vf = rng.randn(B, H, L, D).astype(np.float32)
    positions = np.array([5, 31], np.int32)
    # per-(head, channel) scales exactly as calibrate_kv_scales produces
    ks = (np.abs(kf).max(axis=(0, 2)) / 127.0 + 1e-8).astype(np.float32)
    vs = (np.abs(vf).max(axis=(0, 2)) / 127.0 + 1e-8).astype(np.float32)
    ki = np.clip(np.rint(kf / ks[None, :, None, :]), -127, 127).astype(
        np.int8
    )
    vi = np.clip(np.rint(vf / vs[None, :, None, :]), -127, 127).astype(
        np.int8
    )
    fp = np.asarray(decode_attention(q, kf, vf, positions))
    qd = np.asarray(
        decode_attention(q, ki, vi, positions, k_scale=ks, v_scale=vs)
    )
    assert np.max(np.abs(fp - qd)) < 0.05
    # the fused path and the oracle agree bit-for-bit in intent
    ref = np.asarray(
        decode_attention_reference(
            q, ki, vi, positions, k_scale=ks, v_scale=vs
        )
    )
    np.testing.assert_allclose(qd, ref, atol=1e-5, rtol=1e-5)


def test_decode_attention_scale_pairing_enforced():
    q = np.zeros((1, 1, 4), np.float32)
    kf = np.zeros((1, 1, 8, 4), np.float32)
    pos = np.zeros((1,), np.int32)
    ks = np.ones((1, 4), np.float32)
    with pytest.raises(ValueError, match="together"):
        decode_attention(q, kf, kf, pos, k_scale=ks)
    with pytest.raises(ValueError, match="int8"):
        decode_attention(q, kf, kf, pos, k_scale=ks, v_scale=ks)


# ---------------------------------------------------------------------------
# paged cache: edges, allocator invariants, scatter/gather round trip
# ---------------------------------------------------------------------------


def test_cache_bucket_edges_are_page_multiples():
    edges = cache_bucket_edges(100, 4, page_size=32)
    assert all(e % 32 == 0 for e in edges)
    assert edges[-1] >= 100
    assert edges == sorted(set(edges))
    assert bucket_for(1, edges) == edges[0]
    assert bucket_for(edges[-1], edges) == edges[-1]
    with pytest.raises(ValueError):
        bucket_for(edges[-1] + 1, edges)


def test_paged_cache_alloc_free_invariants():
    cache = PagedKVCache(4, 2, 2, 4, page_size=8)
    assert cache.occupancy() == 0.0
    a = cache.alloc(3)
    assert a is not None and len(a) == 3
    assert cache.occupancy() == pytest.approx(0.75)
    # never-partial: 2 requested, 1 free -> None, and the free page stays
    assert cache.alloc(2) is None
    b = cache.alloc(1)
    assert b is not None
    assert cache.occupancy() == 1.0
    cache.free(a)
    assert cache.occupancy() == pytest.approx(0.25)
    with pytest.raises(RuntimeError):
        cache.free(a)  # double free overflows the free list
    with pytest.raises(ValueError):
        cache.free([99])  # bogus page id
    assert cache.pages_for(1) == 1
    assert cache.pages_for(8) == 1
    assert cache.pages_for(9) == 2


def test_paged_scatter_gather_round_trip():
    rng = np.random.RandomState(4)
    nl, B, H, D, ps = 2, 2, 2, 4, 4
    cache = PagedKVCache(6, nl, H, D, page_size=ps)
    Lp = 6  # spans 2 pages
    kv = rng.randn(nl, B, H, Lp, D).astype(np.float32)
    pages = np.stack([np.asarray(cache.alloc(2)) for _ in range(B)])
    pool = jnp.asarray(cache.k_pool)
    pages2d = np.repeat(pages, ps, axis=1)[:, :Lp]
    slots2d = np.broadcast_to(np.arange(Lp) % ps, (B, Lp))
    pool = scatter_prefill(pool, pages2d, slots2d, jnp.asarray(kv))
    table = np.stack([cache.table(list(p), 2 * ps) for p in pages])
    got = np.asarray(gather_pages(pool, table))  # (nl, B, H, 2*ps, D)
    np.testing.assert_array_equal(got[:, :, :, :Lp], kv)
    # single-row scatter at the decode cursor
    rows = rng.randn(nl, B, H, D).astype(np.float32)
    pool = scatter_rows(
        pool, pages[:, 1], np.full((B,), Lp % ps, np.int32),
        jnp.asarray(rows),
    )
    got = np.asarray(gather_pages(pool, table))
    np.testing.assert_array_equal(got[:, :, :, Lp], rows)
    np.testing.assert_array_equal(got[:, :, :, :Lp], kv)


def test_plan_kv_cache_axes_legality():
    assert ParallelPlan(model=1).kv_cache_axes(4) == (
        None, None, None, None, None,
    )
    assert ParallelPlan(model=2).kv_cache_axes(4) == (
        None, None, CACHE_HEAD_AXIS, None, None,
    )
    with pytest.raises(PlanLegalityError) as ei:
        ParallelPlan(model=3).kv_cache_axes(4)
    assert ei.value.rule == "cache-heads-indivisible"


# ---------------------------------------------------------------------------
# DecodeEngine scheduler (no warm-up: pure python ready-list mechanics)
# ---------------------------------------------------------------------------


def _sched_engine(tiny, *, num_pages=8, decode_batch=3):
    model, variables = tiny
    eng = DecodeEngine(
        model,
        variables,
        bucket_edges=(4, 8),
        decode_batch=decode_batch,
        page_size=4,
        num_pages=num_pages,
        vocab_size=17,
        max_new_tokens=8,
    )
    eng.cache = PagedKVCache(num_pages, 1, 1, 4, page_size=4)
    return eng


def _seq(eng, *, next_pos, bucket, seq_no, n_pages=1, deadline_s=60.0,
         max_new=8):
    req = rq.ServeRequest.make([3, 4, 5], deadline_s)
    pages = eng.cache.alloc(n_pages) if n_pages else []
    assert pages is not None
    s = DecodeSequence(
        req, [3, 4, 5], pages, pending=5, next_pos=next_pos,
        bucket=bucket, max_new=max_new, seq_no=seq_no,
    )
    eng._decode_ready.append(s)
    eng._active += 1
    return s


def test_take_decode_batch_fifo_bucket_affine(tiny):
    eng = _sched_engine(tiny)
    a = _seq(eng, next_pos=1, bucket=4, seq_no=1)
    b = _seq(eng, next_pos=1, bucket=4, seq_no=2)
    c = _seq(eng, next_pos=5, bucket=8, seq_no=3, n_pages=2)
    d = _seq(eng, next_pos=1, bucket=4, seq_no=4)
    live, bucket = eng._take_decode_batch()
    assert [s.seq_no for s in live] == [1, 2, 4]  # FIFO within bucket 4
    assert bucket == 4
    assert list(eng._decode_ready) == [c]  # off-bucket kept, in order
    # next formation picks up the remaining bucket
    live2, bucket2 = eng._take_decode_batch()
    assert live2 == [c] and bucket2 == 8
    assert a.pages and b.pages and d.pages


def test_take_decode_batch_expires_dead_sequences(tiny):
    eng = _sched_engine(tiny)
    s = _seq(eng, next_pos=1, bucket=4, seq_no=1, deadline_s=0.0)
    assert eng._take_decode_batch() is None
    assert s.req.done()
    assert s.req.response.status == rq.STATUS_EXPIRED
    assert s.req.response.reason == rq.EXPIRED_IN_QUEUE
    assert s.pages == [] and eng.cache.occupancy() == 0.0
    assert eng._active == 0


def test_page_exhaustion_preempts_youngest_bystander(tiny):
    eng = _sched_engine(tiny, num_pages=2, decode_batch=1)
    # old sequence needs a second page for its next row; the only free
    # page is owned by a younger bystander in a different bucket
    old = _seq(eng, next_pos=4, bucket=8, seq_no=1)
    young = _seq(eng, next_pos=1, bucket=4, seq_no=2)
    live, bucket = eng._take_decode_batch()
    assert live == [old] and bucket == 8
    assert len(old.pages) == 2
    assert eng.preempted_seqs == 1
    assert young.pages == [] and list(eng._preempted) == [young]
    assert not young.req.done()  # parked for re-prefill, not shed


def test_page_exhaustion_sheds_when_nothing_can_yield(tiny):
    eng = _sched_engine(tiny, num_pages=1, decode_batch=1)
    s = _seq(eng, next_pos=4, bucket=8, seq_no=1)
    assert eng._take_decode_batch() is None
    assert s.req.done()
    assert s.req.response.status == rq.STATUS_SHED
    assert s.req.response.reason == rq.SHED_CACHE_OOM
    assert eng.cache.occupancy() == 0.0 and eng._active == 0


# ---------------------------------------------------------------------------
# DecodeEngine end to end (in process, stepped synchronously)
# ---------------------------------------------------------------------------


def _greedy_rollout(model, variables, prompt, max_new, eos, top):
    """Oracle with the engine's exact stop semantics: greedy tokens from
    full prefill-style forwards (no pad mask — same attention regime as
    the decode plane), eos appended when sampled, capped at max_new
    cached tokens or the top cache bucket."""
    toks = list(prompt)

    def sample():
        logits, _ = model.apply(
            variables, np.asarray([toks], np.int32), method="prefill"
        )
        return int(np.argmax(np.asarray(logits)[0, -1]))

    pending = sample()
    out = []
    if pending == eos or max_new <= 1 or len(toks) + 1 > top:
        return [eos] if pending == eos else []
    while True:
        toks.append(pending)
        out.append(pending)
        nxt = sample()
        if nxt == eos or len(out) >= max_new or len(toks) + 1 > top:
            if nxt == eos:
                out.append(eos)
            return out
        pending = nxt


def _drive(eng, reqs, iters=400):
    for _ in range(iters):
        if all(r.done() for r in reqs):
            return
        eng.step(timeout=0.01)
    raise AssertionError("engine did not finish all requests")


def test_engine_serves_a_bf16_trained_checkpoint(tiny):
    """A ``--bf16`` training run saves bf16 parameters, so the decode step
    computes bf16 K/V rows against the fp32 cache view — the in-module
    cache write must take the cache's dtype (it raised a dtype mismatch at
    warm-up: every earlier decode test served fp32 weights)."""
    model, variables = tiny
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        variables,
    )
    eng = DecodeEngine(
        model, bf16, bucket_edges=(16, 32), decode_batch=2,
        prefill_batch=2, page_size=8, num_pages=12,
        pad_idx=model.padding_idx, eos_idx=2, vocab_size=model.vocab_size,
        max_new_tokens=4,
    )
    eng.warmup()
    req = eng.submit([5, 6, 7, 8], 60.0, request_id="bf16")
    _drive(eng, [req])
    assert req.response.status == rq.STATUS_OK, req.response
    assert 1 <= len(req.response.output) <= 4
    assert np.isfinite(req.response.score)


def test_engine_generates_greedy_rollout(tiny):
    model, variables = tiny
    eng = DecodeEngine(
        model,
        variables,
        bucket_edges=(16, 32),
        decode_batch=2,
        prefill_batch=2,
        page_size=8,
        num_pages=12,
        pad_idx=model.padding_idx,
        eos_idx=2,
        vocab_size=model.vocab_size,
        max_new_tokens=6,
    )
    warmed = eng.warmup()
    # one prefill + one decode program per cache bucket — nothing else
    assert warmed == 2 * len(eng.bucket_edges)
    prompts = [[5, 6, 7, 8], [9, 10, 11], [12, 13, 14, 15, 16]]
    reqs = [
        eng.submit(p, 60.0, request_id=f"g{i}")
        for i, p in enumerate(prompts)
    ]
    _drive(eng, reqs)
    for p, r in zip(prompts, reqs):
        assert r.response.status == rq.STATUS_OK, r.response
        want = _greedy_rollout(model, variables, p, 6, 2, 32)
        assert r.response.output == want
        assert np.isfinite(r.response.score)
    st = eng.stats()
    assert st["mode"] == "decode"
    assert st["active_sequences"] == 0
    assert st["cache_page_occupancy"] == 0.0
    assert st["served"] == 3
    assert st["tokens_generated"] >= sum(len(r.response.output) for r in reqs) - 3
    assert st["requeued"] > 0  # sequences re-entered the queue mid-flight
    # the fusion contract: serving never compiled past warm-up
    assert eng.recompiles_after_warmup == 0
    assert eng._cache_size_probe() == warmed
    assert eng.token_latency_percentiles()["token_p50_ms"] > 0.0


def test_engine_max_new_tokens_clamped_per_request(tiny):
    model, variables = tiny
    eng = DecodeEngine(
        model,
        variables,
        bucket_edges=(16,),
        decode_batch=1,
        page_size=8,
        num_pages=4,
        pad_idx=model.padding_idx,
        eos_idx=-1,  # never sampled: force the max_new stop
        vocab_size=model.vocab_size,
        max_new_tokens=5,
    )
    eng.warmup()
    r_short = eng.submit([5, 6, 7], 60.0, max_new_tokens=2)
    r_capped = eng.submit([8, 9, 10], 60.0, max_new_tokens=99)
    _drive(eng, [r_short, r_capped])
    assert r_short.response.status == rq.STATUS_OK
    assert len(r_short.response.output) == 2
    assert r_capped.response.status == rq.STATUS_OK
    assert len(r_capped.response.output) == 5  # clamped to engine cap


def test_engine_drain_finishes_inflight_generations(tiny):
    model, variables = tiny
    eng = DecodeEngine(
        model,
        variables,
        bucket_edges=(16,),
        decode_batch=2,
        page_size=8,
        num_pages=6,
        pad_idx=model.padding_idx,
        eos_idx=-1,
        vocab_size=model.vocab_size,
        max_new_tokens=4,
    )
    eng.warmup()
    reqs = [eng.submit([5, 6, 7], 60.0), eng.submit([9, 10], 60.0)]
    import threading

    from unicore_tpu.checkpoint.emergency import Deadline

    t = threading.Thread(target=lambda: [eng.step(0.01) for _ in range(200)])
    t.start()
    ok = eng.drain(Deadline(30.0))
    t.join(timeout=30)
    assert ok
    assert all(r.response.status == rq.STATUS_OK for r in reqs)
    assert eng.stats()["active_sequences"] == 0
