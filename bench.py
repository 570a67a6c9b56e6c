#!/usr/bin/env python3
"""Headline benchmark: training-step throughput on the current accelerator.

Runs the REAL training path — the Trainer's fused jitted step (forward,
backward, clip, Adam, EMA).  Default config mirrors the reference's de-facto
perf config (examples/bert/train_bert_test.sh: BERT-base, Adam (0.9, 0.98),
seq 512) in bf16 on one chip.  ``BENCH_CONFIG`` selects the model family:

    BENCH_CONFIG=bert       (default) BERT-base MLM, samples/s/chip
    BENCH_CONFIG=unimol     Uni-Mol pair-bias pretraining step
    BENCH_CONFIG=evoformer  Evoformer masked-MSA step
    BENCH_CONFIG=moe        BERT-base with a top-2 routed expert FFN every
                            other layer (BENCH_MOE_EXPERTS, default 8) —
                            times the scatter dispatch path
    BENCH_CONFIG=serve      the serving plane (unicore_tpu/serve/):
                            continuous-batching BERT-base inference at
                            offered load just under the shedding point —
                            req/s + p50/p90/p99 latency rows
                            (BENCH_SERVE_SECONDS, BENCH_SERVE_BUCKETS)
    BENCH_CONFIG=serve-quant  int8 vs bf16 serving: two engines over the
                            SAME weights driven by the SAME paced offered
                            load (BENCH_QUANT_QPS, default 50 req/s) —
                            one req/s + p99 row per precision, each
                            carrying the calibration drift bound
                            (BENCH_QUANT_LAYERS/EMBED size the model;
                            docs/serving.md "Quantized inference")
    BENCH_CONFIG=decode     incremental decode (unicore_tpu/serve/decode.py):
                            fp32-KV vs int8-KV DecodeEngine over the SAME
                            transformer-LM weights at the SAME paced
                            offered load — one tokens/s row per KV
                            precision with per-token p50/p99, page
                            occupancy, and the one-program-per-bucket +
                            zero-recompile counters
                            (BENCH_DECODE_QPS/SECONDS/LAYERS/EMBED;
                            docs/serving.md "Incremental decode")
    BENCH_CONFIG=fleet      the serving FLEET (unicore_tpu/serve/fleet/):
                            N ∈ {1,2,3} real replica HTTP planes behind
                            the shedding router (lease-registered over a
                            file KV, p2c by admission estimate), driven
                            by a closed-loop worker pool — one aggregate
                            req/s + p50/p99 row per N
                            (BENCH_FLEET_SECONDS, BENCH_FLEET_WORKERS;
                            docs/serving.md "Fleet").  On one CPU the
                            replicas share cores, so scaling is a
                            liveness/overhead statement, not a perf claim
    BENCH_CONFIG=kernels    device-side fused-kernel shootout: one row per
                            op pair — softmax_dropout jnp-vs-Pallas,
                            layernorm jnp-vs-Pallas, Adam tree_map-vs-fused
                            multi-tensor — fwd+bwd (update for Adam) wall
                            time per call
    BENCH_CONFIG=memory     memory-headroom sweep: binary-search the max
                            trainable parameter count per chip at fixed
                            batch against a per-chip memory budget
                            (BENCH_MEMORY_BUDGET_GB, default 2.0), using
                            the compiled train program's OWN memory
                            analysis — device-free, honest on CPU.  One
                            row per {zero-stage} x {grad-accum} x
                            {remat-policy} grid point
                            (BENCH_MEMORY_STAGES/ACCUMS/REMATS trim the
                            grid; docs/performance.md "Memory headroom")
    BENCH_CONFIG=all        run every config except memory (its compile
                            sweep has its own invocation); one JSON line
                            each, failures in one config don't lose the
                            others' results

Prints ONE JSON line per config: {"metric", "value", "unit", "vs_baseline"}
plus diagnostics: "ms_per_step", "mfu" (model-FLOPs utilization — FLOPs from
XLA's own cost analysis of the lowered step with the Pallas kernels routed to
the pure-XLA attention path so every matmul is counted; peak from the chip
table in ``_peak_flops``), "device_kind", and "flops_per_step".
``vs_baseline`` is null — the reference publishes no numbers.

Every row is a device measurement: a run that finds no TPU, meets a
``device_kind`` missing from the peaks table, or has any config fail exits
non-zero.  The CPU is never a fallback.

Resilience (round-2 verdict): each config's result line is ALSO appended to
``BENCH_PARTIAL.jsonl`` the moment it completes, so a later config's hang
can't lose it; and unless ``BENCH_TRACE=0`` a 2-step ``jax.profiler`` trace
is saved under ``bench_traces/<config>/`` for offline perf review.

``BENCH_PIPELINE=1`` (bert only) feeds the step from the REAL data path —
on-disk indexed shards -> WordPiece tokenize -> mask -> pad ->
EpochBatchIterator -> host->device transfer — instead of a staged device
batch, so input-pipeline overheads are included in the number.
"""

import json
import os
import sys
import time
from argparse import Namespace

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _require_tpu():
    """A measurement path that finds no chip fails — at once, with no
    retry loop and no CPU row.  Returns the devices."""
    import jax

    from unicore_tpu.platform_utils import on_tpu

    if not on_tpu():
        sys.stderr.write(
            f"bench: no TPU (default backend {jax.default_backend()!r}); "
            "bench.py measures the device and never falls back to the "
            "CPU\n"
        )
        sys.exit(3)
    return jax.devices()


def _make_args():
    return Namespace(
        seed=1,
        bf16=True,
        fp16=False,
        bf16_sr=False,
        allreduce_fp32_grad=False,
        fp16_init_scale=4,
        fp16_scale_window=None,
        min_loss_scale=1e-4,
        clip_norm=1.0,
        per_sample_clip_norm=0.0,
        data_parallel_size=-1,
        model_parallel_size=1,
        seq_parallel_size=1,
        pipeline_parallel_size=1,
        expert_parallel_size=1,
        zero_shard_optimizer=False,
        optimizer="adam",
        lr_scheduler="fixed",
        lr=[1e-4],
        adam_betas="(0.9, 0.98)",
        adam_eps=1e-6,
        weight_decay=1e-4,
        force_anneal=None,
        lr_shrink=0.1,
        warmup_updates=0,
        ema_decay=-1.0,
        validate_with_ema=False,
        max_update=10_000,
        update_freq=[1],
    )

def _build_config(config, args, batch_size, seq_len):
    """Returns (model, loss, task, sample, metric) for one bench config."""
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask

    vocab = 30522

    class _BenchTask(UnicoreTask):
        class _Dict:
            def pad(self):
                return 1

        dictionary = _Dict()

    task = _BenchTask(args)
    rng = np.random.RandomState(0)

    if config == "bert":
        model = BertModel(
            vocab_size=vocab,
            padding_idx=1,
            encoder_layers=12,
            encoder_embed_dim=768,
            encoder_ffn_embed_dim=3072,
            encoder_attention_heads=12,
            max_seq_len=seq_len,
            post_ln=True,
        )
        loss = LOSS_REGISTRY["masked_lm"](task)
        tokens = rng.randint(4, vocab, size=(batch_size, seq_len)).astype(np.int64)
        target = np.where(rng.rand(batch_size, seq_len) < 0.15, tokens, 1).astype(
            np.int64
        )
        sample = {"net_input": {"src_tokens": tokens}, "target": target}
        metric = f"bert_base_mlm_bf16_seq{seq_len}_samples_per_sec_per_chip"
    elif config == "unimol":
        from unicore_tpu.models.unimol import UniMolModel

        vsz = 32
        task._Dict.pad = lambda self: 0
        model = UniMolModel(
            vocab_size=vsz, padding_idx=0, encoder_layers=15,
            encoder_embed_dim=512, encoder_ffn_embed_dim=2048,
            encoder_attention_heads=64, max_seq_len=seq_len,
        )
        setattr(args, "masked_token_loss", 1.0)
        setattr(args, "masked_coord_loss", 5.0)
        setattr(args, "masked_dist_loss", 10.0)
        loss = LOSS_REGISTRY["unimol"](task)
        tokens = rng.randint(4, vsz, size=(batch_size, seq_len)).astype(np.int64)
        coords = rng.randn(batch_size, seq_len, 3).astype(np.float32)
        diff = coords[:, :, None] - coords[:, None, :]
        dist = np.sqrt((diff ** 2).sum(-1)).astype(np.float32)
        sample = {
            "net_input": {
                "src_tokens": tokens,
                "src_coord": coords,
                "src_distance": dist,
                "src_edge_type": (
                    tokens[:, :, None] * vsz + tokens[:, None, :]
                ).astype(np.int64),
            },
            "target": {
                "tokens_target": np.where(
                    rng.rand(batch_size, seq_len) < 0.15, tokens, 0
                ).astype(np.int64),
                "coord_target": coords,
                "distance_target": dist,
            },
        }
        metric = f"unimol_pretrain_bf16_seq{seq_len}_samples_per_sec_per_chip"
    elif config == "evoformer":
        from unicore_tpu.models.evoformer_model import EvoformerModel

        vsz = 28
        task._Dict.pad = lambda self: 1
        R = int(os.environ.get("BENCH_MSA_ROWS", "32"))
        model = EvoformerModel(
            vocab_size=vsz, padding_idx=1, num_blocks=12,
            msa_dim=256, pair_dim=128, max_seq_len=seq_len,
            remat=True,  # deep pair stack: rematerialize to fit HBM
        )
        loss = LOSS_REGISTRY["masked_msa"](task)
        msa = rng.randint(4, vsz, size=(batch_size, R, seq_len)).astype(np.int64)
        sample = {
            "net_input": {"src_msa": msa},
            "target": np.where(
                rng.rand(batch_size, R, seq_len) < 0.15, msa, 1
            ).astype(np.int64),
        }
        metric = f"evoformer_masked_msa_bf16_L{seq_len}_samples_per_sec_per_chip"
    elif config == "moe":
        # BERT-base body with a top-2 routed expert FFN every other layer —
        # times the scatter dispatch path (modules/moe.py) end to end
        E = int(os.environ.get("BENCH_MOE_EXPERTS", "8"))
        model = BertModel(
            vocab_size=vocab,
            padding_idx=1,
            encoder_layers=12,
            encoder_embed_dim=768,
            encoder_ffn_embed_dim=3072,
            encoder_attention_heads=12,
            max_seq_len=seq_len,
            post_ln=True,
            moe_experts=E,
            moe_every=2,
            moe_top_k=2,
        )
        loss = LOSS_REGISTRY["masked_lm_moe"](task, moe_aux_loss_weight=0.01)
        tokens = rng.randint(4, vocab, size=(batch_size, seq_len)).astype(np.int64)
        target = np.where(rng.rand(batch_size, seq_len) < 0.15, tokens, 1).astype(
            np.int64
        )
        sample = {"net_input": {"src_tokens": tokens}, "target": target}
        metric = (
            f"bert_base_moe{E}_top2_bf16_seq{seq_len}_samples_per_sec_per_chip"
        )
    else:
        raise ValueError(f"unknown BENCH_CONFIG {config}")
    return model, loss, task, sample, metric


#: per-chip bf16 peak FLOP/s keyed by ``device_kind`` exactly as JAX
#: reports it, each with its source.  A kind that is not here is an error,
#: never a default or a substring guess.
_PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip;
    # a v5e chip reports itself as "TPU v5 lite" (chip_smoke.py, PR 23)
    "TPU v5 lite": 197e12,
}


def _peak_flops(device_kind):
    """Per-chip bf16 peak FLOP/s of ``device_kind``; an unknown kind
    raises (MFU against a guessed peak is not a measurement)."""
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"bench: device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(_PEAK_BF16_FLOPS)}); add it with its source"
        ) from None


def _model_flops(trainer, sample):
    """FLOPs of ONE training step from XLA's cost analysis of the lowered
    (not compiled — cheap) jitted step.  Pallas custom calls are opaque to
    the analysis, so the flash-eligibility check is patched off for this one
    trace: the fused-softmax XLA path computes the same attention matmuls,
    which the analysis then counts.  Returns None when unavailable."""
    import unicore_tpu.modules.multihead_attention as mha

    fn = trainer._jit_cache.get("train_step")
    if fn is None:
        return None
    orig = mha._flash_ok
    mha._flash_ok = lambda *a, **kw: (False, None)  # route to XLA attention
    try:
        lowered = fn.lower(
            trainer.state, sample, trainer._step_scalars(0, 1.0),
            trainer._macc,
        )
        ca = lowered.cost_analysis()
    except Exception as e:
        sys.stderr.write(f"bench: flops estimate failed: {e!r}\n")
        return None
    finally:
        mha._flash_ok = orig
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = (ca or {}).get("flops", 0.0)
    return float(flops) if flops and flops > 0 else None


def _finish_result(result, trainer, sample, dt_per_step):
    """Attach ms/step, device kind, FLOPs and MFU to a throughput result."""
    import jax

    result["ms_per_step"] = round(dt_per_step * 1000, 2)
    kind = _device_kind()
    n_chips = jax.device_count()
    result["device_kind"] = kind
    peak = _peak_flops(kind)
    flops = _model_flops(trainer, sample)
    if flops:
        result["flops_per_step"] = flops
        # cost_analysis counts the whole global SPMD step: utilization
        # is against the aggregate peak of all participating chips
        result["mfu"] = round(flops / dt_per_step / (peak * n_chips), 4)
    return result


_RUN_ID = f"{int(time.time())}-{os.getpid()}"


def _telemetry_identity():
    """(run_id, journal path) for this bench invocation: bench rows join
    the same telemetry identity space as training runs and checkpoints
    (docs/observability.md).  The journal lands beside the trace
    artifacts; failures degrade to empty fields, never a lost row."""
    try:
        import argparse

        from unicore_tpu import telemetry

        telemetry.configure(
            argparse.Namespace(
                save_dir=None,
                telemetry_dir=os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "bench_traces", "telemetry",
                ),
                telemetry_sample_interval=0,
                profile_steps=None,
            ),
            rank=0,
            role="bench",
        )
        return telemetry.run_id() or "", telemetry.journal_path() or ""
    except Exception as e:
        sys.stderr.write(f"bench: telemetry identity failed: {e!r}\n")
        return "", ""


def _device_kind():
    """``jax.devices()[0].device_kind``; a failed lookup raises."""
    import jax

    return jax.devices()[0].device_kind


def _label_row(row):
    """Attach the device_kind label in place (shared by every config so
    the labeling can't drift per bench)."""
    row["device_kind"] = _device_kind()
    return row


def _append_partial(result):
    """Append the result line to BENCH_PARTIAL.jsonl immediately — a hang in
    a later config must not lose an earlier config's number.  Lines carry a
    per-invocation run id; each (run, metric) pair appends exactly ONCE,
    fully labeled."""
    try:
        line = dict(result)
        line["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
        line["run"] = _RUN_ID
        run_id, journal = _telemetry_identity()
        line["run_id"] = run_id
        line["telemetry_journal"] = journal
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_PARTIAL.jsonl")
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError as e:
        sys.stderr.write(f"bench: partial write failed: {e!r}\n")
        return
    try:  # journal mirror: same degrade-to-nothing contract as above —
        # a telemetry failure must never lose (or abort) a bench row
        from unicore_tpu import telemetry as _telemetry

        _telemetry.emit("bench-row", **{
            k: v for k, v in line.items()
            if k not in ("run_id", "telemetry_journal")
        })
    except Exception as e:
        sys.stderr.write(f"bench: journal mirror failed: {e!r}\n")


def _save_trace(trainer, sample, config):
    """2-step profiler trace artifact for offline review (BENCH_TRACE=0
    disables)."""
    if os.environ.get("BENCH_TRACE", "1") in ("0", "false"):
        return
    import jax

    logdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench_traces", config)
    try:
        import shutil

        shutil.rmtree(logdir, ignore_errors=True)
        with jax.profiler.trace(logdir):
            for _ in range(2):
                trainer.train_step([sample])
            _force_params(trainer)
    except Exception as e:
        sys.stderr.write(f"bench: trace capture failed: {e!r}\n")


def _force_params(trainer):
    # completion barrier: fetch a value that depends on the last step
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(trainer.state["params"])[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def run_config(config):
    import jax

    from unicore_tpu.trainer import Trainer

    batch_size = int(os.environ.get(
        "BENCH_BATCH", "64" if config in ("bert", "moe") else "8"
    ))
    seq_len = int(os.environ.get(
        "BENCH_SEQ", "512" if config in ("bert", "moe") else "256"
    ))
    warmup, iters = 3, 10

    args = _make_args()
    model, loss, task, sample, metric = _build_config(
        config, args, batch_size, seq_len
    )

    trainer = Trainer(args, task, model, loss)
    # measure the training step itself: stage the batch on device once (the
    # input pipeline overlaps transfers in real runs)
    trainer.init_state(sample)
    sample = trainer._prepare_sample(sample)

    for _ in range(warmup):
        trainer.train_step([sample])
    _force_params(trainer)

    t0 = time.perf_counter()
    for _ in range(iters):
        trainer.train_step([sample])
    _force_params(trainer)
    dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    result = {
        "metric": metric,
        "value": round(batch_size * iters / dt / n_chips, 2),
        "unit": "samples/s/chip",
        "vs_baseline": None,
    }
    _append_partial(result)  # raw number first — diagnostics can hang
    _finish_result(result, trainer, sample, dt / iters)
    _append_partial(result)
    _save_trace(trainer, sample, config)
    return result


# ---------------------------------------------------------------------------
# serving mode (BENCH_CONFIG=serve): continuous-batching inference engine
# ---------------------------------------------------------------------------

def run_serve_bench():
    """Latency/throughput of the REAL serving plane (unicore_tpu/serve/):
    warmed bucket programs, bounded admission, bucket-affine continuous
    batching — offered load just under the shedding point so the number
    is sustained throughput, not shed accounting.  Emits req/s plus
    p50/p90/p99 latency (CPU fallback rows labeled like every other
    config — liveness proof, not a perf claim)."""
    import jax

    from unicore_tpu.checkpoint.emergency import Deadline
    from unicore_tpu.data.data_utils import compute_length_buckets
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.serve import ServeEngine, build_infer_fn

    batch_size = int(os.environ.get("BENCH_BATCH", "16"))
    seq_len = int(os.environ.get("BENCH_SEQ", "256"))
    n_buckets = int(os.environ.get("BENCH_SERVE_BUCKETS", "4"))
    duration = float(os.environ.get("BENCH_SERVE_SECONDS", "10"))
    vocab = 30522

    model = BertModel(
        vocab_size=vocab,
        padding_idx=1,
        encoder_layers=12,
        encoder_embed_dim=768,
        encoder_ffn_embed_dim=3072,
        encoder_attention_heads=12,
        max_seq_len=seq_len,
        post_ln=True,
    )
    rng = np.random.RandomState(0)
    sample = {
        "net_input": {
            "src_tokens": rng.randint(
                4, vocab, size=(batch_size, seq_len)
            ).astype(np.int64)
        }
    }
    variables = model.init_params(jax.random.PRNGKey(0), sample)
    infer_fn, cache_probe = build_infer_fn(model)
    edges = compute_length_buckets(n_buckets, seq_len) or (seq_len,)
    engine = ServeEngine(
        variables,
        infer_fn,
        bucket_edges=edges,
        batch_size=batch_size,
        pad_idx=1,
        admission_capacity=max(64, batch_size * 8),
        cache_size_probe=cache_probe,
    )
    programs = engine.warmup()
    engine.start()

    lengths = [max(1, e - 1) for e in edges]
    t0 = time.perf_counter()
    t_end = t0 + duration
    i = 0
    while time.perf_counter() < t_end:
        if engine.queue.depth() >= engine.queue.capacity - batch_size:
            # stay just under the shedding point: this measures sustained
            # service, the chaos smoke measures shedding
            time.sleep(0.001)
            continue
        engine.submit([5] * lengths[i % len(lengths)], 600.0)
        i += 1
    engine.drain(Deadline(300.0))
    elapsed = time.perf_counter() - t0

    stats = engine.stats()
    result = {
        "metric": f"serve_bert_base_seq{seq_len}_req_per_sec",
        "value": round(stats["served"] / elapsed, 2),
        "unit": "req/s",
        "vs_baseline": None,
        "served": stats["served"],
        "shed": sum(stats["shed"].values()),
        "batches": stats["batches"],
        "bucket_programs": programs,
        "recompiles_after_warmup": stats["recompiles_after_warmup"],
    }
    for k in ("p50_ms", "p90_ms", "p99_ms"):
        if k in stats:
            result[k] = stats[k]
    _append_partial(_label_row(result))
    return result


# ---------------------------------------------------------------------------
# quantized serving (BENCH_CONFIG=serve-quant): int8 vs bf16, same load
# ---------------------------------------------------------------------------

def run_serve_quant_bench():
    """int8 vs bf16 serving throughput at IDENTICAL offered load
    (docs/serving.md "Quantized inference"): two engines over the same
    model/weights — one bf16-cast, one calibrate.prepare()d int8 — each
    driven by the same paced request schedule (BENCH_QUANT_QPS), so the
    req/s + p99 rows compare precision paths, not admission luck.  Rows
    carry the calibration drift bound so throughput is never quoted
    without its quality contract.  CPU fallback rows are labeled like
    every other config — liveness proof, not a perf claim."""
    import jax
    import jax.numpy as jnp

    from unicore_tpu.checkpoint.emergency import Deadline
    from unicore_tpu.data.data_utils import compute_length_buckets
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.quant import calibrate
    from unicore_tpu.serve import ServeEngine, build_infer_fn

    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    seq_len = int(os.environ.get("BENCH_SEQ", "128"))
    n_buckets = int(os.environ.get("BENCH_SERVE_BUCKETS", "2"))
    duration = float(os.environ.get("BENCH_SERVE_SECONDS", "10"))
    qps = float(os.environ.get("BENCH_QUANT_QPS", "50"))
    layers = int(os.environ.get("BENCH_QUANT_LAYERS", "4"))
    embed = int(os.environ.get("BENCH_QUANT_EMBED", "256"))
    vocab = 30522

    model = BertModel(
        vocab_size=vocab,
        padding_idx=1,
        encoder_layers=layers,
        encoder_embed_dim=embed,
        encoder_ffn_embed_dim=4 * embed,
        encoder_attention_heads=max(4, embed // 64),
        max_seq_len=seq_len,
        post_ln=True,
    )
    rng = np.random.RandomState(0)
    sample = {
        "net_input": {
            "src_tokens": rng.randint(
                4, vocab, size=(batch_size, seq_len)
            ).astype(np.int64)
        }
    }
    variables = model.init_params(jax.random.PRNGKey(0), sample)
    edges = compute_length_buckets(n_buckets, seq_len) or (seq_len,)

    def to_bf16(x):
        x = jnp.asarray(x)
        return x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x

    arms = [("bf16", model, jax.tree_util.tree_map(to_bf16, variables),
             None)]
    model_q = model.clone(quantize="int8")
    prepared, qinfo = calibrate.calibrate_for_serving(
        model_q, model, variables, mode="int8", snapshot_path=None,
        vocab_size=vocab, pad_idx=1, bucket_edges=edges,
        batch_size=batch_size, persist=False,
    )
    arms.append(("int8", model_q, jax.device_put(prepared), qinfo))

    last = None
    for precision, m, v, arm_qinfo in arms:
        infer_fn, cache_probe = build_infer_fn(m)
        engine = ServeEngine(
            v,
            infer_fn,
            bucket_edges=edges,
            batch_size=batch_size,
            pad_idx=1,
            admission_capacity=max(64, batch_size * 8),
            cache_size_probe=cache_probe,
            precision=precision,
        )
        programs = engine.warmup()
        engine.start()
        lengths = [max(1, e - 1) for e in edges]
        t0 = time.perf_counter()
        t_end = t0 + duration
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            # identical offered schedule per arm: request i is DUE at
            # t0 + i/qps regardless of how this arm is keeping up
            target = t0 + i / qps
            if now < target:
                time.sleep(min(target - now, 0.01))
                continue
            engine.submit([5] * lengths[i % len(lengths)], 600.0)
            i += 1
        engine.drain(Deadline(300.0))
        elapsed = time.perf_counter() - t0

        stats = engine.stats()
        row = {
            "metric": (
                f"serve_quant_bert_l{layers}e{embed}_seq{seq_len}_"
                f"{precision}_req_per_sec"
            ),
            "value": round(stats["served"] / elapsed, 2),
            "unit": "req/s",
            "vs_baseline": None,
            "precision": precision,
            "offered_qps": qps,
            "offered": i,
            "served": stats["served"],
            "shed": sum(stats["shed"].values()),
            "batches": stats["batches"],
            "bucket_programs": programs,
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
            "encoder_layers": layers,
            "embed_dim": embed,
        }
        for k in ("p50_ms", "p90_ms", "p99_ms"):
            if k in stats:
                row[k] = stats[k]
        if arm_qinfo is not None:
            row["quant_rel_drift"] = round(arm_qinfo["rel_drift"], 6)
            row["quant_sites"] = arm_qinfo["sites"]
        _append_partial(_label_row(row))
        print(json.dumps(row), flush=True)
        last = row
    return last


# ---------------------------------------------------------------------------
# incremental decode (BENCH_CONFIG=decode): fp32-KV vs int8-KV tokens/s
# ---------------------------------------------------------------------------

def run_decode_bench():
    """Token throughput of the incremental-decode plane (docs/serving.md
    "Incremental decode"): a fp32-KV and an int8-KV DecodeEngine over
    the SAME transformer-LM weights, each driven by the same paced
    request schedule (BENCH_DECODE_QPS), every request generating a
    fixed token budget — so tokens/s + per-token p50/p99 compare KV
    precisions, not admission luck.  Rows carry page occupancy and the
    one-program-per-cache-bucket + zero-recompile counters.  CPU
    fallback rows are labeled like every other config — liveness proof,
    not a perf claim."""
    import jax

    from unicore_tpu.checkpoint.emergency import Deadline
    from unicore_tpu.models.transformer_lm import TransformerLMModel
    from unicore_tpu.serve import DecodeEngine, cache_bucket_edges

    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    seq_len = int(os.environ.get("BENCH_SEQ", "128"))
    n_buckets = int(os.environ.get("BENCH_SERVE_BUCKETS", "2"))
    duration = float(os.environ.get("BENCH_DECODE_SECONDS", "10"))
    qps = float(os.environ.get("BENCH_DECODE_QPS", "8"))
    layers = int(os.environ.get("BENCH_DECODE_LAYERS", "4"))
    embed = int(os.environ.get("BENCH_DECODE_EMBED", "256"))
    max_new = int(os.environ.get("BENCH_DECODE_MAX_NEW", "16"))
    page_size = 32
    vocab = 512

    model = TransformerLMModel(
        vocab_size=vocab,
        padding_idx=1,
        decoder_layers=layers,
        decoder_embed_dim=embed,
        decoder_ffn_embed_dim=4 * embed,
        decoder_attention_heads=max(4, embed // 64),
        dropout=0.0,
        emb_dropout=0.0,
        attention_dropout=0.0,
        activation_dropout=0.0,
        max_seq_len=seq_len,
    )
    rng = np.random.RandomState(0)
    sample = {
        "net_input": {
            "src_tokens": rng.randint(
                4, vocab, size=(batch_size, seq_len)
            ).astype(np.int64)
        }
    }
    variables = model.init_params(jax.random.PRNGKey(0), sample)
    edges = cache_bucket_edges(seq_len, n_buckets, page_size=page_size)
    # prompts leave max_new rows of cache headroom below the top bucket
    lengths = [max(4, min(e, edges[-1] - max_new) - 1) for e in edges]
    num_pages = max(
        64, batch_size * 4 * ((edges[-1] + page_size - 1) // page_size)
    )

    last = None
    for kv in ("fp32", "int8"):
        engine = DecodeEngine(
            model,
            variables,
            bucket_edges=edges,
            decode_batch=batch_size,
            page_size=page_size,
            num_pages=num_pages,
            pad_idx=1,
            eos_idx=-1,  # fixed token budget: every request decodes max_new
            vocab_size=vocab,
            kv_dtype=kv,
            max_new_tokens=max_new,
            admission_capacity=max(64, batch_size * 8),
            precision="int8-kv" if kv == "int8" else "",
        )
        programs = engine.warmup()
        engine.start()
        t0 = time.perf_counter()
        t_end = t0 + duration
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            # identical offered schedule per arm: request i is DUE at
            # t0 + i/qps regardless of how this arm is keeping up
            target = t0 + i / qps
            if now < target:
                time.sleep(min(target - now, 0.01))
                continue
            engine.submit([5] * lengths[i % len(lengths)], 600.0)
            i += 1
        engine.drain(Deadline(300.0))
        elapsed = time.perf_counter() - t0
        engine.stop()

        stats = engine.stats()
        row = {
            "metric": (
                f"decode_lm_l{layers}e{embed}_seq{seq_len}_"
                f"{kv}_kv_tokens_per_sec"
            ),
            "value": round(stats["tokens_generated"] / elapsed, 2),
            "unit": "tok/s",
            "vs_baseline": None,
            "kv_dtype": kv,
            "offered_qps": qps,
            "offered": i,
            "served": stats["served"],
            "shed": sum(stats["shed"].values()),
            "tokens_generated": stats["tokens_generated"],
            "decode_steps": stats["decode_steps"],
            "prefill_batches": stats["prefill_batches"],
            "preempted": stats["preempted"],
            "requeued": stats["requeued"],
            "cache_pages": num_pages,
            "cache_page_occupancy": stats["cache_page_occupancy"],
            "max_new_tokens": max_new,
            "bucket_programs": programs,
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
            "decoder_layers": layers,
            "embed_dim": embed,
        }
        for k in ("token_p50_ms", "token_p90_ms", "token_p99_ms"):
            if k in stats:
                row[k] = stats[k]
        _append_partial(_label_row(row))
        print(json.dumps(row), flush=True)
        last = row
    return last


# ---------------------------------------------------------------------------
# serving fleet (BENCH_CONFIG=fleet): N replicas behind the router
# ---------------------------------------------------------------------------

def run_fleet_bench():
    """Aggregate throughput of the REAL fleet path at N ∈ {1,2,3}
    replicas: each replica is a full ServeEngine + HTTP plane, lease-
    registered through a file KV; the router balances by the published
    admission estimates (p2c) and every request crosses the real proxy
    leg.  A closed-loop pool of BENCH_FLEET_WORKERS drives each N for
    BENCH_FLEET_SECONDS; one req/s + p50/p99 row per N.  All replicas
    share this host's cores, so CPU rows measure fleet-plane overhead
    and liveness, not scaling — labeled like every other config."""
    import tempfile
    import threading

    import jax

    from unicore_tpu.checkpoint.emergency import Deadline
    from unicore_tpu.data.data_utils import compute_length_buckets
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.serve import ServeEngine, build_infer_fn
    from unicore_tpu.serve.fleet import (
        FleetView, ReplicaRegistrar, RouterEngine, open_fleet_kv,
    )
    from unicore_tpu.serve.http import bind_server

    batch_size = int(os.environ.get("BENCH_BATCH", "4"))
    seq_len = int(os.environ.get("BENCH_SEQ", "64"))
    n_buckets = int(os.environ.get("BENCH_SERVE_BUCKETS", "2"))
    duration = float(os.environ.get("BENCH_FLEET_SECONDS", "8"))
    workers = int(os.environ.get("BENCH_FLEET_WORKERS", "8"))
    layers = int(os.environ.get("BENCH_FLEET_LAYERS", "2"))
    embed = int(os.environ.get("BENCH_FLEET_EMBED", "128"))
    vocab = 30522

    model = BertModel(
        vocab_size=vocab,
        padding_idx=1,
        encoder_layers=layers,
        encoder_embed_dim=embed,
        encoder_ffn_embed_dim=4 * embed,
        encoder_attention_heads=max(4, embed // 64),
        max_seq_len=seq_len,
        post_ln=True,
    )
    rng = np.random.RandomState(0)
    sample = {
        "net_input": {
            "src_tokens": rng.randint(
                4, vocab, size=(batch_size, seq_len)
            ).astype(np.int64)
        }
    }
    variables = model.init_params(jax.random.PRNGKey(0), sample)
    edges = compute_length_buckets(n_buckets, seq_len) or (seq_len,)
    lengths = [max(1, e - 1) for e in edges]

    last = None
    for n_replicas in (1, 2, 3):
        engines, servers, registrars = [], [], []
        with tempfile.TemporaryDirectory() as kv_root:
            client = open_fleet_kv(kv_root)
            for i in range(n_replicas):
                infer_fn, cache_probe = build_infer_fn(model)
                eng = ServeEngine(
                    variables, infer_fn, bucket_edges=edges,
                    batch_size=batch_size, pad_idx=1,
                    admission_capacity=max(64, batch_size * 8),
                    cache_size_probe=cache_probe,
                )
                eng.warmup()
                eng.start()
                srv = bind_server("127.0.0.1", 0, eng,
                                  read_timeout_s=10.0)
                srv.start()
                reg = ReplicaRegistrar(
                    client, f"b{i}",
                    f"http://127.0.0.1:{srv.server_address[1]}",
                    interval_s=0.5,
                    ready_fn=eng.ready,
                    est_delay_fn=eng.queue.estimated_delay,
                    digest_fn=lambda: "bench",
                    served_fn=lambda e=eng: e.served,
                ).start()
                engines.append(eng)
                servers.append(srv)
                registrars.append(reg)
            view = FleetView(client, timeout=30.0)
            view.poll_once()
            router = RouterEngine(view)
            stop = threading.Event()
            counts = {"ok": 0, "fail": 0}
            lock = threading.Lock()

            def drive(widx):
                i = widx
                while not stop.is_set():
                    code, _ = router.handle_infer(
                        {"tokens": [5] * lengths[i % len(lengths)],
                         "deadline_ms": 60000.0, "id": f"w{widx}-{i}"},
                        Deadline(60.0),
                    )
                    with lock:
                        counts["ok" if code == 200 else "fail"] += 1
                    i += len(lengths)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=drive, args=(w,))
                for w in range(workers)
            ]
            for t in threads:
                t.start()
            time.sleep(duration)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            elapsed = time.perf_counter() - t0
            for reg in registrars:
                reg.stop(goodbye=True)
            for eng in engines:
                eng.drain(Deadline(60.0))
            for srv in servers:
                srv.shutdown()

            stats = router.stats()
            row = {
                "metric": (
                    f"fleet_bert_l{layers}e{embed}_seq{seq_len}_"
                    f"n{n_replicas}_req_per_sec"
                ),
                "value": round(counts["ok"] / elapsed, 2),
                "unit": "req/s",
                "vs_baseline": None,
                "replicas": n_replicas,
                "workers": workers,
                "served": counts["ok"],
                "failed": counts["fail"],
                "retries": stats["retries"],
                "shed": sum(stats["shed"].values()),
                "by_replica": stats["by_replica"],
                "encoder_layers": layers,
                "embed_dim": embed,
            }
            for k in ("p50_ms", "p90_ms", "p99_ms"):
                if k in stats:
                    row[k] = stats[k]
            _append_partial(_label_row(row))
            print(json.dumps(row), flush=True)
            last = row
    return last


# ---------------------------------------------------------------------------
# fused-kernel shootout (BENCH_CONFIG=kernels)
# ---------------------------------------------------------------------------

def _time_fn(fn, *args, warmup=2, iters=None):
    """Median wall ms per call of a jitted fn (completion via jax.block_until_ready)."""
    import jax

    if iters is None:
        iters = int(os.environ.get("BENCH_KERNEL_ITERS", "5"))
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1000)
    times.sort()
    return times[len(times) // 2]


def _kernel_row(metric, jnp_ms, fused_ms, extra=None):
    import jax

    row = {
        "metric": metric,
        "value": round(fused_ms, 3),
        "unit": "ms/call",
        "vs_baseline": None,
        "jnp_ms": round(jnp_ms, 3),
        "fused_ms": round(fused_ms, 3),
        "speedup": round(jnp_ms / fused_ms, 3) if fused_ms > 0 else None,
    }
    if extra:
        row.update(extra)
    _append_partial(_label_row(row))
    print(json.dumps(row), flush=True)
    return row


def run_kernel_bench():
    """jnp-vs-fused rows for the device-side kernel suite (ROADMAP item 2):
    each row times BOTH implementations of one op under jit — the win is a
    measured number, not an assertion."""
    import importlib

    import jax
    import jax.numpy as jnp

    rows = int(os.environ.get("BENCH_KERNEL_ROWS", "2048"))
    seq = int(os.environ.get("BENCH_KERNEL_SEQ", "1024"))
    dim = int(os.environ.get("BENCH_KERNEL_DIM", "1024"))

    results = []
    rng = np.random.RandomState(0)
    key = None

    # -- softmax_dropout: fwd+bwd at training dropout -------------------
    sd = importlib.import_module("unicore_tpu.ops.softmax_dropout")
    x = jnp.asarray(rng.randn(rows, seq).astype(np.float32)).reshape(
        rows // 8, 8, seq
    )
    bias = jnp.asarray(rng.randn(1, 8, seq).astype(np.float32))
    key = jax.random.PRNGKey(0)

    def sd_loss(impl, x_, b_):
        out = impl(x_, 0.1, is_training=True, bias=b_, dropout_rng=key)
        return jnp.sum(out * out)

    jnp_fn = jax.jit(jax.grad(lambda x_: sd_loss(
        sd.softmax_dropout_reference, x_, bias)))
    sd.set_softmax_dropout_mode("on")
    try:
        fused_fn = jax.jit(jax.grad(lambda x_: sd_loss(
            sd.softmax_dropout, x_, bias)))
        results.append(_kernel_row(
            f"kernels_softmax_dropout_r{rows}_L{seq}_fwdbwd",
            _time_fn(jnp_fn, x), _time_fn(fused_fn, x),
        ))
    finally:
        sd.set_softmax_dropout_mode(None)

    # -- layer norm: fwd+bwd --------------------------------------------
    from unicore_tpu.ops.fused_norm import fused_layer_norm

    xn = jnp.asarray(rng.randn(rows * 8, dim).astype(np.float32))
    w = jnp.ones((dim,), jnp.float32)
    b = jnp.zeros((dim,), jnp.float32)

    def ln_jnp(x_, w_, b_):
        xf = x_.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        return ((xf - mean) * jax.lax.rsqrt(var + 1e-5) * w_ + b_).astype(x_.dtype)

    jnp_ln = jax.jit(jax.grad(lambda x_: jnp.sum(ln_jnp(x_, w, b) ** 2)))
    pal_ln = jax.jit(jax.grad(
        lambda x_: jnp.sum(fused_layer_norm(x_, w, b) ** 2)))
    results.append(_kernel_row(
        f"kernels_layernorm_n{rows * 8}_d{dim}_fwdbwd",
        _time_fn(jnp_ln, xn), _time_fn(pal_ln, xn),
    ))

    # -- Adam: tree_map vs fused multi-tensor (runs NATIVELY everywhere —
    # the fused path is flat-buffer XLA, not a Pallas kernel) -----------
    from argparse import Namespace as _NS

    from unicore_tpu.optim import OPTIMIZER_REGISTRY

    n_leaves = int(os.environ.get("BENCH_KERNEL_LEAVES", "48"))
    params = {
        f"layer{i}": {
            "kernel": jnp.asarray(rng.randn(dim, dim).astype(np.float32)),
            "bias": jnp.asarray(rng.randn(dim).astype(np.float32)),
        }
        for i in range(n_leaves // 2)
    }
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)), params
    )

    def adam_args(fused):
        return _NS(
            optimizer="adam", lr=[1e-3], adam_betas="(0.9, 0.999)",
            adam_eps=1e-8, weight_decay=0.01, bf16_sr=False,
            no_weight_decay_names="", fused_adam=fused,
        )

    def make_step(fused):
        opt = OPTIMIZER_REGISTRY["adam"](adam_args(fused))
        state = opt.init_state(params)

        @jax.jit
        def step(g, s, p):
            # the clip rides the fused path too (trainer wiring)
            g, _ = opt.clip_grad_norm(g, 1.0)
            return opt.update(g, s, p, 1e-3)

        return step, state

    tree_step, tree_state = make_step(False)
    fused_step, fused_state = make_step(True)
    results.append(_kernel_row(
        f"kernels_adam_clip_update_{n_leaves}leaves_d{dim}",
        _time_fn(tree_step, grads, tree_state, params),
        _time_fn(fused_step, grads, fused_state, params),
    ))
    return {"metric": "kernels_suite", "rows": len(results),
            "vs_baseline": None}


# ---------------------------------------------------------------------------
# end-to-end input-pipeline mode (BENCH_PIPELINE=1, bert config)
# ---------------------------------------------------------------------------

def _ensure_pipeline_data(data_dir, n_docs, words_per_doc):
    """Synthesize long documents into the native indexed-shard format +
    dict.txt so the REAL bert task pipeline (tokenize -> mask -> pad ->
    batch) runs at the benchmark sequence length."""
    # key the cache on the corpus parameters so a BENCH_SEQ/BENCH_BATCH
    # change regenerates instead of silently measuring stale data
    data_dir = os.path.join(data_dir, f"d{n_docs}_w{words_per_doc}")
    if os.path.exists(os.path.join(data_dir, "train.idx")):
        return data_dir
    os.makedirs(data_dir, exist_ok=True)
    from unicore_tpu.data.indexed_dataset import make_builder

    words = (
        "the of and to in a is that for it as was with be by on not he this "
        "are or his from at which but have an they you were her she all would "
        "there been one their we him two has when who will more no if out so "
        "molecule protein structure energy atom bond model train learn deep"
    ).split()
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + sorted(set(words))
    with open(os.path.join(data_dir, "dict.txt"), "w") as f:
        f.write("\n".join(vocab) + "\n")
    rng = np.random.RandomState(7)
    builder = make_builder(os.path.join(data_dir, "train"))
    for _ in range(n_docs):
        builder.add_item(" ".join(rng.choice(words, size=words_per_doc)))
    builder.finalize()
    return data_dir


def make_pipeline_task(batch_size, seq_len, n_batches, base_args=None):
    """The REAL bert data pipeline at the bench config: synthesize/reuse an
    on-disk corpus sized for ``n_batches`` and return the loaded task.
    Shared by the on-TPU pipeline bench below and the host-only
    scripts/bench_input_pipeline.py so both measure the SAME configuration."""
    from unicore_tpu.tasks import TASK_REGISTRY

    data_dir = os.environ.get("BENCH_DATA", "/tmp/unicore_bench_data")
    # words_per_doc > seq_len so tokenization fills the whole sequence
    data_dir = _ensure_pipeline_data(
        data_dir, n_docs=batch_size * n_batches,
        words_per_doc=seq_len + 64,
    )
    args = base_args if base_args is not None else Namespace(seed=1)
    args.data = data_dir
    args.max_seq_len = seq_len
    args.mask_prob = 0.15
    args.leave_unmasked_prob = 0.1
    args.random_token_prob = 0.1
    args.seq_pad_multiple = 128
    args.batch_size = batch_size
    task = TASK_REGISTRY["bert"].setup_task(args)
    task.load_dataset("train")
    return task, args


def pipeline_batches(task, batch_size, num_workers=2, data_buffer_size=4):
    """Endless epoch-wrapped batch generator over the pipeline task."""
    epoch = 1
    while True:
        itr = task.get_batch_iterator(
            task.datasets["train"], batch_size=batch_size, seed=1,
            epoch=epoch, num_workers=num_workers,
            data_buffer_size=data_buffer_size,
        ).next_epoch_itr(shuffle=True)
        yield from itr
        epoch += 1


def run_pipeline_bench():
    """samples/s with the full data path in the loop (the staged-batch
    number excludes the input pipeline)."""
    import jax

    from unicore_tpu.trainer import Trainer

    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    seq_len = int(os.environ.get("BENCH_SEQ", "512"))
    warmup, iters = 3, 10

    task, args = make_pipeline_task(
        batch_size, seq_len, warmup + iters + 2, base_args=_make_args()
    )
    from unicore_tpu.models.bert import BertModel

    model = BertModel(
        vocab_size=len(task.dictionary), padding_idx=task.dictionary.pad(),
        encoder_layers=12, encoder_embed_dim=768, encoder_ffn_embed_dim=3072,
        encoder_attention_heads=12, max_seq_len=seq_len, post_ln=True,
    )
    from unicore_tpu.losses import LOSS_REGISTRY

    loss = LOSS_REGISTRY["masked_lm"](task)
    trainer = Trainer(args, task, model, loss)

    gen = pipeline_batches(task, batch_size)
    first = next(gen)
    trainer.init_state(first)
    trainer.train_step([first])  # compile
    for _ in range(warmup - 1):
        trainer.train_step([next(gen)])
    _force_params(trainer)

    n = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        batch = next(gen)
        n += len(batch["target"])
        trainer.train_step([batch])
    _force_params(trainer)
    dt = time.perf_counter() - t0

    result = {
        "metric": f"bert_base_mlm_bf16_seq{seq_len}_e2e_pipeline_samples_per_sec_per_chip",
        "value": round(n / dt / jax.device_count(), 2),
        "unit": "samples/s/chip",
        "vs_baseline": None,
    }
    _append_partial(result)  # raw number first — diagnostics can hang
    staged = trainer._prepare_sample(first)
    _finish_result(result, trainer, staged, dt / iters)
    _append_partial(result)
    _save_trace(trainer, staged, "bert_pipeline")
    return result


# ---------------------------------------------------------------------------
# memory-headroom mode (BENCH_CONFIG=memory): max trainable params per chip
# ---------------------------------------------------------------------------

def _memory_probe(stage, accum, remat, embed, vocab, batch, seq, uf):
    """Compile (AOT, no training) the real train program for one config at
    one model width; return (param_count, per-device peak_bytes from the
    compiler's memory analysis)."""
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    args = _make_args()
    args.zero_shard_optimizer = False
    args.zero_stage = stage
    args.grad_accum = accum
    args.fused_adam = True
    args.update_freq = [uf]
    args.fusion_audit = False
    args.no_weight_decay_names = ""

    class _MemTask(UnicoreTask):
        class _Dict:
            def pad(self):
                return 1

        dictionary = _Dict()

    task = _MemTask(args)
    model = BertModel(
        vocab_size=vocab, padding_idx=1, encoder_layers=2,
        encoder_embed_dim=embed, encoder_ffn_embed_dim=4 * embed,
        encoder_attention_heads=8, max_seq_len=seq, post_ln=True,
        remat_policy=remat,
    )
    rng = np.random.RandomState(0)
    tokens = rng.randint(4, vocab, size=(batch, seq)).astype(np.int64)

    def mk(i):
        r = np.random.RandomState(i)
        return {
            "net_input": {"src_tokens": tokens},
            "target": np.where(
                r.rand(batch, seq) < 0.15, tokens, 1
            ).astype(np.int64),
        }

    trainer = Trainer(args, task, model, LOSS_REGISTRY["masked_lm"](task))
    trainer.init_state(mk(0))
    n_params = sum(
        int(np.prod(p.shape))
        for p in __import__("jax").tree_util.tree_leaves(
            trainer.state["params"]
        )
    )
    if uf > 1:
        trainer._get_jit(trainer._scan_jit_name())
        stacked = trainer._try_stack_microbatches([mk(i) for i in range(uf)])
        report = trainer.fusion_audit_scan(stacked)
    else:
        trainer._get_jit("train_step")
        sample, weight = trainer._prepare_sample_or_dummy(mk(0))
        report = trainer.fusion_audit(sample, weight)
    if report is None or "memory" not in report:
        raise RuntimeError("no memory analysis from the compiled program")
    return n_params, report["memory"]["peak_bytes"]


def run_memory_bench():
    """Max trainable parameters per chip at fixed batch, per config: walk a
    model-width ladder (exponential then bisect) until the compiled train
    program's per-device peak allocation exceeds the budget.  The budget
    is a dial (BENCH_MEMORY_BUDGET_GB): on CPU the row is a COMPARATIVE
    headroom number across {zero-stage} x {grad-accum} x {remat}, never an
    HBM claim — device_kind labels it like every other config."""
    import jax

    budget = float(os.environ.get("BENCH_MEMORY_BUDGET_GB", "2.0")) * 1024 ** 3
    batch = int(os.environ.get("BENCH_MEMORY_BATCH", "8"))
    seq = int(os.environ.get("BENCH_MEMORY_SEQ", "64"))
    uf = int(os.environ.get("BENCH_MEMORY_UF", "2"))
    vocab = int(os.environ.get("BENCH_MEMORY_VOCAB", "8192"))
    stages = [int(s) for s in os.environ.get(
        "BENCH_MEMORY_STAGES", "1,2,3").split(",") if s]
    accums = [a for a in os.environ.get(
        "BENCH_MEMORY_ACCUMS", "buffer,adama").split(",") if a]
    remats = [r for r in os.environ.get(
        "BENCH_MEMORY_REMATS", "none").split(",") if r]
    ladder = [int(x) for x in os.environ.get(
        "BENCH_MEMORY_LADDER",
        "128,192,256,384,512,768,1024,1536,2048,3072,4096").split(",")]

    device_kind = jax.devices()[0].device_kind
    rows = []
    for stage in stages:
        for accum in accums:
            for remat in remats:
                # feasibility is monotone in width, so walk the ladder in
                # order and keep the last width whose compiled program
                # fits — the cheap small-model probes come first, and the
                # expensive near-boundary ones are the same compiles a
                # bisection would pay for anyway
                feasible = None  # (ladder idx, n_params, peak)
                for i in range(len(ladder)):
                    try:
                        n, peak = _memory_probe(
                            stage, accum, remat, ladder[i], vocab, batch,
                            seq, uf,
                        )
                    except Exception as e:
                        sys.stderr.write(
                            f"bench memory: probe embed={ladder[i]} "
                            f"zero{stage}/{accum}/{remat} failed: {e!r}\n"
                        )
                        break
                    if peak > budget:
                        break
                    feasible = (i, n, peak)
                if feasible is None:
                    sys.stderr.write(
                        f"bench memory: zero{stage}/{accum}/{remat}: even "
                        f"embed={ladder[0]} exceeds the budget\n"
                    )
                    continue
                _, n_params, peak = feasible
                row = {
                    "metric": (
                        f"max_params_per_chip_zero{stage}_{accum}_"
                        f"remat-{remat}"
                    ),
                    "value": n_params,
                    "unit": "params",
                    "vs_baseline": None,
                    "zero_stage": stage,
                    "grad_accum": accum,
                    "remat_policy": remat,
                    "embed_dim": ladder[feasible[0]],
                    "peak_bytes": peak,
                    "budget_bytes": int(budget),
                    "batch_size": batch,
                    "seq_len": seq,
                    "update_freq": uf,
                    "n_chips": jax.device_count(),
                    "device_kind": device_kind,
                }
                _append_partial(row)
                rows.append(row)
                print(json.dumps(row), flush=True)
    if not rows:
        raise RuntimeError("memory sweep produced no feasible rows")
    return rows[-1]


# ---------------------------------------------------------------------------
# hierarchical gradient reduction (BENCH_CONFIG=hierarchy): flat vs two-level
# ---------------------------------------------------------------------------

def run_hierarchy_bench():
    """Flat all-reduce vs the two-level path (sum / adasum) over a
    realistic flat-buffer size on a 2-pod mesh across the visible devices
    (docs/PARALLELISM.md, 'The plan').  Two numbers per arm: wall ms per
    reduction call, and the fusion-audit comm section's per-tier operand
    bytes — the bytes are the PORTABLE claim (cross-tier reduction bytes
    = 1/pod_size of the flat-buffer bytes), the CPU wall time is a
    liveness harness, never a perf claim (device_kind labels it)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from unicore_tpu.analysis import fusion_audit as FA
    from unicore_tpu.parallel import DATA_AXIS, POD_AXIS, make_mesh
    from unicore_tpu.parallel import hierarchy as H
    from unicore_tpu.parallel.compat import shard_map

    n = jax.device_count()
    if n < 2 or n % 2:
        raise RuntimeError(
            f"hierarchy bench needs an even device count >= 2 (got {n}); "
            "on CPU set UNICORE_TPU_PLATFORM=cpu UNICORE_TPU_CPU_DEVICES=8"
        )
    pods, pod_size = 2, n // 2
    mb = float(os.environ.get("BENCH_HIER_MB", "16"))
    length = int(mb * 1024 ** 2) // 4
    length -= length % max(1, pod_size)
    mesh = make_mesh(pods=pods, data=pod_size)
    spec = P((POD_AXIS, DATA_AXIS))

    def build(mode, deterministic):
        if mode == "flat":
            def body(xs):
                return jax.lax.psum(xs[0], (POD_AXIS, DATA_AXIS))
        else:
            def body(xs):
                (out,) = H.two_level_reduce(
                    [xs[0]], n_pods=pods, pod_size=pod_size, mode=mode,
                    deterministic=deterministic,
                )
                return out
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(spec,), out_specs=P(),
            check_vma=False,  # lint: replicated-by-collectives
        ))

    rng = np.random.RandomState(0)
    x = rng.randn(n, length).astype(np.float32)
    flat_bytes = length * 4
    last = None
    arms = [
        ("flat", "flat", False),
        ("two_level_sum", "sum", False),
        ("two_level_sum_det", "sum", True),
        ("two_level_adasum", "adasum", False),
    ]
    for name, mode, det in arms:
        # ONE compile per arm: the audited program is byte-identical to
        # the timed one (lower().compile() would otherwise build a
        # second executable beside the jit cache's)
        compiled = build(mode, det).lower(x).compile()
        ms = _time_fn(compiled, x)
        comm = FA.audit_compiled(compiled, devices_per_pod=pod_size)["comm"]
        dcn = comm["tiers"].get("dcn", {})
        row = {
            "metric": f"hierarchy_reduce_{name}_ms",
            "value": round(ms, 3),
            "unit": "ms/call",
            "vs_baseline": None,
            "combine": mode,
            "deterministic": det,
            "pods": pods,
            "pod_size": pod_size,
            "buffer_bytes": flat_bytes,
            "collectives": comm["collectives"],
            "dcn_ops": dcn.get("ops", 0),
            "dcn_operand_bytes": dcn.get("operand_bytes", 0),
            "dcn_bytes_vs_flat": (
                round(dcn.get("operand_bytes", 0) / flat_bytes, 4)
                if flat_bytes else None
            ),
        }
        _append_partial(_label_row(row))
        print(json.dumps(row), flush=True)
        last = row
    return last


_RUNNERS = {
    "serve": run_serve_bench,
    "serve-quant": run_serve_quant_bench,
    "decode": run_decode_bench,
    "fleet": run_fleet_bench,
    "kernels": run_kernel_bench,
    "hierarchy": run_hierarchy_bench,
    "memory": run_memory_bench,
}


def main():
    _require_tpu()
    from unicore_tpu.platform_utils import configure_compilation_cache

    configure_compilation_cache()
    if os.environ.get("BENCH_PIPELINE", "") not in ("", "0", "false"):
        print(json.dumps(run_pipeline_bench()))
        return
    config = os.environ.get("BENCH_CONFIG", "bert")
    configs = (
        ["bert", "unimol", "evoformer", "moe", "serve", "kernels"]
        if config == "all" else [config]
    )
    failed = []
    for c in configs:
        runner = _RUNNERS.get(c, lambda c=c: run_config(c))
        try:
            print(json.dumps(runner()), flush=True)
        except Exception as e:
            # the other configs still run and print their rows, but one
            # failed config fails the run
            sys.stderr.write(f"bench: config {c} failed: {e!r}\n")
            failed.append(c)
    if failed:
        sys.stderr.write(f"bench: FAILED configs: {failed}\n")
        sys.exit(4)


if __name__ == "__main__":
    main()
