#!/usr/bin/env python3
"""On-TPU attention-kernel shootout: which path should the module pick?

Times the three attention implementations the module router can choose
between (modules/multihead_attention.py):

  fullrow  one-shot softmax over the whole row, single fused backward
           (ops/attention_fullrow.py — built for the bundled <=512 shapes)
  flash    blockwise-online softmax, two-pass backward
           (ops/flash_attention.py), swept over (block_q, block_k)
  xla      fused-softmax XLA path (ops/softmax_dropout.py route) —
           materializes the attention matrix; the fallback

for the shapes the bundled model families actually run (BERT-base seq
512/256, Uni-Mol pair-bias seq 256), forward and forward+backward, with and
without bias/dropout.  One JSON line per (path, config); `best` summary
lines at the end name the winner per config — feed that into the router
defaults.

Usage (needs a TPU; exits non-zero without one):
    python scripts/bench_attention.py             # full sweep
    BENCH_ATTN_REPS=50 python scripts/bench_attention.py
Results append to BENCH_PARTIAL.jsonl like bench.py so a later hang can't
lose earlier rows.
"""

import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from unicore_tpu.platform_utils import on_tpu

REPS = int(os.environ.get("BENCH_ATTN_REPS", "30"))
PARTIAL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_PARTIAL.jsonl",
)


def _emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    try:
        with open(PARTIAL, "a") as f:
            f.write(line + "\n")
    except OSError:
        pass


def _time(fn, *args):
    """Median-of-3 wall time for REPS dispatches; a fetch of the last
    output is the completion barrier."""
    import jax
    import numpy as np

    out = fn(*args)  # compile
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    times = []
    for _i in range(3):
        t0 = time.perf_counter()
        for _j in range(REPS):
            out = fn(*args)
        _ = np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        times.append((time.perf_counter() - t0) / REPS)
    return sorted(times)[1]


def main():
    import jax
    import jax.numpy as jnp

    from unicore_tpu.ops.flash_attention import flash_attention, mha_reference
    from unicore_tpu.ops.attention_fullrow import (
        fullrow_attention, supported as fullrow_supported,
    )

    if not on_tpu():
        sys.exit(
            f"bench_attention: no TPU (default backend "
            f"{jax.default_backend()!r}); interpret-mode timings are not "
            "kernel timings, so there is no CPU mode"
        )
    kind = jax.devices()[0].device_kind
    print(f"# device={kind} reps={REPS}", file=sys.stderr)

    # (name, B, H, L, D, bias_mode) — the bundled families' hot shapes.
    # bias_mode: None, 'shared' ((1,H,L,L) broadcast — rel-pos style),
    # 'per_batch' ((B,H,L,L)), or 'grouped' ((8,H,L,L) with B % 8 == 0 —
    # the REAL evoformer MSA-row layout: runs of B/8 rows share a slab,
    # indexed in-kernel since round 4; per_batch is kept as the
    # materialized-form comparison row).
    configs = [
        ("bert_seq512", 16, 12, 512, 64, None),
        ("bert_seq256", 32, 12, 256, 64, None),
        ("unimol_pair_seq256", 16, 8, 256, 64, "shared"),
        ("evoformer_msarow_seq256", 256, 8, 256, 32, "grouped"),
        ("evoformer_msarow_seq256_materialized", 256, 8, 256, 32,
         "per_batch"),
    ]
    flash_blocks = [(128, 128), (128, 256), (256, 256), (256, 512),
                    (512, 512)]

    best = {}
    for name, B, H, L, D, bias_mode in configs:
        key = jax.random.PRNGKey(0)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (B, H, L, D),
                              jnp.bfloat16)
            for i in range(3)
        )
        bias = None
        if bias_mode is not None:
            bias_b = {"shared": 1, "grouped": min(8, B)}.get(bias_mode, B)
            bias = jax.random.normal(
                jax.random.fold_in(key, 7), (bias_b, H, L, L), jnp.float32
            )
        sm = D ** -0.5

        candidates = []
        if fullrow_supported(
            L, L, D, None if bias is None else bias.shape[0]
        ):
            candidates.append((
                "fullrow",
                lambda q, k, v: fullrow_attention(
                    q, k, v, bias=bias, sm_scale=sm
                ),
            ))
        for bq, bk in flash_blocks:
            if L % min(bq, 128) or bq > L or bk > L:
                continue
            candidates.append((
                f"flash_bq{bq}_bk{bk}",
                lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, bias=bias, sm_scale=sm, block_q=bq, block_k=bk
                ),
            ))
        candidates.append((
            "xla",
            lambda q, k, v: mha_reference(q, k, v, bias=bias, sm_scale=sm),
        ))

        for path, fn in candidates:
            row = {"config": name, "path": path, "shape": [B, H, L, D],
                   "bias": bias_mode, "device_kind": kind}
            try:
                fwd = jax.jit(fn)
                row["fwd_ms"] = round(_time(fwd, q, k, v) * 1e3, 3)

                def loss(q, k, v, fn=fn):
                    return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

                fb = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                row["fwdbwd_ms"] = round(_time(fb, q, k, v) * 1e3, 3)
            except Exception as e:
                row["error"] = repr(e)[:300]
            _emit(row)
            if "fwdbwd_ms" in row:
                cur = best.get(name)
                if cur is None or row["fwdbwd_ms"] < cur["fwdbwd_ms"]:
                    best[name] = {"path": path,
                                  "fwdbwd_ms": row["fwdbwd_ms"]}

    for name, win in best.items():
        _emit({"config": name, "best": win["path"],
               "fwdbwd_ms": win["fwdbwd_ms"], "device_kind": kind})


if __name__ == "__main__":
    main()
