#!/usr/bin/env python3
"""Host-only input-pipeline throughput: shards -> WordPiece tokenize ->
BERT mask -> pad -> EpochBatchIterator, NO device in the loop.

The host-side feeding rate can be measured without a chip; the full
on-TPU check (bench.py BENCH_PIPELINE=1, <5% input wait) needs one.  The
pipeline cannot be the bottleneck while this number comfortably exceeds
the chip's training step rate for the same batch and sequence length (not
measured on today's code; PERF_LEDGER.jsonl will hold it) — the
BufferedIterator's background thread only has to keep a small buffer
ahead of a slower consumer (the reference's bottleneck-warning contract,
/root/reference/unicore/data/iterators.py:471-554).

The warmup consumes the full pre-production depth (data_buffer_size plus
the loader's ~2 in-flight batches per worker) and the timed window is 10x
that depth, so batches pre-produced before t0 cannot inflate the rate.
Uses the SAME task/iterator construction as bench.py's BENCH_PIPELINE=1
mode (shared helpers), so the two modes measure one configuration.

Prints one JSON line: {"metric": "input_pipeline_samples_per_sec", ...}.
Env: BENCH_BATCH (64), BENCH_SEQ (512), BENCH_WORKERS (2).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _append_partial, make_pipeline_task, pipeline_batches  # noqa: E402

BUFFER = 4  # matches pipeline_batches' data_buffer_size


def main():
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    seq_len = int(os.environ.get("BENCH_SEQ", "512"))
    workers = int(os.environ.get("BENCH_WORKERS", "2"))
    # pre-production depth: the BufferedIterator queue plus ~2 in-flight
    # batches per loader worker (data/iterators.py) — warm through ALL of
    # it, then time a window 10x deeper than it
    depth = BUFFER + 2 * workers
    warmup, iters = depth, 10 * depth

    task, _ = make_pipeline_task(batch_size, seq_len, warmup + iters + 2)
    gen = pipeline_batches(
        task, batch_size, num_workers=workers, data_buffer_size=BUFFER
    )
    for _ in range(warmup):
        next(gen)
    n = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        batch = next(gen)
        n += len(batch["target"])
    dt = time.perf_counter() - t0
    sps = n / dt
    row = {
        "metric": "input_pipeline_samples_per_sec",
        "value": round(sps, 1),
        "unit": "samples/s (host only, no device)",
        "batch_size": batch_size,
        "seq_len": seq_len,
        "num_workers": workers,
    }
    print(json.dumps(row))
    _append_partial(row)  # same crash-resilience convention as bench.py


if __name__ == "__main__":
    main()
