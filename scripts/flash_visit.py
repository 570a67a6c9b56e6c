"""What a VISIT costs in each blockwise attention kernel, on the chip.

    python3 scripts/flash_visit.py [--shape 1,8,32768,128] [--windows none,1024]
        [--blocks 256,512] [--dtype bfloat16] [--repeats 5] [--out FILE.json]

A visit is one grid step that does work: one ``(block_q, block_k)`` block of
scores and what the kernel does with it.  The script runs
``flash_attention`` alone (no model, no cell imports this), forward and
backward under ``jax.grad``, at the given ``(B, H, L, D)`` under each band
(``none``: causal alone; a number: that sliding window; ``dense``: no band,
every block), under the profiler, and divides each kernel's device time by
the visits its walk lists (``band_block_map``'s counts; a dead item of a row
without a visit is not one).  The kernels are found in the trace by their
``name=`` (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``), by which the
benchmark's readers find them too.  A visit's products at the MXU's peak are
printed beside it: what is left is the kernel's own bookkeeping.

Needs a TPU: a CPU timing of interpret mode is no speed and is refused.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def kernel_seconds(trace_dir, names):
    """{name: [device seconds of each event]} over the newest capture under
    ``trace_dir``: the events of a device plane's op line
    (``benchmark/reduce.py``) whose HLO instruction's name holds the
    kernel's ``name=`` (under ``jax.grad`` alone the instructions are called
    ``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_dq__``; none of the three
    names is part of another)."""
    from benchmark import reduce

    path = max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    out = {name: [] for name in names}
    for events in reduce.device_events(reduce._load(path)).values():
        for start, end, text in events:
            label = reduce.parse_op(text)[0]
            for name in names:
                if name in label:
                    out[name].append((end - start) * 1e-9)
    return out


def profiled(fn, args, names, repeats):
    """Median device seconds a call of each named kernel, ``fn(*args)``
    run ``repeats`` times under the profiler after one warm call."""
    import jax
    import numpy as np

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(repeats):
                jax.block_until_ready(fn(*args))
        seconds = kernel_seconds(trace_dir, names)
    return {name: float(np.median(s)) for name, s in seconds.items() if s}


def visits_of(fa, band, B, H, L, blocks):
    """The live grid steps of a call's forward (``flash_bwd_dq`` and
    ``flash_bwd_dkv`` walk the same blocks the other way round)."""
    if band is None:
        bq, bk = (fa._pick_block(L, b) for b in blocks)
        return B * H * (L // bq) * (L // bk)
    counts = fa.band_block_map(band, L, L, *blocks).kv_counts
    return B * H * int(counts.sum())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1,8,32768,128")
    ap.add_argument("--windows", default="none,1024")
    ap.add_argument("--blocks", default="256,512")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmark.flops.kernels import PRODUCTS
    from unicore_tpu.ops import flash_attention as fa

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("flash_visit.py times the kernels on a TPU; "
                         f"this is {device.platform}")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)[device.device_kind]["bf16_flops_per_s"]
    B, H, L, D = (int(x) for x in args.shape.split(","))
    blocks = tuple(int(x) for x in args.blocks.split(","))
    bq, bk = (fa._pick_block(L, b) for b in blocks)
    dtype = jnp.dtype(args.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, (B, H, L, D), dtype) for key in keys)
    q = q * D ** -0.5  # pre-scaled, as the decoders hand it over

    rows = []
    for window in args.windows.split(","):
        band = {"dense": None, "none": fa.Band(None)}.get(window)
        if band is None and window != "dense":
            band = fa.Band(int(window))

        def loss(q, k, v, w):
            out = fa.flash_attention(q, k, v, block_q=blocks[0],
                                     block_k=blocks[1], band=band)
            return jnp.sum((out * w).astype(jnp.float32))

        seconds = profiled(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                           (q, k, v, w), KERNELS, args.repeats)
        visits = visits_of(fa, band, B, H, L, blocks)
        for name, s in seconds.items():
            at_peak = len(PRODUCTS[name]) * 2 * bq * bk * D / peak
            rows.append({
                "kernel": name, "window": window, "visits": visits,
                "ms": s * 1e3, "us_a_visit": s * 1e6 / visits,
                "products_at_peak_us": at_peak * 1e6,
            })
            print(f"{name:14s} window {window:>6s}  {visits:7d} visits  "
                  f"{s * 1e3:9.3f} ms  {s * 1e6 / visits:6.3f} us a visit  "
                  f"(products at the peak {at_peak * 1e6:.3f})", flush=True)
    result = {
        "device_kind": device.device_kind, "shape": [B, H, L, D],
        "blocks": list(blocks), "dtype": str(dtype), "rows": rows,
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    main()
