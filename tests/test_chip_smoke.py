"""CPU rehearsal of ``chip_smoke.py``'s control flow at a tiny size: the
parent never imports jax, every child is checked, a failing child fails the
script, and a CPU is never reported as ok."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# runs chip_smoke.run() as the PARENT would (a fresh interpreter, stdlib
# only) with a tiny Plan, then reports what the parent imported
DRIVER = r"""
import json, sys
sys.path.insert(0, {repo!r})
import chip_smoke
plan = chip_smoke.Plan(**{plan!r})
rc = chip_smoke.run(plan, phases={phases!r})
heavy = sorted(m for m in ("jax", "jaxlib", "flax", "numpy", "unicore_tpu")
               if m in sys.modules)
print("PARENT_IMPORTED=" + json.dumps(heavy))
sys.exit(rc)
"""

TINY = dict(
    platform="cpu", vocab=300, doc_words=(70, 90), n_train_docs=48,
    n_valid_docs=4, bert_arch="bert_tiny", seq_len=64, seq_pad_multiple=8,
    batch=4, updates=4, warmup_updates=4, lm_arch="transformer_lm_tiny",
    lm_updates=3, max_new_tokens=4,
    kernel_sizes=dict(B=2, H=2, L=128, D=32, dims=(128, 256),
                      matmul_shapes=((32, 128, 128),),
                      rows_add_shapes=((96, 48, 256),)),
    serve_extra=("--serve-batch-size", "2", "--serve-buckets", "2",
                 "--decode-batch-size", "2", "--cache-pages", "32"),
)


def _drive(plan, phases, timeout=900, devices=1):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    # no compile-cache override: the children resolve it as a user's would
    # (JAX_COMPILATION_CACHE_DIR if the caller set it, else
    # <checkout>/.jax_cache), so nothing is written outside the checkout
    env.update(JAX_PLATFORMS="cpu", UNICORE_TPU_PLATFORM="cpu",
               UNICORE_TPU_CPU_DEVICES=str(devices))
    return subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(repo=REPO, plan=plan, phases=tuple(phases))],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


def test_rehearsal_all_phases_parent_stays_off_jax():
    proc = _drive(TINY, ("kernels", "train", "serve", "decode"))
    out = proc.stdout
    assert proc.returncode == 0, out[-6000:] + proc.stderr[-2000:]
    lines = out.strip().splitlines()
    # the parent imported none of the heavy modules: the chip would have
    # belonged to it, and no child could have had it
    assert lines[-1] == "PARENT_IMPORTED=[]", lines[-1]
    # the result line is the last thing run() prints, and carries the
    # device as the children reported it
    result = json.loads(lines[-2])
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    for phase in ("kernels", "train", "serve", "decode"):
        assert f"=== phase {phase}: ok" in out, phase
    # every child reported its devices; the phases read what they claim
    assert out.count("devices platform=cpu") >= 7
    assert "kernel decode-int8-cache: max_rel_err=" in out
    assert "kernel quant-matmul-gelu-32x128x128: max_rel_err=" in out
    assert "loss first=" in out and "checkpoint:" in out
    assert "serve: recompiles_after_warmup=0" in out
    assert "decode-serve: answers carried 8 tokens" in out
    assert out.count("SIGTERM drained, exit 0") == 2


def test_rehearsal_four_chips_on_virtual_devices():
    """``--four-chips`` on four virtual CPU devices: the two trainer
    children, the loss comparison and the DEVICE-SHARES reading (the batch
    laid over the data axis: a quarter of the rows on each device)."""
    plan = dict(
        TINY, n_train_docs=96, batch=8,
        one_chip_env={"UNICORE_TPU_CPU_DEVICES": "1"},
    )
    proc = _drive(plan, ("four-chips",), devices=4)
    out = proc.stdout
    assert proc.returncode == 0, out[-6000:] + proc.stderr[-2000:]
    lines = out.strip().splitlines()
    assert lines[-1] == "PARENT_IMPORTED=[]"
    assert json.loads(lines[-2])["device"]["count"] == 4
    assert "train-4chip: devices platform=cpu kind='cpu' count=4" in out
    assert "train-1chip: devices platform=cpu kind='cpu' count=1" in out
    assert "per-device shards [[2, 64], [2, 64], [2, 64], [2, 64]]" in out
    assert "four-chips: per-update loss, 4 chips vs 1 chip" in out


def test_failing_child_fails_the_script_and_prints_no_result():
    # an architecture the trainer child refuses: the child exits non-zero,
    # so must the script, and the later phases never start
    plan = dict(TINY, bert_arch="no_such_arch")
    proc = _drive(plan, ("train", "serve"))
    assert proc.returncode == 1, proc.stdout[-3000:]
    assert "chip_smoke: FAILED: train: child exited" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "=== phase serve" not in proc.stdout


def test_script_never_reports_a_cpu_as_ok():
    """``python chip_smoke.py`` as the driver runs it, in this sandbox:
    the first child finds no TPU, so the script exits non-zero and its
    last line is not a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert proc.returncode != 0
    assert "needs platform 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout
