#!/usr/bin/env python3
"""Prove on the attached TPU that the three main paths still start:
kernels -> train -> serve -> decode, through the entry points a user types.

    python chip_smoke.py               # one chip, four phases
    python chip_smoke.py --four-chips  # data-parallel train on 4 chips
                                       # against the same run on 1 chip

This (parent) process never imports jax, flax or unicore_tpu: a TPU belongs
to the first process that initializes a backend on it, so every phase is a
child process, strictly one alive at a time, and the first child that exits
non-zero ends the script non-zero.  Children are the real CLIs
(``python -m unicore_tpu_cli.train`` / ``.serve``); the two that are not a
CLI (the kernel table, the seeded corpus writer) are this script
re-entering itself.  All children share one persistent compile cache
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``)
and one scratch directory, ``<checkout>/.chip_smoke``.

What is printed are smoke readings (wall seconds, losses, parity errors),
each named for what it is — not benchmark numbers.  The last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as JAX reported
it to the children; without a TPU the script exits non-zero and prints no
such line.
"""

import argparse
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(REPO, ".chip_smoke")


@dataclasses.dataclass
class Plan:
    """Sizes of one smoke run.  The defaults are the real widths; the CPU
    rehearsal in tests/test_chip_smoke.py builds a tiny one."""

    platform: str = "tpu"
    device_count: int = 1
    # corpus: a 30,522-entry dict.txt (BERT's vocabulary, so bert_base is
    # the real ~110M-parameter model including its LM head) and documents
    # longer than the 512-token context, tokens drawn Zipf-like
    vocab: int = 30522
    doc_words: tuple = (520, 640)
    n_train_docs: int = 512
    n_valid_docs: int = 16
    # train (BERT-base MLM, bf16, seq 512)
    bert_arch: str = "bert_base"
    seq_len: int = 512
    seq_pad_multiple: int = 128
    batch: int = 16
    updates: int = 20
    warmup_updates: int = 4
    # decode (transformer_lm: 6 L / 768 / 12 heads / 512 ctx)
    lm_arch: str = "transformer_lm"
    lm_updates: int = 6
    max_new_tokens: int = 16
    # the kernel table (unicore_tpu/ops/kernel_cases.py defaults when None)
    kernel_sizes: dict = None
    # four chips vs one: measured 8.3e-05 on the chip (PR 23); the bound
    # leaves two orders of magnitude for bf16 reduction order and for the
    # attention-dropout masks, which depend on the device layout
    bf16_loss_tol: float = 1e-2
    serve_extra: tuple = ()
    # --four-chips: the runtime's own visible-devices settings (libtpu's)
    # that hold the comparison run to one chip; not a program option
    one_chip_env: dict = dataclasses.field(default_factory=lambda: {
        "TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # the same bounds under their older names, which a host may preset
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1", "TPU_HOST_BOUNDS": "1,1,1",
    })
    # no child may outlive this: a hung child is killed and fails its phase
    child_timeout_s: float = 900.0


def say(msg):
    print(msg, flush=True)


class PhaseFailed(Exception):
    pass


def child_env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def run_child(name, cmd, plan, env=None, on_line=None):
    """Run one child to its end, handing each output line to ``on_line``.
    Returns (lines, device dict, wall seconds); raises PhaseFailed on a
    non-zero exit or a child that did not see the planned platform."""
    say(f"--- child {name}: {' '.join(cmd[:4])} ...")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=child_env(env), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    lines = []
    watchdog = threading.Timer(plan.child_timeout_s, proc.kill)
    watchdog.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            if on_line is not None:
                on_line(line, time.monotonic() - t0)
        rc = proc.wait()
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    wall = time.monotonic() - t0
    if rc != 0:
        say("\n".join(lines[-60:]))
        raise PhaseFailed(f"{name}: child exited {rc}")
    return lines, check_device(name, lines, plan), wall


def check_device(name, lines, plan):
    """The child's own ``DEVICES {json}`` report (jax.devices() as it saw
    them); anything but the planned platform and count fails the phase."""
    for line in lines:
        at = line.find("DEVICES {")
        if at >= 0:
            dev = json.loads(line[at + len("DEVICES "):])
            break
    else:
        raise PhaseFailed(f"{name}: child reported no DEVICES line")
    say(f"{name}: devices platform={dev['platform']} "
        f"kind={dev['kind']!r} count={dev['count']}")
    if dev["platform"] != plan.platform:
        raise PhaseFailed(
            f"{name}: child saw platform {dev['platform']!r}, "
            f"not {plan.platform!r}"
        )
    if dev["count"] != plan.device_count:
        raise PhaseFailed(
            f"{name}: child saw {dev['count']} device(s), "
            f"not {plan.device_count}"
        )
    return dev


def reenter(name, *args, plan, env=None):
    payload = json.dumps(dataclasses.asdict(plan))

    def relay(line, _t):  # the child's own readings, minus runtime noise
        if line.startswith(_READINGS):
            say("  " + line)

    return run_child(
        name,
        [sys.executable, os.path.abspath(__file__), "--child", name,
         "--plan", payload, *args],
        plan, env=env, on_line=relay,
    )


#: what the re-entered children print for the parent to relay
_READINGS = ("kernel ", "kernels: ", "data: ", "checkpoint: ")


# ---------------------------------------------------------------------------
# children that are not a CLI (this script re-entered; they may touch jax)
# ---------------------------------------------------------------------------

def _report_devices(plan):
    """Print this child's ``DEVICES`` line; a child that does not see the
    planned platform stops here, before it does any work."""
    from unicore_tpu.platform_utils import describe_devices

    dev = describe_devices()
    say("DEVICES " + json.dumps(dev))
    if dev["platform"] != plan.platform:
        say(f"this child needs platform {plan.platform!r}, JAX found "
            f"{dev['platform']!r}")
        sys.exit(3)
    return dev


def child_data(plan, out_dir):
    """Seeded corpus in the framework's native indexed-shard format."""
    import numpy as np

    from unicore_tpu.data.indexed_dataset import make_builder

    _report_devices(plan)
    os.makedirs(out_dir, exist_ok=True)
    specials = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    n_words = plan.vocab - len(specials)
    letters = "abcdefghijklmnopqrstuvwxyz"

    def word(i):  # distinct all-letter words: WordPiece keeps each whole
        s = ""
        for _ in range(4):
            s = letters[i % 26] + s
            i //= 26
        return s

    words = np.array([word(i) for i in range(n_words)])
    with open(os.path.join(out_dir, "dict.txt"), "w") as f:
        f.write("\n".join(specials + list(words)) + "\n")
    # Zipf-like: p(rank r) ~ 1/(r+1) — something to learn in 20 updates
    p = 1.0 / np.arange(1, n_words + 1)
    p /= p.sum()
    rng = np.random.RandomState(20230923)
    lo, hi = plan.doc_words
    for split, n in (("train", plan.n_train_docs),
                     ("valid", plan.n_valid_docs)):
        builder = make_builder(os.path.join(out_dir, split))
        for _ in range(n):
            ids = rng.choice(n_words, size=rng.randint(lo, hi), p=p)
            builder.add_item(" ".join(words[ids]))
        builder.finalize()
    say(f"data: {plan.n_train_docs}+{plan.n_valid_docs} documents of "
        f"{lo}-{hi} words, dict.txt {plan.vocab} entries -> {out_dir}")


def child_kernels(plan):
    """Every kernel family, compiled (interpret off) and entered directly,
    against its jnp oracle."""
    import jax

    from unicore_tpu.ops import _pallas
    from unicore_tpu.ops.kernel_cases import (
        all_finite, kernel_cases, make_inputs, max_rel_err,
    )
    from unicore_tpu.platform_utils import configure_compilation_cache

    _report_devices(plan)
    configure_compilation_cache()
    interpret = plan.platform != "tpu"  # only the CPU rehearsal
    _pallas.set_interpret(interpret)
    failed = []
    first_call_s = 0.0
    for case in kernel_cases(**(plan.kernel_sizes or {})):
        if case.tpu_prng and interpret:
            say(f"kernel {case.name}: skipped (in-kernel dropout needs "
                "the TPU PRNG)")
            continue
        args = make_inputs(case)
        t0 = time.monotonic()
        got = jax.block_until_ready(jax.jit(case.kernel)(*args))
        dt = time.monotonic() - t0
        first_call_s += dt
        if case.oracle is None:
            ok = all_finite(got)
            say(f"kernel {case.name}: finite={ok} (no comparable oracle: "
                f"in-kernel dropout) first_call_s={dt:.2f}")
        else:
            with jax.default_matmul_precision("highest"):
                want = jax.jit(case.oracle)(*args)
            err = max_rel_err(got, want)
            ok = err <= case.tol
            say(f"kernel {case.name}: max_rel_err={err:.3e} "
                f"tol={case.tol:.0e} {'ok' if ok else 'FAIL'} "
                f"first_call_s={dt:.2f}")
        if not ok:
            failed.append(case.name)
    say(f"kernels: first-call seconds (compile + one run) summed "
        f"{first_call_s:.1f}")
    if failed:
        say(f"kernels: FAILED {failed}")
        sys.exit(1)


def child_verify_checkpoint(plan, path):
    """Load the checkpoint back through the read-verifying loader."""
    import jax

    from unicore_tpu import checkpoint_utils

    _report_devices(plan)
    state = checkpoint_utils.load_checkpoint_to_cpu(path)
    n = sum(
        int(getattr(leaf, "size", 0))
        for leaf in jax.tree_util.tree_leaves(state["model"])
    )
    say(f"checkpoint: {path} loaded read-verified, {n} model parameters")
    if n <= 0:
        sys.exit(1)


# ---------------------------------------------------------------------------
# phases (parent side: stdlib only)
# ---------------------------------------------------------------------------

def train_argv(plan, data, save, task, loss, arch, updates, extra=()):
    return [
        sys.executable, "-m", "unicore_tpu_cli.train", data,
        "--task", task, "--loss", loss, "--arch", arch, "--bf16",
        "--max-seq-len", str(plan.seq_len),
        "--seq-pad-multiple", str(plan.seq_pad_multiple),
        "--optimizer", "adam", "--adam-betas", "(0.9, 0.98)",
        "--adam-eps", "1e-6", "--clip-norm", "1.0", "--weight-decay", "1e-4",
        "--lr-scheduler", "polynomial_decay", "--lr", "3e-4",
        "--warmup-updates", "2", "--total-num-update", str(updates),
        "--max-update", str(updates), "--max-epoch", "100",
        "--batch-size", str(plan.batch), "--update-freq", "1",
        "--compile-warmup-updates", str(plan.warmup_updates),
        "--log-interval", "1", "--log-format", "json", "--no-progress-bar",
        "--save-interval-updates", str(updates), "--disable-validation",
        "--save-dir", os.path.join(save, "ckpt"),
        "--tmp-save-dir", os.path.join(save, "tmp"),
        "--num-workers", "2", "--seed", "1", *extra,
    ]


def run_train(name, plan, argv, env=None):
    """One trainer child; checks everything its log can show.  Returns
    (per-update losses, the whole log text, the device it reported)."""
    marks = {}

    def on_line(line, t):
        if "first_update_s" not in marks and '"loss"' in line:
            marks["first_update_s"] = t

    lines, _dev, wall = run_child(name, argv, plan, env=env, on_line=on_line)
    losses, gnorms = [], []
    for line in lines:
        at = line.find('{"')
        if at < 0 or '"loss"' not in line:
            continue
        try:
            row = json.loads(line[at:])
        except ValueError:
            continue
        if "num_updates" not in row and "update" not in row:
            continue
        if "train_loss" in row:  # the end-of-epoch summary, not an update
            continue
        losses.append(float(row["loss"]))
        if "gnorm" in row:
            gnorms.append(float(row["gnorm"]))
    say(f"{name}: wall_s={wall:.1f} "
        f"to_first_update_s={marks.get('first_update_s', float('nan')):.1f} "
        "(process start to first logged update: imports, data, compile)")
    if len(losses) < 2:
        raise PhaseFailed(f"{name}: fewer than two logged updates")
    bad = [x for x in losses + gnorms if x != x or abs(x) == float("inf")]
    if bad:
        raise PhaseFailed(f"{name}: non-finite loss/gnorm in the log")
    if not gnorms or min(gnorms) <= 0.0:
        raise PhaseFailed(f"{name}: gradient norm missing or zero")
    say(f"{name}: {len(losses)} updates, loss first={losses[0]:.4f} "
        f"last={losses[-1]:.4f} "
        f"({'fell' if losses[-1] < losses[0] else 'DID NOT FALL'}), "
        f"gnorm min={min(gnorms):.3f} max={max(gnorms):.3f}")
    text = "\n".join(lines)
    if "recompile after warmup" in text:
        raise PhaseFailed(f"{name}: recompile after --compile-warmup-updates")
    return losses, text, _dev


def phase_kernels(plan, ctx):
    _, ctx["device"], _ = reenter("kernels", plan=plan)


def phase_train(plan, ctx):
    data = os.path.join(SCRATCH, "data")
    if not os.path.exists(os.path.join(data, "train.idx")):
        reenter("data", data, plan=plan)
    save = os.path.join(SCRATCH, "bert")
    losses, text, _ = run_train(
        "train", plan,
        train_argv(plan, data, save, "bert", "masked_lm", plan.bert_arch,
                   plan.updates),
    )
    if plan.platform == "tpu" and "flash attention unavailable" in text:
        raise PhaseFailed("train: the flash path did not engage "
                          "(_warn_flash_fallback fired)")
    ckpt = os.path.join(save, "ckpt", "checkpoint_last.pt")
    reenter("verify-checkpoint", ckpt, plan=plan)
    ctx["bert_ckpt"] = ckpt
    ctx["bert_losses"] = losses


def _http(method, url, payload=None, timeout=60):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


class Server:
    """One ``python -m unicore_tpu_cli.serve`` child; the parent talks to
    it with plain urllib."""

    def __init__(self, name, plan, ckpt, extra=()):
        self.name, self.plan = name, plan
        self.log_path = os.path.join(SCRATCH, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "unicore_tpu_cli.serve", "--path", ckpt,
             "--port", "0", "--default-deadline-ms", "30000",
             "--drain-deadline", "60", *plan.serve_extra, *extra],
            cwd=REPO, env=child_env(), stdout=self._log,
            stderr=subprocess.STDOUT,
        )
        self.base = None

    def log(self):
        with open(self.log_path) as f:
            return f.read()

    def _wait(self, what, budget, probe):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            got = probe()
            if got:
                return got
            if self.proc.poll() is not None:
                say(self.log()[-6000:])
                raise PhaseFailed(
                    f"{self.name}: server exited {self.proc.returncode} "
                    f"before {what}"
                )
            time.sleep(0.5)
        say(self.log()[-6000:])
        raise PhaseFailed(f"{self.name}: not {what} within {budget}s")

    def wait_ready(self, budget=900):
        def listening():
            for line in self.log().splitlines():
                if "SERVE listening" in line:
                    port = line.rsplit(":", 1)[1].split()[0].strip("/")
                    return f"http://127.0.0.1:{port}"

        self.base = self._wait("listening", budget, listening)

        def ready():
            try:
                code, body = _http("GET", self.base + "/readyz", timeout=5)
            except (OSError, ValueError):
                return False
            return code == 200 and body.get("ready")

        self._wait("ready", budget, ready)
        say(f"{self.name}: ready_s={time.monotonic() - self.t0:.1f} "
            "(process start to /readyz: imports, checkpoint, warm-up "
            "compile of every bucket)")
        return check_device(self.name, self.log().splitlines(), self.plan)

    def drain(self):
        """SIGTERM, then the documented clean-drain exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            self.kill()
        if rc != 0:
            say(self.log()[-6000:])
            raise PhaseFailed(f"{self.name}: drain exited {rc}, not 0")
        say(f"{self.name}: SIGTERM drained, exit 0")

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self._log.closed:
            self._log.close()


def _no_recompiles(server):
    code, stats = _http("GET", server.base + "/stats")
    if code != 200:
        raise PhaseFailed(f"{server.name}: /stats answered {code}")
    n = stats.get("recompiles_after_warmup")
    say(f"{server.name}: recompiles_after_warmup={n} "
        f"served={stats.get('served')}")
    if n != 0:
        raise PhaseFailed(f"{server.name}: {n} recompile(s) after warm-up")
    return stats


def _tokens(n, vocab, seed):
    # stdlib LCG: the parent has no numpy contract to keep, ids in [5, vocab)
    out, x = [], seed * 2654435761 % 2**32 or 1
    for _ in range(n):
        x = (1103515245 * x + 12345) % 2**31
        out.append(5 + x % (vocab - 5))
    return out


def phase_serve(plan, ctx):
    server = Server("serve", plan, ctx["bert_ckpt"])
    try:
        server.wait_ready()
        _, stats = _http("GET", server.base + "/stats")
        edges = stats["buckets"]
        say(f"serve: warmed buckets {edges}")
        # one request inside every warmed bucket, and one at each edge
        lengths = sorted({max(1, e - 3) for e in edges} | set(edges))
        for i, n in enumerate(lengths):
            code, body = _http(
                "POST", server.base + "/v1/infer",
                {"tokens": _tokens(n, plan.vocab, i + 1),
                 "deadline_ms": 30000},
            )
            out = body.get("output")
            score = body.get("score")
            ok = (
                code == 200 and body.get("status") == "ok"
                and isinstance(out, list) and len(out) == n
                and all(isinstance(t, int) and 0 <= t < plan.vocab + 8
                        for t in out)
                and isinstance(score, float) and score == score
                and abs(score) != float("inf")
            )
            say(f"serve: /v1/infer len={n} -> {code} bucket="
                f"{body.get('bucket')} score={score} "
                f"latency_ms={body.get('latency_ms')}")
            if not ok:
                raise PhaseFailed(f"serve: bad answer for len {n}: "
                                  f"{str(body)[:300]}")
        _no_recompiles(server)
        server.drain()
    finally:
        server.kill()


def phase_decode(plan, ctx):
    data = os.path.join(SCRATCH, "data")
    save = os.path.join(SCRATCH, "lm")
    run_train(
        "decode-train", plan,
        train_argv(plan, data, save, "causal_lm", "lm_cross_entropy",
                   plan.lm_arch, plan.lm_updates),
    )
    ckpt = os.path.join(save, "ckpt", "checkpoint_last.pt")
    server = Server("decode-serve", plan, ckpt,
                    extra=("--max-new-tokens", str(plan.max_new_tokens)))
    try:
        server.wait_ready()
        log = server.log()
        say("decode-serve: buffer donation of the KV pools is "
            + ("ON (the on_tpu() branch of serve/decode.py)"
               if plan.platform == "tpu" else "off (not a TPU)"))
        if "donated buffers were not usable" in log.lower():
            raise PhaseFailed("decode-serve: donation requested but unusable")
        asks = [(7, 4), (40, plan.max_new_tokens), (130, 8),
                (300, plan.max_new_tokens)]
        total = 0
        for i, (n, new) in enumerate(asks):
            if n + new > plan.seq_len:
                continue
            code, body = _http(
                "POST", server.base + "/v1/generate",
                {"tokens": _tokens(n, plan.vocab, 100 + i),
                 "max_new_tokens": new, "deadline_ms": 60000},
                timeout=120,
            )
            out = body.get("output")
            # the token count is the one asked for; generation stops short
            # only at an end-of-sequence special (ids < 5), never runs long
            ok = (
                code == 200 and body.get("status") == "ok"
                and isinstance(out, list) and 1 <= len(out) <= new
                and (len(out) == new or out[-1] < 5)
                and all(isinstance(t, int) and 0 <= t < plan.vocab + 8
                        for t in out)
            )
            say(f"decode-serve: /v1/generate prompt={n} asked={new} -> "
                f"{code} got={len(out) if isinstance(out, list) else out} "
                f"latency_ms={body.get('latency_ms')}")
            if not ok:
                raise PhaseFailed(f"decode-serve: bad answer: "
                                  f"{str(body)[:300]}")
            total += len(out)
        stats = _no_recompiles(server)
        say(f"decode-serve: answers carried {total} tokens; the engine "
            f"sampled tokens_generated={stats.get('tokens_generated')} in "
            f"decode_steps={stats.get('decode_steps')}")
        server.drain()
    finally:
        server.kill()


def phase_four_chips(plan, ctx):
    """Synchronous data-parallel training over four chips against the same
    global batch and seed on one chip (the runtime's own visible-devices
    setting restricts the second run; no program option)."""
    data = os.path.join(SCRATCH, "data")
    four = dataclasses.replace(plan, device_count=4)
    if not os.path.exists(os.path.join(data, "train.idx")):
        reenter("data", data, plan=four)
    runs = {}
    for name, p, batch, extra, env in (
        # --batch-size is per device: 4 x (batch/4) and 1 x batch are the
        # same global batch
        ("train-4chip", four, plan.batch // 4,
         ("--data-parallel-size", "4"), None),
        ("train-1chip", plan, plan.batch, (), plan.one_chip_env),
    ):
        argv = train_argv(
            dataclasses.replace(p, batch=batch), data,
            os.path.join(SCRATCH, name), "bert", "masked_lm", plan.bert_arch,
            plan.updates, extra=extra,
        )
        runs[name] = run_train(name, p, argv, env=env)
    a, b = runs["train-4chip"][0], runs["train-1chip"][0]
    worst = max(abs(x - y) / max(abs(y), 1e-6) for x, y in zip(a, b))
    say(f"four-chips: per-update loss, 4 chips vs 1 chip: max relative "
        f"difference {worst:.3e} over {min(len(a), len(b))} updates "
        f"(tolerance {plan.bf16_loss_tol:.0e}: bf16 reduction order, and "
        "dropout masks that are a function of the device layout)")
    if len(a) != len(b) or worst > plan.bf16_loss_tol:
        raise PhaseFailed("four-chips: losses disagree")
    shares = [
        json.loads(line[line.find("DEVICE-SHARES ") + 14:])
        for line in runs["train-4chip"][1].splitlines()
        if "DEVICE-SHARES " in line
    ]
    if not shares:
        raise PhaseFailed("four-chips: trainer reported no DEVICE-SHARES")
    share = shares[0]
    say(f"four-chips: batch {share['batch_global_shape']} spec "
        f"{share['batch_spec']} -> per-device shards "
        f"{share['batch_shard_shapes']}; bytes in use per device "
        f"{share['bytes_in_use']}")
    rows = share["batch_global_shape"][0]
    if (len(share["batch_shard_shapes"]) != 4
            or any(sh[0] * 4 != rows for sh in share["batch_shard_shapes"])):
        raise PhaseFailed("four-chips: the batch is not laid over the "
                          "data axis of all four devices")
    # (the CPU backend of the rehearsal reports no memory statistics)
    if plan.platform == "tpu" and (
            len(share["bytes_in_use"]) != 4
            or min(share["bytes_in_use"]) <= 0):
        raise PhaseFailed("four-chips: not every device holds memory")
    ctx["device"] = runs["train-4chip"][2]


PHASES = {
    "kernels": phase_kernels,
    "train": phase_train,
    "serve": phase_serve,
    "decode": phase_decode,
    "four-chips": phase_four_chips,
}


def run(plan, phases=("kernels", "train", "serve", "decode")):
    """Run ``phases`` in order; returns the exit code.  The result line —
    the last line of stdout — is printed only when every phase passed."""
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(SCRATCH)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    say(f"chip_smoke: phases {list(phases)}; compile cache {cache}; "
        f"scratch {SCRATCH}")
    ctx = {}
    t_all = time.monotonic()
    try:
        for name in phases:
            t0 = time.monotonic()
            say(f"=== phase {name}")
            PHASES[name](plan, ctx)
            say(f"=== phase {name}: ok wall_s={time.monotonic() - t0:.1f}")
    except PhaseFailed as e:
        say(f"chip_smoke: FAILED: {e}")
        return 1
    say(f"chip_smoke: all phases ok, total wall_s="
        f"{time.monotonic() - t_all:.1f}")
    print(json.dumps({"ok": True, "device": ctx["device"]}), flush=True)
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run ONLY data-parallel training over four chips and the "
             "one-chip run it is compared with",
    )
    parser.add_argument("--child", help=argparse.SUPPRESS)
    parser.add_argument("--plan", help=argparse.SUPPRESS)
    parser.add_argument("rest", nargs="*", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        # a re-entered child honours the same explicit CPU switch as the
        # CLIs (UNICORE_TPU_PLATFORM=cpu: the rehearsal in the tests)
        from unicore_tpu.platform_utils import force_host_cpu_from_env

        force_host_cpu_from_env(default_devices=1)
        plan = Plan(**json.loads(args.plan))
        plan.doc_words = tuple(plan.doc_words)
        {"data": child_data, "kernels": child_kernels,
         "verify-checkpoint": child_verify_checkpoint}[args.child](
            plan, *args.rest)
        return 0
    plan = Plan()
    phases = ("four-chips",) if args.four_chips else (
        "kernels", "train", "serve", "decode")
    return run(plan, phases)


if __name__ == "__main__":
    sys.exit(main())
