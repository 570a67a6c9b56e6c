"""Traffic kind ``train``: one update per batch through the program's own
task pipeline, trainer and optimizer.

Set-up builds ONE trainer (the compiled step with its state), drives it
from the seed through its first three updates on the pipeline's own
batches, warms every remaining shape of the cell, and hands that same
object to the measured window.  The plain reference follows the first
three updates after the window has closed and the trainer is freed.
"""

import collections
import gc
import os
import shutil
import statistics
import tempfile
import time
from argparse import Namespace

import numpy as np

from benchmark import harness, traffic, weights

#: updates the check follows (the contract's three)
CHECKED_UPDATES = 3
#: updates dispatched ahead of the one the host waits for: the device
#: always has work queued, and the host cannot run minutes ahead of it
IN_FLIGHT = 2


def trainer_args(cell, data_dir, seed):
    """The namespace the program's task / model / trainer read: the
    framework's defaults for what a cell does not state, then the
    configuration's model and optimizer settings, then the cell's."""
    cfg, tr = cell.config, cell.traffic
    args = Namespace(
        data=data_dir, seed=weights.fold_seed(seed), fp16=False, bf16_sr=False,
        allreduce_fp32_grad=False, fp16_init_scale=4, fp16_scale_window=None,
        min_loss_scale=1e-4, per_sample_clip_norm=0.0,
        data_parallel_size=-1, model_parallel_size=1, seq_parallel_size=1,
        pipeline_parallel_size=1, expert_parallel_size=1,
        zero_shard_optimizer=False, force_anneal=None, lr_shrink=0.1,
        warmup_updates=0, ema_decay=-1.0, validate_with_ema=False,
        max_update=1_000_000, update_freq=[1],
    )
    sizes = {k: v for k, v in cfg.items() if isinstance(v, (int, float, str))}
    for group in (sizes, cfg["train_args"], tr.get("task_args", {})):
        for k, v in group.items():
            setattr(args, k, v)
    args.lr = [float(cfg["train_args"]["lr"])]
    args.adam_betas = str(tuple(cfg["train_args"]["adam_betas"]))
    args.batch_size = int(tr["batch_size"]) * cell.chips
    return args


def seeded_model_class(base, seed):
    """The program's model class with one method replaced: its parameters
    come from ``benchmark/weights.py`` and the seed, in the shapes the
    program's own initializer would have made."""
    import jax

    def init_params(self, rng, sample):
        shapes = jax.eval_shape(
            lambda: base.init_params(self, rng, sample)
        )
        return weights.make(shapes, seed)

    return type("Seeded" + base.__name__, (base,), {"init_params": init_params})


class Feed:
    """The program's batch iterator, epoch after epoch without end.  It
    counts what it hands out: the ``epoch`` the last batch came from, that
    batch's place ``at`` in it and the ``batches`` the epoch holds, so that
    a run can say whether an epoch ended inside its window (a new iterator
    and an empty buffer: a gap no device work fills)."""

    def __init__(self, task, args, workers, buffer):
        self.epoch = self.at = self.batches = 0
        self._stream = self._endless(task, args, workers, buffer)

    def _endless(self, task, args, workers, buffer):
        while True:
            self.epoch += 1
            itr = task.get_batch_iterator(
                task.datasets["train"], batch_size=args.batch_size,
                seed=args.seed, epoch=self.epoch, num_workers=workers,
                data_buffer_size=buffer,
            ).next_epoch_itr(shuffle=True)
            self.batches = len(itr)
            for self.at, batch in enumerate(itr, 1):
                yield batch

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._stream)


def open_feed(cell, seed, work):
    """The cell's traffic as the program's own pipeline delivers it: the
    seeded corpus written under ``work``, the program's task over it, an
    endless stream of collated batches (:class:`Feed`), and ``shaped`` (the
    cell's host padding to its edges, where it has any)."""
    from unicore_tpu.tasks import TASK_REGISTRY

    cfg, tr = cell.config, cell.traffic
    traffic.write_corpus(tr["corpus"], work, seed)
    args = trainer_args(cell, work, seed)
    task = TASK_REGISTRY[cfg["task"]].setup_task(args)
    task.load_dataset("train")
    pad_idx = task.dictionary.pad()
    edges = tr.get("pad_edges")
    pad_values = {
        k: ((pad_idx if v[0] == "PAD" else v[0]), v[1])
        for k, v in tr.get("pad_values", {}).items()
    }

    def shaped(batch):
        if not edges:
            return batch, traffic._get(batch, tr["token_key"]).shape[1]
        return traffic.pad_to_edges(batch, edges, pad_values, tr["token_key"])

    batches = Feed(task, args, int(tr["data_workers"]),
                   int(tr["data_buffer"]))
    return args, task, batches, shaped, pad_values


def hyper_of(cfg, task):
    return dict(cfg["train_args"], pad_idx=int(task.dictionary.pad()),
                vocab_size=len(task.dictionary))


def read_sums(trainer):
    """The trainer's device-side running sums (loss, sample size, skipped
    updates, ...) as host floats: one small fetch."""
    import jax

    return {k: float(v) for k, v in jax.device_get(trainer._macc).items()}


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(t)
    ])
    return np.asarray(jax.device_get(fn(tree)), dtype=np.float64)


def change_norms(master, init, rounded):
    """Per-leaf norm of the master weights' change from the seeded weights,
    which a bfloat16 run first rounded to bfloat16 — leaf by leaf outside
    any compiled program: inside one, XLA on the chip drops the float32 ->
    bfloat16 -> float32 round trip as excess precision."""
    import jax
    import jax.numpy as jnp

    if rounded:
        init = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), init
        )
    return leaf_norms(
        jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
            master, init
        )
    )


def worst_leaf_gap(prog, ref):
    """The gap between the program's norm and the reference's on the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = float(np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare(program, reference, limits, names):
    """The numbers compared, each with its limit (see PERF.md for the
    readings each limit was set from)."""
    checks = []
    for k in range(CHECKED_UPDATES):
        checks.append({
            "name": f"loss_rel_gap.step{k + 1}",
            "value": abs(program["loss"][k] - reference["loss"][k])
            / abs(reference["loss"][k]),
            "limit": limits["loss_rel_gap"],
            "note": f"program {program['loss'][k]:.6f} "
                    f"reference {reference['loss'][k]:.6f}",
        })
    g, gi = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    checks.append({
        "name": "first_grad_norm_gap.worst_leaf", "value": g,
        "limit": limits["grad_norm_gap"], "note": names[gi],
    })
    d, di = worst_leaf_gap(program["delta_norms"], reference["delta_norms"])
    checks.append({
        "name": "param_change_norm_gap.worst_leaf", "value": d,
        "limit": limits["delta_norm_gap"], "note": names[di],
    })
    return checks


def run(cell, seed, seconds, trace, device, peaks):
    import jax

    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    from unicore_tpu.trainer import Trainer

    cfg, tr = cell.config, cell.traffic
    spans = harness.Spans()
    phase = harness.Phases("imports")
    work = tempfile.mkdtemp(prefix="unicore_bench_")
    try:
        args, task, batches, shaped, pad_values = open_feed(cell, seed, work)
        phase("corpus and task")
        model = seeded_model_class(
            ARCH_MODEL_REGISTRY[cfg["arch"]], seed
        ).build_model(args, task)
        loss = LOSS_REGISTRY[cfg["loss"]](task)
        trainer = Trainer(args, task, model, loss)
        pad_idx = task.dictionary.pad()
        edges = tr.get("pad_edges")
        phase("model and trainer")

        # -- set-up: the first three updates, recorded ----------------------
        program = {"loss": [], "grad_norms": None, "delta_norms": None}
        kept, seen_edges = [], set()
        prev = {"loss": 0.0, "sample_size": 0.0}
        for k in range(CHECKED_UPDATES):
            batch, edge = shaped(next(batches))
            kept.append(batch)
            seen_edges.add(edge)
            trainer.train_step([batch])
            macc = read_sums(trainer)
            program["loss"].append(
                (macc["loss"] - prev["loss"])
                / (macc["sample_size"] - prev["sample_size"])
            )
            prev = macc
            if k == 0:
                beta1 = float(cfg["train_args"]["adam_betas"][0])
                program["grad_norms"] = leaf_norms(
                    trainer.state["opt"]["slots"]["m"]
                ) / (1.0 - beta1)
        master = trainer.state["opt"]["master"]
        program["delta_norms"] = change_norms(
            # a float32 run keeps no separate master copy
            trainer.state["params"] if master is None else master,
            weights.make(trainer.state["params"], seed),
            rounded=master is not None,
        )
        del master
        names = weights.leaf_names(trainer.state["params"])
        phase("weights and the three checked updates")

        # -- set-up: every other shape of the cell, then a steady start -----
        for edge in sorted(set(edges or ()) - seen_edges):
            trainer.train_step([traffic.fit_to_edge(
                kept[0], edge, pad_values, tr["token_key"]
            )])
        for _ in range(int(tr["warm_updates"])):
            trainer.train_step([shaped(next(batches))[0]])
        jax.block_until_ready(trainer.state["params"])
        compiled_before = trainer._recompile_count
        overflow_before = read_sums(trainer)["overflow"]

        tokens = updates = sum_n2 = 0
        shapes = collections.Counter()
        in_flight = collections.deque()

        def drive(t_from, until):
            """The window's own call and feed, until ``until`` seconds
            have passed since ``t_from``."""
            nonlocal tokens, updates, sum_n2
            while True:
                with spans.span("data"):
                    batch, edge = shaped(next(batches))
                with spans.span("dispatch"):
                    trainer.train_step([batch])
                in_flight.append(trainer.state["loss_scale"])
                if len(in_flight) > IN_FLIGHT:
                    with spans.span("wait_device"):
                        in_flight.popleft().block_until_ready()
                lens = traffic.real_lengths(batch, tr["token_key"], pad_idx)
                tokens += int(lens.sum())
                sum_n2 += int(np.square(lens).sum())
                updates += 1
                shapes[edge] += 1
                if time.perf_counter() - t_from >= until:
                    break
            with spans.span("fetch"):
                jax.block_until_ready(trainer.state["params"])

        # -- a traced run: the same loop under the profiler, before the
        # window, so that tracing's cost stays out of the host-clock numbers
        traced = None
        if trace:
            tracer = harness.Tracer(
                os.path.join(harness.ROOT, ".bench_trace", cell.name)
            )
            spans.annotate = True
            tracer.start()
            drive(time.perf_counter(), float(tr["trace_seconds"]))
            traced = tracer.stop()
            spans.annotate = False
            tokens = updates = sum_n2 = 0
            shapes.clear()
        phase("warm-up and trace")

        # -- the measured window --------------------------------------------
        epoch_at_open, batch_at_open = batches.epoch, batches.at
        t0 = time.perf_counter()
        setup_s = t0 - harness.T_START
        drive(t0, seconds)
        t1 = time.perf_counter()
        window_s = t1 - t0
        epochs_ended = batches.epoch - epoch_at_open

        recompiles = trainer._recompile_count - compiled_before
        macc = read_sums(trainer)
        skipped = int(macc["overflow"] - overflow_before)
        finite = bool(np.isfinite(macc["loss"]))
        peak = harness.memory_peak_bytes()
        hyper = hyper_of(cfg, task)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    tokens_per_s = tokens / window_s / cell.chips
    harness.say(
        f"window: {updates} updates, {tokens} real tokens in "
        f"{window_s:.3f}s; shapes {dict(sorted(shapes.items()))}; "
        f"recompiles_in_window={recompiles} skipped_updates={skipped}; "
        f"epochs_ended_in_window={epochs_ended} (opened after batch "
        f"{batch_at_open} of epoch {epoch_at_open}, closed on batch "
        f"{batches.at} of {batches.batches})"
    )

    # -- outside the window: free the program, then follow it ---------------
    del trainer, model, loss, task, batches, in_flight
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    reference = harness.load_module(
        "reference", cfg["reference"], cell.base
    ).train_check(cfg, hyper, kept, seed, int(tr.get("reference_rows", 4)))
    ref_s = time.perf_counter() - t_ref
    checks = compare(program, reference, tr["limits"], names)
    checks.append({"name": "recompiles_in_window", "value": recompiles,
                   "limit": 0})
    checks.append({"name": "skipped_or_nonfinite_updates",
                   "value": skipped + (0 if finite else 1), "limit": 0})
    correct = harness.report_checks(checks)
    harness.say(f"reference: {ref_s:.1f}s for {CHECKED_UPDATES} updates "
                f"(not counted in setup_s)")

    out = {
        "correct": correct, "attempted": updates, "failed": skipped,
        "memory_peak_bytes": peak, "checks": checks,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        # what the per-layer readers read
        "window_s": window_s, "updates": updates,
        "epochs_ended_in_window": epochs_ended, "sum_n": tokens,
        "sum_n2": sum_n2, "mask_prob": float(tr["task_args"]["mask_prob"]),
        "peaks": peaks, "chips": cell.chips, "config": cfg, "base": cell.base,
        "data_wait_ms_median": 1e3 * statistics.median(
            spans.durations("data", since=t0)
        ),
        "trace": None,
    }
    if traced is not None:
        from benchmark import reduce

        out["trace"] = reduce.reduce(traced)
    return out
