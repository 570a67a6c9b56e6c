#!/usr/bin/env python3
"""Training entry point: epoch loop, validation cadence, stop handling.

Covers the same operator surface as the reference CLI
(/root/reference/unicore_cli/train.py): gradient-accumulation grouping,
mid-epoch and end-of-epoch save/validate cadence, early stopping on a
validation metric, and the --max-epoch / --max-update / --stop-time-hours /
--stop-min-lr / --patience stop knobs — driving the TPU Trainer's fused
SPMD step instead of a torch DDP loop.
"""

import json
import logging
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

_LOG_FIELDS = ("asctime", "levelname", "name", "message")
logging.basicConfig(
    stream=sys.stdout,
    level=os.environ.get("LOGLEVEL", "INFO").upper(),
    format=" | ".join(f"%({f})s" for f in _LOG_FIELDS),
    datefmt="%Y-%m-%d %H:%M:%S",
)
logger = logging.getLogger("unicore_tpu_cli.train")


class EarlyStopMonitor:
    """Trips once the tracked validation metric fails to improve ``patience``
    validations in a row.  A non-positive patience disables the monitor;
    validations that produced no metric are ignored entirely."""

    def __init__(self, patience: int, maximize: bool):
        self.patience = patience
        self.maximize = maximize
        self.best: Optional[float] = None
        self.strikes = 0

    def _improved(self, value: float) -> bool:
        if self.best is None:
            return True
        return value > self.best if self.maximize else value < self.best

    def should_stop(self, value: Optional[float]) -> bool:
        if value is None or self.patience <= 0:
            return False
        if self._improved(value):
            self.best = value
            self.strikes = 0
            return False
        self.strikes += 1
        if self.strikes < self.patience:
            return False
        logger.info(
            f"early stop: validation metric stagnant for {self.strikes} "
            f"consecutive validations (patience {self.patience})"
        )
        return True


class TrainSession:
    """One training run: owns the trainer, the early-stop monitor, the
    async checkpoint pool, and the save/validate cadence decisions."""

    def __init__(self, args, trainer, task):
        from unicore_tpu import checkpoint_utils

        self.args = args
        self.trainer = trainer
        self.task = task
        self.early_stop = EarlyStopMonitor(
            args.patience, args.maximize_best_checkpoint_metric
        )
        self.copy_pool = (
            checkpoint_utils.make_copy_pool() if args.async_checkpoint else None
        )
        self.valid_subsets = args.valid_subset.split(",")

    # -- stop conditions ------------------------------------------------

    _SIG_UNQUERIED = object()  # "caller did not supply a stop decision"

    def hard_stop_reason(self, preempt_sig=_SIG_UNQUERIED) -> Optional[str]:
        """Unconditional stop checks (budget-style limits, checked every
        inner step): a pending graceful-stop signal (``preempt_sig``, the
        COLLECTIVELY agreed SIGTERM/SIGINT decision — every host must stop
        at the same update or the survivors hang in the next collective;
        an explicit None means "agreed: no stop" and is NOT re-sampled,
        which could diverge from the peers), update budget, and wall-clock
        budget."""
        if preempt_sig is TrainSession._SIG_UNQUERIED:
            from unicore_tpu.distributed import guard

            preempt_sig = guard.stop_requested()  # local-only convenience
        if preempt_sig:
            sig = str(preempt_sig)
            if sig.startswith(("HOST-LOSS", "CONTROL-PLANE", "SELF-STALE")):
                # elastic verdict: every survivor stops HERE (the reason
                # rode the agreed slot-plan gather), saves a checkpoint,
                # and exits with the retryable taxonomy code so the
                # supervisor re-forms the run — not exit 0
                return (
                    f"elastic verdict {sig}: stopping all survivors at "
                    "this agreed update; saving a checkpoint, then exiting "
                    "for a supervised restart"
                )
            return (
                f"received {preempt_sig}: graceful stop — the in-flight "
                "update finished; saving a checkpoint and exiting 0"
            )
        n = self.trainer.get_num_updates()
        if self.args.max_update and n >= self.args.max_update:
            return f"num_updates: {n} hit --max-update ({self.args.max_update})"
        if self.args.stop_time_hours > 0:
            trained_h = self.trainer.cumulative_training_time() / 3600.0
            if trained_h > self.args.stop_time_hours:
                return (
                    f"exceeded --stop-time-hours "
                    f"({trained_h:.2f}h > {self.args.stop_time_hours}h)"
                )
        return None

    def lr_floor_reached(self) -> bool:
        if self.args.stop_min_lr <= -1:
            return False
        return self.trainer.get_lr() <= self.args.stop_min_lr

    # -- save / validate cadence ----------------------------------------

    @staticmethod
    def _on_interval(count: int, every: int) -> bool:
        return every > 0 and count > 0 and count % every == 0

    def cadence(self, epoch: int, end_of_epoch: bool, stopping: bool):
        """Decide (save?, validate?) for the current position in the run.

        Saves happen at epoch boundaries (--save-interval epochs), every
        --save-interval-updates mid-epoch (once past
        --validate-after-updates), and always when stopping.  Validation
        accompanies every mid-epoch save, happens at --validate-interval
        epoch boundaries and every --validate-interval-updates, and always
        when stopping — unless disabled outright."""
        n = self.trainer.get_num_updates()
        a = self.args
        save = (
            stopping
            or (end_of_epoch and self._on_interval(epoch, a.save_interval))
            or (
                self._on_interval(n, a.save_interval_updates)
                and n >= a.validate_after_updates
            )
        )
        validate = not a.disable_validation and (
            stopping
            or (save and not end_of_epoch)
            or (end_of_epoch and self._on_interval(epoch, a.validate_interval))
            or self._on_interval(n, a.validate_interval_updates)
        )
        return save, validate

    def checkpoint_and_validate(
        self, epoch_itr, end_of_epoch: bool
    ) -> Tuple[List[Optional[float]], bool]:
        """The per-step bookkeeping tail: evaluate stop conditions, run
        validation and/or write checkpoints per the cadence, and report
        (validation losses, should_stop)."""
        from unicore_tpu import checkpoint_utils
        from unicore_tpu.distributed import guard

        # ONE collective agreement per step: both the stop decision and the
        # skip-validation decision must be identical on every host (a host
        # validating while its peers skip desyncs the validation collectives)
        preempt_sig = guard.stop_requested_global()
        reason = self.hard_stop_reason(preempt_sig)
        if reason:
            logger.info(f"stopping training: {reason}")
            from unicore_tpu import telemetry

            # the collectively-agreed stop point: every survivor journals
            # the SAME update here, which is what the merged trace's
            # post-mortem names as "agreed stop"
            telemetry.emit(
                "agreed-stop",
                update=self.trainer.get_num_updates(),
                reason=reason,
                signal=str(preempt_sig) if preempt_sig else None,
            )
        stopping = reason is not None

        do_save, do_validate = self.cadence(
            epoch_itr.epoch, end_of_epoch, stopping
        )
        if preempt_sig:
            # preemption budget is short: save and get out, skip validation
            do_validate = False

        valid_losses: List[Optional[float]] = [None]
        if do_validate:
            self.trainer.flush_metrics()
            valid_losses = validate(
                self.args, self.trainer, self.task, epoch_itr,
                self.valid_subsets,
            )

        if self.early_stop.should_stop(valid_losses[0]):
            stopping = True
        if self.lr_floor_reached():
            logger.info(
                f"stopping training: lr {self.trainer.get_lr()} fell to "
                f"--stop-min-lr ({self.args.stop_min_lr})"
            )
            stopping = True

        if do_save or stopping:
            # --preemption-save-deadline: a SIGTERM grace budget is short
            # and non-negotiable, so the preemption save takes the
            # deadline-bounded MINIMAL path (one fsync'd checkpoint_last,
            # no publish copies / best bookkeeping / retention / retries)
            emergency = (
                "preempt"
                if preempt_sig
                and getattr(self.args, "preemption_save_deadline", 0) > 0
                else None
            )
            checkpoint_utils.save_checkpoint(
                self.args, self.trainer, epoch_itr, valid_losses[0],
                self.copy_pool, emergency=emergency,
            )
            if emergency is not None:
                # the emergency path drained + closed the pool (its
                # queued publishes of OLDER checkpoints must not land
                # after the emergency rename); close() must not re-join
                self.copy_pool = None
        return valid_losses, stopping

    def close(self):
        if self.copy_pool is not None:
            self.copy_pool.close()
            self.copy_pool.join()


def main(args) -> None:
    from unicore_tpu import checkpoint_utils, tasks, telemetry, utils
    from unicore_tpu.distributed import elastic, guard
    from unicore_tpu.distributed import utils as distributed_utils
    from unicore_tpu.logging import metrics
    from unicore_tpu.trainer import Trainer

    utils.import_user_module(args)

    # SIGTERM/SIGINT request a graceful stop: finish the in-flight update,
    # save a checkpoint, exit 0 — preemption doesn't lose work (a second
    # SIGINT aborts immediately)
    guard.install_signal_handlers()

    assert args.batch_size is not None, (
        "Must specify batch size either with --batch-size"
    )
    assert args.loss, "Please specify loss to train a model"

    metrics.reset()

    import jax
    import numpy as np

    from unicore_tpu.platform_utils import (
        configure_compilation_cache,
        describe_devices,
    )

    np.random.seed(args.seed)
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    # persistent XLA compile cache: restarts and repeated runs of the same
    # config reload their train-step programs instead of recompiling
    # (docs/performance.md, "Where the compile cache lives")
    configure_compilation_cache(
        getattr(args, "jax_compilation_cache_dir", None)
    )

    if distributed_utils.is_master(args):
        for d in (args.save_dir, args.tmp_save_dir):
            checkpoint_utils.verify_checkpoint_directory(d)

    logger.info(args)
    logger.info("DEVICES " + json.dumps(describe_devices()))

    task = tasks.setup_task(args)
    model = task.build_model(args)
    loss = task.build_loss(args)
    for label, obj in (("task", task), ("model", model), ("loss", loss)):
        logger.info(f"{label}: {obj.__class__.__name__}")

    trainer = Trainer(args, task, model, loss)
    logger.info(
        f"training on {jax.device_count()} devices across "
        f"{jax.process_count()} hosts"
    )

    # unified telemetry plane (docs/observability.md): the per-host event
    # journal + step spans + profiler window, and the optional Prometheus
    # port.  Configured BEFORE elastic.start so heartbeat leases can
    # publish the spans' step wall for straggler attribution.
    telemetry.configure(
        args, rank=jax.process_index(),
        step_provider=trainer.get_num_updates, role="trainer",
    )
    # --fused-norm: one documented flag drives LayerNorm/RMSNorm kernel
    # selection (modules/layer_norm.py); each module instance journals its
    # chosen path at trace time through the telemetry plane just configured
    from unicore_tpu.modules.layer_norm import configure_fused_norm

    configure_fused_norm(getattr(args, "fused_norm", "auto"))
    from unicore_tpu.telemetry import prometheus as _prom

    _prom.start_metrics_server(getattr(args, "metrics_port", 0) or 0)

    # elastic control plane: publish this host's liveness lease (always on
    # for multi-host runs); under --elastic, also monitor every peer's and
    # turn lease expiry into a named-rank verdict + agreed stop + restart
    elastic_runtime = elastic.start(
        args, step_fn=trainer.get_num_updates,
        step_wall_fn=telemetry.spans.avg_step_wall,
        collect_peer_walls=telemetry.spans.recorder().sample_interval > 0,
    )

    task.load_dataset(args.train_subset, combine=False, epoch=1)
    extra_state, epoch_itr = restore_session(args, trainer)

    if args.tensorboard_logdir and distributed_utils.is_master(args):
        os.makedirs(args.tensorboard_logdir, exist_ok=True)

    session = TrainSession(args, trainer, task)
    last_epoch = args.max_epoch or math.inf

    profiling = bool(getattr(args, "profile", False))
    if profiling:
        jax.profiler.start_trace(
            os.path.join(args.save_dir, "jax_trace"),
            create_perfetto_link=False,
        )

    started = time.time()
    try:
        while epoch_itr.next_epoch_idx <= last_epoch:
            valid_losses, stop = train_epoch(args, session, epoch_itr)
            if stop:
                break
            # epoch-level lr schedules key off the FIRST subset's metric
            trainer.lr_step(epoch_itr.epoch, valid_losses[0])
            epoch_itr = trainer.get_train_iterator(
                epoch_itr.next_epoch_idx,
                load_dataset=task.has_sharded_data("train"),
                disable_iterator_cache=False,
            )
    except Exception as err:
        _maybe_emergency_save_on_error(args, trainer, epoch_itr, err)
        raise
    finally:
        if profiling:
            jax.profiler.stop_trace()
        # a --profile-steps window still open at run end (or at an error
        # unwind) must close cleanly, not leave a torn trace
        telemetry.profiler.close(trainer.get_num_updates())
        session.close()
        # elastic runtime deliberately NOT stopped here: its monitor keeps
        # working toward a verdict while a terminal error unwinds, so the
        # CLI wrapper can reclassify an opaque collective failure as the
        # named host loss that caused it (cli_main stops it)

    # a host-loss/control-plane verdict stopped the run at an agreed
    # update and the checkpoint above landed — exit with the RETRYABLE
    # taxonomy code (never 0) so the supervisor re-forms the run
    if elastic_runtime is not None:
        elastic_runtime.raise_if_lost()

    logger.info(f"done training in {time.time() - started:.1f} seconds")


def _maybe_emergency_save_on_error(args, trainer, epoch_itr, err) -> None:
    """--emergency-save-on-error: before a fatal trainer exception unwinds
    the process, attempt one minimal save to ``checkpoint_emergency.pt``
    (a separate name — the crashing state may itself be the problem, so
    it must neither clobber checkpoint_last nor be auto-resumed).  Best
    effort only: a second failure here must not mask the original one."""
    if not getattr(args, "emergency_save_on_error", False):
        return
    from unicore_tpu import checkpoint_utils

    logger.error(
        f"fatal trainer exception ({type(err).__name__}: {err}); attempting "
        "an emergency checkpoint before aborting (--emergency-save-on-error)"
    )
    try:
        checkpoint_utils.save_checkpoint(
            args, trainer, epoch_itr, None, None, emergency="error"
        )
    except Exception:
        logger.exception("emergency save failed; aborting without it")


def restore_session(args, trainer):
    """Load the latest checkpoint (if any) and position the epoch iterator
    where the saved run left off."""
    from unicore_tpu import checkpoint_utils

    extra_state = checkpoint_utils.load_checkpoint(args, trainer)
    saved_itr = (
        (extra_state or {}).get("train_iterator")
        if not args.reset_dataloader
        else None
    )
    if saved_itr is not None:
        epoch_itr = trainer.get_train_iterator(
            epoch=saved_itr["epoch"], load_dataset=False
        )
        epoch_itr.load_state_dict(saved_itr)
    else:
        epoch_itr = trainer.get_train_iterator(epoch=1, load_dataset=False)
    trainer.maybe_init_from_iterator(epoch_itr)
    return extra_state, epoch_itr


_EPOCH_DONE = object()


def train_epoch(args, session, epoch_itr):
    """Run one epoch of updates; returns (valid_losses, should_stop)."""
    from unicore_tpu import telemetry
    from unicore_tpu.data import iterators
    from unicore_tpu.distributed import utils as distributed_utils
    from unicore_tpu.logging import metrics

    trainer, task = session.trainer, session.task

    with metrics.aggregate(name="train"):
        epoch = epoch_itr.epoch
        itr = epoch_itr.next_epoch_itr(
            fix_batches_to_gpus=args.fix_batches_to_gpus,
            shuffle=(epoch_itr.next_epoch_idx > args.curriculum),
        )
        # --update-freq may vary per epoch; past the schedule's end the last
        # entry applies
        uf_schedule = args.update_freq
        update_freq = uf_schedule[min(epoch, len(uf_schedule)) - 1]
        itr = iterators.GroupedIterator(itr, update_freq)
        # --prefetch-to-device: a producer thread plans/stacks/transfers
        # update N+1 while update N computes; items arrive as
        # PreparedUpdate/RawUpdate and train_step dispatches accordingly.
        # The prefetcher also overrides epoch_itr's position bookkeeping so
        # mid-epoch checkpoints record the CONSUMED position.
        itr = trainer.maybe_prefetch(itr, epoch_itr=epoch_itr, epoch=epoch)

        progress = _make_progress(
            args, itr, epoch,
            wandb_project=(
                args.wandb_project
                if distributed_utils.is_master(args)
                else None
            ),
            wandb_name=args.wandb_name,
        )

        # run identity into the external sinks (tensorboard text / wandb
        # config): run_id + attempt + journal path make the dashboards
        # joinable with journals, checkpoint headers, and BENCH rows
        progress.log_config(telemetry.log_config_payload(args))

        trainer.begin_epoch(epoch)
        valid_losses, stop = [None], False
        num_updates = trainer.get_num_updates()

        try:
            progress_iter = iter(progress)
            while True:
                # data_wait between-span: how long the training thread
                # sat waiting on the (possibly prefetched) iterator —
                # attributed to the NEXT update; entering it also
                # resolves the pending lag-1 device_busy probe at the
                # earliest idle host point
                with telemetry.spans.recorder().between_span("data_wait"):
                    grouped_samples = next(progress_iter, _EPOCH_DONE)
                if grouped_samples is _EPOCH_DONE:
                    break
                with metrics.aggregate("train_inner"):
                    step_ok = trainer.train_step(grouped_samples) is not None
                    # training-health sentinel tick (no-op unless
                    # --sentinel-interval > 0): observe this update's metrics,
                    # rewind + fast-forward `itr` on a confirmed anomaly, and
                    # capture rewind snapshots on the --snapshot-interval
                    # cadence.  Before flush_metrics so the device-side sums
                    # still include this update.
                    trainer.health_check(epoch_itr, itr)
                    num_updates = trainer.get_num_updates()
                    at_log_point = num_updates % args.log_interval == 0
                    if at_log_point:
                        # one device fetch per interval, inside the
                        # train_inner scope so the sums land in this
                        # aggregator
                        trainer.flush_metrics()

                if step_ok and at_log_point:
                    progress.log(
                        _with_wall(metrics.get_smoothed_values("train_inner")),
                        tag="train_inner", step=num_updates,
                    )
                    # interval stats restart here; the epoch aggregate above
                    # keeps accumulating independently
                    metrics.reset_meters("train_inner")

                valid_losses, stop = session.checkpoint_and_validate(
                    epoch_itr, end_of_epoch=not itr.has_next()
                )
                if stop:
                    break
        finally:
            # stop the prefetch producer (no-op for a plain iterator);
            # checkpoints taken above already recorded the consumed position
            trainer.finish_prefetch(itr)

    logger.info(f"end of epoch {epoch} (average epoch stats below)")
    trainer.flush_metrics()
    progress.print(
        _with_wall(metrics.get_smoothed_values("train")),
        tag="train", step=num_updates,
    )
    metrics.reset_meters("train")
    return valid_losses, stop


def _make_progress(args, itr, epoch, **extra):
    """Progress/logging wrapper around a batch iterator; tensorboard output
    only from the master host."""
    from unicore_tpu.distributed import utils as distributed_utils
    from unicore_tpu.logging import progress_bar

    tb_dir = args.tensorboard_logdir if distributed_utils.is_master(args) else None
    fmt = "simple" if args.no_progress_bar else "tqdm"
    return progress_bar.progress_bar(
        itr, log_format=args.log_format, log_interval=args.log_interval,
        epoch=epoch, tensorboard_logdir=tb_dir, default_log_format=fmt,
        **extra,
    )


def _with_wall(stats: Dict[str, Any]) -> Dict[str, Any]:
    from unicore_tpu.logging import metrics

    stats["wall"] = round(metrics.get_meter("default", "wall").elapsed_time, 0)
    return stats


def validate(args, trainer, task, epoch_itr, subsets: List[str]) -> List[Optional[float]]:
    """Evaluate on each validation subset; returns one metric per subset.

    Per-batch logging outputs accumulate ON DEVICE (trainer.valid_step with
    ``accumulate=True``); the host fetches the summed totals once per
    subset instead of once per batch.  Losses that declare their eval
    logging outputs non-summable (``logging_outputs_can_be_summed(False)``)
    opt out: their outputs are collected per batch and handed to
    ``reduce_metrics`` unsummed, matching the reference's list semantics."""
    from unicore_tpu.logging import metrics

    fixed_seed = args.fixed_validation_seed  # None -> step-keyed eval rng
    summable = task.logging_outputs_can_be_summed(trainer.loss, is_train=False)

    trainer.begin_valid_epoch(epoch_itr.epoch)
    results = []
    for subset in subsets:
        logger.info(f'begin validation on "{subset}" subset')
        if subset not in task.datasets:
            task.load_dataset(subset, combine=False, epoch=1)
        itr = trainer.get_valid_iterator(subset).next_epoch_itr(shuffle=False)
        progress = _make_progress(
            args, itr, epoch_itr.epoch, prefix=f"valid on '{subset}' subset"
        )

        # separate metrics root: validation must not bleed into train meters
        with metrics.aggregate(new_root=True) as agg:
            per_batch = []
            for i, sample in enumerate(progress):
                if args.max_valid_steps is not None and i > args.max_valid_steps:
                    break
                out = trainer.valid_step(
                    sample, seed=fixed_seed, accumulate=summable
                )
                if not summable and out is not None:
                    per_batch.append(out)
            if summable:
                totals = trainer.finish_valid_accum()
                per_batch = [totals] if totals else []
            task.reduce_metrics(per_batch, trainer.loss, subset)

        stats = _finalize_valid_stats(args, trainer, agg.get_smoothed_values())
        progress.print(stats, tag=subset, step=trainer.get_num_updates())
        results.append(stats.get(args.best_checkpoint_metric, None))
    return results


def _finalize_valid_stats(args, trainer, stats: Dict[str, Any]) -> Dict[str, Any]:
    from unicore_tpu import checkpoint_utils

    stats["num_updates"] = trainer.get_num_updates()
    metric = args.best_checkpoint_metric
    best_so_far = checkpoint_utils.best_score()
    if best_so_far is not None and metric in stats:
        pick = max if args.maximize_best_checkpoint_metric else min
        stats[f"best_{metric}"] = pick(best_so_far, stats[metric])
    return stats


def cli_main(modify_parser: Optional[Callable] = None) -> None:
    # UNICORE_TPU_PLATFORM=cpu forces the virtual-CPU mesh BEFORE any jax
    # backend init (UNICORE_TPU_CPU_DEVICES sets its size, default 8) —
    # the one explicit CPU switch for example scripts, tests and CI
    # (platform_utils); nothing selects the CPU unasked.
    from unicore_tpu.platform_utils import force_host_cpu_from_env

    force_host_cpu_from_env(default_devices=8)

    from unicore_tpu import options, telemetry
    from unicore_tpu.distributed import elastic
    from unicore_tpu.distributed import utils as distributed_utils

    parser = options.get_training_parser()
    args = options.parse_args_and_arch(parser, modify_parser=modify_parser)

    # mint (or inherit) the run identity BEFORE any child can spawn: the
    # --elastic supervisor passes its environment through, so restarted
    # incarnations share the run_id and differ only in the attempt count
    telemetry.ensure_run_id()

    if getattr(args, "elastic", False) and not elastic.is_child():
        # --elastic: this process becomes the per-host supervisor; training
        # runs in a child it restarts on retryable failures (the child
        # re-parses this same argv with the child env marker set).
        # One process per chip: a TPU belongs to the first process that
        # initializes a backend on it, so the supervisor must never do so.
        # Up to here it has only IMPORTED jax (option parsing pulls in the
        # model registry) and elastic.supervise keeps it that way — it
        # spawns, waits and classifies exit codes; no jax.devices(), no
        # default_backend()/on_tpu(), no array (tests/test_elastic.py
        # asserts the backend table stays empty).
        sys.exit(elastic.supervise(args, sys.argv[1:]))

    try:
        distributed_utils.call_main(args, main)
    except KeyboardInterrupt:
        raise
    except Exception as err:
        # distinct, documented exit codes for the terminal error taxonomy
        # (docs/robustness.md "Elastic runs"): external supervisors — k8s,
        # slurm, the --elastic loop — tell retryable from fatal without
        # log-grepping.  A dead peer races its own diagnosis, so an
        # opaque failure first gives the heartbeat monitor one timeout to
        # name the culprit.  Unclassified errors keep the stock
        # traceback/rc 1.
        code = elastic.reclassify_with_verdict(err, elastic.exit_code(err))
        if code == elastic.EXIT_UNCAUGHT:
            raise
        retryable = code in elastic.RETRYABLE_EXIT_CODES
        logger.error(
            f"FATAL: {type(err).__name__}: {err} — exiting "
            f"{code} ({elastic.EXIT_CODE_NAMES[code]}, "
            f"{'retryable' if retryable else 'not retryable'})",
            exc_info=True,
        )
        sys.exit(code)
    finally:
        elastic.stop()


if __name__ == "__main__":
    cli_main()
