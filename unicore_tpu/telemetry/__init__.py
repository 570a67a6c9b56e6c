"""Unified telemetry plane (docs/observability.md).

One package:

* :mod:`~unicore_tpu.telemetry.journal` — the per-host JSONL **event
  journal** every verdict-class event lands in (``emit(kind, **fields)``;
  the ``untracked-verdict-event`` lint rule polices that verdict log
  lines also emit here);
* :mod:`~unicore_tpu.telemetry.spans` — **step-time spans** for the hot
  loop (data_wait / plan_exchange / h2d / dispatch, plus lag-1 sampled
  ``device_busy``) feeding the ``host_blocked``/``device_busy`` metrics
  and cross-host straggler attribution;
* :mod:`~unicore_tpu.telemetry.prometheus` — text-format **/metrics**
  exposition for the serve plane and the optional trainer
  ``--metrics-port``;
* :mod:`~unicore_tpu.telemetry.profiler` — ``--profile-steps START:END``
  programmatic **XLA profiling** windows;
* :mod:`~unicore_tpu.telemetry.hlo_scopes` — the **scope table** of each
  program launched inside a profiler capture (device operation -> the
  module that owns it), left beside the capture;
* :mod:`~unicore_tpu.telemetry.trace` — the ``unicore-tpu-trace`` CLI
  that merges per-host journals into one causally-ordered timeline,
  Perfetto JSON, and a post-mortem summary.

``configure(args, rank=..., step_provider=...)`` wires the whole plane
for one process; ``emit`` is importable and safe everywhere (a no-op
until configured), so subsystems never need a configured-or-not branch.
"""

from unicore_tpu.telemetry import journal as _journal_mod
from unicore_tpu.telemetry import hlo_scopes, profiler, spans
from unicore_tpu.telemetry.journal import (
    ENV_RUN_ID,
    Journal,
    attempt,
    emit,
    ensure_run_id,
    journal_dir,
    journal_file,
    journal_path,
    mint_run_id,
    run_id,
    sync_run_id,
)

__all__ = [
    "ENV_RUN_ID",
    "Journal",
    "attempt",
    "configure",
    "configure_supervisor",
    "emit",
    "ensure_run_id",
    "hlo_scopes",
    "journal_dir",
    "journal_file",
    "journal_path",
    "log_config_payload",
    "mint_run_id",
    "profiler",
    "reset",
    "run_id",
    "spans",
    "sync_run_id",
]


def configure(args, *, rank: int, step_provider=None, role: str = "trainer"):
    """Wire journal + spans + profiler for this process (idempotent).
    Returns the journal."""
    if role == "trainer":
        # one run_id per multi-host run: peers adopt rank 0's before the
        # journal bakes it into every record
        _journal_mod.sync_run_id()
    j = _journal_mod.configure(
        args, rank=rank, step_provider=step_provider, role=role
    )
    spans.configure(args)
    profiler.configure(args, journal_dir(args), rank)
    return j


def configure_supervisor(args, rank: int):
    """Journal-only wiring for the --elastic supervisor process (no jax,
    no spans — it only narrates restarts)."""
    return _journal_mod.configure(
        args, rank=rank, step_provider=None, role="supervisor"
    )


def log_config_payload(args) -> dict:
    """The run-identity dict threaded through ``progress_bar``'s
    ``update_config`` so tensorboard/wandb runs are joinable with
    journals, checkpoints, and BENCH rows."""
    return {
        "run_id": run_id() or "",
        "attempt": attempt(),
        "telemetry_journal": journal_path() or "",
    }


def reset() -> None:
    """Clear all process-global telemetry state (tests)."""
    from unicore_tpu.telemetry import prometheus

    _journal_mod.reset()
    spans.reset()
    profiler.reset()
    hlo_scopes.reset()
    prometheus.reset()
