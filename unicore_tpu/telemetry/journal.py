"""Structured per-host JSONL event journal — the one stream every
subsystem's story lands in.

PRs 2-7 taught each subsystem to narrate its verdicts through ad-hoc
``logger`` lines: guard diagnoses, sentinel rewinds, checkpoint
fallbacks, elastic restarts, serve sheds.  Diagnosing a multi-host
incident from those means grepping N interleaved text logs with no
shared clock.  The journal replaces that with ONE machine-readable
append-only stream per host:

    {"run_id": ..., "attempt": 0, "rank": 1, "membership_epoch": 0,
     "update": 1412, "mono": 812.031, "wall": 1754300000.12,
     "kind": "elastic-verdict", ...event fields...}

Schema invariants (``unicore-tpu-trace`` and the tests depend on them):

* every record carries ``run_id`` / ``attempt`` / ``rank`` /
  ``membership_epoch`` / ``update`` / ``mono`` / ``wall`` / ``kind``;
* ``mono`` is ``time.monotonic()`` — comparable within one process only;
* ``wall`` is ``time.time()`` — comparable across hosts up to clock
  skew, which the trace merger corrects by anchoring on shared updates;
* ``update`` is the trainer's update counter at emission time (-1 when
  no trainer context exists, e.g. the serve plane or the supervisor);
* event fields never collide with the envelope (they are namespaced by
  the caller choosing distinct names).

``emit()`` is safe EVERYWHERE: before :func:`configure`, it drops the
record (debug-logged) instead of raising — a verdict path must never
die on its own telemetry.  Writes are line-buffered under a lock and
flushed per record, so a host killed mid-incident (the chaos
``host-loss`` kind is ``os._exit``) loses at most the record being
written.
"""

import json
import logging
import os
import threading
import time
import uuid
from typing import Any, Callable, Dict, Optional

logger = logging.getLogger(__name__)

#: run identity env contract: minted once at ``cli_main`` and inherited
#: by elastic restart children (the supervisor passes its environment
#: through), so every incarnation of one run shares the run_id and
#: journals and checkpoints stay joinable across restarts
ENV_RUN_ID = "UNICORE_TPU_RUN_ID"

_JOURNAL_DIRNAME = "telemetry"


def mint_run_id() -> str:
    """A new run id: sortable wall stamp + random tail."""
    return time.strftime("%Y%m%d-%H%M%S") + "-" + uuid.uuid4().hex[:8]


def ensure_run_id() -> str:
    """The run id from the environment, minting (and exporting) one if
    absent — call at the entry point BEFORE any child process spawns so
    elastic restarts inherit it."""
    rid = os.environ.get(ENV_RUN_ID)
    if not rid:
        rid = mint_run_id()
        os.environ[ENV_RUN_ID] = rid
    return rid


def sync_run_id(timeout: float = 30.0) -> str:
    """Cluster-consistent run id: rank 0 publishes its (env-inherited or
    minted) id to the coordination-service KV store and every other rank
    adopts it — so one multi-host run writes journals/checkpoints under
    ONE run_id even when the launcher didn't export UNICORE_TPU_RUN_ID.
    Falls back to the local id on any control-plane trouble (telemetry
    must never block training).  Stable across elastic restarts: the
    supervisor's environment carries the id into every incarnation."""
    rid = ensure_run_id()
    try:
        import jax

        from unicore_tpu.utils import retry

        if jax.process_count() <= 1:
            return rid
        client = retry.coordination_client()
        if client is None:
            return rid
        key = "unicore_tpu/telemetry/run_id"
        if jax.process_index() == 0:
            try:
                client.key_value_set(key, rid, allow_overwrite=True)
            except TypeError:  # older jaxlib without allow_overwrite
                client.key_value_set(key, rid)
            return rid
        adopted = retry.kv_wait(
            client, key, timeout=timeout, poll_s=1.0,
            describe="run-id adoption from rank 0",
        )
        if adopted:
            os.environ[ENV_RUN_ID] = str(adopted)
            return str(adopted)
    except Exception as err:
        logger.warning(
            f"cluster run-id adoption failed ({err}); journals from this "
            "host keep the locally-minted run id"
        )
    return rid


def run_id() -> Optional[str]:
    """The configured (or environment) run id, else None."""
    j = _journal
    if j is not None:
        return j.run_id
    return os.environ.get(ENV_RUN_ID)


def attempt() -> int:
    """Elastic incarnation counter (0 = first launch)."""
    from unicore_tpu.distributed import elastic

    return elastic.restart_count()


class Journal:
    """One per-host append-only JSONL event stream."""

    def __init__(self, path: str, *, run_id: str, rank: int,
                 attempt: int = 0,
                 step_provider: Optional[Callable[[], int]] = None):
        self.path = path
        self.run_id = run_id
        self.rank = int(rank)
        self.attempt = int(attempt)
        self._step_provider = step_provider
        self._lock = threading.Lock()
        self._file = None
        self._dropped = 0

    def _ensure_open(self):
        if self._file is None:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            self._file = open(self.path, "a", encoding="utf-8")
        return self._file

    def _update(self) -> int:
        if self._step_provider is None:
            return -1
        try:
            return int(self._step_provider())
        except Exception:
            return -1

    def record(self, kind: str, fields: Dict[str, Any]) -> Dict[str, Any]:
        from unicore_tpu.distributed import elastic

        rec = {
            "run_id": self.run_id,
            "attempt": self.attempt,
            "rank": self.rank,
            "membership_epoch": elastic.membership_epoch(),
            "update": fields.pop("update", None)
            if "update" in fields
            else self._update(),
            "mono": round(time.monotonic(), 6),
            "wall": round(time.time(), 6),
            "kind": str(kind),
        }
        rec.update(fields)
        return rec

    def emit(self, kind: str, **fields) -> None:
        rec = self.record(kind, fields)
        try:
            line = json.dumps(rec, default=_json_safe)
        except (TypeError, ValueError) as err:
            logger.debug(f"journal record for {kind!r} not serializable: {err}")
            return
        with self._lock:
            try:
                f = self._ensure_open()
                f.write(line + "\n")
                f.flush()
            except OSError as err:
                # telemetry must never kill the path it narrates; say so
                # once per journal instead of spamming a dying disk
                self._dropped += 1
                if self._dropped == 1:
                    logger.warning(
                        f"event journal write to {self.path} failed "
                        f"({err}); further failures drop silently"
                    )

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


def _json_safe(obj):
    """Last-resort coercion for event fields (numpy scalars, paths,
    exceptions) — the journal prefers a stringy record over a lost one."""
    try:
        import numpy as np

        if isinstance(obj, np.generic):
            return obj.item()
    except ImportError:
        pass
    return repr(obj)


# ---------------------------------------------------------------------------
# module-level journal (one per process)
# ---------------------------------------------------------------------------

_journal: Optional[Journal] = None


def journal_dir(args) -> str:
    """Where this run's journals live: ``--telemetry-dir`` when set, else
    ``<save_dir>/telemetry`` (beside the checkpoints the events narrate)."""
    explicit = getattr(args, "telemetry_dir", None)
    if explicit:
        return explicit
    save_dir = getattr(args, "save_dir", None) or "."
    return os.path.join(save_dir, _JOURNAL_DIRNAME)


def journal_file(directory: str, rank: int, role: str = "") -> str:
    """Per-process journal path.  Non-trainer roles (supervisor) get
    their own file: the supervisor and its training child share a rank,
    and two processes appending one file can tear lines."""
    suffix = f"_{role}" if role and role != "trainer" else ""
    return os.path.join(directory, f"events_rank{int(rank)}{suffix}.jsonl")


def configure(args, *, rank: int,
              step_provider: Optional[Callable[[], int]] = None,
              role: Optional[str] = None) -> Journal:
    """Install the per-process journal (idempotent per (path, attempt)).
    ``role`` lands in a ``run-start`` record so merged timelines show
    which plane (trainer / supervisor / serve) wrote each file."""
    global _journal
    path = journal_file(journal_dir(args), rank, role or "")
    att = attempt()
    if (
        _journal is not None
        and _journal.path == path
        and _journal.attempt == att
    ):
        return _journal
    _journal = Journal(
        path,
        run_id=ensure_run_id(),
        rank=rank,
        attempt=att,
        step_provider=step_provider,
    )
    if role is not None:
        _journal.emit("run-start", role=role)
    return _journal


def active() -> Optional[Journal]:
    return _journal


def journal_path() -> Optional[str]:
    return _journal.path if _journal is not None else None


def reset() -> None:
    """Drop the process journal (tests)."""
    global _journal
    if _journal is not None:
        _journal.close()
    _journal = None


def emit(kind: str, **fields) -> None:
    """Append one event to the per-host journal.  Safe before
    :func:`configure` (the record is dropped with a debug note) and safe
    on any thread — verdict paths call this and must never die on their
    own telemetry."""
    j = _journal
    if j is None:
        logger.debug(f"journal not configured; dropping event {kind!r}")
        return
    try:
        j.emit(kind, **fields)
    except Exception as err:  # pragma: no cover - defensive
        logger.debug(f"journal emit({kind!r}) failed: {err}")
