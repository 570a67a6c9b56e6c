"""On-demand XLA profiling: ``--profile-steps START:END``.

The always-on ``--profile`` flag traces a whole run — useless for "show
me updates 1200..1210 of a week-long job".  This window arms a
programmatic ``jax.profiler`` capture per host: the trace starts when
the update counter first reaches START and stops at END (or at run end,
whichever comes first), writing per-host TensorBoard-loadable traces to
``<telemetry-dir>/profile_rank<r>/`` and journaling ``profile-start`` /
``profile-stop`` events so merged timelines show exactly which updates
the capture covers.  The program's own ``unicore:`` spans
(telemetry/spans.py) land in the capture by themselves, and when it stops
the scope table of each captured step program is written beside it as
``hlo_scopes_<module>.json`` (telemetry/hlo_scopes.py; read both with
``python3 -m benchmark.trace_scopes <file.xplane.pb>``).

The tick is two integer compares per update when armed (and zero when
not constructed); the capture itself costs whatever XLA's profiler
costs — that is the point of bounding it to a window."""

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)


def parse_profile_steps(spec: Optional[str]):
    """``"START:END"`` -> (start, end) with 0 <= START < END, or None for
    an empty/absent spec.  Malformed specs raise ValueError at parse time
    (flag errors must fail the launch, not update 1200)."""
    if not spec:
        return None
    parts = str(spec).split(":")
    if len(parts) != 2:
        raise ValueError(
            f"--profile-steps wants START:END, got {spec!r}"
        )
    try:
        start, end = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--profile-steps wants integer START:END, got {spec!r}"
        ) from None
    if start < 0 or end <= start:
        raise ValueError(
            f"--profile-steps wants 0 <= START < END, got {spec!r}"
        )
    return start, end


class ProfileWindow:
    """Per-process profiling window driven by ``tick(update)``."""

    def __init__(self, start: int, end: int, out_dir: str, rank: int = 0):
        self.start = int(start)
        self.end = int(end)
        self.out_dir = os.path.join(out_dir, f"profile_rank{int(rank)}")
        self.active = False
        self.done = False

    def tick(self, update: int) -> None:
        if self.done:
            return
        if not self.active and self.start <= update < self.end:
            self._begin(update)
        elif self.active and update >= self.end:
            self._finish(update)

    def close(self, update: Optional[int] = None) -> None:
        """Stop a still-open capture at run end (a window past the last
        update must still produce a trace, not a corrupt half-file)."""
        if self.active:
            self._finish(update if update is not None else self.end)

    def _begin(self, update: int) -> None:
        import jax

        from unicore_tpu.telemetry import journal

        os.makedirs(self.out_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(self.out_dir, create_perfetto_link=False)
        except Exception as err:
            logger.warning(
                f"--profile-steps capture could not start ({err}); "
                "profiling disabled for this run"
            )
            self.done = True
            return
        self.active = True
        logger.info(
            f"PROFILE capture started at update {update} "
            f"(window {self.start}:{self.end}) -> {self.out_dir}"
        )
        journal.emit("profile-start", update=int(update),
                     window=[self.start, self.end], dir=self.out_dir)

    def _finish(self, update: int) -> None:
        import jax

        from unicore_tpu.telemetry import hlo_scopes, journal

        try:
            jax.profiler.stop_trace()
        except Exception as err:
            logger.warning(f"--profile-steps capture failed to stop: {err}")
        self.active = False
        self.done = True
        # which module owns each device operation of the captured
        # programs: hlo_scopes_<module>.json beside the trace
        scopes = hlo_scopes.write_tables(self.out_dir)
        logger.info(
            f"PROFILE capture stopped at update {update}; trace in "
            f"{self.out_dir} (load with TensorBoard or xprof; "
            f"{len(scopes)} scope table(s) beside it)"
        )
        journal.emit("profile-stop", update=int(update), dir=self.out_dir,
                     scope_tables=[os.path.basename(p) for p in scopes])


_window: Optional[ProfileWindow] = None


def configure(args, out_dir: str, rank: int) -> Optional[ProfileWindow]:
    """Arm the window from ``--profile-steps`` (None = unarmed)."""
    global _window
    parsed = parse_profile_steps(getattr(args, "profile_steps", None))
    if parsed is None:
        _window = None
        return None
    _window = ProfileWindow(parsed[0], parsed[1], out_dir, rank)
    return _window


def tick(update: int) -> None:
    if _window is not None:
        _window.tick(update)


def close(update: Optional[int] = None) -> None:
    if _window is not None:
        _window.close(update)


def reset() -> None:
    global _window
    _window = None
