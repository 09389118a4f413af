"""Health sentinel: progress-engine heartbeat + per-op stall deadlines.

The breaker can only degrade a tier that *fails*; a tier that
*wedges* (a device call that never returns, a peer that stopped
draining its ring) hangs the collective forever — the failure the
bench watchdog used to abort the whole run on. The sentinel turns a
wedge into an ordinary tier fault:

- **heartbeat** — ``core/progress`` stamps ``beat()`` on every sweep
  (injected via ``progress.set_heartbeat`` so core never imports
  health); ``heartbeat_age()`` is the supervisor's "is the progress
  engine itself alive" signal.

- **bounded dispatch** — ``run_bounded(fn, deadline_s)`` runs the
  tier's plan on a worker thread and raises ``StallError`` when the
  deadline lapses. tuned's dispatch loop catches it like any tier
  fault: breaker trips, ledger quarantines, and the collective is
  re-issued on the next healthy tier mid-flight instead of hanging
  the job. The wedged worker is abandoned (daemon thread — Python
  cannot cancel a stuck C call); its eventual result is discarded,
  which is safe within one process because every tier is a pure
  function of its input buffer. Across controllers a rank-local stall
  leaves an extra in-flight device op behind — see the abandoned-op
  hazard in docs/DESIGN.md §17 before arming bounded dispatch on a
  multi-controller mesh.

Off by default (``health_sentinel_deadline_ms=0``): the bounded path
costs a thread handoff per collective, so only drills, bench sweeps
and wedge-prone deployments arm it.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from ..core import clock
from ..core import config
from ..core.counters import SPC
from ..core.errors import OmpiTpuError
from ..core.logging import get_logger

logger = get_logger("health.sentinel")

_deadline_var = config.register(
    "health", "sentinel", "deadline_ms", type=float, default=0.0,
    description="Per-collective stall deadline: a tier that does not "
    "complete within this window raises StallError and the dispatch "
    "falls to the next tier (0 disables bounded dispatch)",
)
_stall_ms_var = config.register(
    "health", "sentinel", "heartbeat_stall_ms", type=float,
    default=5000.0,
    description="Progress-engine heartbeat age past which the "
    "supervisor reports the engine itself stalled",
)


class StallError(OmpiTpuError):
    """An operation exceeded its sentinel deadline — the tier is
    wedged, not failed. Tuned treats it exactly like a tier fault."""

    errclass = "ERR_INTERN"


# -- progress heartbeat -------------------------------------------------

_last_beat = 0.0  # monotonic; 0 = never beaten
_installed = False


def beat() -> None:
    """Stamp the heartbeat (called from ProgressEngine.progress once
    per sweep — one attribute store, no lock)."""
    global _last_beat
    _last_beat = clock.monotonic()


def install() -> None:
    """Wire beat() into the progress engine (idempotent)."""
    global _installed
    if _installed:
        return
    from ..core import progress

    progress.set_heartbeat(beat)
    _installed = True
    beat()


def heartbeat_age() -> float:
    """Seconds since the last progress sweep (inf before the first)."""
    if not _last_beat:
        return float("inf")
    return clock.monotonic() - _last_beat


def heartbeat_stalled() -> bool:
    """True when the engine has been pumped at least once but not
    within the configured stall window."""
    if not _installed or not _last_beat:
        return False
    return heartbeat_age() * 1e3 > _stall_ms_var.value


# -- bounded dispatch ---------------------------------------------------

def run_bounded(fn: Callable[[], Any], deadline_s: float, *,
                what: str = "op") -> Any:
    """Run ``fn`` with a stall deadline. Returns its result, re-raises
    its exception, or raises StallError after ``deadline_s`` — the
    worker is then abandoned (daemon), its late result dropped."""
    box: dict = {}
    done = threading.Event()

    def _worker() -> None:
        try:
            box["out"] = fn()
        # commlint: allow(broadexcept) — relayed to the caller, not eaten
        except BaseException as exc:  # noqa: B036
            box["exc"] = exc
        finally:
            done.set()

    t = threading.Thread(target=_worker, daemon=True,
                         name=f"ompi-tpu-sentinel:{what}")
    t.start()
    if not clock.wait_event(done, deadline_s):
        SPC.record("health_stalls")
        from ..trace import span as tspan

        tspan.instant("health.stall", cat="health", what=what,
                      deadline_ms=deadline_s * 1e3)
        logger.warning("sentinel: %s stalled past %.0f ms; cancelling",
                       what, deadline_s * 1e3)
        raise StallError(
            f"{what} exceeded its {deadline_s * 1e3:.0f} ms stall "
            f"deadline (tier wedged)"
        )
    if "exc" in box:
        raise box["exc"]
    return box["out"]


def deadline_s() -> Optional[float]:
    """The active per-op stall deadline in seconds, or None when
    bounded dispatch is off."""
    ms = _deadline_var.value
    return (ms / 1e3) if ms and ms > 0 else None


def maybe_bounded(fn: Callable[[], Any], *, what: str = "op") -> Any:
    """fn() directly when bounded dispatch is off (the default — zero
    overhead), else run_bounded with the configured deadline."""
    d = deadline_s()
    if d is None:
        return fn()
    return run_bounded(fn, d, what=what)


def reset() -> None:
    """Tests: forget the heartbeat (install state is kept)."""
    global _last_beat
    _last_beat = 0.0
