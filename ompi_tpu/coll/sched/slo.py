"""SLO-aware schedule selection: latency targets over the frontier.

The autotuner's winner is the pure-throughput point — minimum modeled
cost. An SLO flips the objective: given a per-communicator p50 target,
``decide_*`` should pick the *cheapest-wire* point on the cached
latency/bandwidth frontier that still meets the target (don't spend
fabric bytes on latency headroom nobody asked for), falling back to
the throughput winner when no point meets it (the watchtower then
accounts the violation minutes per tenant scope).

Frontier semantics: retune/autotune store per-candidate
``{"algo", "score", "steps", "wire"}`` points on the cache entry
(non-semantic: excluded from the digest). Estimated p50 for a point is
score-proportional off the entry's live-measured baseline::

    est_p50_us(c) = baseline_p50_us * score(c) / score(winner)

so the estimate self-calibrates to the machine the baseline was
measured on. With no baseline stamped yet there is no absolute
latency scale and the winner stands — SLO selection is advisory
until the watchtower has observed the key once.

Targets: the ``coll_slo_p50_us`` cvar is the fleet-wide default
(0 = off); ``set_target(scope, us)`` overrides per communicator
scope (the health ledger's scope convention, ``str(comm.cid)``).
"""

from __future__ import annotations

import threading
from typing import Optional

from ...core import config, dispatch_epoch
from ...core.counters import SPC
from ...core.logging import get_logger

logger = get_logger("coll.sched")

_target_var = config.register(
    "coll", "slo", "p50_us", type=float, default=0.0,
    description="Fleet-wide allreduce p50 SLO target in microseconds "
                "(0 = off): decide_* picks the cheapest-wire frontier "
                "point meeting it instead of the pure-throughput "
                "winner; per-communicator overrides via "
                "slo.set_target(scope, us)",
)

_mu = threading.Lock()
_targets: dict[str, float] = {}
_violation_s: dict[str, float] = {}


def set_target(scope: str, p50_us: Optional[float]) -> None:
    """Per-scope SLO override (None/0 clears it). Bumps the dispatch
    epoch so memoized allreduce routes re-consult (the global cvar
    rides the config mutation's bump)."""
    with _mu:
        if not p50_us:
            _targets.pop(str(scope), None)
        else:
            _targets[str(scope)] = float(p50_us)
        dispatch_epoch.bump()


def target_for(scope: Optional[str] = None) -> float:
    """The effective p50 target (µs) for a scope; 0 = no SLO."""
    if scope is not None:
        with _mu:
            t = _targets.get(str(scope))
        if t:
            return t
    return float(_target_var.value or 0.0)


def targets() -> dict[str, float]:
    """Every scope with an explicit target (the watchtower's
    violation-accounting worklist; the global cvar rides scope
    ``"world"`` when set)."""
    with _mu:
        out = dict(_targets)
    g = float(_target_var.value or 0.0)
    if g and "world" not in out:
        out["world"] = g
    return out


def frontier_pick(entry: dict, target_us: float) -> Optional[str]:
    """The SLO point on an entry's frontier: among candidates whose
    estimated p50 meets ``target_us``, the one with the least wire
    bytes. None when the frontier/baseline is missing or when not even
    the winner meets the target (the caller keeps the winner and the
    violation is accounted, not hidden by a worse pick)."""
    frontier = entry.get("frontier")
    baseline = entry.get("baseline_p50_us")
    if not frontier or not baseline or target_us <= 0:
        return None
    best_score = min(c["score"] for c in frontier)
    if best_score <= 0:
        return None
    feasible = [c for c in frontier
                if baseline * c["score"] / best_score <= target_us]
    if not feasible:
        return None
    return min(feasible, key=lambda c: (c["wire"], c["score"]))["algo"]


def note_violation(scope: str, seconds: float) -> None:
    """Accumulate SLO-violation wall time for a tenant scope (the
    watchtower calls this per tick the live p50 misses the target)."""
    with _mu:
        _violation_s[str(scope)] = (_violation_s.get(str(scope), 0.0)
                                    + float(seconds))
    SPC.record("sched_slo_violation_ticks")


def violation_minutes() -> dict[str, float]:
    """Per-scope violation minutes (the Prometheus export shape)."""
    with _mu:
        return {s: round(v / 60.0, 6) for s, v in _violation_s.items()}


def reset_for_testing() -> None:
    with _mu:
        _targets.clear()
        _violation_s.clear()
        dispatch_epoch.bump()


__all__ = [
    "frontier_pick", "note_violation",
    "reset_for_testing", "set_target", "target_for", "targets",
    "violation_minutes",
]
