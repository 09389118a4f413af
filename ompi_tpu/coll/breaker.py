"""Per-tier circuit breaker for collective algorithm selection.

A pallas/quant kernel fault or a transport failure inside one
algorithm tier used to abort the collective; production traffic wants
the T3/EQuARX-style tiers to *degrade* instead — fall to the next
cheaper tier, keep the training step, and re-probe the fast tier once
it has had time to recover. Classic circuit breaker, keyed by
(operation, algorithm):

    CLOSED     tier healthy, used normally
    OPEN       tier tripped (`coll_breaker_threshold` consecutive
               failures); selection routes around it until
               `coll_breaker_cooldown_ms` elapses
    HALF_OPEN  cooldown elapsed; the next call may probe the tier —
               success closes it, failure re-opens (and restarts the
               cooldown)

Integration (coll/tuned.py):

- decision time — ``route(op, algo)`` walks the degradation chain
  (quant_pallas → quant_ring → ring → gather_reduce) past every OPEN
  tier; this also covers the traced path (parallel/bucketer) where
  runtime catching is impossible,
- dispatch time — ``TunedColl.allreduce`` catches a tier failure,
  calls ``record_failure`` and retries the next tier, recording the
  ``coll_tier_fallbacks`` SPC.

State is process-local and advisory: every rank degrades the same way
only if every rank observes the fault — rank-divergent tier choices
produce rank-divergent *results* only for quant tiers, which is why
the fallback target of every quant tier is the plain-precision chain
(bit-identical across ranks regardless of breaker state).
"""

from __future__ import annotations

import threading
from typing import Optional

from ..core import clock, dispatch_epoch
from ..core import config
from ..core.counters import SPC
from ..core.logging import get_logger

logger = get_logger("coll.breaker")

_enable = config.register(
    "coll", "breaker", "enable", type=bool, default=True,
    description="Degrade collective tiers on kernel/transport fault "
    "instead of failing the call",
)
_threshold = config.register(
    "coll", "breaker", "threshold", type=int, default=1,
    description="Consecutive tier failures before the breaker opens",
)
_cooldown = config.register(
    "coll", "breaker", "cooldown_ms", type=int, default=30000,
    description="How long an OPEN tier stays routed-around before a "
    "half-open re-probe",
)

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

# Degradation chain: derived from the schedule lattice (coll/sched/
# lattice.py) — the single declarative algorithm -> (tier, fallback)
# map that health/ledger's tier_of_algo also reads. The breaker's
# routing is a deny-set walk over that lattice where the deny set is
# the OPEN/denied tiers of the moment. sched/lattice is pure data
# (stdlib only), so this import cannot cycle.
from .sched import lattice as _lattice  # noqa: E402

NEXT_TIER = _lattice.fallback_map()
TERMINAL = _lattice.TERMINAL


class _Tier:
    __slots__ = ("state", "failures", "opened_at", "probing")

    def __init__(self) -> None:
        self.state = CLOSED
        self.failures = 0
        self.opened_at = 0.0
        self.probing = False


_tiers: dict[tuple[str, str], _Tier] = {}
_mu = threading.Lock()
# Every recorded failure, every success that changes a tier, a restore
# and a reset bump the dispatch epoch: breaker activity invalidates the
# memoized allreduce routes.


def enabled() -> bool:
    return _enable.value


def quiet() -> bool:
    """True when no tier is in a non-CLOSED state — the precondition
    for memoizing a routed dispatch (an OPEN tier's cooldown expiry is
    a lazy transition that a memoized route would never observe)."""
    if not _tiers:
        return True
    with _mu:
        return all(t.state == CLOSED for t in _tiers.values())


def _get(op: str, algo: str) -> _Tier:
    t = _tiers.get((op, algo))
    if t is None:
        t = _tiers[(op, algo)] = _Tier()
    return t


def state(op: str, algo: str) -> str:
    with _mu:
        return _tiers.get((op, algo), _Tier()).state


def is_open(op: str, algo: str) -> bool:
    """True while the tier should be routed around. An OPEN tier whose
    cooldown has elapsed transitions to HALF_OPEN and lets ONE caller
    through as the probe (subsequent callers keep routing around until
    the probe reports)."""
    if not _enable.value or not _tiers:
        return False
    with _mu:
        t = _tiers.get((op, algo))
        if t is None or t.state == CLOSED:
            return False
        if t.state == OPEN:
            elapsed_ms = (clock.monotonic() - t.opened_at) * 1e3
            if elapsed_ms < _cooldown.value:
                return True
            t.state = HALF_OPEN
            t.probing = False
        # HALF_OPEN: admit exactly one probe
        if not t.probing:
            t.probing = True
            SPC.record("coll_breaker_reprobes")
            from ..trace import span as tspan

            tspan.instant("breaker.reprobe", cat="coll", op=op,
                          algo=algo)
            logger.info("breaker %s/%s: half-open re-probe", op, algo)
            return False
        return True


def record_failure(op: str, algo: str) -> None:
    with _mu:
        t = _get(op, algo)
        t.failures += 1
        if t.state == HALF_OPEN or t.failures >= _threshold.value:
            if t.state != OPEN:
                SPC.record("coll_breaker_trips")
                from ..trace import span as tspan

                tspan.instant("breaker.trip", cat="coll", op=op,
                              algo=algo, failures=t.failures)
                logger.warning(
                    "breaker %s/%s: OPEN after %d failure(s); "
                    "degrading to %r for %d ms", op, algo, t.failures,
                    NEXT_TIER.get(algo, TERMINAL), _cooldown.value,
                )
            t.state = OPEN
            t.opened_at = clock.monotonic()
            t.probing = False
        dispatch_epoch.bump()  # after quiet() can see the new state


def record_success(op: str, algo: str) -> None:
    if not _tiers:  # hot path: nothing ever tripped, skip the lock
        return
    with _mu:
        t = _tiers.get((op, algo))
        if t is None:
            return
        if t.state != CLOSED or t.failures:
            dispatch_epoch.bump()
        if t.state != CLOSED:
            logger.info("breaker %s/%s: probe succeeded, CLOSED", op,
                        algo)
        t.state = CLOSED
        t.failures = 0
        t.probing = False


def next_tier(algo: str) -> Optional[str]:
    """The next-cheaper tier, or None at the end of the chain."""
    if algo == TERMINAL:
        return None
    return NEXT_TIER.get(algo, TERMINAL)


def on_tier_restored(tier: str) -> None:
    """health-ledger restore hook: the transport tier is HEALTHY
    again, so close every (op, algo) breaker riding it — the next
    dispatch goes straight back to the fast tier instead of waiting
    out each breaker's own cooldown."""
    if not _tiers:
        return
    from ..health.ledger import tier_of_algo

    with _mu:
        closed = []
        for (op, algo), t in _tiers.items():
            if t.state != CLOSED and tier_of_algo(algo) == tier:
                t.state = CLOSED
                t.failures = 0
                t.probing = False
                closed.append((op, algo))
        if closed:
            dispatch_epoch.bump()
    for op, algo in closed:
        logger.info("breaker %s/%s: closed by tier %r restore", op,
                    algo, tier)


def _health_denied(algo: str, scope: Optional[str]) -> bool:
    """True when the algorithm's transport tier is QUARANTINED in the
    health ledger (comm scope or global). Checked lock-free first so
    the fully-healthy hot path costs two attribute loads."""
    from ..health import ledger as _hl

    if _hl.LEDGER.quiet():
        return False
    return _hl.LEDGER.is_denied(_hl.tier_of_algo(algo), scope)


def route(op: str, algo: str, *, deny: tuple = (),
          scope: Optional[str] = None) -> str:
    """Walk the degradation chain past OPEN/denied/quarantined tiers.
    ``scope`` is the calling communicator's health scope (its cid);
    the health ledger's QUARANTINED verdict denies the whole transport
    tier, on top of the per-(op, algo) breaker state. Records the
    ``coll_tier_fallbacks`` SPC per step so monitoring sees routed
    degradation, not just dispatch-time retries."""
    if not _enable.value:
        return algo
    from ..health import ledger as _hl

    if not _tiers and not deny and _hl.LEDGER.quiet():
        return algo
    seen = []
    while algo in deny or is_open(op, algo) \
            or _health_denied(algo, scope):
        seen.append(algo)
        nxt = next_tier(algo)
        if nxt is None or nxt in seen:
            break
        SPC.record("coll_tier_fallbacks")
        algo = nxt
    if seen:
        from ..trace import span as tspan

        tspan.instant("breaker.fallback", cat="coll", op=op,
                      routed=seen, algo=algo)
        logger.info("breaker: %s routed %s -> %s", op,
                    " -> ".join(seen), algo)
    return algo


def reset() -> None:
    """Forget all tier state (tests / re-init)."""
    with _mu:
        dispatch_epoch.bump()
        _tiers.clear()
