"""Health ledger: per-tier liveness state machine with hysteresis.

The PR-5 circuit breaker (coll/breaker.py) is keyed (op, algo): a
quant kernel fault opens *that* breaker, but the underlying cause —
a device call wedged, the shm segment torn — takes out every
algorithm riding the same transport **tier**. The ledger promotes the
failure domain from (op, algo) to the tier itself, a small lattice of
transport planes:

    device    XLA/pallas device collectives over the fabric
    fastpath  shared-ring doorbell lane (btl/sm fp_*)
    shm       shm v2 segment transfers
    dcn       cross-slice TCP links
    fabric    pml/fabric engine p2p
    host      numpy gather_reduce — the always-healthy terminal

Each (scope, tier) entry walks a four-state machine with hysteresis
on both edges (one flaky success must not restore a dead tier, one
flaky failure must not quarantine a healthy one):

    HEALTHY ──failure──▶ SUSPECT ──suspect_threshold failures──▶
    QUARANTINED ──probe success──▶ PROBATION
    PROBATION ──probation_successes successes──▶ HEALTHY
    PROBATION ──any failure──▶ QUARANTINED   (hysteresis)
    SUSPECT ──success──▶ HEALTHY             (consecutive counts reset)

``scope`` is a communicator cid (or "global"): one comm's quarantines
never trip another's tiers — the isolation precursor to the
multi-tenant daemon (ROADMAP). Routing (``is_denied``) consults both
the comm scope and the global scope, so a supervisor-level global
quarantine still protects every comm.

Determinism: the transition log records (seq, scope, tier, from→to,
cause) and **no timestamps**, so the same fault schedule reproduces a
byte-identical ``digest()`` across runs and ranks — the same
reproducibility contract faultline's plan digest carries. Wall-clock
state (when a quarantine began, for time-to-restore pvars and the
lazy cooldown) lives outside the log.

When the supervisor cannot actively re-probe a tier — no supervisor
thread running, or no canary registered for it — a QUARANTINED entry
whose ``health_ledger_quarantine_ms`` has elapsed transitions to
PROBATION anyway: lazily at the next routing decision (``is_denied``)
or from the supervisor's tick (``apply_cooldown``). This is the
pre-supervisor in-band cooldown probe, kept so health degrades
gracefully to exactly the PR-5 behaviour when the prober is off, and
so a quarantine never outlives its cooldown just because nothing can
probe the tier.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Callable, Optional

from ..core import clock
from ..core import config, dispatch_epoch
from ..core.counters import SPC
from ..core.logging import get_logger

logger = get_logger("health.ledger")

_enable = config.register(
    "health", "base", "enable", type=bool, default=True,
    description="Track per-tier health and route collectives around "
    "QUARANTINED tiers (the breaker's failure domain promoted from "
    "(op, algo) to the transport tier)",
)
_suspect_threshold = config.register(
    "health", "ledger", "suspect_threshold", type=int, default=3,
    description="Consecutive tier failures before SUSPECT escalates "
    "to QUARANTINED (hysteresis on the down edge)",
)
_probation_successes = config.register(
    "health", "ledger", "probation_successes", type=int, default=2,
    description="Consecutive successes a PROBATION tier needs before "
    "it is HEALTHY again (hysteresis on the up edge)",
)
_quarantine_ms = config.register(
    "health", "ledger", "quarantine_ms", type=int, default=60000,
    description="Without a running supervisor, how long a QUARANTINED "
    "tier stays denied before the lazy in-band cooldown admits a "
    "probe (the supervisor's background re-probe replaces this)",
)

HEALTHY, SUSPECT, QUARANTINED, PROBATION = (
    "healthy", "suspect", "quarantined", "probation",
)

#: The transport tiers, fastest first. "device_pallas" is the sched
#: compiler's fused-kernel tier (sched/pallas_lower) sitting above the
#: hand-written device kernels; "host" is the terminal plane (pure
#: numpy + device_put) and is never quarantined — there must always be
#: a routable tier.
TIERS = ("device_pallas", "device", "fastpath", "shm", "dcn", "fabric",
         "host")

GLOBAL_SCOPE = "global"

#: Fallback algorithm -> tier map, used only if the schedule lattice
#: (coll/sched/lattice.py — the authoritative source) is unimportable.
_ALGO_TIER = {
    "gather_reduce": "host",
}


def tier_of_algo(algo: str) -> str:
    """The transport tier a collective algorithm executes on.
    Delegates to the schedule lattice — the single declarative
    algorithm -> (tier, fallback) map that coll/breaker also derives
    its degradation chain from."""
    try:
        from ..coll.sched import lattice
    except ImportError:
        return _ALGO_TIER.get(algo, "device")
    return lattice.tier_of(algo)


class _Entry:
    __slots__ = ("state", "failures", "successes", "quarantined_at",
                 "cause")

    def __init__(self) -> None:
        self.state = HEALTHY
        self.failures = 0       # consecutive failures
        self.successes = 0      # consecutive successes (PROBATION)
        self.quarantined_at = 0.0  # monotonic; time-to-restore pvar
        self.cause = ""


class Ledger:
    """The process health lattice: (scope, tier) -> state machine
    entry plus the deterministic transition log."""

    def __init__(self) -> None:
        self._mu = threading.RLock()
        self._entries: dict[tuple[str, str], _Entry] = {}
        self._log: list[str] = []
        self._generation = 0
        # Lock-free fast-path flags (GIL-atomic bool reads): the hot
        # dispatch path checks these before taking any lock.
        self._any_tracked = False     # any entry exists at all
        self._any_unhealthy = False   # any entry not HEALTHY
        self._restore_cbs: list[Callable[[str, str], None]] = []
        # (tier, scope) restores whose callbacks are still owed —
        # queued under _mu by _transition, fired outside it by
        # _drain_restored so a slow callback cannot stall dispatch.
        self._pending_restored: list[tuple[str, str]] = []

    # -- cheap reads (no lock; GIL-atomic attribute loads) -------------

    def quiet(self) -> bool:
        """True when every tracked tier is HEALTHY — the precondition
        for memoized routing (the communicator's allreduce lane)."""
        return not self._any_unhealthy

    def tracked(self) -> bool:
        return self._any_tracked

    def generation(self) -> int:
        return self._generation

    # -- state machine -------------------------------------------------

    def _entry(self, scope: str, tier: str) -> _Entry:
        e = self._entries.get((scope, tier))
        if e is None:
            e = self._entries[(scope, tier)] = _Entry()
            self._any_tracked = True
        return e

    def _transition(self, scope: str, tier: str, e: _Entry,
                    to_state: str, cause: str) -> None:
        """Record one edge: log line (timestamp-free — the digest
        contract), generation bump, trace instant, pvars."""
        frm = e.state
        e.state = to_state
        e.cause = cause
        self._generation += 1
        self._log.append(
            f"{len(self._log)} {scope} {tier} {frm}->{to_state} {cause}"
        )
        self._any_unhealthy = any(
            x.state != HEALTHY for x in self._entries.values()
        )
        # after quiet() can see the new state: a route memoized under
        # the new epoch was built against it
        dispatch_epoch.bump()
        from ..trace import span as tspan

        tspan.instant(f"health.{to_state}", cat="health", tier=tier,
                      scope=scope, prev=frm, cause=cause)
        if to_state == QUARANTINED:
            if frm != QUARANTINED:
                e.quarantined_at = clock.monotonic()
            SPC.record("health_quarantines")
            logger.warning("health: tier %r QUARANTINED (scope=%s, "
                           "cause=%s)", tier, scope, cause)
        elif to_state == HEALTHY and frm in (PROBATION, QUARANTINED):
            SPC.record("health_restores")
            if e.quarantined_at:
                SPC.record_latency(
                    "health_time_to_restore",
                    clock.monotonic() - e.quarantined_at,
                )
            e.quarantined_at = 0.0
            logger.warning("health: tier %r restored to HEALTHY "
                           "(scope=%s)", tier, scope)
            # Callbacks and breaker.on_tier_restored fire outside _mu
            # (_drain_restored): a slow callback under the lock would
            # stall every concurrent dispatch, and taking breaker._mu
            # under ledger._mu would pin a ledger->breaker lock order
            # a future breaker->ledger path could deadlock against.
            self._pending_restored.append((tier, scope))
        else:
            logger.info("health: %s/%s %s -> %s (%s)", scope, tier,
                        frm, to_state, cause)

    def _drain_restored(self) -> None:
        """Fire restore callbacks + breaker.on_tier_restored for every
        restore queued by _transition. Called by the mutators after
        releasing ``_mu`` — never while holding it."""
        if not self._pending_restored:
            return  # GIL-atomic read; the common path stays lock-free
        while True:
            with self._mu:
                if not self._pending_restored:
                    return
                items = self._pending_restored
                self._pending_restored = []
                cbs = list(self._restore_cbs)
            from ..coll import breaker

            for tier, scope in items:
                for cb in cbs:
                    try:
                        cb(tier, scope)
                    except Exception:  # commlint: allow(broadexcept)
                        logger.exception(
                            "health: restore callback failed")
                # Tier back: close every (op, algo) breaker riding it
                # so the next dispatch goes straight to the restored
                # tier.
                breaker.on_tier_restored(tier)

    def report_failure(self, tier: str, *, scope: str = GLOBAL_SCOPE,
                       cause: str = "") -> None:
        """An in-band operation (or probe) on ``tier`` failed."""
        if not _enable.value or tier == "host":
            return  # host is the terminal plane; never quarantined
        with self._mu:
            e = self._entry(scope, tier)
            e.failures += 1
            e.successes = 0
            if e.state == HEALTHY:
                self._transition(scope, tier, e, SUSPECT, cause)
            if e.state == SUSPECT \
                    and e.failures >= _suspect_threshold.value:
                self._transition(scope, tier, e, QUARANTINED, cause)
            elif e.state == PROBATION:
                # hysteresis: one failure on probation re-quarantines
                self._transition(scope, tier, e, QUARANTINED, cause)
        self._drain_restored()

    def report_success(self, tier: str, *, scope: str = GLOBAL_SCOPE
                       ) -> None:
        """An in-band operation (or probe) on ``tier`` completed."""
        if not self._any_tracked or not _enable.value:
            return  # hot path: nothing ever failed, skip the lock
        with self._mu:
            e = self._entries.get((scope, tier))
            if e is None:
                return
            e.failures = 0
            if e.state == SUSPECT:
                e.successes = 0
                self._transition(scope, tier, e, HEALTHY, "recovered")
            elif e.state == QUARANTINED:
                # a probe got through (breaker HALF_OPEN / supervisor)
                e.successes = 1
                self._transition(scope, tier, e, PROBATION, "probe_ok")
                if e.successes >= _probation_successes.value:
                    self._transition(scope, tier, e, HEALTHY,
                                     "probation_passed")
            elif e.state == PROBATION:
                e.successes += 1
                if e.successes >= _probation_successes.value:
                    self._transition(scope, tier, e, HEALTHY,
                                     "probation_passed")
        self._drain_restored()

    def suspect(self, tier: str, *, scope: str = GLOBAL_SCOPE,
                cause: str = "") -> None:
        """Out-of-band suspicion (the telemetry straggler detector):
        move a HEALTHY tier to SUSPECT *without* charging a
        consecutive failure. Skew evidence is circumstantial — it puts
        the tier on the supervisor's SUSPECT sweep so the prober
        decides, but escalation to QUARANTINED stays reserved for
        in-band/probe failures (``report_failure``). Repeated skew
        reports therefore never quarantine a tier by themselves."""
        if not _enable.value or tier == "host":
            return
        with self._mu:
            e = self._entry(scope, tier)
            if e.state == HEALTHY:
                self._transition(scope, tier, e, SUSPECT, cause)

    def quarantine(self, tier: str, *, scope: str = GLOBAL_SCOPE,
                   cause: str = "forced") -> None:
        """Operator/supervisor override: straight to QUARANTINED."""
        if not _enable.value or tier == "host":
            return
        with self._mu:
            e = self._entry(scope, tier)
            e.failures = max(e.failures, _suspect_threshold.value)
            e.successes = 0
            if e.state != QUARANTINED:
                self._transition(scope, tier, e, QUARANTINED, cause)

    def restore(self, tier: str, *, scope: str = GLOBAL_SCOPE,
                cause: str = "forced") -> None:
        """Operator override: straight back to HEALTHY."""
        with self._mu:
            e = self._entries.get((scope, tier))
            if e is None or e.state == HEALTHY:
                return
            e.failures = 0
            e.successes = 0
            self._transition(scope, tier, e, HEALTHY, cause)
        self._drain_restored()

    def apply_cooldown(self, tier: str, *,
                       scope: str = GLOBAL_SCOPE) -> bool:
        """Time-based QUARANTINED -> PROBATION once ``quarantine_ms``
        has elapsed — the fallback for a quarantined tier the
        supervisor cannot actively re-probe (no registered canary:
        operator quarantine on an unwired tier, probe retired). True
        when the transition fired."""
        with self._mu:
            e = self._entries.get((scope, tier))
            if e is None or e.state != QUARANTINED:
                return False
            if not e.quarantined_at or (
                    (clock.monotonic() - e.quarantined_at) * 1e3
                    < _quarantine_ms.value):
                return False
            e.successes = 0
            self._transition(scope, tier, e, PROBATION, "cooldown")
            return True

    # -- routing consult -----------------------------------------------

    def state(self, tier: str, scope: str = GLOBAL_SCOPE) -> str:
        with self._mu:
            e = self._entries.get((scope, tier))
            return e.state if e is not None else HEALTHY

    def is_denied(self, tier: str, scope: Optional[str] = None) -> bool:
        """True while routing must avoid ``tier``: QUARANTINED in the
        caller's scope or globally. Only QUARANTINED denies — SUSPECT
        and PROBATION tiers keep taking traffic (that traffic *is* the
        hysteresis evidence). Applies the lazy cooldown when the
        supervisor cannot re-probe the tier (not running, or no canary
        registered for it)."""
        if not self._any_unhealthy or not _enable.value:
            return False
        if tier == "host":
            return False
        scopes = (GLOBAL_SCOPE,) if scope in (None, GLOBAL_SCOPE) \
            else (scope, GLOBAL_SCOPE)
        with self._mu:
            for s in scopes:
                e = self._entries.get((s, tier))
                if e is None or e.state != QUARANTINED:
                    continue
                from . import prober

                if (not prober.running()
                        or not prober.has_probe(tier)) \
                        and e.quarantined_at and (
                        (clock.monotonic() - e.quarantined_at) * 1e3
                        >= _quarantine_ms.value):
                    # lazy in-band cooldown: admit the next call as
                    # the probe (PR-5 breaker semantics, tier-wide)
                    e.successes = 0
                    self._transition(s, tier, e, PROBATION, "cooldown")
                    continue
                return True
        return False

    def quarantined_tiers(self) -> list[tuple[str, str]]:
        """(scope, tier) pairs currently QUARANTINED — the supervisor's
        re-probe worklist."""
        if not self._any_unhealthy:
            return []
        with self._mu:
            return [k for k, e in self._entries.items()
                    if e.state == QUARANTINED]

    def suspect_tiers(self) -> list[tuple[str, str]]:
        """(scope, tier) pairs currently SUSPECT — swept by the
        supervisor so a SUSPECT entry can escalate or recover instead
        of dead-ending (a stuck SUSPECT would pin quiet() false and
        disable memoized routing forever)."""
        if not self._any_unhealthy:
            return []
        with self._mu:
            return [k for k, e in self._entries.items()
                    if e.state == SUSPECT]

    def scopes(self) -> list[str]:
        """Sorted distinct scopes with live entries — the bulkhead's
        zero-orphaned-scopes audit: after a tenant eviction, no
        ``tenant:*`` or session-cid scope it owned may remain."""
        with self._mu:
            return sorted({s for (s, _t) in self._entries})

    # -- recovery (ft/lifeboat) ------------------------------------------

    def gc_scope(self, scope: str, *, cause: str = "recover") -> int:
        """Drop every entry in ``scope`` (a revoked communicator's
        cid): the comm is gone, so its quarantines must not leak into
        the process forever. Each collection is a timestamp-free log
        line (``<state>->gc``) so same-seed recoveries keep the digest
        byte-identical. Returns the number of entries collected."""
        if scope == GLOBAL_SCOPE:
            return 0  # the global scope outlives every comm
        with self._mu:
            keys = sorted(k for k in self._entries if k[0] == scope)
            for k in keys:
                e = self._entries.pop(k)
                self._log.append(
                    f"{len(self._log)} {k[0]} {k[1]} {e.state}->gc "
                    f"{cause}"
                )
            if keys:
                self._generation += 1
                self._any_tracked = bool(self._entries)
                self._any_unhealthy = any(
                    x.state != HEALTHY for x in self._entries.values()
                )
                dispatch_epoch.bump()
        return len(keys)

    def seed_scope(self, scope: str, *,
                   src: str = GLOBAL_SCOPE,
                   cause: str = "recover") -> int:
        """Seed a fresh comm scope (the shrunk communicator's cid)
        from ``src``'s non-HEALTHY entries — by default the global
        scope, so a process-wide quarantine observed before a shrink
        keeps denying the new comm without waiting to re-learn it.
        The daemon's bulkhead passes ``src="tenant:<id>"`` both ways:
        a tenant's namespace seeds its fresh session comms, and a
        faulted session comm is absorbed back into the tenant
        namespace before its scope is GC'd, so quarantines follow the
        tenant across session churn instead of leaking to everyone or
        dying with the comm. Returns the number of entries seeded."""
        if scope == src:
            return 0
        seeded = 0
        with self._mu:
            for (s, tier) in sorted(self._entries):
                e = self._entries[(s, tier)]
                if s != src or e.state == HEALTHY:
                    continue
                ne = self._entry(scope, tier)
                ne.failures = e.failures
                ne.successes = e.successes
                if ne.state != e.state:
                    self._transition(scope, tier, ne, e.state, cause)
                seeded += 1
        return seeded

    # -- introspection ---------------------------------------------------

    def snapshot(self) -> dict:
        """Ledger state for monitoring dumps / modex publication."""
        with self._mu:
            return {
                "generation": self._generation,
                "entries": {
                    f"{scope}/{tier}": {
                        "state": e.state,
                        "failures": e.failures,
                        "successes": e.successes,
                        "cause": e.cause,
                    }
                    for (scope, tier), e in sorted(self._entries.items())
                },
                "transitions": len(self._log),
            }

    def transitions(self) -> list[str]:
        with self._mu:
            return list(self._log)

    def digest(self) -> str:
        """sha256 of the transition log — byte-identical for the same
        fault schedule (the drill-reproducibility check)."""
        with self._mu:
            return hashlib.sha256(
                "\n".join(self._log).encode()).hexdigest()

    def on_restore(self, cb: Callable[[str, str], None]) -> None:
        """Register cb(tier, scope) fired on a HEALTHY restore."""
        with self._mu:
            if cb not in self._restore_cbs:
                self._restore_cbs.append(cb)

    def reset(self) -> None:
        """Forget all state (tests / re-init)."""
        with self._mu:
            self._entries.clear()
            self._log.clear()
            self._generation += 1
            self._any_tracked = False
            self._any_unhealthy = False
            dispatch_epoch.bump()
            self._restore_cbs.clear()
            self._pending_restored = []


LEDGER = Ledger()


def enabled() -> bool:
    return _enable.value


# -- module-level convenience (the API the rest of the tree uses) -------

def report_failure(tier: str, *, scope: str = GLOBAL_SCOPE,
                   cause: str = "") -> None:
    LEDGER.report_failure(tier, scope=scope, cause=cause)


def report_success(tier: str, *, scope: str = GLOBAL_SCOPE) -> None:
    LEDGER.report_success(tier, scope=scope)


def suspect(tier: str, *, scope: str = GLOBAL_SCOPE,
            cause: str = "") -> None:
    LEDGER.suspect(tier, scope=scope, cause=cause)


def is_denied(tier: str, scope: Optional[str] = None) -> bool:
    return LEDGER.is_denied(tier, scope)


def state(tier: str, scope: str = GLOBAL_SCOPE) -> str:
    return LEDGER.state(tier, scope)


def quiet() -> bool:
    return LEDGER.quiet()


def generation() -> int:
    return LEDGER.generation()


def snapshot() -> dict:
    return LEDGER.snapshot()


def digest() -> str:
    return LEDGER.digest()


def gc_scope(scope: str, *, cause: str = "recover") -> int:
    return LEDGER.gc_scope(scope, cause=cause)


def seed_scope(scope: str, *, src: str = GLOBAL_SCOPE,
               cause: str = "recover") -> int:
    return LEDGER.seed_scope(scope, src=src, cause=cause)


def scopes() -> list[str]:
    return LEDGER.scopes()


def reset() -> None:
    LEDGER.reset()
