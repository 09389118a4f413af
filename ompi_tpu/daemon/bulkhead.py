"""bulkhead — per-tenant fault isolation over the health ledger.

The ledger (PR 8) already scopes every (tier, state) entry by
communicator cid, and tuned's dispatch charges failures to
``str(comm.cid)``. The bulkhead turns those comm scopes into a
*tenant* boundary by adding one durable namespace per tenant,
``tenant:<id>``, and moving state across the two scope kinds at the
session lifecycle edges:

    attach   seed the fresh session comm's scope FROM the tenant
             namespace — a tenant that wedged its device tier five
             sessions ago is still denied it on session six
    absorb   after a session-scoped fault, mirror the comm scope's
             non-HEALTHY entries INTO the tenant namespace — the
             quarantine survives the session
    evict    lifeboat.detach() the comm (revoke → quiesce → free →
             comm-scope GC); when the tenant's last session is gone
             and the eviction is tenant-level, GC the tenant
             namespace too — zero orphaned scopes

Shared warm state (sched winner cache, fastpath rings, the device
handles) is never scoped to a tenant, so none of this touches it: one
tenant's quarantine denies *its* scopes only, and ``is_denied`` for
every other tenant keeps consulting (their scope, global) exactly as
before.

Decisions land in a numbered, timestamp-free log (ledger/lifeboat
idiom) whose sha256 is byte-identical across same-seed controllers.
"""

from __future__ import annotations

import hashlib
import threading

from ..core.counters import SPC
from ..ft import lifeboat
from ..health import ledger as health

TENANT_PREFIX = "tenant:"


def tenant_scope(tenant: str) -> str:
    """The tenant's durable ledger namespace."""
    return TENANT_PREFIX + tenant


class DecisionLog:
    """Numbered timestamp-free decision lines + sha256 digest — the
    same byte-identity contract as the ledger transition log and
    lifeboat's recovery log."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._lines: list[str] = []

    def note(self, line: str) -> None:
        with self._mu:
            self._lines.append(f"{len(self._lines)} {line}")

    def lines(self) -> list[str]:
        with self._mu:
            return list(self._lines)

    def digest(self) -> str:
        with self._mu:
            text = "\n".join(self._lines)
        return hashlib.sha256(text.encode()).hexdigest()


class Bulkhead:
    """Scope plumbing between session comms and tenant namespaces."""

    def __init__(self, log: DecisionLog) -> None:
        self.log = log

    def on_attach(self, tenant: str, comm) -> int:
        """Seed the new session comm scope from the tenant namespace
        (then from global, which seed_scope's default path already
        gives every comm via tuned's normal consult order)."""
        seeded = health.LEDGER.seed_scope(
            str(comm.cid), src=tenant_scope(tenant),
            cause="bulkhead-attach",
        )
        if seeded:
            self.log.note(
                f"seed tenant={tenant} cid={comm.cid} "
                f"entries={seeded}"
            )
        return seeded

    def absorb(self, tenant: str, comm, *, cause: str) -> int:
        """Mirror the session comm's non-HEALTHY ledger entries into
        the tenant namespace so the fault outlives the session."""
        absorbed = health.LEDGER.seed_scope(
            tenant_scope(tenant), src=str(comm.cid),
            cause=f"bulkhead-{cause}",
        )
        if absorbed:
            SPC.record("daemon_faults_absorbed", absorbed)
            self.log.note(
                f"absorb tenant={tenant} cid={comm.cid} "
                f"cause={cause} entries={absorbed}"
            )
        return absorbed

    def denied_tiers(self, comm) -> list[str]:
        """Tiers the ledger denies for this session's scope — the
        per-dispatch observation the isolation drill asserts stays
        empty for the compliant tenant."""
        scope = str(comm.cid)
        return [t for t in health.TIERS
                if health.LEDGER.is_denied(t, scope)]

    def evict_session(self, tenant: str, comm, *, cause: str) -> dict:
        """One session's deterministic teardown: absorb its faults
        into the tenant namespace, then lifeboat's revoke → quiesce →
        detach (which GCs the comm scope)."""
        absorbed = self.absorb(tenant, comm, cause=cause)
        report = lifeboat.detach(comm, cause=f"evict-{tenant}")
        self.log.note(
            f"evict tenant={tenant} cid={comm.cid} cause={cause} "
            f"absorbed={absorbed} drained={report['drained']} "
            f"cancelled={report['cancelled']} "
            f"ledger_gc={report['ledger_gc']}"
        )
        SPC.record("daemon_evictions")
        return report

    def release_tenant(self, tenant: str) -> int:
        """Tenant-level eviction epilogue: GC the tenant namespace.
        After this, ``health.LEDGER.scopes()`` must show no scope
        owned by the tenant — the zero-orphaned-scopes invariant."""
        gcd = health.LEDGER.gc_scope(tenant_scope(tenant),
                                     cause="evict")
        self.log.note(f"release tenant={tenant} ledger_gc={gcd}")
        return gcd
