"""Health prober: deadline-bounded canary ops per tier + the
background supervisor that re-probes and restores quarantined tiers.

Each transport tier registers a **probe** — a tiny canary operation
that exercises the tier end to end without touching application
state:

    device    device enumeration + a tiny device reduction
    fastpath  native fp_echo round trip (btl/sm registers it)
    shm       shm v2 segment liveness (btl/sm registers it)
    dcn       per-link peer ping (btl/dcn registers it)
    fabric    pml sendrecv self-check (pml/fabric registers it)

Probes register at component-selection time (the same seam faultline
and the sanitizer interpose at), so only tiers that are actually
wired up get probed — and the ``healthseam`` commlint rule flags a
transport component that registers without one.

Every probe runs deadline-bounded on a scratch daemon thread: a probe
that *hangs* is indistinguishable from a dead tier, so a join timeout
is a failure, not an error (the worker is abandoned; canaries touch
no shared mutable state).

The **supervisor** is a background daemon thread:

- quarantined tiers are re-probed on a seeded ``core/backoff``
  schedule (fast first retry, exponential to the cap) — a restored
  tier comes back within ``reprobe_initial_ms`` of recovering instead
  of waiting out a fixed cooldown;
- HEALTHY and SUSPECT tiers get a low-cadence liveness sweep
  (``health_prober_interval_ms``): a silently-dead tier is caught
  before application traffic hits it, and a SUSPECT tier keeps
  accumulating evidence until it escalates to QUARANTINED or recovers
  to HEALTHY instead of dead-ending;
- a quarantined tier with **no registered probe** (operator
  quarantine on an unwired tier, canary retired with its endpoint)
  falls back to the time-based ``health_ledger_quarantine_ms``
  cooldown instead of staying denied until restart;
- probe successes feed the ledger exactly like in-band successes, so
  QUARANTINED → PROBATION → HEALTHY runs entirely in the background
  and ``breaker.on_tier_restored`` re-opens the fast tiers with no
  live collective at risk;
- the ledger snapshot is published over the modex on generation
  change (best effort) so peers can see each other's health lattice.

Not started by default (``health_base_autostart``): bench sweeps,
drills and long-running services opt in via ``start()``.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from ..core import clock
from ..core import config
from ..core.backoff import Backoff
from ..core.counters import SPC
from ..core.logging import get_logger
from . import ledger

logger = get_logger("health.prober")

_autostart = config.register(
    "health", "base", "autostart", type=bool, default=False,
    description="Start the health supervisor thread at init() "
    "(bench/drills/services opt in; short-lived scripts skip the "
    "thread)",
)
_interval_ms = config.register(
    "health", "prober", "interval_ms", type=int, default=5000,
    description="Cadence of the healthy-tier liveness sweep",
)
_reprobe_initial_ms = config.register(
    "health", "prober", "reprobe_initial_ms", type=int, default=250,
    description="First re-probe delay after a quarantine (grows "
    "exponentially to reprobe_max_ms on repeated failures)",
)
_reprobe_max_ms = config.register(
    "health", "prober", "reprobe_max_ms", type=int, default=5000,
    description="Cap on the quarantined-tier re-probe backoff",
)
_deadline_ms = config.register(
    "health", "prober", "deadline_ms", type=float, default=1000.0,
    description="Default probe deadline: a canary that has not "
    "returned by then counts as a tier failure (hang == dead)",
)


class ProbeRetired(Exception):
    """Raised by a canary whose endpoint has been torn down (dead
    weakref): the probe verified *nothing*, so it must not advance the
    ledger — a success here would march a quarantined tier back to
    HEALTHY on zero evidence. ``probe_tier`` unregisters the probe;
    component re-wire re-registers it with live endpoints."""


class _Probe:
    __slots__ = ("fn", "deadline_s", "description")

    def __init__(self, fn: Callable[[], None],
                 deadline_s: Optional[float],
                 description: str) -> None:
        self.fn = fn
        self.deadline_s = deadline_s
        self.description = description


_probes: dict[str, _Probe] = {}
_probes_mu = threading.Lock()


def register_probe(tier: str, fn: Callable[[], None], *,
                   deadline_s: Optional[float] = None,
                   description: str = "") -> None:
    """Register the canary for ``tier`` (last registration wins — a
    re-selected component re-registers with its live endpoints).
    ``fn`` takes no arguments; raising or hanging past the deadline is
    a tier failure, returning is success."""
    if tier not in ledger.TIERS:
        raise ValueError(f"unknown tier {tier!r}; known: {ledger.TIERS}")
    with _probes_mu:
        _probes[tier] = _Probe(fn, deadline_s, description)
    logger.debug("health: probe registered for tier %r (%s)", tier,
                 description or fn)


def unregister_probe(tier: str) -> None:
    with _probes_mu:
        _probes.pop(tier, None)


def has_probe(tier: str) -> bool:
    """True when a canary is registered for ``tier`` (the supervisor
    and the ledger's lazy cooldown both branch on this)."""
    with _probes_mu:
        return tier in _probes


def probes() -> dict[str, str]:
    """tier -> description of every registered probe (info tools)."""
    with _probes_mu:
        return {t: p.description or repr(p.fn)
                for t, p in sorted(_probes.items())}


def ensure_builtin_probes() -> None:
    """Register the built-in canaries that need no component state:
    the device tier (device enumeration + a tiny device reduction) and
    the device_pallas tier (a compiled Mosaic kernel on a TPU backend,
    the sched compiler's table simulation elsewhere).
    Transport probes register at their components' selection seams."""
    if "device" not in _probes:
        def _device_canary() -> None:
            import jax
            import jax.numpy as jnp
            import numpy as np

            devs = jax.devices()  # raises when the backend is gone
            if not devs:
                raise RuntimeError("no devices visible")
            # tiny on-device op: the canary allreduce degenerate case —
            # proves dispatch + transfer, costs microseconds
            out = jax.device_get(jnp.sum(jnp.arange(8, dtype=jnp.int32)))
            if int(np.asarray(out)) != 28:
                raise RuntimeError(f"device canary miscomputed: {out!r}")

        register_probe("device", _device_canary,
                       description="device enumeration + tiny device sum")

    if "device_pallas" not in _probes:
        import jax

        if jax.default_backend() == "tpu":
            register_probe("device_pallas", _device_pallas_canary_tpu,
                           deadline_s=60.0,  # the first probe compiles
                           description="compile + run the one-chip "
                           "self-DMA ring kernel")
        else:
            register_probe("device_pallas", _device_pallas_canary_sim,
                           description="simulated (no TPU backend): "
                           "analyze + table-simulate a tiny ring "
                           "program")


def _device_pallas_canary_tpu() -> None:
    """The Mosaic path for real: the chunked ring kernel's one-device
    self-DMA variant, compiled and run on a tiny payload."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..coll import pallas_ring

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    fn = jax.jit(jax.shard_map(
        lambda b: pallas_ring.ring_allreduce_chunked(b[0], "x")[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    x = jnp.arange(2048, dtype=jnp.float32).reshape(1, 2048)
    out = np.asarray(fn(x))
    if not np.array_equal(out, np.asarray(x)):
        raise RuntimeError("device_pallas canary: self-DMA kernel "
                           "returned wrong data")


def _device_pallas_canary_sim() -> None:
    """CPU backend: no Mosaic compile is possible, so the canary checks
    the codegen plane only — analyze + table-simulate a tiny ring
    program — and counts that it simulated."""
    import numpy as np

    from ..coll.sched import ir, pallas_lower

    sched = ir.with_lowering(ir.ring(4), "pallas")
    data = np.ones((4, 4, 8), np.float32)
    out = np.asarray(pallas_lower.simulate(sched, data, "sum"))
    if out.shape != (4, 4, 8) or not np.all(out == 4.0):
        raise RuntimeError(
            f"device_pallas canary miscomputed: {out.shape}")
    SPC.record("health_device_pallas_simulated")


def probe_tier(tier: str, *, scope: str = ledger.GLOBAL_SCOPE) -> bool:
    """Run the tier's canary deadline-bounded and report the outcome
    to the ledger. True on success; False on failure, timeout (hang ==
    dead), or no registered probe."""
    with _probes_mu:
        p = _probes.get(tier)
    if p is None:
        return False
    deadline = p.deadline_s
    if deadline is None:
        deadline = max(0.05, _deadline_ms.value / 1e3)
    SPC.record("health_probes")
    from . import sentinel
    from ..trace import span as tspan

    ok, cause = True, ""
    try:
        sentinel.run_bounded(p.fn, deadline, what=f"probe[{tier}]")
    except ProbeRetired:
        # endpoint gone: the canary verified nothing. Retire the probe
        # (re-wire re-registers) and leave the ledger untouched — no
        # evidence is neither a success nor a failure, and with no
        # probe left the tier falls to the time-based cooldown.
        unregister_probe(tier)
        tspan.instant("health.probe", cat="health", tier=tier,
                      ok=False, scope=scope, cause="probe_retired")
        logger.info("health: probe for tier %r retired (endpoint "
                    "gone)", tier)
        return False
    except sentinel.StallError:
        ok, cause = False, "probe_timeout"
    except Exception as exc:  # commlint: allow(broadexcept)
        # any canary failure is evidence, never an error to propagate
        ok, cause = False, f"probe_{type(exc).__name__}"
    tspan.instant("health.probe", cat="health", tier=tier, ok=ok,
                  scope=scope, cause=cause or None)
    if ok:
        ledger.LEDGER.report_success(tier, scope=scope)
    else:
        SPC.record("health_probe_failures")
        ledger.LEDGER.report_failure(tier, scope=scope, cause=cause)
    return ok


# -- the supervisor thread ----------------------------------------------

class Supervisor(threading.Thread):
    """Background medic: re-probe quarantined tiers on backoff, sweep
    healthy ones on a slow cadence, publish the ledger on change."""

    def __init__(self, *, seed: int = 0) -> None:
        super().__init__(name="ompi-tpu-health", daemon=True)
        self._stop_ev = threading.Event()
        self._seed = seed
        # (scope, tier) -> [Backoff, next_probe_at_monotonic]
        self._backoffs: dict[tuple[str, str], list] = {}
        self._published_gen = -1
        self._last_sweep = 0.0

    def stop(self) -> None:
        self._stop_ev.set()

    # one scheduling quantum; split out so tests can drive the
    # supervisor synchronously without the thread
    def tick(self) -> None:
        now = clock.monotonic()
        quarantined = ledger.LEDGER.quarantined_tiers()
        for (scope, tier) in quarantined:
            if not has_probe(tier):
                # No canary to run (operator quarantine on an unwired
                # tier, probe retired): the time-based cooldown is the
                # only way back — otherwise the tier stays denied
                # until restart, strictly worse than no supervisor.
                self._backoffs.pop((scope, tier), None)
                ledger.LEDGER.apply_cooldown(tier, scope=scope)
                continue
            ent = self._backoffs.get((scope, tier))
            if ent is None:
                ent = self._backoffs[(scope, tier)] = [Backoff(
                    initial=max(0.001, _reprobe_initial_ms.value / 1e3),
                    maximum=max(0.001, _reprobe_max_ms.value / 1e3),
                    seed=self._seed,
                ), 0.0]
            if now < ent[1]:
                continue
            probe_tier(tier, scope=scope)
            bo = ent[0]
            delay = bo.next_delay()
            bo.attempts += 1
            ent[1] = clock.monotonic() + delay
        # a tier that left quarantine drops its backoff; PROBATION
        # tiers keep probing every tick until the ledger settles
        live = set(quarantined)
        for key in list(self._backoffs):
            if key not in live:
                scope, tier = key
                if (ledger.LEDGER.state(tier, scope) == ledger.PROBATION
                        and has_probe(tier)):
                    probe_tier(tier, scope=scope)
                else:
                    del self._backoffs[key]
        # slow liveness sweep: HEALTHY tiers for silent-death
        # detection, SUSPECT tiers so the entry can escalate to
        # QUARANTINED or recover to HEALTHY — without probing SUSPECT
        # a probe-fed tier dead-ends there (never quarantined, never
        # restored, quiet() pinned false).
        if (now - self._last_sweep) * 1e3 >= _interval_ms.value:
            self._last_sweep = now
            with _probes_mu:
                tiers = list(_probes)
            for tier in tiers:
                if ledger.LEDGER.state(tier) in (ledger.HEALTHY,
                                                 ledger.SUSPECT):
                    probe_tier(tier)
            # comm-scoped SUSPECT entries (in-band failures on a comm
            # that went idle) would dead-end the same way
            for (scope, tier) in ledger.LEDGER.suspect_tiers():
                if scope != ledger.GLOBAL_SCOPE and has_probe(tier):
                    probe_tier(tier, scope=scope)
        self._maybe_publish()

    def _maybe_publish(self) -> None:
        gen = ledger.LEDGER.generation()
        if gen == self._published_gen:
            return
        self._published_gen = gen
        try:
            from ..runtime import modex

            modex.publish_health(ledger.LEDGER.snapshot())
        except Exception:  # commlint: allow(broadexcept)
            pass  # best effort: no runtime / modex not up yet

    def run(self) -> None:
        logger.info("health supervisor started")
        while not self._stop_ev.is_set():
            try:
                self.tick()
            except Exception:  # commlint: allow(broadexcept)
                logger.exception("health supervisor tick failed")
            # quarantines need the fast cadence; otherwise idle at a
            # fraction of the sweep interval so stop() stays snappy
            busy = bool(self._backoffs) \
                or bool(ledger.LEDGER.quarantined_tiers())
            wait_s = (max(0.01, _reprobe_initial_ms.value / 2e3)
                      if busy else
                      max(0.05, _interval_ms.value / 1e3 / 8))
            clock.wait_event(self._stop_ev, wait_s)
        logger.info("health supervisor stopped")


_SUP: Optional[Supervisor] = None
_sup_mu = threading.Lock()


def running() -> bool:
    s = _SUP
    return s is not None and s.is_alive()


def start(*, seed: int = 0) -> Supervisor:
    """Start (or return) the process supervisor thread."""
    global _SUP
    with _sup_mu:
        if _SUP is not None and _SUP.is_alive():
            return _SUP
        ensure_builtin_probes()
        from . import sentinel

        sentinel.install()
        _SUP = Supervisor(seed=seed)
        _SUP.start()
        return _SUP


def stop(timeout: float = 2.0) -> None:
    global _SUP
    with _sup_mu:
        s = _SUP
        _SUP = None
    if s is not None and s.is_alive():
        s.stop()
        s.join(timeout)


def supervisor() -> Optional[Supervisor]:
    return _SUP


def autostart_enabled() -> bool:
    return _autostart.value
