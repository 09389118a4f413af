"""faultline — deterministic, seeded fault injection at the comm
boundaries.

The ft/ layer carries the ULFM-style recovery surface (events,
``elastic.shrink/agree/respawn``, checkpoint manager, quiesce) but
nothing in the repo could *provoke* the failures those paths exist
for. faultline closes that loop: a **fault plan** — a seeded list of
fault specs — is armed process-wide, and pass-through wrappers at the
BTL (sm + dcn), PML, modex/KV, and collective-dispatch boundaries
consult it on every operation (the sanitizer's interpose-at-selection
pattern from ``analysis/sanitizer.py``: wrappers install when the
component stack is selected, delegate everything they don't fault).

Fault-plan grammar
------------------
A plan is ``;``-separated specs, each ``action@layer[:key=val,...]``::

    drop@btl_dcn:peer=1,tag=100-200,count=2
    delay@pml:op=send,ms=50,count=3
    duplicate@btl_dcn:op=send,count=1
    corrupt@btl_sm:count=1
    disconnect@btl_dcn:peer=0,link=1,count=1
    disconnect@coll:op=allreduce,algo=quant_ring,count=1
    rank_kill@coll:op=allreduce,after=2
    rank_kill@coll:op=allreduce,after=1,exit=17
    rank_kill@coll:op=allreduce,after_step=2,peer=3
    rank_kill@modex:op=get,peer=1
    drop@modex:key=dcn/3,count=1,prob=0.5
    wedge@coll:op=allreduce,algo=native,count=1
    wedge@btl_dcn:op=send,ms=500,count=1

Actions: ``drop`` (message vanishes on the wire — the sender still
completes, exactly like TCP loss), ``delay`` (``ms=`` sleep before the
operation), ``duplicate`` (the operation runs twice), ``corrupt``
(payload perturbed — bytes XOR 0xFF at the BTL, ``leaf + 1`` at the
PML), ``disconnect`` (kill one DCN link via the engine's
``dcn_kill_link``; at the coll layer: the named algorithm tier raises
``FaultInjected``, the kernel/transport-fault the circuit breaker
degrades on), ``rank_kill`` (raise ``FaultInjected`` — or ``os._exit``
when ``exit=`` is given — modelling a controller death mid-call),
``wedge`` (the operation STALLS — blocks until ``ms=`` elapses, or
indefinitely until ``disarm()`` releases it; the hang-not-fail mode
the health sentinel's stall deadlines exist for).

Scoping keys: ``op`` (operation name at the layer: send/recv at
pml/btl, get/put at modex, the collective name at coll), ``peer``
(int; at the coll layer it is not a filter but names the victim world
rank for ``rank_kill``), ``tag=N`` or ``tag=LO-HI`` (inclusive range),
``count`` (fire
at most N times, default 1; ``count=inf`` = every match), ``after``
(alias ``skip``: let the first N matching occurrences pass),
``after_step`` (coll only: fire once the chosen schedule reaches IR
step N — tuned probes ``coll_step`` per step of the dispatched
program, so ``rank_kill@coll:after_step=k`` kills a rank
mid-collective at step granularity), ``prob``
(fire with this probability, drawn from the plan's seeded RNG),
``ms`` (delay milliseconds), ``link`` (DCN link index), ``algo``
(collective algorithm tier), ``key`` (modex key substring), ``exit``
(process exit code for rank_kill).

Determinism: the only randomness is the plan's ``random.Random(seed)``
(used by ``prob`` draws), and every fired fault is appended to an
ordered log — ``plan.schedule()`` renders it and ``plan.digest()``
hashes it, so the same seed and workload produce a byte-identical
fault schedule across runs (the drill-reproducibility contract).

Usage::

    from ompi_tpu.ft import inject
    plan = inject.arm("drop@btl_dcn:peer=1,count=2", seed=7)
    ...                      # run the workload; faults fire
    print(plan.schedule())   # what fired, in order
    inject.disarm()

Arm **before** ``init()``/first communication: like the sanitizer, the
PML/coll wrappers interpose at component-selection time and cached
selections are not rewrapped retroactively. Subprocess drills arm via
the ``faultline_base_plan`` / ``faultline_base_seed`` cvars
(``OMPITPU_MCA_faultline_base_plan=...`` in the environment) and call
``inject.arm()`` with no arguments.
"""

from __future__ import annotations

import hashlib
import math
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ..core import config, dispatch_epoch
from ..core.counters import SPC
from ..core.errors import OmpiTpuError
from ..core.logging import get_logger

logger = get_logger("ft.inject")

LAYERS = ("btl_sm", "btl_dcn", "pml", "modex", "coll", "daemon")
ACTIONS = ("drop", "delay", "duplicate", "corrupt", "disconnect",
           "rank_kill", "wedge", "flood", "hog")

#: Which actions make sense at which boundary (parse-time validation —
#: a spec that could never fire is a plan bug, not a quiet no-op).
#: wedge is valid everywhere: any seam can stall indefinitely.
#: flood/hog are the adversarial-tenant primitives: they only make
#: sense at the daemon admission boundary, where the daemon amplifies
#: a fired spec into `rate=` synthetic submits or charges `bytes=` of
#: queue memory against the probing tenant's budget.
_VALID = {
    "btl_sm": {"drop", "delay", "corrupt", "wedge"},
    "btl_dcn": {"drop", "delay", "duplicate", "corrupt", "disconnect",
                "wedge"},
    "pml": {"drop", "delay", "duplicate", "corrupt", "wedge"},
    "modex": {"drop", "delay", "wedge", "rank_kill"},
    "coll": {"delay", "disconnect", "rank_kill", "wedge"},
    "daemon": {"delay", "wedge", "flood", "hog"},
}

_plan_var = config.register(
    "faultline", "base", "plan", type=str, default="",
    description="Fault plan grammar armed by inject.arm() when no "
    "explicit plan is given (';'-separated action@layer:k=v specs)",
)
_seed_var = config.register(
    "faultline", "base", "seed", type=int, default=0,
    description="Fault-plan RNG seed (same seed => byte-identical "
    "fault schedule)",
)


class FaultInjected(OmpiTpuError):
    """An injected fault surfaced as a failure (rank_kill / tier
    disconnect). Carries the spec that fired."""

    errclass = "ERR_INTERN"


class PlanError(OmpiTpuError):
    errclass = "ERR_ARG"


@dataclass
class FaultSpec:
    """One scoped fault: what to do, where, and how often."""

    action: str
    layer: str
    op: Optional[str] = None
    peer: Optional[int] = None
    tag_lo: Optional[int] = None
    tag_hi: Optional[int] = None
    count: float = 1          # max firings (inf = unlimited)
    skip: int = 0             # matching occurrences to let pass first
    after_step: Optional[int] = None  # coll schedule step to fire at
    prob: Optional[float] = None
    ms: float = 0.0           # delay milliseconds
    link: int = 0             # DCN link index for disconnect
    algo: Optional[str] = None
    key: Optional[str] = None  # modex key / daemon tenant substring
    exit_code: Optional[int] = None
    cid: Optional[int] = None  # communicator scope (coll/daemon probes)
    rate: int = 0             # flood: synthetic submits per firing
    nbytes: int = 0           # hog: queue-memory bytes per firing
    # runtime state
    seen: int = field(default=0, compare=False)
    fired: int = field(default=0, compare=False)

    def __post_init__(self) -> None:
        if self.action not in ACTIONS:
            raise PlanError(f"unknown action {self.action!r}; "
                            f"expected one of {ACTIONS}")
        if self.layer not in LAYERS:
            raise PlanError(f"unknown layer {self.layer!r}; "
                            f"expected one of {LAYERS}")
        if self.action not in _VALID[self.layer]:
            raise PlanError(
                f"{self.action}@{self.layer} is not a meaningful "
                f"fault; {self.layer} supports "
                f"{sorted(_VALID[self.layer])}"
            )
        if self.after_step is not None and self.layer != "coll":
            raise PlanError(
                f"after_step only scopes coll-layer specs "
                f"(got {self.action}@{self.layer})"
            )

    def scope_matches(self, layer: str, op: Optional[str],
                      peer: Optional[int], tag: Optional[int],
                      algo: Optional[str], key: Optional[str],
                      step: Optional[int] = None,
                      cid: Optional[int] = None) -> bool:
        if layer != self.layer:
            return False
        if self.op is not None and op != self.op:
            return False
        # cid= pins a spec to one communicator scope — how a drill
        # targets one tenant's session comm on a shared daemon without
        # perturbing its neighbours. Non-strict: an unscoped spec
        # still matches probes that carry a cid.
        if self.cid is not None and cid != self.cid:
            return False
        # For rank_kill (and all coll-layer specs) `peer=` is not a
        # scope filter: those probes carry no peer; the key instead
        # names the victim world rank (driver mode hosts every rank
        # in-process, so rank_kill@modex:peer=N kills rank N when the
        # modex op fires, it does not filter on a wire peer).
        if self.peer is not None and self.layer != "coll" \
                and self.action != "rank_kill" \
                and peer != self.peer:
            return False
        if self.tag_lo is not None:
            if tag is None or not self.tag_lo <= tag <= self.tag_hi:
                return False
        # algo scoping is strict both ways so the two coll probes stay
        # disjoint: the dispatch probe (algo=None, on_coll) never
        # advances tier-scoped specs and the tier probe (kernel_fault)
        # never advances dispatch-scoped ones — occurrence counts
        # (`after=`) would otherwise double-step per collective.
        if (self.algo is None) != (algo is None) or algo != self.algo:
            return False
        if self.key is not None and (key is None or self.key not in key):
            return False
        # step scoping is strict both ways like algo: the per-step
        # probe (coll_step) only advances after_step specs and the
        # dispatch probe (on_coll) never does — occurrence counts
        # would otherwise step once per IR step, not per collective.
        if (self.after_step is None) != (step is None):
            return False
        if self.after_step is not None and step != self.after_step:
            return False
        return True

    def describe(self) -> str:
        parts = [f"{self.action}@{self.layer}"]
        kv = []
        for name, val in (("op", self.op), ("peer", self.peer),
                          ("algo", self.algo), ("key", self.key),
                          ("cid", self.cid)):
            if val is not None:
                kv.append(f"{name}={val}")
        if self.tag_lo is not None:
            kv.append(f"tag={self.tag_lo}-{self.tag_hi}")
        if self.after_step is not None:
            kv.append(f"after_step={self.after_step}")
        if self.rate:
            kv.append(f"rate={self.rate}")
        if self.nbytes:
            kv.append(f"bytes={self.nbytes}")
        if kv:
            parts.append(":" + ",".join(kv))
        return "".join(parts)


def _parse_spec(text: str) -> FaultSpec:
    head, _, tail = text.strip().partition(":")
    action, at, layer = head.partition("@")
    if not at or not action or not layer:
        raise PlanError(f"spec {text!r}: expected action@layer[:k=v,..]")
    spec = FaultSpec(action=action.strip(), layer=layer.strip())
    if not tail:
        return spec
    for kv in tail.split(","):
        k, eq, v = kv.partition("=")
        k, v = k.strip(), v.strip()
        if not eq or not k or not v:
            raise PlanError(f"spec {text!r}: malformed key=value {kv!r}")
        if k == "op":
            spec.op = v
        elif k == "peer":
            spec.peer = int(v)
        elif k == "tag":
            lo, dash, hi = v.partition("-")
            spec.tag_lo = int(lo)
            spec.tag_hi = int(hi) if dash else spec.tag_lo
            if spec.tag_hi < spec.tag_lo:
                raise PlanError(f"spec {text!r}: empty tag range {v!r}")
        elif k == "count":
            spec.count = math.inf if v == "inf" else int(v)
        elif k in ("after", "skip"):
            spec.skip = int(v)
        elif k == "after_step":
            spec.after_step = int(v)
        elif k == "prob":
            spec.prob = float(v)
            if not 0.0 <= spec.prob <= 1.0:
                raise PlanError(f"spec {text!r}: prob out of [0,1]")
        elif k == "ms":
            spec.ms = float(v)
        elif k == "link":
            spec.link = int(v)
        elif k == "algo":
            spec.algo = v
        elif k == "key":
            spec.key = v
        elif k == "cid":
            spec.cid = int(v)
        elif k == "rate":
            spec.rate = int(v)
        elif k == "bytes":
            spec.nbytes = int(v)
        elif k == "exit":
            spec.exit_code = int(v)
        else:
            raise PlanError(f"spec {text!r}: unknown key {k!r}")
    if spec.after_step is not None and spec.layer != "coll":
        raise PlanError(
            f"spec {text!r}: after_step only scopes coll-layer specs"
        )
    if spec.action == "flood" and spec.rate <= 0:
        raise PlanError(f"spec {text!r}: flood needs rate=N>0")
    if spec.action == "hog" and spec.nbytes <= 0:
        raise PlanError(f"spec {text!r}: hog needs bytes=N>0")
    return spec


class FaultPlan:
    """A seeded, ordered set of fault specs plus the append-only log
    of every fault that fired. Thread-safe: the wrappers consult it
    from transport and progress threads."""

    def __init__(self, specs, *, seed: int = 0) -> None:
        if isinstance(specs, str):
            specs = [s for s in specs.split(";") if s.strip()]
        self.specs: list[FaultSpec] = [
            s if isinstance(s, FaultSpec) else _parse_spec(s)
            for s in specs
        ]
        self.seed = seed
        self._rng = random.Random(seed)
        self._mu = threading.Lock()
        self.fired: list[str] = []

    def decide(self, layer: str, op: Optional[str] = None, *,
               peer: Optional[int] = None, tag: Optional[int] = None,
               algo: Optional[str] = None, key: Optional[str] = None,
               step: Optional[int] = None,
               cid: Optional[int] = None) -> list[FaultSpec]:
        """All specs firing for this occurrence, in plan order. Each
        scope match advances the spec's occurrence counter (and the
        seeded RNG when ``prob`` is set) whether or not it fires, so
        the schedule is a pure function of (plan, workload)."""
        out: list[FaultSpec] = []
        with self._mu:
            for spec in self.specs:
                if not spec.scope_matches(layer, op, peer, tag, algo,
                                          key, step, cid):
                    continue
                spec.seen += 1
                if spec.seen <= spec.skip or spec.fired >= spec.count:
                    continue
                if spec.prob is not None \
                        and self._rng.random() >= spec.prob:
                    continue
                spec.fired += 1
                self.fired.append(
                    f"{len(self.fired)} {spec.describe()} "
                    f"op={op} peer={peer} tag={tag} occ={spec.seen}"
                )
                SPC.record("faultline_fired")
                # commtrace: every injected fault is tagged on the
                # timeline so drill traces distinguish injected from
                # organic failures (injected=True is the contract the
                # drill suite asserts).
                from ..trace import span as tspan

                tspan.instant(f"fault.{spec.action}", cat="fault",
                              injected=True, layer=layer, op=op,
                              peer=peer, tag=tag, algo=algo, key=key,
                              step=step, occ=spec.seen)
                logger.warning("faultline: %s fired (op=%s peer=%s "
                               "tag=%s occ=%d)", spec.describe(), op,
                               peer, tag, spec.seen)
                out.append(spec)
        return out

    def schedule(self) -> str:
        """The fired-fault log, one line per fault, in firing order."""
        with self._mu:
            return "\n".join(self.fired)

    def digest(self) -> str:
        """sha256 of the schedule — byte-identical for the same seed
        and workload (the drill-reproducibility check)."""
        return hashlib.sha256(self.schedule().encode()).hexdigest()


# -- module-level arming ------------------------------------------------

_PLAN: Optional[FaultPlan] = None


def armed() -> bool:
    return _PLAN is not None


def plan() -> Optional[FaultPlan]:
    return _PLAN


def arm(specs=None, *, seed: Optional[int] = None) -> FaultPlan:
    """Install a fault plan process-wide and drop cached component
    selections so the wrappers interpose on next use. With no
    arguments, reads the ``faultline_base_plan`` / ``_seed`` cvars
    (the env path subprocess drills use)."""
    global _PLAN
    if specs is None:
        specs = _plan_var.value or ""
    if seed is None:
        seed = _seed_var.value
    p = specs if isinstance(specs, FaultPlan) else \
        FaultPlan(specs, seed=seed)
    _WEDGE_EV.clear()  # wedges in this plan will park
    _PLAN = p
    dispatch_epoch.bump()  # every drill must see the real dispatch
    _reset_selections()
    logger.info("faultline armed: %d spec(s), seed=%d", len(p.specs),
                p.seed)
    return p


def disarm() -> Optional[FaultPlan]:
    """Remove the plan; returns it (for schedule/digest inspection)."""
    global _PLAN
    p = _PLAN
    _PLAN = None
    dispatch_epoch.bump()
    _WEDGE_EV.set()  # release every wedged thread
    if p is not None:
        _reset_selections()
    return p


def _reset_selections() -> None:
    from ..pml import framework as pml_fw

    pml_fw.reset_selection()


# -- fault application helpers -----------------------------------------

def _apply_delay(spec: FaultSpec) -> None:
    if spec.ms > 0:
        time.sleep(spec.ms / 1000.0)


# Wedged operations park on this event, not a sleep: ``disarm()`` sets
# it, releasing every wedged thread at once — how a drill (or the
# bench) un-wedges the world after the sentinel has already abandoned
# the stalled workers. arm() re-arms it for the next plan.
_WEDGE_EV = threading.Event()


def _apply_wedge(spec: FaultSpec) -> None:
    """Stall the calling thread: for ``ms=`` when given, else until
    the plan is disarmed (the indefinite-hang injection the health
    sentinel's deadlines exist to catch). The stall is deliberately
    un-failing — a wedged tier hangs, it does not raise."""
    timeout = spec.ms / 1000.0 if spec.ms > 0 else None
    _WEDGE_EV.wait(timeout)


def _corrupt_bytes(data) -> bytes:
    buf = bytearray(bytes(data))
    if buf:
        buf[0] ^= 0xFF
    return bytes(buf)


def _corrupt_value(value):
    """Perturb an array/pytree payload detectably (leaf + 1)."""
    import jax

    try:
        return jax.tree.map(lambda l: l + 1, value)
    except TypeError:
        return value


def _rank_kill(spec: FaultSpec, where: str) -> None:
    if spec.exit_code is not None:
        logger.warning("faultline: rank_kill exiting process (%s, "
                       "code %d)", where, spec.exit_code)
        os._exit(spec.exit_code)
    from . import events

    # peer= names the rank that "dies" (driver mode hosts every rank
    # in one process, so the kill is modeled as a failure event for
    # that world rank — elastic tracking then excludes it).
    events.raise_event(events.EventClass.PROC_FAILED,
                       injected=True, where=where,
                       world_rank=spec.peer)
    raise FaultInjected(f"rank_kill injected at {where}")


# -- PML boundary (interposed in pml/framework.select_for_comm) --------

class FaultPml:
    """Pass-through PML applying pml-layer faults to send/isend (drop /
    delay / duplicate / corrupt) and delay to recv/irecv. Unknown
    attributes delegate to the host (sanitizer wrapper idiom)."""

    NAME = "faultline"

    def __init__(self, host) -> None:
        self.host = host

    def __getattr__(self, name):
        return getattr(self.host, name)

    def _sendish(self, fn, comm, value, dest, tag, source):
        p = _PLAN
        if p is not None:
            for spec in p.decide("pml", "send", peer=dest, tag=tag):
                if spec.action == "delay":
                    _apply_delay(spec)
                elif spec.action == "wedge":
                    _apply_wedge(spec)
                elif spec.action == "corrupt":
                    value = _corrupt_value(value)
                elif spec.action == "duplicate":
                    fn(comm, value, dest, tag, source=source)
                elif spec.action == "drop":
                    # message lost on the wire: sender-side success
                    from ..core.request import CompletedRequest

                    return CompletedRequest(value)
        return fn(comm, value, dest, tag, source=source)

    def send(self, comm, value, dest, tag, source=None):
        req = self._sendish(self.host.send, comm, value, dest, tag,
                            source)
        return req

    def isend(self, comm, value, dest, tag, source=None):
        return self._sendish(self.host.isend, comm, value, dest, tag,
                             source)

    def _recvish(self, comm, source, tag) -> None:
        p = _PLAN
        if p is not None:
            for spec in p.decide("pml", "recv", peer=source, tag=tag):
                if spec.action == "delay":
                    _apply_delay(spec)
                elif spec.action == "wedge":
                    _apply_wedge(spec)

    def recv(self, comm, source, tag, *, dest):
        self._recvish(comm, source, tag)
        return self.host.recv(comm, source, tag, dest=dest)

    def irecv(self, comm, source, tag, *, dest):
        self._recvish(comm, source, tag)
        return self.host.irecv(comm, source, tag, dest=dest)


def maybe_wrap_pml(selected):
    """pml/framework hook: interpose when a plan is armed (inside the
    sanitizer wrapper, so the sanitizer still sees the traffic as the
    application issued it)."""
    if _PLAN is None or selected is None:
        return selected
    return FaultPml(selected)


# -- BTL boundaries ----------------------------------------------------

# Fake send ids handed out for dropped DCN sends: far above any native
# msgid (those start at 1 and count up) so completion polling can't
# collide.
_FAKE_MSGID = 1 << 62
_fake_mu = threading.Lock()


def _next_fake_msgid() -> int:
    global _FAKE_MSGID
    with _fake_mu:
        _FAKE_MSGID += 1
        return _FAKE_MSGID


class FaultDcnEndpoint:
    """Pass-through DcnEndpoint applying btl_dcn faults on the send
    path (drop / delay / duplicate / corrupt / disconnect). Dropped
    sends complete locally — the bytes vanish on the wire, exactly the
    loss mode TCP gives a dead link."""

    NAME = "faultline"

    def __init__(self, host) -> None:
        self.host = host

    def __getattr__(self, name):
        return getattr(self.host, name)

    def send_bytes(self, peer: int, tag: int, data) -> int:
        p = _PLAN
        if p is not None:
            for spec in p.decide("btl_dcn", "send", peer=peer, tag=tag):
                if spec.action == "delay":
                    _apply_delay(spec)
                elif spec.action == "wedge":
                    _apply_wedge(spec)
                elif spec.action == "corrupt":
                    data = _corrupt_bytes(data)
                elif spec.action == "duplicate":
                    self.host.send_bytes(peer, tag, data)
                elif spec.action == "disconnect":
                    self.host.kill_link(peer, spec.link)
                elif spec.action == "drop":
                    msgid = _next_fake_msgid()
                    with self.host._send_mu:
                        self.host._pending_send_done.append(msgid)
                    return msgid
        return self.host.send_bytes(peer, tag, data)

    def connect(self, ip: str, port: int, **kw) -> int:
        p = _PLAN
        if p is not None:
            for spec in p.decide("btl_dcn", "connect", peer=None,
                                 tag=None):
                if spec.action == "delay":
                    _apply_delay(spec)
                elif spec.action == "wedge":
                    _apply_wedge(spec)
        return self.host.connect(ip, port, **kw)

    def close(self) -> None:
        self.host.close()


def maybe_wrap_dcn(endpoint):
    """btl/dcn hook: wrap an endpoint when a plan is armed (DcnBtl
    installs this at endpoint creation; drills wrap standalone
    endpoints the same way)."""
    if _PLAN is None or endpoint is None:
        return endpoint
    if isinstance(endpoint, FaultDcnEndpoint):
        return endpoint
    return FaultDcnEndpoint(endpoint)


class FaultSmBtl:
    """Pass-through sm BTL: drop (raises CommError — a torn shared
    segment), delay, corrupt on transfer()."""

    def __init__(self, host) -> None:
        self.host = host
        self.NAME = host.NAME
        self.PRIORITY = host.PRIORITY

    def __getattr__(self, name):
        return getattr(self.host, name)

    def transfer(self, value, src_proc, dst_proc):
        p = _PLAN
        if p is not None:
            dst = getattr(dst_proc, "process_index", None)
            for spec in p.decide("btl_sm", "transfer", peer=dst,
                                 tag=None):
                if spec.action == "delay":
                    _apply_delay(spec)
                elif spec.action == "wedge":
                    _apply_wedge(spec)
                elif spec.action == "corrupt":
                    value = _corrupt_value(value)
                elif spec.action == "drop":
                    from ..core.errors import CommError

                    raise CommError(
                        "faultline: sm transfer dropped (injected)"
                    )
        return self.host.transfer(value, src_proc, dst_proc)


def maybe_wrap_sm(component):
    if _PLAN is None or component is None:
        return component
    if isinstance(component, FaultSmBtl):
        return component
    return FaultSmBtl(component)


def on_fp_send(endpoint, peer: int, tag: Optional[int]) -> None:
    """btl/sm fastpath descriptor-post hook. ``corrupt@btl_sm:
    op=fp_send`` arms the endpoint's corrupt-next latch: the native
    sender posts the next descriptor with its CRC XORed, and the drill
    proves the receiver's validate path rejects and DROPS it (counted
    in sm_fp_crc_drops) instead of delivering garbage or wedging the
    ring. drop raises before the post (a torn lane); delay models a
    descheduled producer."""
    p = _PLAN
    if p is None:
        return
    for spec in p.decide("btl_sm", "fp_send", peer=peer, tag=tag):
        if spec.action == "corrupt":
            endpoint.fp_corrupt_next()
            SPC.record("faultline_fp_corrupts")
        elif spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            _apply_wedge(spec)
        elif spec.action == "drop":
            from ..core.errors import CommError

            raise CommError(
                "faultline: fp descriptor post dropped (injected)"
            )


# -- modex/KV boundary (hooked inside runtime/modex.py) ----------------

def on_modex(op: str, key: str) -> None:
    """modex.get/put entry hook: drop raises ModexError (the KV entry
    is unreachable), delay sleeps (models a slow coordinator)."""
    p = _PLAN
    if p is None:
        return
    for spec in p.decide("modex", op, key=key):
        if spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            _apply_wedge(spec)
        elif spec.action == "rank_kill":
            # a controller dying inside the business-card exchange —
            # the worst-moment variant drills arm for recover()
            _rank_kill(spec, f"modex {op} {key}")
        elif spec.action == "drop":
            from ..runtime.modex import ModexError

            raise ModexError(
                f"faultline: modex {op}({key!r}) dropped (injected)"
            )


# -- collective-dispatch boundary (coll/framework.select_for_comm) -----

def _wrap_coll_fn(opname: str, comp, fn):
    def faulted(comm, *args, **kw):
        on_coll(comm, opname)
        return fn(comm, *args, **kw)

    return comp, faulted


def maybe_wrap_coll(table: dict):
    """coll/framework hook: wrap every per-op entry of a comm's coll
    vtable when a plan is armed."""
    if _PLAN is None:
        return table
    return {
        opname: _wrap_coll_fn(opname, comp, fn)
        for opname, (comp, fn) in table.items()
    }


def on_coll(comm, opname: str) -> None:
    """Collective-dispatch entry: delay and rank_kill fire here (the
    algorithm-tier `disconnect` fires deeper, at tuned's dispatch,
    where the chosen tier is known — see kernel_fault)."""
    p = _PLAN
    if p is None:
        return
    for spec in p.decide("coll", opname, cid=comm.cid):
        if spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            _apply_wedge(spec)
        elif spec.action == "rank_kill":
            _rank_kill(spec, f"{opname} on {comm.name}")


def coll_step(comm, opname: str, step: int) -> None:
    """Per-IR-step probe: tuned walks the chosen schedule's steps
    (when a plan is armed — zero cost otherwise) and probes each, so
    ``rank_kill@coll:after_step=k`` fires mid-collective at step
    granularity. Driver-model honesty: the fused XLA program cannot be
    interrupted between device steps, so the kill lands between the
    dispatch-time step probes — the program for the remaining steps is
    never launched, which is exactly what a controller death after
    step k means for every rank it hosts."""
    p = _PLAN
    if p is None:
        return
    for spec in p.decide("coll", opname, step=step, cid=comm.cid):
        if spec.action == "rank_kill":
            _rank_kill(spec,
                       f"{opname} step {step} on {comm.name}")
        elif spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            _apply_wedge(spec)


def kernel_fault(opname: str, algo: str,
                 cid: Optional[int] = None) -> None:
    """tuned-dispatch hook: a `disconnect@coll:algo=X` spec makes tier
    X raise FaultInjected — the kernel/transport fault the circuit
    breaker (coll/breaker.py) degrades on. ``cid`` scopes the probe
    to the dispatching communicator so `cid=` specs can wedge one
    tenant's tier without touching a neighbour's."""
    p = _PLAN
    if p is None:
        return
    for spec in p.decide("coll", opname, algo=algo, cid=cid):
        if spec.action == "disconnect":
            raise FaultInjected(
                f"injected {opname} tier fault in {algo!r}"
            )
        if spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            # the tier STALLS (no raise): only a sentinel deadline —
            # or disarm() — gets the collective off this tier
            _apply_wedge(spec)


# -- daemon boundary (interposed in daemon/service request handlers) ----

def on_daemon(op: str, *, tenant: Optional[str] = None,
              cid: Optional[int] = None) -> list[FaultSpec]:
    """Daemon-boundary probe (``op`` is the request kind: attach /
    submit / dispatch / detach). ``key=`` scopes a spec to a tenant
    substring, ``cid=`` to one session comm. delay/wedge are applied
    in place; flood/hog specs are *returned* — the daemon amplifies a
    flood into ``rate=`` synthetic admission attempts and charges a
    hog's ``bytes=`` against the probing tenant's queue budget, so
    the adversarial pressure goes through the same admission path
    (counted, logged, never silent) as organic traffic."""
    p = _PLAN
    if p is None:
        return []
    out: list[FaultSpec] = []
    for spec in p.decide("daemon", op, key=tenant, cid=cid):
        if spec.action == "delay":
            _apply_delay(spec)
        elif spec.action == "wedge":
            _apply_wedge(spec)
        else:
            out.append(spec)
    return out
