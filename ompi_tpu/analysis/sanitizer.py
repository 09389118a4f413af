"""commsan — opt-in runtime communication sanitizer.

The dynamic half of commlint (DESIGN.md §13). Where the linter reasons
about source, the sanitizer watches the live comm stack, in the style of
MUST / MPI-Checker's runtime mode:

- **request tracking**: every `core.request.Request` reports its
  creation/start/completion/free to the tracker (module-global hook in
  core/request.py — zero cost when disabled). Requests still ACTIVE at
  finalize are leaks (the missing-wait defect), reported through
  ``core.memchecker`` — an unwaited recv buffer is exactly a buffer that
  stays undefined forever.
- **p2p matching**: a pass-through PML wrapper (the ft/vprotocol
  interposition idiom) counts sends and posted recvs per directed
  ``(cid, src, dst)`` pair; unmatched sends surface at finalize.
- **collective ordering**: ``Communicator._coll_call`` reports every
  collective; the per-process ``cid:op`` sequence is CRC-chained, marked
  at each barrier, published through the modex at finalize, and compared
  across processes — rank-divergent collective order is the classic
  deadlock the linter's ``colldiv`` rule can only approximate.
- **partitioned contracts**: a part-framework wrapper annotates
  Psend_init requests; an ACTIVE partitioned send whose partitions were
  never all Pready'd is flagged (the runtime twin of ``partready``).

Everything reports through SPC pvars (``sanitizer_*``) plus one
structured report at finalize (reusing analysis.report.Finding, so the
static and dynamic halves render identically).

Enable with ``sanitizer.enable()`` *before* ``ompi_tpu.init()`` (the
PML/part wrappers interpose at selection time), or set the
``sanitizer_base_enable`` cvar — ``init()`` honors it.
"""

from __future__ import annotations

import os
import threading
import traceback
import zlib
from collections import Counter as _Counter
from dataclasses import dataclass, field
from typing import Any, Optional

from ..core import config, dispatch_epoch
from ..core import request as _request
from ..core.counters import SPC
from ..core.errors import OmpiTpuError
from ..core.logging import get_logger, show_help
from .report import Finding, Report, Severity

logger = get_logger("analysis.sanitizer")

_enable = config.register(
    "sanitizer", "base", "enable", type=bool, default=False,
    description="Interpose the runtime communication sanitizer at init",
)
_fatal = config.register(
    "sanitizer", "base", "fatal", type=bool, default=True,
    description="Raise at finalize when the sanitizer found defects",
)
_max_events = config.register(
    "sanitizer", "base", "max_events", type=int, default=4096,
    description="Collective-sequence events kept verbatim (the CRC "
                "chain keeps matching past the cap)",
)
_lockwitness = config.register(
    "sanitizer", "base", "lockwitness", type=bool, default=False,
    description="Interpose inventoried threading locks (locksmith "
                "witness): record runtime acquisition-order edges; "
                "finalize reports runtime cycles and static lock-order "
                "edges never witnessed",
)

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SanitizerError(OmpiTpuError):
    errclass = "ERR_OTHER"


def _origin() -> tuple[str, int]:
    """First stack frame outside the ompi_tpu package (the user call
    site), newest-first; falls back to the newest frame."""
    stack = traceback.extract_stack(limit=25)
    for fr in reversed(stack[:-1]):
        if not os.path.abspath(fr.filename).startswith(_PKG_ROOT):
            return fr.filename, fr.lineno or 0
    fr = stack[-1]
    return fr.filename, fr.lineno or 0


@dataclass
class _Rec:
    req: Any
    kind: str
    origin: tuple[str, int]
    detail: str = ""


@dataclass
class _CollLog:
    seq: list[str] = field(default_factory=list)
    crc: int = 0
    count: int = 0
    barrier_marks: list[tuple[int, int]] = field(default_factory=list)


class Tracker:
    """Per-process sanitizer state (one per enable()/finalize cycle)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._live: dict[int, _Rec] = {}
        self._coll = _CollLog()
        self._sends: _Counter = _Counter()  # "cid:src:dst" -> n
        self._recvs: _Counter = _Counter()  # "cid:src:dst" ('*' wildcard)

    # -- request lifecycle hooks (called from core/request.py) ---------

    def created(self, req) -> None:
        with self._lock:
            self._live[id(req)] = _Rec(
                req, type(req).__name__, _origin()
            )
            SPC.hwm("sanitizer_live_requests_hwm", len(self._live))
        SPC.record("sanitizer_requests_tracked")

    def started(self, req) -> None:
        # persistent re-arm: track the new active cycle's call site
        with self._lock:
            if id(req) not in self._live:
                self._live[id(req)] = _Rec(
                    req, type(req).__name__, _origin()
                )

    def completed(self, req) -> None:
        with self._lock:
            self._live.pop(id(req), None)

    def freed(self, req) -> None:
        with self._lock:
            self._live.pop(id(req), None)

    def annotate(self, req, kind: str, detail: str = "") -> None:
        with self._lock:
            rec = self._live.get(id(req))
            if rec is not None:
                rec.kind = kind
                rec.detail = detail

    # -- traffic recording (called from the pml/part wrappers) ---------

    def p2p_send(self, comm, src, dst, tag) -> None:
        s = -1 if src is None else int(src)
        with self._lock:
            self._sends[f"{comm.cid}:{s}:{int(dst)}"] += 1
        SPC.record("sanitizer_sends_recorded")

    def p2p_recv(self, comm, src, tag, dst) -> None:
        s = "*" if src is None or int(src) < 0 else str(int(src))
        with self._lock:
            self._recvs[f"{comm.cid}:{s}:{int(dst)}"] += 1
        SPC.record("sanitizer_recvs_recorded")

    def record_coll(self, comm, opname: str) -> None:
        key = f"{comm.cid}:{opname}"
        cap = int(_max_events.value or 4096)
        with self._lock:
            log = self._coll
            log.crc = zlib.crc32(key.encode(), log.crc)
            log.count += 1
            if len(log.seq) < cap:
                log.seq.append(key)
            if opname == "barrier":
                log.barrier_marks.append((log.count, log.crc))
        SPC.record("sanitizer_coll_recorded")

    # -- finalize-time analysis ----------------------------------------

    def _leak_findings(self) -> list[Finding]:
        out = []
        with self._lock:
            recs = list(self._live.values())
        for rec in recs:
            state = getattr(rec.req, "state", None)
            if state is not _request.RequestState.ACTIVE:
                continue
            where = rec.detail and f" ({rec.detail})" or ""
            out.append(Finding(
                rule="san-leak", severity=Severity.ERROR,
                path=rec.origin[0], line=rec.origin[1],
                message=f"leaked {rec.kind}{where}: still active at "
                        "finalize — missing wait/test/free",
            ))
            flagged = getattr(rec.req, "_flagged", None)
            if flagged is not None and getattr(rec.req, "sending", False) \
                    and not all(flagged):
                missing = [i for i, f in enumerate(flagged) if not f]
                out.append(Finding(
                    rule="san-partready", severity=Severity.ERROR,
                    path=rec.origin[0], line=rec.origin[1],
                    message=f"partitioned send: partition(s) {missing} "
                            "never marked Pready this cycle — the "
                            "transfer cannot complete",
                ))
        return out

    def _payload(self) -> dict:
        with self._lock:
            return {
                "coll_seq": list(self._coll.seq),
                "coll_crc": self._coll.crc,
                "coll_count": self._coll.count,
                "barriers": [list(m) for m in self._coll.barrier_marks],
                "sends": dict(self._sends),
                "recvs": dict(self._recvs),
            }

    @staticmethod
    def _unmatched_findings(sends: _Counter, recvs: _Counter
                            ) -> list[Finding]:
        """Directed-pair accounting: sends to (cid, dst) must be covered
        by specific recvs plus the destination's wildcard posts."""
        out = []
        wild = _Counter()
        for key, n in recvs.items():
            cid, src, dst = key.split(":")
            if src == "*":
                wild[f"{cid}:{dst}"] += n
        for key, n in sorted(sends.items()):
            cid, src, dst = key.split(":")
            specific = recvs.get(key, 0)
            if src == "-1":  # unattributed source: match any specific
                specific = sum(
                    v for k, v in recvs.items()
                    if k.split(":")[0] == cid and k.split(":")[2] == dst
                )
            short = n - specific
            if short <= 0:
                continue
            avail = wild[f"{cid}:{dst}"]
            take = min(short, avail)
            wild[f"{cid}:{dst}"] -= take
            short -= take
            if short > 0:
                out.append(Finding(
                    rule="san-unmatched", severity=Severity.ERROR,
                    path="<runtime>", line=0,
                    message=f"{short} send(s) {src}->{dst} on cid {cid} "
                            "with no matching posted recv",
                ))
        return out

    def _divergence_findings(self, mine: dict, peers: dict[int, dict],
                             my_rank: int) -> list[Finding]:
        out = []
        for rank, theirs in sorted(peers.items()):
            if theirs["coll_crc"] == mine["coll_crc"] \
                    and theirs["coll_count"] == mine["coll_count"]:
                continue
            a, b = mine["coll_seq"], theirs["coll_seq"]
            idx = next(
                (i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)),
            )
            here = a[idx] if idx < len(a) else "<nothing>"
            there = b[idx] if idx < len(b) else "<nothing>"
            # first barrier epoch already past the divergence point
            epoch = next(
                (k for k, (cnt, _crc) in enumerate(mine["barriers"])
                 if cnt > idx), None,
            )
            at = f" (before barrier #{epoch})" if epoch is not None else ""
            out.append(Finding(
                rule="san-colldiv", severity=Severity.ERROR,
                path="<runtime>", line=0,
                message=f"collective order diverges from rank {rank} at "
                        f"call #{idx}{at}: this rank issued {here}, "
                        f"rank {rank} issued {there} — ranks block in "
                        "different collectives (deadlock)",
            ))
        return out

    def report(self) -> Report:
        findings = self._leak_findings()
        mine = self._payload()
        my_rank, nproc = 0, 1
        try:
            import jax

            nproc = jax.process_count()
            my_rank = jax.process_index()
        except (ImportError, RuntimeError, ValueError):
            pass
        if nproc > 1:
            from ..runtime import modex

            peers: dict[int, dict] = {}
            try:
                modex.put(f"sanitizer/fin/{my_rank}", mine)
                for r in range(nproc):
                    if r != my_rank:
                        peers[r] = modex.get(
                            f"sanitizer/fin/{r}", timeout_s=20.0
                        )
            except modex.ModexError as exc:
                logger.warning("cross-rank compare skipped: %s", exc)
            findings.extend(
                self._divergence_findings(mine, peers, my_rank)
            )
            sends = _Counter(mine["sends"])
            recvs = _Counter(mine["recvs"])
            for p in peers.values():
                sends.update(p["sends"])
                recvs.update(p["recvs"])
            if my_rank == 0:
                findings.extend(self._unmatched_findings(sends, recvs))
        else:
            findings.extend(self._unmatched_findings(
                _Counter(mine["sends"]), _Counter(mine["recvs"])
            ))
        return Report(findings)


# -- module-level state ------------------------------------------------

_TRACKER: Optional[Tracker] = None


def active() -> bool:
    return _TRACKER is not None


def tracker() -> Optional[Tracker]:
    return _TRACKER


def enable() -> Tracker:
    """Install the sanitizer. Call before init()/first communication —
    the PML/part wrappers interpose at component-selection time and a
    communicator's cached pml is not rewrapped retroactively."""
    global _TRACKER
    if _TRACKER is None:
        _TRACKER = Tracker()
        _request.set_tracker(_TRACKER)
        dispatch_epoch.bump()  # lane entries skip record_coll
        # NOTE: deliberately does not set the enable cvar — programmatic
        # enable() covers one init/finalize cycle; only the cvar (user
        # config) makes the sanitizer sticky across re-inits.
        from ..part import framework as part_fw
        from ..pml import framework as pml_fw

        pml_fw.reset_selection()
        part_fw.reset_selection()
        logger.info("communication sanitizer enabled")
    return _TRACKER


def maybe_enable() -> None:
    """init()-time hook: honor the sanitizer_base_enable and
    sanitizer_base_lockwitness cvars."""
    if _enable.value and not active():
        enable()
    if _lockwitness.value:
        from . import locksmith

        locksmith.witness_enable()


def record_coll(comm, opname: str) -> None:
    t = _TRACKER
    if t is not None:
        t.record_coll(comm, opname)


def finalize_check() -> Optional[BaseException]:
    """Run the finalize-time matching; returns (not raises) the error so
    api.finalize can finish teardown first and a second finalize stays
    clean."""
    global _TRACKER
    t = _TRACKER
    from . import locksmith

    wit_findings = locksmith.witness_finalize()
    if t is None and not wit_findings:
        return None
    if t is not None:
        _TRACKER = None
        _request.set_tracker(None)
        dispatch_epoch.bump()
        from ..part import framework as part_fw
        from ..pml import framework as pml_fw

        pml_fw.reset_selection()
        part_fw.reset_selection()
        rep = t.report()
    else:
        rep = Report([])
    if wit_findings:
        rep = Report(list(rep.findings) + wit_findings)
    if not len(rep):
        logger.info("sanitizer: clean at finalize")
        return None
    SPC.record("sanitizer_findings", len(rep))
    show_help("sanitizer report", "%s", rep.render(), once=False)
    if not _fatal.value:
        return None
    if rep.max_severity() < Severity.WARNING:
        # witness-unseen notes (static edges this run never exercised)
        # are coverage information, not defects
        return None
    leaks = rep.by_rule("san-leak")
    if leaks:
        from ..core import memchecker

        return memchecker.leak_report(
            f"sanitizer: {len(leaks)} leaked request(s) at finalize\n"
            + rep.render()
        )
    return SanitizerError(
        "sanitizer findings at finalize\n" + rep.render()
    )


# -- interposition wrappers --------------------------------------------

class SanitizerPml:
    """Pass-through PML recording p2p traffic (vprotocol idiom: wraps
    rather than replaces the selected component; unknown attributes —
    improbe, comm_freed, _infer_source — delegate to the host)."""

    NAME = "sanitizer"

    def __init__(self, host) -> None:
        self.host = host

    def __getattr__(self, name):
        return getattr(self.host, name)

    def _src(self, comm, value, source):
        infer = getattr(self.host, "_infer_source", None)
        if source is None and infer is not None:
            try:
                return infer(comm, value, source)
            except Exception:  # commlint: allow(broadexcept)
                return None  # inference is best-effort bookkeeping
        return source

    def isend(self, comm, value, dest, tag, source=None):
        t = _TRACKER
        if t is not None:
            t.p2p_send(comm, self._src(comm, value, source), dest, tag)
        req = self.host.isend(comm, value, dest, tag, source=source)
        if t is not None:
            t.annotate(
                req, "isend",
                f"dst={dest} tag={tag} comm={comm.name}",
            )
        return req

    def send(self, comm, value, dest, tag, source=None):
        t = _TRACKER
        if t is not None:
            t.p2p_send(comm, self._src(comm, value, source), dest, tag)
            # blocking send completes before return; count the matching
            # side only.
        return self.host.send(comm, value, dest, tag, source=source)

    def irecv(self, comm, source, tag, *, dest):
        t = _TRACKER
        if t is not None:
            t.p2p_recv(comm, source, tag, dest)
        req = self.host.irecv(comm, source, tag, dest=dest)
        if t is not None:
            t.annotate(
                req, "irecv",
                f"src={source} tag={tag} comm={comm.name}",
            )
        return req

    def recv(self, comm, source, tag, *, dest):
        t = _TRACKER
        if t is not None:
            t.p2p_recv(comm, source, tag, dest)
        return self.host.recv(comm, source, tag, dest=dest)


class SanitizerPart:
    """Pass-through part component annotating partitioned requests."""

    NAME = "sanitizer"

    def __init__(self, host) -> None:
        self.host = host

    def __getattr__(self, name):
        return getattr(self.host, name)

    def psend_init(self, comm, value, partitions, dest, tag=0, *,
                   source=None):
        req = self.host.psend_init(
            comm, value, partitions, dest, tag, source=source
        )
        t = _TRACKER
        if t is not None:
            t.annotate(
                req, "psend_init",
                f"partitions={partitions} dst={dest} tag={tag} "
                f"comm={comm.name}",
            )
        return req

    def precv_init(self, comm, partitions, source, tag=0, *, dest, like):
        req = self.host.precv_init(
            comm, partitions, source, tag, dest=dest, like=like
        )
        t = _TRACKER
        if t is not None:
            t.annotate(
                req, "precv_init",
                f"partitions={partitions} src={source} tag={tag} "
                f"comm={comm.name}",
            )
        return req


def maybe_wrap_pml(selected):
    if _enable.value and not active():
        enable()
    return SanitizerPml(selected) if active() else selected


def maybe_wrap_part(selected):
    if _enable.value and not active():
        enable()
    return SanitizerPart(selected) if active() else selected
