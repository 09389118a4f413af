"""commtrace flight recorder: the per-process event ring.

The recorder is a fixed-capacity ring of fixed-shape event records,
always on by default (``trace_base_enable``). Writers never block and
never allocate beyond one record: a monotonically increasing sequence
number (``itertools.count`` — atomic under the GIL, the same reasoning
SPC's lock-dodging record() documents) picks the slot, so concurrent
writers from transport/progress threads interleave without a lock and
an old record is simply overwritten once the ring laps. This is the
MPI-world "peruse event trace" idea recast as a flight recorder: the
last N events are always available post-mortem, even from a wedged
process (signal handler / the bench watchdog path).

Record shape (one tuple per slot, fixed field order):

    (seq, t_ns, ph, name, cat, span, parent, tid, args)

``ph`` is the Chrome trace_event phase ("B"/"E"/"i"), ``t_ns`` is
``time.perf_counter_ns()`` (CLOCK_MONOTONIC on Linux — deliberately the
same clock the native ring stamps with ``clock_gettime(MONOTONIC)``, so
the two merge on one axis). ``encode()``/``decode()`` give the
fixed-size binary record form (48 bytes/record + string/args tables)
used when buffers travel over the modex at finalize.

The native counterpart (native/src/tracering.cc) records C++-side
events — doorbell parks, slab spills, CRC drops, link re-stripes —
without crossing into Python; ``drain_native()`` folds them in.
"""

from __future__ import annotations

import ctypes
import itertools
import json
import os
import signal
import struct
import threading
import time
from typing import Any, Optional

from ..core import config
from ..core.logging import get_logger

logger = get_logger("trace")

_enable = config.register(
    "trace", "base", "enable", type=bool, default=True,
    description="Flight recorder + span tracing (always-on design; "
    "disable to shed the last few hundred ns per traced call)",
)
_DEFAULT_ENTRIES = 65536
_entries = config.register(
    "trace", "base", "ring_entries", type=int, default=_DEFAULT_ENTRIES,
    description="Flight-recorder ring capacity (rounded up to a power "
    "of two; oldest records are overwritten)",
)
_dir = config.register(
    "trace", "base", "dir", type=str, default="",
    description="Directory for per-rank trace dumps at finalize / on "
    "signal (empty: finalize does not dump; signal dumps to TMPDIR)",
)
_signal_var = config.register(
    "trace", "base", "signal", type=str, default="USR2",
    description="Signal that dumps the flight recorder post-mortem "
    "(SIG<name>; empty disables the handler)",
)
_gather = config.register(
    "trace", "base", "gather", type=bool, default=False,
    description="At finalize, publish the per-rank buffer over the "
    "modex and have rank 0 write a merged Perfetto trace",
)

#: kind -> event name for native tracering records.
NATIVE_KINDS = {
    1: "fp_futex_park",
    2: "fp_ring_full",
    3: "fp_slab_spill",
    4: "fp_crc_drop",
    5: "shm_doorbell_park",
    6: "shm_drain_park",
    7: "dcn_restripe",
    8: "dcn_link_drop",
}


def enabled() -> bool:
    return _enable.value


class FlightRecorder:
    """Lock-free ring of fixed-shape event records (see module doc)."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        cap = int(capacity or _entries.value or _DEFAULT_ENTRIES)
        cap = 1 << max(6, (cap - 1).bit_length())
        self._slots: list = [None] * cap
        self._mask = cap - 1
        self._seq = itertools.count()
        # Paired clock samples taken at construction: map the monotonic
        # record timestamps onto the epoch clock when merging ranks.
        self.epoch_perf_ns = time.perf_counter_ns()
        self.epoch_unix_ns = time.time_ns()
        # mpisync offset vs rank 0 (remote - local, seconds); stamped
        # into dumps so the merge tool can align without re-measuring.
        self.clock_offset_s = 0.0

    @property
    def capacity(self) -> int:
        return self._mask + 1

    def emit(self, ph: str, name: str, cat: str = "", span: int = 0,
             parent: int = 0, args: Optional[dict] = None,
             t_ns: Optional[int] = None) -> None:
        """Append one record. Hot path: one counter bump, one clock
        read, one tuple, one slot store — no locks, no branches on
        ring state (wrap is just modular slot reuse)."""
        if not _enable.value:
            return
        n = next(self._seq)
        self._slots[n & self._mask] = (
            n,
            time.perf_counter_ns() if t_ns is None else t_ns,
            ph, name, cat, span, parent,
            threading.get_ident() & 0xFFFF,
            args,
        )

    def records(self) -> list[tuple]:
        """Snapshot, oldest first. Torn slots (a writer mid-store on
        another thread) simply show the old or new tuple — slot
        assignment is atomic under the GIL."""
        out = [r for r in self._slots if r is not None]
        out.sort(key=lambda r: r[0])
        return out

    def clear(self) -> None:
        self._slots = [None] * (self._mask + 1)
        self._seq = itertools.count()

    # -- fixed-size binary record codec ---------------------------------

    # seq:u64 t_ns:i64 span:u64 parent:u64 name:i32 cat:i32 args:i32
    # tid:u16 ph:u8 pad:u8  => 48 bytes per record
    _REC = struct.Struct("<QqQQiiiHBx")
    _MAGIC = b"OTTRACE1"

    @classmethod
    def encode(cls, records: list[tuple]) -> bytes:
        """records -> fixed-size binary records + string/args tables."""
        strings: list[str] = []
        sidx: dict[str, int] = {}
        argtab: list[str] = []

        def intern(s: str) -> int:
            i = sidx.get(s)
            if i is None:
                i = sidx[s] = len(strings)
                strings.append(s)
            return i

        body = bytearray()
        for (seq, t_ns, ph, name, cat, span, parent, tid, args) in records:
            ai = -1
            if args:
                ai = len(argtab)
                argtab.append(json.dumps(args, default=str,
                                         sort_keys=True))
            body += cls._REC.pack(seq, t_ns, span, parent, intern(name),
                                  intern(cat or ""), ai, tid,
                                  ord(ph[0]))
        tail = json.dumps({"strings": strings, "args": argtab}).encode()
        return (cls._MAGIC + struct.pack("<I", len(records))
                + bytes(body) + tail)

    @classmethod
    def decode(cls, blob: bytes) -> list[tuple]:
        if blob[:8] != cls._MAGIC:
            raise ValueError("not an ompi_tpu trace blob")
        (n,) = struct.unpack_from("<I", blob, 8)
        off = 12
        tail = json.loads(blob[off + n * cls._REC.size:].decode())
        strings, argtab = tail["strings"], tail["args"]
        out = []
        for i in range(n):
            seq, t_ns, span, parent, ni, ci, ai, tid, ph = \
                cls._REC.unpack_from(blob, off + i * cls._REC.size)
            out.append((seq, t_ns, chr(ph), strings[ni], strings[ci],
                        span, parent, tid,
                        json.loads(argtab[ai]) if ai >= 0 else None))
        return out


_RECORDER = FlightRecorder()


def get() -> FlightRecorder:
    return _RECORDER


def configure(capacity: Optional[int] = None) -> FlightRecorder:
    """Rebuild the process recorder (tests / cvar changes). Records
    already emitted are dropped."""
    global _RECORDER
    _RECORDER = FlightRecorder(capacity)
    return _RECORDER


def set_clock_offset(offset_s: float) -> None:
    """Stamp this rank's mpisync offset vs rank 0 (remote - local,
    seconds; tools/mpisync OffsetEstimate.offset_s) so dumps carry it
    and the merge aligns without re-measuring."""
    _RECORDER.clock_offset_s = float(offset_s)


def emit(ph: str, name: str, **kw: Any) -> None:
    _RECORDER.emit(ph, name, **kw)


# -- native ring bridge -----------------------------------------------------

class _NtRec(ctypes.Structure):
    _fields_ = [
        ("t_ns", ctypes.c_longlong),
        ("kind", ctypes.c_int),
        ("a", ctypes.c_int),
        ("b", ctypes.c_longlong),
        ("c", ctypes.c_longlong),
    ]


def drain_native() -> list[tuple]:
    """Copy the native tracering out as instant-event records (cat
    "native"). Non-destructive; returns [] without the library."""
    from ..native import build

    lib = build.get_lib()
    if lib is None or not hasattr(lib, "nt_trace_dump"):
        return []
    cap = int(lib.nt_trace_capacity())
    buf = (_NtRec * cap)()
    n = int(lib.nt_trace_dump(buf, cap))
    out = []
    for i in range(n):
        r = buf[i]
        name = NATIVE_KINDS.get(r.kind, f"native_kind_{r.kind}")
        out.append((i, r.t_ns, "i", name, "native", 0, 0, 0,
                    {"a": r.a, "b": r.b, "c": r.c}))
    return out


def native_trace_enable(on: bool) -> None:
    from ..native import build

    lib = build.get_lib()
    if lib is not None and hasattr(lib, "nt_trace_enable"):
        lib.nt_trace_enable(1 if on else 0)


def native_trace_reset() -> None:
    from ..native import build

    lib = build.get_lib()
    if lib is not None and hasattr(lib, "nt_trace_reset"):
        lib.nt_trace_reset()


# -- identity + post-mortem dumps -------------------------------------------

_rank: Optional[int] = None


def set_rank(rank: int) -> None:
    global _rank
    _rank = rank


def process_rank() -> int:
    """This controller's rank for dump labelling: explicit set_rank()
    (api.init) > OMPI_TPU_TRACE_RANK env > jax process_index > 0."""
    if _rank is not None:
        return _rank
    env = os.environ.get("OMPI_TPU_TRACE_RANK")
    if env is not None:
        return int(env)
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # commlint: allow(broadexcept)
        return 0  # pre-init best effort: any label beats no dump


def dump_dir() -> str:
    import tempfile

    return _dir.value or tempfile.gettempdir()


def dump_post_mortem(reason: str = "") -> Optional[str]:
    """Write this process's buffer as a rank dump — the signal-handler
    / watchdog path, so it must never raise."""
    try:
        from . import export

        path = os.path.join(
            dump_dir(),
            f"ompi_tpu-trace-rank{process_rank()}-pid{os.getpid()}.json",
        )
        export.write_rank_dump(path, reason=reason)
        logger.warning("trace: dumped %d record(s) to %s (%s)",
                       len(_RECORDER.records()), path, reason or "request")
        try:
            # the telemetry snapshot lands next to the trace dump: a
            # post-mortem needs the counters/health state that led up
            # to the wedge, not just the event ring
            from ..telemetry import export as _texport

            _texport.write_json(path[:-5] + "-telemetry.json")
        except Exception:  # commlint: allow(broadexcept)
            pass  # telemetry is optional garnish on the trace dump
        return path
    except Exception:  # commlint: allow(broadexcept)
        # last-resort diagnostics must not take the process down
        logger.exception("trace: post-mortem dump failed")
        return None


def _on_signal(signum, frame) -> None:
    dump_post_mortem(reason=f"signal {signum}")


def install_signal_handler() -> bool:
    """Arm the post-mortem dump signal (``trace_base_signal``). Only
    legal from the main thread; returns whether a handler was set."""
    name = (_signal_var.value or "").strip().upper()
    if not name or not _enable.value:
        return False
    signum = getattr(signal, f"SIG{name}", None)
    if signum is None:
        logger.warning("trace: unknown signal %r", name)
        return False
    if threading.current_thread() is not threading.main_thread():
        return False
    try:
        signal.signal(signum, _on_signal)
    except (ValueError, OSError) as exc:
        logger.info("trace: signal handler not installed: %s", exc)
        return False
    return True
