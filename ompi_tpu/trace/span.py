"""commtrace spans: cross-rank-correlatable begin/end tracing.

Span IDs ride the same derived-namespace arithmetic the partitioned
transport uses for its wire tags (part/persist:
``(user_tag + 1) * stride + k``): a collective's trace ID is

    trace_id = ((cid + 1) << 20) | (per-comm collective seq & 0xFFFFF)

computed locally on every rank. MPI semantics already require each rank
to issue collectives on a communicator in the same order (the
sanitizer's cross-rank coll-order CRC enforces exactly this), so the
per-(cid) sequence numbers — and therefore the trace IDs — agree on
every rank without a wire exchange. One allreduce's spans on rank 0 and
rank 1 carry the same ``trace_id`` and line up in the merged Perfetto
view. The ``+1``/shift keeps IDs disjoint from user tags and from the
part framework's derived window, i.e. trace IDs live in the same tag
namespace and cannot collide with traffic tags.

Where the spans open: ``Communicator._coll_call`` opens ``coll.<op>``
around every collective it dispatches, preamble included, and
``Communicator.allreduce`` opens ``coll.allreduce`` itself for a lane
hit; the tuned allreduce opens ``coll.launch`` around the plan call, and its host tier
``coll.host_fetch`` / ``coll.host_put`` around the device-to-host fetch
and the ``device_put``; ``ops.reduce_local`` opens ``op.reduce_local``.
The selected PML is wrapped at its selection seam (pml/framework). A
span does nothing while the recorder's enable cvar is off, so toggling
tracing needs no selection reset.

While a ``jax.profiler`` session records, every span also opens a
``TraceAnnotation`` of its name: the program's spans then sit on the
profiler's host plane, on the clock of the device ops, and a device
idle gap can be put down to the span open during it. The ring keeps
its own ``perf_counter_ns`` stamps.

Span begin/end also feed the Histogram pvar class (core/counters):
``coll_<op>`` / ``pml_send`` / ``pml_recv`` latency distributions with
p50/p99 snapshots for the bench rows and, later, the autotuner.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from ..core.counters import SPC
from . import recorder

_SEQ_BITS = 20
_SEQ_MASK = (1 << _SEQ_BITS) - 1

_local = threading.local()
_span_ids = itertools.count(1)
_coll_seq: dict[int, Any] = {}


def enabled() -> bool:
    return recorder.enabled()


def coll_trace_id(cid: int) -> int:
    """Next trace ID for a collective on communicator ``cid`` (see
    module doc for the derivation). Deterministic per rank-local call
    order, which MPI requires to agree across ranks."""
    ctr = _coll_seq.get(cid)
    if ctr is None:
        ctr = _coll_seq.setdefault(cid, itertools.count())
    return ((cid + 1) << _SEQ_BITS) | (next(ctr) & _SEQ_MASK)


def reset_for_testing() -> None:
    _coll_seq.clear()
    st = getattr(_local, "stack", None)
    if st:
        del st[:]


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> Optional["Span"]:
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


class Span:
    """Begin/end event pair. Plain __enter__/__exit__ (no
    contextmanager generator) keeps the per-span cost to two records
    plus bookkeeping. Nested spans inherit the trace ID and record the
    enclosing span as ``parent``. With the recorder off a span records
    nothing; while a profiler session records it is also a
    ``TraceAnnotation`` (module doc)."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "args", "hist", "t0_ns", "ann")

    def __init__(self, name: str, cat: str = "span",
                 trace_id: Optional[int] = None,
                 histogram: Optional[str] = None,
                 args: Optional[dict] = None) -> None:
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.args = args
        self.hist = histogram
        self.span_id = 0
        self.parent_id = 0
        self.t0_ns = 0
        self.ann = None

    def __enter__(self) -> "Span":
        if not recorder.enabled():
            return self  # span_id stays 0: __exit__ records nothing
        st = _stack()
        parent = st[-1] if st else None
        if parent is not None:
            self.parent_id = parent.span_id
            if self.trace_id is None:
                self.trace_id = parent.trace_id
        self.span_id = next(_span_ids)
        a = {"trace_id": self.trace_id or 0}
        if self.args:
            a.update(self.args)
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        # the ring's own emit: the module-level one repacks its keywords
        recorder.get().emit("B", self.name, cat=self.cat,
                            span=self.span_id, parent=self.parent_id,
                            args=a, t_ns=self.t0_ns)
        st.append(self)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if not self.span_id:
            return False
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        t1 = time.perf_counter_ns()
        recorder.get().emit(
            "E", self.name, cat=self.cat, span=self.span_id,
            parent=self.parent_id, t_ns=t1,
            args={"error": et.__name__} if et is not None else None,
        )
        if self.ann is not None:
            self.ann.__exit__(et, ev, tb)
        if self.hist is not None:
            SPC.record_latency(self.hist, (t1 - self.t0_ns) * 1e-9)
        return False


def span(name: str, cat: str = "span", trace_id: Optional[int] = None,
         histogram: Optional[str] = None, **args: Any) -> Span:
    return Span(name, cat, trace_id, histogram, args or None)


def instant(name: str, cat: str = "event", **args: Any) -> None:
    """One instant event, attributed to the current span/trace if any.
    Callable from any layer; a no-op when tracing is off."""
    if not recorder.enabled():
        return
    cur = current()
    if cur is not None:
        args.setdefault("trace_id", cur.trace_id or 0)
        recorder.emit("i", name, cat=cat, parent=cur.span_id, args=args)
    else:
        recorder.emit("i", name, cat=cat, args=args or None)


# -- interposition wrappers --------------------------------------------------

class TracePml:
    """Pass-through PML recording p2p spans (vprotocol idiom: wraps the
    selected component; unknown attributes — including NAME — delegate
    to the host, so component-identity assertions keep working)."""

    def __init__(self, host) -> None:
        self.host = host

    def __getattr__(self, name):
        return getattr(self.host, name)

    @property
    def __class__(self):  # noqa: D401 - transparent-proxy idiom
        # isinstance() must see through the tracer: FT tests assert the
        # selected pml IS the PessimistPml they enabled. type(self)
        # still reports TracePml, so tracer-identity checks also hold.
        return type(self.host)

    def send(self, comm, value, dest, tag, source=None):
        with Span("pml.send", "pml", histogram="pml_send",
                  args={"cid": comm.cid, "peer": dest, "tag": tag}):
            return self.host.send(comm, value, dest, tag, source=source)

    def recv(self, comm, source, tag, *, dest):
        with Span("pml.recv", "pml", histogram="pml_recv",
                  args={"cid": comm.cid, "peer": source, "tag": tag}):
            return self.host.recv(comm, source, tag, dest=dest)

    def isend(self, comm, value, dest, tag, source=None):
        # nonblocking: the span covers the post, not the transfer —
        # completion shows up as the progress engine's own events
        with Span("pml.isend", "pml",
                  args={"cid": comm.cid, "peer": dest, "tag": tag}):
            return self.host.isend(comm, value, dest, tag, source=source)

    def irecv(self, comm, source, tag, *, dest):
        with Span("pml.irecv", "pml",
                  args={"cid": comm.cid, "peer": source, "tag": tag}):
            return self.host.irecv(comm, source, tag, dest=dest)


def maybe_wrap_pml(selected):
    return TracePml(selected)
