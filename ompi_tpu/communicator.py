"""Communicators: groups of ranks with collective/p2p capability.

TPU-native equivalent of ompi/communicator (reference: comm.c, comm_init.c,
comm_cid.c). Design mapping:

- A rank is a TPU device; a communicator owns an ordered device list (its
  group's world ranks index the world device list).
- The per-communicator collective function table (`reference: c_coll`,
  ompi/mca/coll/coll.h:629-702) is `self._coll`: per-operation
  (component, fn) pairs merged by priority at creation
  (reference: coll_base_comm_select.c:110-152).
- Context id (CID) allocation: the reference runs a distributed agreement
  (comm_cid.c:53-147) because each process allocates independently; in
  the single-controller driver model every host executes the same
  deterministic program, so a replicated monotonic counter yields
  identical CIDs on all hosts by construction.
- Compiled collective plans are cached per (op, algorithm, shape, dtype)
  — the TPU answer to ob1's latency tricks (SURVEY §7 hard parts:
  "persistent, pre-compiled collective plans").
- The allreduce lane (`self._lane`): the plan the tuned layer routed for
  a plain array's (shape, dtype, op), memoized under the process-wide
  dispatch epoch (core/dispatch_epoch); a repeat call costs one dict
  lookup and one int compare before the plan launch.

Driver-mode buffer convention ("rank-major"): a collective argument is a
jax.Array whose leading axis is the rank index, sharded one block per
rank-device over the comm's 1-D mesh. `comm.put_rank_major` builds one.
"""

from __future__ import annotations

import itertools
import threading
from typing import Any, Optional, Sequence

import jax
import numpy as np

from .core import config, dispatch_epoch
from .core.attributes import HasAttributes
from .core.counters import SPC
from .core.errors import (ArgumentError, CommError, HasErrhandler,
                          RankError, RevokedError)
from .core.info import Info
from .core.logging import get_logger
from .group import Group
from .trace.span import Span, coll_trace_id

logger = get_logger("comm")

_cid_counter = itertools.count(0)
_cid_lock = threading.Lock()

# Every live communicator, for finalize-time teardown (weak: a dropped
# comm needs no explicit free, matching Python object semantics).
import weakref

live_comms: "weakref.WeakSet[Communicator]" = weakref.WeakSet()


def _next_cid() -> int:
    with _cid_lock:
        return next(_cid_counter)


def reset_cids_for_testing() -> None:
    """Restart cid allocation at 0 (sim/test isolation). Only safe
    when no communicator from the previous epoch is still in use:
    decision logs key on cids, so deterministic replay needs each run
    to allocate the same ids."""
    global _cid_counter
    with _cid_lock:
        _cid_counter = itertools.count(0)


class Communicator(HasAttributes, HasErrhandler):
    """A communication context over an ordered set of rank-devices."""

    def __init__(
        self,
        group: Group,
        world_procs: Sequence,
        *,
        name: str = "",
        info: Optional[Info] = None,
        parent_cid: Optional[int] = None,
    ) -> None:
        self.group = group
        self.cid = _next_cid()
        self._span_args = {"cid": self.cid}
        self.name = name or f"comm{self.cid}"
        self.info = info or Info()
        self.parent_cid = parent_cid
        self._freed = False
        # ULFM state (ft/lifeboat): the epoch is stamped into the wire
        # tag namespace (trace/span derives ids from (cid, epoch)) and
        # bumped by recover(); _revoked is the in-band poison flag —
        # one attribute read on every dispatch, nothing on the wire.
        self.epoch = 0
        self._revoked = False
        self._world_procs = world_procs
        self.procs = [world_procs[r] for r in group.world_ranks]
        self.devices = [p.device for p in self.procs]
        self._mesh = None
        self._plan_cache: dict[tuple, Any] = {}
        # (shape, dtype, op as given) -> (epoch, plan, algo counter,
        # launch): see allreduce()
        self._lane: dict[tuple, tuple] = {}
        self._coll: dict[str, tuple[Any, Any]] = {}
        self._pml = None
        self.topo = None  # attached by topo framework (cart/graph)
        self._select_frameworks()
        live_comms.add(self)

    # -- framework selection ---------------------------------------------

    def _select_frameworks(self) -> None:
        from .coll.framework import select_for_comm as coll_select

        self._coll = coll_select(self)
        dispatch_epoch.bump()  # lane entries stand in for the old vtable

    # -- basic accessors --------------------------------------------------

    @property
    def size(self) -> int:
        return self.group.size

    @property
    def mesh(self):
        """1-D jax Mesh over this comm's devices (lazily built)."""
        if self._mesh is None:
            from .runtime import mesh as mesh_mod

            if len(set(self.devices)) != len(self.devices):
                raise CommError(
                    f"{self.name}: duplicate devices; no mesh available"
                )
            self._mesh = mesh_mod.comm_mesh(self.devices)
        return self._mesh

    def rank_sharding(self):
        """NamedSharding placing leading-axis block i on rank i's device."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("ranks"))

    def replicated_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def put_rank_major(self, value) -> Any:
        """Place a (size, ...) array so block i lives on rank i's device."""
        import jax
        import jax.numpy as jnp

        arr = jnp.asarray(value)
        if arr.shape[0] != self.size:
            raise ArgumentError(
                f"rank-major leading dim {arr.shape[0]} != comm size "
                f"{self.size}"
            )
        if self.size == 1:
            return jax.device_put(arr, self.devices[0])
        return jax.device_put(arr, self.rank_sharding())

    def from_rank_values(self, values: Sequence) -> Any:
        """Assemble one array per rank into a rank-major buffer without
        moving data: block i stays on rank i's device (zero-copy when
        the values already live there)."""
        import jax
        import jax.numpy as jnp

        if len(values) != self.size:
            raise ArgumentError(
                f"{len(values)} values for comm of size {self.size}"
            )
        if self.size == 1:
            return self.put_rank_major(jnp.asarray(values[0])[None])
        blocks = [
            jnp.expand_dims(jax.device_put(jnp.asarray(v), d), 0)
            for v, d in zip(values, self.devices)
        ]
        shape = (self.size,) + tuple(blocks[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, self.rank_sharding(), blocks
        )

    def check_rank(self, rank: int) -> int:
        if not 0 <= rank < self.size:
            raise RankError(
                f"rank {rank} out of range for {self.name} (size {self.size})"
            )
        return rank

    def _check_alive(self) -> None:
        if self._freed:
            raise CommError(f"{self.name} has been freed")
        if self._revoked:
            raise RevokedError(
                f"{self.name} (cid={self.cid} epoch={self.epoch}) has "
                f"been revoked; run ft.lifeboat.recover"
            )

    # -- collectives (dispatch through the per-comm vtable) ---------------

    def _coll_call(self, opname: str, *args, **kw):
        # Counter, span and histogram names interned once per comm: the
        # f-string build cost ~1 us per call in r05 dispatch profiles —
        # real money at small-message rates.
        names = self.__dict__.setdefault("_coll_names", {})
        interned = names.get(opname)
        if interned is None:
            interned = names[opname] = (f"coll_{opname}_calls",
                                        f"coll.{opname}", f"coll_{opname}")
        counter, span_name, hist = interned
        # the span covers the whole call; every rank derives the same
        # trace_id (trace/span.py)
        with Span(span_name, "coll", coll_trace_id(self.cid), hist,
                  self._span_args):
            self._check_alive()
            entry = self._coll.get(opname)
            if entry is None:
                raise CommError(
                    f"{self.name}: no coll component provides {opname}"
                )
            component, fn = entry
            SPC.record(counter)
            from .core import memchecker

            if memchecker.enabled() and args:
                memchecker.check_defined(args[0], f"{opname} buffer")
            from .monitoring import MONITOR

            if MONITOR.enabled:
                nbytes = 0
                if args:
                    import jax

                    for leaf in jax.tree.leaves(args[0]):
                        if hasattr(leaf, "nbytes"):
                            nbytes += leaf.nbytes
                MONITOR.record_coll(self.cid, opname, nbytes)
            from .analysis import sanitizer

            if sanitizer.active():
                sanitizer.record_coll(self, opname)
            return fn(self, *args, **kw)

    def allreduce(self, x, op="sum"):
        # The lane: a plain array whose (shape, dtype, op) the tuned
        # layer already routed within this dispatch epoch skips the
        # vtable, the op lookup, the gates and the route. A miss, or any
        # state the lane cannot prove unchanged, takes _coll_call, which
        # refills the lane through TunedColl (see _lane_store).
        if isinstance(x, jax.Array):
            key = (x.shape, x.dtype, op)
            try:
                ent = self._lane.get(key)
            except TypeError:  # an unhashable op: _coll_call names it
                ent = None
            if ent is not None and ent[0] == dispatch_epoch.value:
                with Span("coll.allreduce", "coll",
                          coll_trace_id(self.cid), "coll_allreduce",
                          self._span_args):
                    self._check_alive()
                    SPC.record("coll_allreduce_calls")
                    SPC.record(ent[2])
                    SPC.record("coll_allreduce_lane_hits")
                    return ent[3](self, ent[1], x, op, key)
        return self._coll_call("allreduce", x, op)

    def _lane_store(self, fn, x, op, ent) -> Optional[tuple]:
        """Memoize a fast-route entry ``(epoch, plan, algo counter,
        launch)`` that the vtable function ``fn`` just built for the
        plain array ``x``; returns its key, or None when the lane must
        stay out: the vtable dispatches through a wrapper or another
        component, or a per-call hook (memchecker, MONITOR, sanitizer)
        is on. Enabling any of them bumps the dispatch epoch, so a
        stored entry never skips a hook turned on after it."""
        from .analysis import sanitizer
        from .core import memchecker
        from .monitoring import MONITOR

        vt = self._coll.get("allreduce")
        if (vt is None or getattr(vt[1], "__func__", None) is not fn
                or memchecker.enabled() or MONITOR.enabled
                or sanitizer.active()):
            return None
        key = (x.shape, x.dtype, op)
        try:
            self._lane[key] = ent
        except TypeError:  # an unhashable op
            return None
        SPC.record("coll_allreduce_lane_builds")
        return key

    def bcast(self, x, root: int = 0):
        return self._coll_call("bcast", x, self.check_rank(root))

    def reduce(self, x, op="sum", root: int = 0):
        return self._coll_call("reduce", x, op, self.check_rank(root))

    def allgather(self, x):
        return self._coll_call("allgather", x)

    def reduce_scatter_block(self, x, op="sum"):
        return self._coll_call("reduce_scatter_block", x, op)

    def alltoall(self, x):
        return self._coll_call("alltoall", x)

    def gather(self, x, root: int = 0):
        return self._coll_call("gather", x, self.check_rank(root))

    def scatter(self, x, root: int = 0):
        return self._coll_call("scatter", x, self.check_rank(root))

    def scan(self, x, op="sum"):
        return self._coll_call("scan", x, op)

    def exscan(self, x, op="sum"):
        return self._coll_call("exscan", x, op)

    def barrier(self):
        token = self._coll_call("barrier")
        if token is not None:
            import jax

            jax.block_until_ready(token)

    # vector (ragged) variants — per-rank block lists carry the counts
    def allgatherv(self, values):
        return self._coll_call("allgatherv", list(values))

    def gatherv(self, values, root: int = 0):
        return self._coll_call("gatherv", list(values),
                               self.check_rank(root))

    def scatterv(self, blocks, root: int = 0):
        return self._coll_call("scatterv", list(blocks),
                               self.check_rank(root))

    def alltoallv(self, blocks):
        return self._coll_call("alltoallv", [list(b) for b in blocks])

    def alltoallw(self, blocks):
        return self._coll_call("alltoallw", [list(b) for b in blocks])

    def reduce_scatter(self, values, counts, op="sum"):
        return self._coll_call("reduce_scatter", list(values),
                               list(counts), op)

    # neighborhood collectives (need an attached cart/graph topology)
    def neighbor_allgather(self, x):
        return self._coll_call("neighbor_allgather", x)

    def neighbor_alltoall(self, sendblocks):
        return self._coll_call("neighbor_alltoall", sendblocks)

    # Nonblocking variants: JAX async dispatch enqueues the device work
    # immediately; the request completes when the result array is ready.
    def _icoll(self, opname: str, *args, **kw):
        from .coll.framework import DeviceRequest

        result = self._coll_call(opname, *args, **kw)
        return DeviceRequest(result)

    def iallreduce(self, x, op="sum"):
        from .coll.framework import DeviceRequest

        return DeviceRequest(self.allreduce(x, op))

    def ibcast(self, x, root: int = 0):
        return self._icoll("bcast", x, self.check_rank(root))

    def ireduce(self, x, op="sum", root: int = 0):
        return self._icoll("reduce", x, op, self.check_rank(root))

    def iallgather(self, x):
        return self._icoll("allgather", x)

    def ireduce_scatter_block(self, x, op="sum"):
        return self._icoll("reduce_scatter_block", x, op)

    def ialltoall(self, x):
        return self._icoll("alltoall", x)

    def igather(self, x, root: int = 0):
        return self._icoll("gather", x, self.check_rank(root))

    def iscatter(self, x, root: int = 0):
        return self._icoll("scatter", x, self.check_rank(root))

    def iscan(self, x, op="sum"):
        return self._icoll("scan", x, op)

    def ibarrier(self):
        return self._icoll("barrier")

    def iallgatherv(self, values):
        return self._icoll("allgatherv", list(values))

    def ialltoallv(self, blocks):
        return self._icoll("alltoallv", [list(b) for b in blocks])

    def ireduce_scatter(self, values, counts, op="sum"):
        return self._icoll("reduce_scatter", list(values), list(counts), op)

    def ineighbor_allgather(self, x):
        return self._icoll("neighbor_allgather", x)

    def ineighbor_alltoall(self, sendblocks):
        return self._icoll("neighbor_alltoall", sendblocks)

    # Persistent collectives (MPI-4 *_init / mpiext pcollreq analog;
    # reference: the 22-operation table of coll_base_functions.h:45-66
    # and ompi/mpiext/pcollreq): the compiled plan IS the persistent
    # schedule; start() re-dispatches the cached executable against the
    # bound buffer. Every blocking operation below has an _init form,
    # including the vector and neighborhood families.
    def _pinit(self, opname: str, x, *args):
        from .coll.framework import PersistentColl

        return PersistentColl(self, opname, args, x)

    def allreduce_init(self, x, op="sum"):
        return self._pinit("allreduce", x, op)

    def bcast_init(self, x, root: int = 0):
        return self._pinit("bcast", x, self.check_rank(root))

    def reduce_init(self, x, op="sum", root: int = 0):
        return self._pinit("reduce", x, op, self.check_rank(root))

    def allgather_init(self, x):
        return self._pinit("allgather", x)

    def reduce_scatter_block_init(self, x, op="sum"):
        return self._pinit("reduce_scatter_block", x, op)

    def alltoall_init(self, x):
        return self._pinit("alltoall", x)

    def gather_init(self, x, root: int = 0):
        return self._pinit("gather", x, self.check_rank(root))

    def scatter_init(self, x, root: int = 0):
        return self._pinit("scatter", x, self.check_rank(root))

    def scan_init(self, x, op="sum"):
        return self._pinit("scan", x, op)

    def exscan_init(self, x, op="sum"):
        return self._pinit("exscan", x, op)

    def barrier_init(self):
        return self._pinit("barrier", None)

    def allgatherv_init(self, values):
        return self._pinit("allgatherv", list(values))

    def gatherv_init(self, values, root: int = 0):
        return self._pinit("gatherv", list(values),
                           self.check_rank(root))

    def scatterv_init(self, blocks, root: int = 0):
        return self._pinit("scatterv", list(blocks),
                           self.check_rank(root))

    def alltoallv_init(self, blocks):
        return self._pinit("alltoallv", [list(b) for b in blocks])

    def alltoallw_init(self, blocks):
        return self._pinit("alltoallw", [list(b) for b in blocks])

    def reduce_scatter_init(self, values, counts, op="sum"):
        return self._pinit("reduce_scatter", list(values),
                           list(counts), op)

    def neighbor_allgather_init(self, x):
        return self._pinit("neighbor_allgather", x)

    def neighbor_alltoall_init(self, sendblocks):
        return self._pinit("neighbor_alltoall", sendblocks)

    # Persistent p2p (MPI_Send_init / MPI_Recv_init, reference pml.h:292
    # `pml_isend_init`): binds the envelope once; each start() re-issues
    # through the selected PML against the currently bound buffer.
    def send_init(self, value, dest: int, tag: int = 0, *, source=None):
        return PersistentSend(
            self, value, self.check_rank(dest), tag, source
        )

    def recv_init(self, source: int = -1, tag: int = -1, *, dest: int):
        return PersistentRecv(self, source, tag, dest)

    # Partitioned p2p (MPI-4 MPI_Psend_init / MPI_Precv_init, reference
    # ompi/mca/part): N user partitions of one buffer drain as M
    # internal pml transfers, eagerly as Pready flags land.
    def psend_init(self, value, partitions: int, dest: int, tag: int = 0,
                   *, source=None):
        self._check_alive()
        from .part.framework import select_for_comm as part_select

        if source is not None:
            source = self.check_rank(source)
        return part_select(self).psend_init(
            self, value, partitions, self.check_rank(dest), tag,
            source=source,
        )

    def precv_init(self, partitions: int, source: int, tag: int = 0, *,
                   dest: int, like):
        """`like` supplies the receive shape/dtype (an array or
        jax.ShapeDtypeStruct); total element count and dtype must match
        the sender's buffer."""
        self._check_alive()
        from .part.framework import select_for_comm as part_select

        return part_select(self).precv_init(
            self, partitions, self.check_rank(source), tag,
            dest=self.check_rank(dest), like=like,
        )

    # -- p2p (delegated to the selected PML) ------------------------------

    @property
    def pml(self):
        if self._pml is None:
            from .pml.framework import select_for_comm as pml_select

            self._pml = pml_select(self)
        return self._pml

    def send(self, value, dest: int, tag: int = 0, *, source=None):
        """Send `value` to rank `dest`. The source rank is inferred from
        the value's device placement, or passed explicitly."""
        self._check_alive()
        return self.pml.send(
            self, value, self.check_rank(dest), tag, source=source
        )

    def recv(self, source: int = -1, tag: int = -1, *, dest: int):
        self._check_alive()
        return self.pml.recv(self, source, tag, dest=dest)

    def isend(self, value, dest: int, tag: int = 0, *, source=None):
        self._check_alive()
        return self.pml.isend(
            self, value, self.check_rank(dest), tag, source=source
        )

    def irecv(self, source: int = -1, tag: int = -1, *, dest: int):
        self._check_alive()
        return self.pml.irecv(self, source, tag, dest=dest)

    def probe(self, source: int = -1, tag: int = -1, *, dest: int):
        self._check_alive()
        return self.pml.probe(self, source, tag, dest=dest, blocking=True)

    def iprobe(self, source: int = -1, tag: int = -1, *, dest: int):
        self._check_alive()
        return self.pml.probe(self, source, tag, dest=dest, blocking=False)

    def improbe(self, source: int = -1, tag: int = -1, *, dest: int):
        """MPI_Improbe: match-and-remove; returns a Message or None."""
        self._check_alive()
        pml = self.pml
        base = pml
        while not hasattr(base, "improbe") and hasattr(base, "host"):
            base = base.host
        if not hasattr(base, "improbe"):
            raise CommError(
                f"selected pml {pml.NAME} has no matched-probe support"
            )
        return base.improbe(self, source, tag, dest=dest)

    def rank(self, rank: int) -> "RankEndpoint":
        """A rank's-eye view with the MPI-faithful call signatures."""
        return RankEndpoint(self, self.check_rank(rank))

    # -- construction of derived communicators ----------------------------

    def dup(self, info: Optional[Info] = None) -> "Communicator":
        self._check_alive()
        new = Communicator(
            self.group,
            self._world_procs,
            name=f"{self.name}.dup",
            info=(info or self.info.dup()),
            parent_cid=self.cid,
        )
        self.copy_attrs_to(new)
        return new

    def create(self, group: Group) -> "Communicator":
        """MPI_Comm_create: new comm over a subgroup."""
        self._check_alive()
        for wr in group.world_ranks:
            if wr not in self.group:
                raise ArgumentError(
                    f"group rank {wr} not in parent {self.name}"
                )
        return Communicator(
            group,
            self._world_procs,
            name=f"{self.name}.sub",
            parent_cid=self.cid,
        )

    def split(self, colors: Sequence[int], keys: Optional[Sequence[int]] = None
              ) -> dict[int, "Communicator"]:
        """MPI_Comm_split, driver form: the controller supplies every
        rank's (color, key); returns {color: communicator}. Color < 0
        (MPI_UNDEFINED) ranks are excluded."""
        self._check_alive()
        if len(colors) != self.size:
            raise ArgumentError("need one color per rank")
        keys = list(keys) if keys is not None else list(range(self.size))
        if len(keys) != self.size:
            raise ArgumentError(
                f"need one key per rank: got {len(keys)} for size {self.size}"
            )
        buckets: dict[int, list[tuple[int, int]]] = {}
        for r, (c, k) in enumerate(zip(colors, keys)):
            if c < 0:
                continue
            buckets.setdefault(c, []).append((k, r))
        out = {}
        for color, members in sorted(buckets.items()):
            members.sort()
            g = Group(self.group.world_rank(r) for _, r in members)
            out[color] = Communicator(
                g,
                self._world_procs,
                name=f"{self.name}.split{color}",
                parent_cid=self.cid,
            )
        return out

    def free(self) -> None:
        self.free_attrs()
        self._plan_cache.clear()
        self._lane.clear()
        if self._pml is not None and hasattr(self._pml, "comm_freed"):
            self._pml.comm_freed(self)
        self._freed = True

    # -- misc -------------------------------------------------------------

    def set_name(self, name: str) -> None:
        self.name = name

    def __repr__(self) -> str:
        return (
            f"<Communicator {self.name} cid={self.cid} size={self.size}>"
        )


class _PersistentP2P:
    """Shared machinery: a persistent request owning an inner active
    request per start() (reference: ob1 persistent requests re-enter
    the start path, pml_ob1_start.c)."""

    def _poll(self) -> bool:
        if self.done:
            return True
        inner = self._inner
        if inner is not None and inner._poll():
            self._complete(inner._result, inner.status)
        return self.done

    def wait(self, timeout: float | None = None):
        from .core.request import RequestState

        inner = self._inner
        if inner is None or self.state == RequestState.INACTIVE:
            # base wait: raises on inactive persistent requests
            return _Request.wait(self, timeout)
        if not self.done:
            inner.wait(timeout)
            self._poll()
        if self.status.error is not None:
            raise self.status.error
        return self.status


from .core.request import Request as _Request  # noqa: E402


class PersistentSend(_PersistentP2P, _Request):
    def __init__(self, comm, value, dest, tag, source) -> None:
        super().__init__(persistent=True)
        self._comm = comm
        self.buffer = value
        self._dest = dest
        self._tag = tag
        self._source = source
        self._inner = None

    def bind(self, value) -> None:
        """Rebind the send buffer for the next start()."""
        self.buffer = value

    def _start(self) -> None:
        self._inner = self._comm.isend(
            self.buffer, self._dest, self._tag, source=self._source
        )


class PersistentRecv(_PersistentP2P, _Request):
    def __init__(self, comm, source, tag, dest) -> None:
        super().__init__(persistent=True)
        self._comm = comm
        self._source = source
        self._tag = tag
        self._dest = dest
        self._inner = None

    def _start(self) -> None:
        self._inner = self._comm.irecv(
            self._source, self._tag, dest=self._dest
        )


def start_all(requests) -> list:
    """MPI_Startall. Cross-process starts open the fabric's dispatch-
    coalescing window: every small shm post issued by the batch rides
    ONE native descriptor sweep + one doorbell per destination instead
    of a wake per request."""
    if len(requests) > 1:
        from .core.errors import ComponentError
        from .pml.framework import PML

        try:
            eng = getattr(PML.component("ob1"), "_fabric", None)
        except ComponentError:
            eng = None
        if eng is not None and eng.shm is not None:
            with eng.batch_dispatch():
                return [r.start() for r in requests]
    return [r.start() for r in requests]


class RankEndpoint:
    """One rank's view of a communicator: MPI-faithful p2p signatures
    (send(value, dest, tag) / recv(source, tag)) with the endpoint's rank
    as the implicit source/destination — the driver-model equivalent of
    "my rank" inside an SPMD process."""

    def __init__(self, comm: Communicator, rank: int) -> None:
        self.comm = comm
        self.rank = rank

    @property
    def device(self):
        return self.comm.devices[self.rank]

    def send(self, value, dest: int, tag: int = 0):
        return self.comm.send(value, dest, tag, source=self.rank)

    def isend(self, value, dest: int, tag: int = 0):
        return self.comm.isend(value, dest, tag, source=self.rank)

    def recv(self, source: int = -1, tag: int = -1):
        return self.comm.recv(source, tag, dest=self.rank)

    def irecv(self, source: int = -1, tag: int = -1):
        return self.comm.irecv(source, tag, dest=self.rank)

    def probe(self, source: int = -1, tag: int = -1):
        return self.comm.probe(source, tag, dest=self.rank)

    def iprobe(self, source: int = -1, tag: int = -1):
        return self.comm.iprobe(source, tag, dest=self.rank)

    def sendrecv(self, value, dest: int, source: int = -1, tag: int = 0):
        req = self.isend(value, dest, tag)
        out = self.recv(source, tag)
        req.wait()
        return out

    def put(self, value):
        """Place a host value on this rank's device."""
        import jax

        return jax.device_put(value, self.device)

    def __repr__(self) -> str:
        return f"<RankEndpoint {self.comm.name}:{self.rank}>"
