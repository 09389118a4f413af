"""The coll framework: per-communicator collective selection + plans.

TPU-native equivalent of ompi/mca/coll's framework base (reference:
coll.h:480 `collm_comm_query`, coll.h:629-702 per-comm function table,
coll_base_comm_select.c:110-152 highest-priority-per-function merge).

Driver-mode collectives operate on "rank-major" buffers: jax.Arrays with
leading axis == comm.size, sharded one block per rank-device. Each
component lowers an operation to a *plan* — a jitted shard_map program
over the comm's 1-D mesh — cached per (operation, algorithm, shape,
dtype) on the communicator. Plan reuse is the latency strategy: the
reference re-runs its decision + schedule machinery per call (ob1 fastbox
/ sendi tricks, SURVEY §7); here the steady-state call is a single cached
XLA executable launch.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

from ..core import component as mca
from ..core import config
from ..core.errors import ArgumentError, CommError
from ..core.logging import get_logger
from ..core.request import Request, Status
from ..ops import Op, lookup as op_lookup

logger = get_logger("coll")

# Collective operations a component may provide (reference enumerates 22
# in coll_base_functions.h:45-66; the nonblocking/persistent variants are
# derived from these at the communicator layer).
OPERATIONS = (
    "allreduce",
    "bcast",
    "reduce",
    "allgather",
    "reduce_scatter_block",
    "alltoall",
    "gather",
    "scatter",
    "scan",
    "exscan",
    "barrier",
    # vector (per-rank counts) variants, reference
    # coll_base_functions.h:75-76 (alltoallv/w) and the *v family
    "allgatherv",
    "gatherv",
    "scatterv",
    "alltoallv",
    "alltoallw",
    "reduce_scatter",
    # neighborhood collectives over the comm topology, reference
    # coll_base_functions.h:62-66
    "neighbor_allgather",
    "neighbor_alltoall",
)

COLL = mca.framework("coll", "collective operations")


class CollComponent(mca.Component):
    """Base class: a coll component provides a subset of OPERATIONS as
    methods fn(comm, *args)."""

    def provided(self) -> list[str]:
        return [op for op in OPERATIONS if hasattr(self, op)]

    def persistent_program(self, comm, opname: str, x, args):
        """Pre-bound dispatch for persistent collectives: return
        ``prog(buffer) -> pending`` with every per-call decision
        (validation, algorithm choice, cache-key build, plan lookup)
        already resolved against (comm, args) — or None when the
        operation has no clean single-plan form (e.g. root-sliced
        reduce, ragged variants). PersistentColl binds the program on
        first start(); every subsequent start() is then one plan
        launch, skipping the vtable/_coll_call path entirely (the
        pcollreq promise: MPI_Start must be cheaper than a fresh
        call)."""
        return None


def select_for_comm(comm) -> dict[str, tuple[Any, Callable]]:
    """Merge per-operation tables: for each op, the highest-priority
    available component that implements it (the reference's merge loop,
    coll_base_comm_select.c:110-152)."""
    ensure_components()
    table: dict[str, tuple[Any, Callable]] = {}
    for comp in COLL.select_all(comm=comm):
        for opname in comp.provided():
            if opname not in table:
                table[opname] = (comp, getattr(comp, opname))
    if comm.size > 0 and len(table) < len(OPERATIONS):
        missing = [o for o in OPERATIONS if o not in table]
        logger.info("comm %s missing coll ops: %s", comm.name, missing)
    # faultline interposes at selection (sanitizer pattern): when a
    # fault plan is armed, every vtable entry consults it on dispatch.
    from ..ft import inject

    return inject.maybe_wrap_coll(table)


# ---------------------------------------------------------------------------
# Plan compilation
# ---------------------------------------------------------------------------

def compile_plan(
    comm,
    key: tuple,
    per_rank_fn: Callable,
    *,
    donate: bool = False,
    check_vma: bool = True,
) -> Callable:
    """Build (or fetch) the jitted shard_map program applying
    ``per_rank_fn(block)`` on every rank's leading-axis block."""
    cache = comm._plan_cache
    plan = cache.get(key)
    if plan is not None:
        return plan

    import jax
    from jax.sharding import PartitionSpec as P

    mesh = comm.mesh

    def wrapped(block):
        squeezed = jax.tree.map(lambda b: b[0], block)
        res = per_rank_fn(squeezed)
        return jax.tree.map(lambda r: r[None], res)

    # check_vma=False is for pallas plans only: pallas_call outputs
    # mix varying and replicated values that trip jax's vma tracking
    # (jax's documented workaround); other components keep the check.
    fn = jax.shard_map(
        wrapped, mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
        check_vma=check_vma,
    )
    plan = jax.jit(fn, donate_argnums=(0,) if donate else ())
    cache[key] = plan
    from ..core.counters import SPC

    SPC.record("coll_plans_compiled")
    return plan


def rank_major_check(comm, x, min_ndim: int = 1):
    import jax.numpy as jnp

    arr = jnp.asarray(x)
    if arr.ndim < min_ndim or arr.shape[0] != comm.size:
        raise ArgumentError(
            f"expected rank-major buffer with leading dim {comm.size}, "
            f"got shape {arr.shape}"
        )
    return arr


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------

class DeviceRequest(Request):
    """A nonblocking collective: the device work is already enqueued by
    JAX async dispatch; completion == result arrays ready."""

    def __init__(self, result: Any) -> None:
        super().__init__()
        self._pending = result

    def _leaves(self):
        import jax

        return [
            leaf
            for leaf in jax.tree.leaves(self._pending)
            if hasattr(leaf, "is_ready")
        ]

    def _poll(self) -> bool:
        if self.done:
            return True
        if all(leaf.is_ready() for leaf in self._leaves()):
            self._complete(self._pending)
        return self.done

    def wait(self, timeout: float | None = None) -> Status:
        import jax

        from ..core import progress as _progress

        if not self.done:
            if timeout is None:
                jax.block_until_ready(self._pending)
                self._complete(self._pending)
            elif not _progress.ENGINE.progress_until(self._poll, timeout):
                raise TimeoutError("collective wait timed out")
        return self.status


class PersistentColl(Request):
    """Persistent collective (MPI_Allreduce_init / pcollreq extension):
    binds (comm, operation, args); each start() re-dispatches the cached
    plan against the bound buffer."""

    def __init__(self, comm, opname: str, args: tuple, x: Any) -> None:
        super().__init__(persistent=True)
        self._comm = comm
        self._opname = opname
        self._args = args
        self.buffer = x
        self._pending = None
        self._dispatch = None  # resolved once, on first start()
        # Interned at construction: start() is the latency-critical
        # call (persistent_start_us bench row) and must do no per-call
        # string building or allocation beyond the dispatch itself.
        self._spc_name = f"coll_persistent_{opname}_starts"

    def bind(self, x: Any) -> None:
        """Rebind the input buffer (same shape/dtype reuses the plan)."""
        self.buffer = x

    def _resolve(self) -> None:
        """First-start binding: ask the providing component for a
        pre-bound program; fall back to a direct (vtable-resolved once)
        component call for operations without a plan form. Either way,
        later starts never re-enter _coll_call — no vtable lookup, no
        SPC/memchecker/monitor interposition, no per-call decision."""
        comm = self._comm
        comm._check_alive()
        entry = comm._coll.get(self._opname)
        if entry is None:
            raise CommError(
                f"{comm.name}: no coll component provides {self._opname}"
            )
        component, fn = entry
        prog = component.persistent_program(
            comm, self._opname, self.buffer, self._args
        )
        if prog is not None:
            self._dispatch = prog
        elif self._opname == "barrier":  # the one bufferless operation
            self._dispatch = lambda _x, f=fn, c=comm: f(c)
        else:
            self._dispatch = (
                lambda x, f=fn, c=comm, a=self._args: f(c, x, *a)
            )
        # Monitoring/memchecker interposition happens once, at bind
        # time — started iterations are pure dispatch (the documented
        # pcollreq trade; DESIGN.md §11).
        from ..core import memchecker

        if memchecker.enabled() and self.buffer is not None:
            memchecker.check_defined(self.buffer,
                                     f"{self._opname} buffer")
        from ..monitoring import MONITOR

        if MONITOR.enabled and self.buffer is not None:
            import jax

            nbytes = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.buffer)
                if hasattr(leaf, "nbytes")
            )
            MONITOR.record_coll(comm.cid, self._opname, nbytes)

    def _start(self) -> None:
        if self._dispatch is None:
            self._resolve()
        from ..core.counters import SPC
        from ..trace import span as tspan

        SPC.record(self._spc_name)
        # pure-dispatch iterations stay off the span path (the pcollreq
        # latency promise); one instant record marks each start so the
        # timeline still shows persistent traffic.
        tspan.instant("coll.persistent_start", cat="coll",
                      op=self._opname, cid=self._comm.cid)
        self._pending = self._dispatch(self.buffer)

    def _poll(self) -> bool:
        if self.done:
            return True
        if self._pending is not None:
            import jax

            leaves = [
                l for l in jax.tree.leaves(self._pending)
                if hasattr(l, "is_ready")
            ]
            if all(l.is_ready() for l in leaves):
                self._complete(self._pending)
        return self.done

    def wait(self, timeout: float | None = None) -> Status:
        import jax

        from ..core import progress as _progress
        from ..core.errors import RequestError
        from ..core.request import RequestState

        if self.state == RequestState.INACTIVE:
            raise RequestError("wait on persistent collective before start()")
        if not self.done and self._pending is not None:
            if timeout is None:
                jax.block_until_ready(self._pending)
                self._complete(self._pending)
            elif not _progress.ENGINE.progress_until(self._poll, timeout):
                raise TimeoutError("persistent collective wait timed out")
        return self.status


def register_components() -> None:
    """Import all in-tree coll components so they self-register."""
    from . import (  # noqa: F401
        basic,
        demo,
        hier,
        pallas_ring,
        quant,
        selfcoll,
        smcoll,
        sync,
        tuned,
        xla,
    )


_registered = False


def ensure_components() -> None:
    global _registered
    if not _registered:
        register_components()
        _registered = True
