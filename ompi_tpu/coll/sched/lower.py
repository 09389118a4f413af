"""Lowering: compile a Schedule IR program to a fused jitted callable.

Three lowering modes, selected by ``Schedule.meta["lowering"]`` (or
the explicit ``lower(sched, strategy=...)`` override):

``interpret``
    A genuine IR executor: the step program is compiled round-by-round
    into a traced jax program — each round becomes one
    ``lax.ppermute`` (the ICI DMA) driven by per-round index tables
    (who sends which chunk where, who reduces/copies what), the
    reduction is the Op's combine on the VPU/MXU. The tables are
    python-side constants, so the whole schedule unrolls into the XLA
    graph exactly like the hand-written spmd algorithms — and XLA
    fuses/overlaps the rounds of independent chunk chains (segmented
    ring) for free.

``primitive``
    Tier-mapped: the schedule names an existing lowered primitive —
    the XLA-native collective, the Pallas
    ``pltpu.make_async_remote_copy`` device kernels (coll/pallas_ring),
    the quantized-wire codec (coll/quant), or the host tiers — and the
    IR is the *documentation + validation contract* for it.

``pallas``
    Compiled: the step program itself is lowered into one fused
    ``make_async_remote_copy`` kernel (sched/pallas_lower.py) — every
    round a remote DMA overlapped with the combine, double-buffered
    chunk slots sized from the IR's chunk plan. The ``device_pallas``
    lattice tier.

The lowered callable has the ALLREDUCE_ALGOS signature
``fn(x, axis_name, op)`` and composes with coll/framework's
``compile_plan`` (jit(shard_map(...))) like every other tier.

``validate`` is the validity checker: it proves a lowered schedule
bit-identical to the ``ring`` reference tier by running both over
integer-valued payloads (exactly representable at every combine, so
reduction-order differences cannot produce ULP noise) and comparing
raw result bytes. Quantized-wire schedules are validated on
block-constant payloads — the one family the int8 block codec
round-trips exactly — which checks the wiring end-to-end without
conflating it with the codec's documented precision loss.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from ...core.errors import ArgumentError
from .ir import ANNOTATIONS, Schedule

#: lowered-callable memo, keyed by (schedule digest, strategy): the
#: digest covers meta["lowering"], but the explicit strategy override
#: must not collide with the meta-selected lowering of the same
#: program (table construction is pure python; jit caching happens
#: downstream in compile_plan).
_LOWERED: dict[tuple, Callable] = {}

#: The three lowering strategies, in maturity order.
STRATEGIES = ("interpret", "primitive", "pallas")


def _round_tables(sched: Schedule) -> list[tuple]:
    """Per-round constant tables: (perm, send_chunk, recv_mode,
    recv_chunk) with recv_mode 0=idle, 1=reduce, 2=copy."""
    n = sched.nranks
    by_round: dict[int, list] = {}
    for s in sched.steps:
        if s.kind in ANNOTATIONS:
            continue
        by_round.setdefault(s.round, []).append(s)
    tables = []
    for rnd in sorted(by_round):
        perm: list[tuple[int, int]] = []
        send_chunk = [0] * n
        recv_mode = [0] * n
        recv_chunk = [0] * n
        for s in by_round[rnd]:
            if s.kind == "send":
                perm.append((s.rank, s.peer))
                send_chunk[s.rank] = s.chunk
            else:
                recv_mode[s.rank] = 1 if s.kind == "reduce" else 2
                recv_chunk[s.rank] = s.chunk
        tables.append((tuple(perm), np.asarray(send_chunk, np.int32),
                       np.asarray(recv_mode, np.int32),
                       np.asarray(recv_chunk, np.int32)))
    return tables


def _lower_interpret(sched: Schedule) -> Callable:
    """Compile the step program into a traced round loop."""
    tables = _round_tables(sched)
    nranks, nchunks = sched.nranks, sched.nchunks

    def run(x, axis_name: str, op):
        import jax.numpy as jnp
        from jax import lax

        from .. import spmd

        n = lax.axis_size(axis_name)
        if n != nranks:
            raise ArgumentError(
                f"schedule {sched.name!r} compiled for {nranks} ranks, "
                f"axis {axis_name!r} has {n}"
            )
        rank = lax.axis_index(axis_name)
        flat, total = spmd._flatten_pad(x, nchunks)
        state = flat.reshape(nchunks, -1)
        for perm, send_chunk, recv_mode, recv_chunk in tables:
            sidx = jnp.take(jnp.asarray(send_chunk), rank)
            val = jnp.take(state, sidx, axis=0)
            recvd = lax.ppermute(val, axis_name, list(perm))
            mode = jnp.take(jnp.asarray(recv_mode), rank)
            ridx = jnp.take(jnp.asarray(recv_chunk), rank)
            cur = jnp.take(state, ridx, axis=0)
            new = jnp.where(mode == 1, op.combine(recvd, cur),
                            jnp.where(mode == 2, recvd, cur))
            state = state.at[ridx].set(new)
        return state.reshape(-1)[:total].reshape(x.shape)

    return run


def _lower_primitive(sched: Schedule) -> Callable:
    """Map the schedule to an already-lowered tier entry point."""
    prim = sched.meta.get("primitive", "")
    if prim == "native":
        from .. import spmd

        return spmd.allreduce_native
    if prim == "gather_reduce":
        from .. import spmd

        return spmd._allreduce_gather_reduce
    if prim == "quant_ring":
        from .. import quant

        wire = sched.meta.get("wire")
        block = sched.meta.get("block")

        def _quant_ring(x, axis_name, op):
            # the schedule pins the wire/block it was generated (and
            # validated/tuned) for; cvars only fill the gaps
            return quant.allreduce_quant_ring(x, axis_name, op,
                                              wire=wire, block=block)

        return _quant_ring
    if prim == "quant_pallas":
        from .. import quant

        return quant.allreduce_block_quant
    if prim == "pallas_ring":
        from .. import pallas_ring

        return pallas_ring.allreduce_block
    raise ArgumentError(
        f"schedule {sched.name!r} names unknown primitive {prim!r}"
    )


def lower(sched: Schedule, strategy: Optional[str] = None) -> Callable:
    """Schedule -> callable with the registered-algo signature
    (ALLREDUCE_ALGOS for allreduce programs, REDUCE_SCATTER_ALGOS for
    reduce-scatter ones). ``strategy`` overrides the schedule's own
    ``meta["lowering"]`` directive. Memoized on (digest, strategy);
    emits one ``sched.compile`` trace instant per actual lowering and
    counts every selection in the per-strategy SPC counters (the
    ``sched_lower_strategy_total`` telemetry series)."""
    if strategy is None:
        strategy = sched.meta.get("lowering", "interpret")
        if strategy not in STRATEGIES:
            strategy = "interpret"
    elif strategy not in STRATEGIES:
        raise ArgumentError(
            f"unknown lowering strategy {strategy!r}; known: "
            f"{list(STRATEGIES)}")
    from ...core.counters import SPC

    SPC.record(f"sched_lower_strategy_{strategy}")
    key = (sched.digest(), strategy)
    fn = _LOWERED.get(key)
    if fn is not None:
        return fn
    if strategy == "primitive":
        fn = _lower_primitive(sched)
    elif strategy == "pallas":
        from . import pallas_lower

        fn = pallas_lower.compile_schedule(sched)
    else:
        fn = _lower_interpret(sched)
    _LOWERED[key] = fn
    from ...trace import span as tspan

    tspan.instant("sched.compile", cat="sched", schedule=sched.name,
                  nranks=sched.nranks, rounds=sched.rounds(),
                  lowering=strategy, digest=key[0])
    return fn


def clear_lowered() -> None:
    """Forget memoized lowerings (tests / re-init)."""
    _LOWERED.clear()
    from . import pallas_lower

    pallas_lower.clear_compiled()


# ---------------------------------------------------------------------------
# validity checker
# ---------------------------------------------------------------------------

def _payload(nranks: int, nelems: int, dtype, *,
             block_constant: bool) -> np.ndarray:
    """Power-of-two payload ({1, 2}), exactly representable in every
    supported dtype under every reduction order AND every op: sums over
    8 ranks top out at 16, products at 256 = 2^8 — both exact in bf16,
    f16, f32 and every int type, so a schedule that combines in a
    different order than the ring reference still lands on the same
    bits. ``block_constant`` makes each rank's buffer one constant —
    the family the int8 block-scaled codec round-trips exactly
    (scale=v/127, q=±127)."""
    rng = np.random.default_rng(0xC011)
    if block_constant:
        per_rank = 2 ** rng.integers(0, 2, size=(nranks, 1))
        data = np.broadcast_to(per_rank, (nranks, nelems)).copy()
    else:
        data = 2 ** rng.integers(0, 2, size=(nranks, nelems))
    return data.astype(dtype)


def validate(comm, fn: Callable, op, dtype, *, nelems: int = 192,
             label: str = "candidate",
             block_constant: bool = False,
             check_vma: bool = True) -> bool:
    """Bit-identical check of ``fn`` against the ring reference tier on
    ``comm``. True when every result byte matches."""
    import jax

    from ..framework import compile_plan
    from .. import spmd
    from ...ops import lookup as op_lookup

    op = op_lookup(op)
    data = _payload(comm.size, nelems, dtype,
                    block_constant=block_constant)
    x = comm.put_rank_major(data)
    ref_key = ("sched.validate.ref", op.cache_key, str(np.dtype(dtype)),
               x.shape)
    ref_plan = compile_plan(
        comm, ref_key, lambda b: spmd.allreduce_ring(b, "ranks", op))
    got_key = ("sched.validate", label, op.cache_key,
               str(np.dtype(dtype)), x.shape)
    got_plan = compile_plan(comm, got_key,
                            lambda b: fn(b, "ranks", op),
                            check_vma=check_vma)
    ref = np.asarray(jax.device_get(ref_plan(x)))
    got = np.asarray(jax.device_get(got_plan(x)))
    return ref.dtype == got.dtype and ref.shape == got.shape \
        and ref.tobytes() == got.tobytes()


def _validate_bounded(comm, fn: Callable, op, dtype, *, wire, block,
                      nelems: int, label: str) -> bool:
    """Lossy-tier validity: result within coll/quant's analytic
    worst-case error bound of the ring reference, elementwise."""
    import jax

    from ..framework import compile_plan
    from .. import quant, spmd
    from ...ops import lookup as op_lookup

    op = op_lookup(op)
    data = _payload(comm.size, nelems, dtype, block_constant=False)
    x = comm.put_rank_major(data)
    ref_plan = compile_plan(
        comm, ("sched.validate.ref", op.cache_key, str(np.dtype(dtype)),
               x.shape),
        lambda b: spmd.allreduce_ring(b, "ranks", op))
    got_plan = compile_plan(
        comm, ("sched.validate", label, op.cache_key,
               str(np.dtype(dtype)), x.shape),
        lambda b: fn(b, "ranks", op))
    ref = np.asarray(jax.device_get(ref_plan(x)), np.float64)
    got = np.asarray(jax.device_get(got_plan(x)), np.float64)
    bound = np.asarray(jax.device_get(
        quant.analytic_error_bound(data, wire=wire, block=block)),
        np.float64)
    return ref.shape == got.shape and bool(
        np.all(np.abs(ref - got) <= bound[None, :] + 1e-12))


def _validate_reduce_scatter(comm, fn: Callable, op, dtype, *,
                             nelems: int, label: str,
                             check_vma: bool = True) -> bool:
    """Bit-identical check of a reduce-scatter callable (input: the
    local (n, chunk) contribution view; output: the own reduced block)
    against the ring reference ``spmd.reduce_scatter_ring``."""
    import jax

    from ..framework import compile_plan
    from .. import spmd
    from ...ops import lookup as op_lookup

    op = op_lookup(op)
    n = comm.size
    data = _payload(n, n * nelems, dtype,
                    block_constant=False).reshape(n, n, nelems)
    x = comm.put_rank_major(data)
    # compile_plan hands each rank its (n, nelems) block.
    ref_plan = compile_plan(
        comm, ("sched.validate.rs_ref", op.cache_key,
               str(np.dtype(dtype)), x.shape),
        lambda b: spmd.reduce_scatter_ring(b, "ranks", op))
    got_plan = compile_plan(
        comm, ("sched.validate", label, op.cache_key,
               str(np.dtype(dtype)), x.shape),
        lambda b: fn(b, "ranks", op), check_vma=check_vma)
    ref = np.asarray(jax.device_get(ref_plan(x)))
    got = np.asarray(jax.device_get(got_plan(x)))
    return ref.dtype == got.dtype and ref.shape == got.shape \
        and ref.tobytes() == got.tobytes()


#: Primitives whose lowered callable contains a Mosaic pallas_call.
_MOSAIC_PRIMITIVES = ("pallas_ring", "quant_pallas")


def _needs_vma_exemption(sched: Schedule) -> bool:
    """True only when the lowered callable actually invokes a Mosaic
    ``pallas_call``: its outputs mix varying and replicated values in a
    way jax's vma tracking rejects, so those plans compile with
    ``check_vma=False`` (jax's documented workaround — see
    framework.compile_plan). Scoped to the known Mosaic primitives and
    the pallas lowering strategy, not any name containing "pallas", so
    every other schedule keeps full vma checking."""
    return sched.meta.get("primitive", "") in _MOSAIC_PRIMITIVES \
        or sched.meta.get("lowering") == "pallas"


def validate_schedule(comm, sched: Schedule, op, dtype, *,
                      nelems: int = 192) -> bool:
    """Validity check for a lowered Schedule.

    Exact tiers (everything but the int8 quantized wire) must be
    BIT-IDENTICAL to the ring reference — the power-of-two payload
    family makes every reduction order exact, so any deviation is a
    compiler bug, not float noise. The bf16 quantized wire is held to
    the same bar: its hop path is pure casts and adds (no division),
    exact on small integers. The int8 wire is lossy by design — its
    scale arithmetic (max/127) is not even stable across XLA fusion
    choices — so it validates against coll/quant's analytic worst-case
    error bound instead, the same contract quant's own tests enforce.

    Pallas-lowered and Mosaic-primitive schedules are held to the same
    bit-identity bar on every dtype (bf16 included); only the vma
    *plan check* is exempted for them (``_needs_vma_exemption``) — the
    byte comparison itself never is. On the CPU backend the pallas
    kernels run under Mosaic's TPU interpret mode."""
    quantized = sched.meta.get("primitive", "").startswith("quant") \
        or any(s.kind in ANNOTATIONS for s in sched.steps)
    if quantized and sched.meta.get("wire", "int8") != "bf16":
        return _validate_bounded(
            comm, lower(sched), op, dtype,
            wire=sched.meta.get("wire", "int8"),
            block=sched.meta.get("block"), nelems=nelems,
            label=f"sched:{sched.digest()}")
    check_vma = not _needs_vma_exemption(sched)
    if sched.op == "reduce_scatter":
        return _validate_reduce_scatter(
            comm, lower(sched), op, dtype, nelems=nelems,
            label=f"sched:{sched.digest()}", check_vma=check_vma)
    return validate(
        comm, lower(sched), op, dtype, nelems=nelems,
        label=f"sched:{sched.digest()}",
        check_vma=check_vma,
    )


__all__ = ["STRATEGIES", "clear_lowered", "lower", "validate",
           "validate_schedule"]
