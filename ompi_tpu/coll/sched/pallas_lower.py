"""Pallas lowering backend: Schedule IR -> one fused TPU kernel.

Where ``lower.py``'s interpret mode turns each IR round into a
``lax.ppermute``, this backend compiles the whole step program into a
single ``pltpu.make_async_remote_copy`` kernel: every round is one
remote DMA overlapped with the combine on the VPU, flowing through the
two-slot comm-buffer credit discipline proven in
``coll/pallas_ring.py``'s hand-written ring kernels — but generated
from the IR, so topology ring orders, segment counts and future step
programs ride the same codegen.

Supported programs — the "dense chained round-uniform" contract:

- **dense**: every rank sends exactly once and receives exactly once
  in every round (ring, segmented ring, the reduce-scatter phase;
  *not* hierarchical, whose member ranks idle during the leader
  chain);
- **chained or fresh**: for each round r >= 1 either every rank sends
  the chunk it received in round r-1 (the value is already in the comm
  buffer — the ring chain), or every rank sends a chunk it has never
  received (a segment boundary: re-stage from the input). Mixed rounds
  are rejected;
- **round-uniform**: the receive kind (reduce/copy) and the
  is-last-receive-of-chunk property must not vary across ranks within
  a round, so they unroll to Python constants in the kernel.

The kernel is rank-generic: the per-round peer/chunk assignments are
passed as four (rounds, nranks) int32 tables in SMEM and indexed by
``lax.axis_index`` at trace time, so one compiled kernel serves every
rank exactly like the hand-written ones.

Slot math (the double-buffer invariant): round r reads comm_buf[r%2]
and lands the incoming chunk in comm_buf[(r+1)%2]. The slot a round
drains is refilled two rounds later, and that refill is gated by the
drain credit (cap_sem) signalled to the *round r+2 sender* — which the
tables name explicitly, where the hand kernels could hardcode "left".
Global slot parity means segment boundaries need no extra barrier: the
re-staged slot's previous arrival was drained locally one round
earlier, and the next remote write into it is still credit-gated.

Layout and entry: the state is ``(nchunks, rows, 128)`` tiles (the
pallas_ring layout rule), run one kernel call per VMEM-sized row
segment, and every call first meets all peers on its barrier
semaphore — the tables name different senders and receivers per round.

Validation: ``lower.validate_schedule`` runs these kernels under
Mosaic's TPU interpret mode on CPU (the mode that emulates remote DMA
+ semaphore signals) and byte-compares against the ring reference —
tier-1 covers the codegen path without hardware; compiling them for
the chip is ``tests/test_chip_compile.py``'s job. ``simulate`` executes
the same table program on the host with the kernel's exact slot/store
semantics: the CPU-side canary and a reference for the tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import numpy as np

from ...core.errors import ArgumentError
from .ir import ANNOTATIONS, Schedule, Step, check as _check

#: Program op -> key of its barrier-semaphore id in
#: ``pallas_ring.COLLECTIVE_IDS`` (one id per kernel across the tree).
_COLLECTIVE_ID = {"allreduce": "sched_allreduce",
                  "reduce_scatter": "sched_reduce_scatter",
                  "allgather": "sched_allgather", "window": "sched_window"}

#: compiled-wrapper memo keyed by schedule digest (kernel analysis is
#: pure python; jit caching happens downstream in compile_plan).
_COMPILED: dict[str, Callable] = {}


@dataclass(frozen=True)
class _Program:
    """Kernel-ready constants extracted from a Schedule.

    The tables are (rounds, nranks) int32; ``mode``/``last``/``brk``
    are per-round Python constants (round-uniformity is what makes the
    unrolled kernel rank-generic)."""

    op: str
    nranks: int
    nchunks: int
    rounds: int
    mode: tuple       # 1=reduce, 2=copy
    last: tuple       # this round's value is the chunk's final value
    brk: tuple        # chain-break round: re-stage send chunk from x
    t_dst: np.ndarray    # [r, k] -> peer k sends to
    t_src: np.ndarray    # [r, k] -> peer that sends to k
    t_schunk: np.ndarray  # [r, k] -> chunk k sends
    t_rchunk: np.ndarray  # [r, k] -> chunk k receives into


def analyze(sched: Schedule) -> _Program:
    """Check the dense/chained/round-uniform contract and extract the
    kernel tables. Raises ArgumentError with the violated clause."""
    n, rounds = sched.nranks, sched.rounds()
    if sched.op not in _COLLECTIVE_ID:
        raise ArgumentError(
            f"pallas lowering supports ops {sorted(_COLLECTIVE_ID)}, "
            f"schedule {sched.name!r} is op={sched.op!r}")
    if any(s.kind in ANNOTATIONS for s in sched.steps):
        raise ArgumentError(
            f"schedule {sched.name!r} carries quant/dequant annotations"
            f" — quantized wires keep the primitive lowering")
    if rounds < 1:
        raise ArgumentError(f"schedule {sched.name!r} has no rounds")
    sends: list[dict] = [{} for _ in range(rounds)]
    recvs: list[dict] = [{} for _ in range(rounds)]
    for s in sched.steps:
        (sends if s.kind == "send" else recvs)[s.round][s.rank] = s
    t_dst = np.zeros((rounds, n), np.int32)
    t_src = np.zeros((rounds, n), np.int32)
    t_schunk = np.zeros((rounds, n), np.int32)
    t_rchunk = np.zeros((rounds, n), np.int32)
    mode, last, brk = [], [], []
    seen: list[set] = [set() for _ in range(n)]  # chunks k received
    for r in range(rounds):
        if set(sends[r]) != set(range(n)) or set(recvs[r]) != set(range(n)):
            raise ArgumentError(
                f"schedule {sched.name!r} round {r} is not dense: every"
                f" rank must send once and receive once (hierarchical-"
                f"style idle ranks have no pallas lowering)")
        kinds = {recvs[r][k].kind for k in range(n)}
        if len(kinds) != 1:
            raise ArgumentError(
                f"schedule {sched.name!r} round {r} mixes receive kinds"
                f" {sorted(kinds)} across ranks")
        mode.append(1 if kinds.pop() == "reduce" else 2)
        for k in range(n):
            t_dst[r, k] = sends[r][k].peer
            t_src[r, k] = recvs[r][k].peer
            t_schunk[r, k] = sends[r][k].chunk
            t_rchunk[r, k] = recvs[r][k].chunk
        if r == 0:
            brk.append(True)  # round 0 always stages from the input
        else:
            chained = all(t_schunk[r, k] == t_rchunk[r - 1, k]
                          for k in range(n))
            fresh = all(t_schunk[r, k] not in seen[k] for k in range(n))
            if not chained and not fresh:
                raise ArgumentError(
                    f"schedule {sched.name!r} round {r} is neither "
                    f"chained (send what round {r - 1} received) nor a "
                    f"uniform re-stage of untouched chunks")
            brk.append(not chained)
        if mode[r] == 1:
            for k in range(n):
                if t_rchunk[r, k] in seen[k]:
                    raise ArgumentError(
                        f"schedule {sched.name!r} round {r}: rank {k} "
                        f"reduces into chunk {t_rchunk[r, k]} it already"
                        f" received — the kernel combines against the "
                        f"original input")
        for k in range(n):
            seen[k].add(int(t_rchunk[r, k]))
    for r in range(rounds):
        flags = {t_rchunk[r, k] not in
                 {int(t_rchunk[q, k]) for q in range(r + 1, rounds)}
                 for k in range(n)}
        if len(flags) != 1:
            raise ArgumentError(
                f"schedule {sched.name!r} round {r}: is-last-receive "
                f"varies across ranks")
        last.append(flags.pop())
    if sched.op == "allreduce":
        for k in range(n):
            if seen[k] != set(range(sched.nchunks)):
                raise ArgumentError(
                    f"schedule {sched.name!r}: rank {k} never receives "
                    f"chunks {sorted(set(range(sched.nchunks)) - seen[k])}"
                    f" — the output would be partial")
    if sched.op == "allgather":
        # A rank never receives its own chunk: it reaches the output at
        # the stage/re-stage rounds instead, so completeness is
        # received ∪ staged.
        for k in range(n):
            own = {int(t_schunk[r, k]) for r in range(rounds) if brk[r]}
            missing = set(range(sched.nchunks)) - (seen[k] | own)
            if missing:
                raise ArgumentError(
                    f"schedule {sched.name!r}: rank {k} neither receives"
                    f" nor stages chunks {sorted(missing)} — the output "
                    f"would be partial")
    if sched.op == "window":
        # Boundary-spanning programs (slipstream): completeness holds
        # member-wise. Segments are the brk-delimited round runs; each
        # must be mode-uniform — an allgather tail member is all-copy,
        # a reduce-scatter member all-reduce — and copy segments must
        # cover their chunk universe like a standalone allgather.
        seg_start = [r for r in range(rounds) if brk[r]]
        for si, s0 in enumerate(seg_start):
            s1 = (seg_start[si + 1] if si + 1 < len(seg_start)
                  else rounds)
            modes = {mode[r] for r in range(s0, s1)}
            if len(modes) != 1:
                raise ArgumentError(
                    f"schedule {sched.name!r}: window segment rounds "
                    f"{s0}..{s1 - 1} mix reduce and copy receive kinds")
            if modes == {2}:
                universe = {int(t_schunk[r, k])
                            for r in range(s0, s1) for k in range(n)}
                universe |= {int(t_rchunk[r, k])
                             for r in range(s0, s1) for k in range(n)}
                for k in range(n):
                    got = {int(t_rchunk[r, k]) for r in range(s0, s1)}
                    got |= {int(t_schunk[r, k]) for r in range(s0, s1)
                            if brk[r]}
                    missing = universe - got
                    if missing:
                        raise ArgumentError(
                            f"schedule {sched.name!r}: window copy "
                            f"segment at round {s0}: rank {k} neither "
                            f"receives nor stages chunks "
                            f"{sorted(missing)}")
    return _Program(op=sched.op, nranks=n, nchunks=sched.nchunks,
                    rounds=rounds, mode=tuple(mode), last=tuple(last),
                    brk=tuple(brk), t_dst=t_dst, t_src=t_src,
                    t_schunk=t_schunk, t_rchunk=t_rchunk)


def fuse_schedules(name: str, scheds) -> Schedule:
    """Chain same-op, same-rank-count dense schedules into ONE table
    program: member i's chunks occupy the id range ``[base_i, base_i +
    nchunks_i)`` and its rounds follow member i-1's. The first round of
    each member is a segment boundary — every rank re-stages a chunk it
    has never received, exactly ``segmented_ring``'s structure, which
    ``analyze`` already accepts as a chain-break re-stage — so a whole
    step program's worth of ring collectives compiles to a single
    fused kernel instead of one per bucket.

    Reduce-scatter members are rejected: the RS kernel's output
    contract is one chunk per rank, which a multi-segment table would
    silently violate.
    """
    scheds = list(scheds)
    if not scheds:
        raise ArgumentError("fuse_schedules needs at least one schedule")
    op, n = scheds[0].op, scheds[0].nranks
    if op == "reduce_scatter":
        raise ArgumentError(
            "fuse_schedules: reduce_scatter programs keep per-node "
            "kernels (single-chunk output contract)")
    for s in scheds:
        if s.op != op or s.nranks != n:
            raise ArgumentError(
                f"fuse_schedules: member {s.name!r} is "
                f"(op={s.op!r}, nranks={s.nranks}), group is "
                f"(op={op!r}, nranks={n})")
    steps: list[Step] = []
    chunk_base = round_base = 0
    for s in scheds:
        for st in s.steps:
            steps.append(Step(st.round + round_base, st.kind, st.rank,
                              st.peer, st.chunk + chunk_base))
        chunk_base += s.nchunks
        round_base += s.rounds()
    fused = Schedule(
        name=name, op=op, nranks=n, nchunks=chunk_base,
        steps=tuple(steps),
        meta={"tier": "device_pallas", "lowering": "pallas",
              "segments": len(scheds)},
    )
    _check(fused)
    analyze(fused)  # enforce the dense/chained/round-uniform contract
    return fused


def fuse_window(name: str, tail_scheds, next_scheds) -> Schedule:
    """Fuse a step-boundary window into ONE table program (slipstream):
    step N's merged broadcast tail — its dense round-uniform allgather
    members — chained with step N+1's first reduce-scatter group. Same
    chunk-base/round-base chaining as ``fuse_schedules``; each member
    start is a chain-break re-stage, which ``analyze`` already accepts.
    The fused op is ``"window"`` (collective_id 15): copy segments
    write like an allgather, reduce segments emit each rank's own
    reduced chunk at their segment-final round.

    The contract is strict — every tail member must be op="allgather",
    every next-step member op="reduce_scatter", all on one rank count —
    because a window that silently dropped a member would break the
    two-step bit-identity oracle. Callers treat ArgumentError as "keep
    per-node kernels for this boundary"."""
    tail = list(tail_scheds)
    nxt = list(next_scheds)
    if not tail or not nxt:
        raise ArgumentError(
            "fuse_window needs at least one tail member and one "
            "next-step member")
    n = tail[0].nranks
    for s in tail:
        if s.op != "allgather":
            raise ArgumentError(
                f"fuse_window: tail member {s.name!r} is op={s.op!r}, "
                f"the broadcast tail fuses allgather members only")
    for s in nxt:
        if s.op != "reduce_scatter":
            raise ArgumentError(
                f"fuse_window: next-step member {s.name!r} is "
                f"op={s.op!r}, the boundary fuses into the next step's "
                f"reduce-scatter group only")
    for s in tail + nxt:
        if s.nranks != n:
            raise ArgumentError(
                f"fuse_window: member {s.name!r} has nranks="
                f"{s.nranks}, window is nranks={n}")
    steps: list[Step] = []
    chunk_base = round_base = 0
    for s in tail + nxt:
        for st in s.steps:
            steps.append(Step(st.round + round_base, st.kind, st.rank,
                              st.peer, st.chunk + chunk_base))
        chunk_base += s.nchunks
        round_base += s.rounds()
    fused = Schedule(
        name=name, op="window", nranks=n, nchunks=chunk_base,
        steps=tuple(steps),
        meta={"tier": "device_pallas", "lowering": "pallas",
              "segments": len(tail) + len(nxt),
              "boundary": len(tail)},
    )
    _check(fused)
    analyze(fused)  # enforce the dense/chained/round-uniform contract
    return fused


def compile_schedule(sched: Schedule) -> Callable:
    """Schedule -> callable. Allreduce programs get the
    ALLREDUCE_ALGOS signature ``fn(x, axis_name, op)``; reduce-scatter
    programs the REDUCE_SCATTER_ALGOS one (``x`` is the local (n,
    chunk) contribution view, result the own reduced block)."""
    key = sched.digest()
    fn = _COMPILED.get(key)
    if fn is None:
        prog = analyze(sched)
        fn = _COMPILED[key] = _make_wrapper(prog, sched.name)
    return fn


def clear_compiled() -> None:
    """Forget compiled wrappers (tests / re-init)."""
    _COMPILED.clear()


def simulate(sched, data, op):
    """Host-side oracle: execute the extracted table program with the
    exact slot/store semantics of ``_kernel``, one rank at a time.

    ``data`` is the stacked per-rank input, shape (nranks, nchunks,
    chunk). Returns the stacked per-rank outputs: (nranks, nchunks,
    chunk) for allreduce, (nranks, chunk) for reduce_scatter.

    The simulator and the kernel share the table program, the two-slot
    comm-buffer discipline, the conditional combine store and the
    out-write gating, so a schedule whose simulation matches the
    mathematical reference exercises every decision ``analyze`` baked
    into the kernel. Uses jnp so bfloat16 rounds exactly as on device.
    """
    import jax.numpy as jnp

    from ...ops import lookup as op_lookup

    op = op_lookup(op)
    prog = analyze(sched) if isinstance(sched, Schedule) else sched
    n, rounds = prog.nranks, prog.rounds
    data = jnp.asarray(data)
    if data.ndim != 3 or data.shape[0] != n or data.shape[1] != prog.nchunks:
        raise ArgumentError(
            f"simulate expects data shaped ({n}, {prog.nchunks}, chunk),"
            f" got {data.shape}")
    comm: list[list] = [[None, None] for _ in range(n)]
    if prog.op == "reduce_scatter":
        out: list = [None] * n
    else:
        out = [[None] * prog.nchunks for _ in range(n)]
    for k in range(n):
        comm[k][0] = data[k, int(prog.t_schunk[0, k])]
    for r in range(rounds):
        slot, nslot = r % 2, (r + 1) % 2
        if r >= 1 and prog.brk[r]:
            for k in range(n):
                comm[k][slot] = data[k, int(prog.t_schunk[r, k])]
        if prog.brk[r] and (prog.op == "allgather"
                            or (prog.op == "window"
                                and prog.mode[r] == 2)):
            # Own chunk never travels: it reaches the output at the
            # stage round, mirroring the kernel's out-write. In a
            # window program this fires only for copy (allgather tail)
            # segments — a reduce-scatter member's stage round feeds
            # the wire, never the output.
            for k in range(n):
                c = int(prog.t_schunk[r, k])
                out[k][c] = data[k, c]
        # All round-r sends read their source slot before any round-r
        # arrival lands (the credit discipline guarantees this order on
        # device; here a snapshot does).
        arrivals = [comm[int(prog.t_src[r, k])][slot] for k in range(n)]
        for k in range(n):
            comm[k][nslot] = arrivals[k]
            if prog.mode[r] == 1:
                val = op.combine(comm[k][nslot],
                                 data[k, int(prog.t_rchunk[r, k])])
                if r + 1 < rounds and not prog.brk[r + 1]:
                    comm[k][nslot] = val
            else:
                val = comm[k][nslot]
            if prog.op == "reduce_scatter":
                if r == rounds - 1:
                    out[k] = val
            elif prog.op == "window" and prog.mode[r] == 1:
                # Reduce segment of a boundary window: only the
                # segment-final receive is fully reduced (the rank's
                # own shard) — intermediate receives are partial sums
                # forwarded down the chain, unlike an allreduce where
                # a chunk's last receive is final by construction.
                if r == rounds - 1 or prog.brk[r + 1]:
                    out[k][int(prog.t_rchunk[r, k])] = val
            elif prog.last[r]:
                out[k][int(prog.t_rchunk[r, k])] = val
    if prog.op == "reduce_scatter":
        return jnp.stack(out)
    if prog.op == "window":
        # Reduce-segment chunks a rank does not own never reach its
        # output — backfill with the rank's input so the stacked
        # result is dense (callers read only owned shards there).
        for k in range(n):
            for c in range(prog.nchunks):
                if out[k][c] is None:
                    out[k][c] = data[k, c]
    return jnp.stack([jnp.stack(row) for row in out])


def _kernel(axis_name: str, op, prog: _Program,
            t_dst, t_src, t_schunk, t_rchunk, x_ref, out_ref,
            comm_buf, send_sem, recv_sem, cap_sem):
    """The generated kernel body: the two-slot credit discipline of
    pallas_ring's ``_allreduce_kernel`` driven by the IR tables."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from .. import pallas_ring

    me = jax.lax.axis_index(axis_name)
    rounds = prog.rounds
    # Entry barrier with every peer: the tables name each rank's
    # senders and receivers, which differ per round.
    pallas_ring.entry_barrier(pallas_ring._all_peers(me, prog.nranks))
    comm_buf[0] = x_ref[t_schunk[0, me]]
    # Post-seed credit: gates the round-1 write into comm_buf[0] so a
    # fast upstream cannot land it before the seed (kernel-start skew;
    # no implicit entry barrier). A 1-round program has no round 1 —
    # the credit would leave cap_sem[0] non-zero at kernel exit.
    if rounds >= 2:
        pltpu.semaphore_signal(
            cap_sem.at[0], inc=1, device_id=t_src[1, me],
            device_id_type=pltpu.DeviceIdType.LOGICAL)
    for r in range(rounds):
        slot = r % 2
        nslot = (r + 1) % 2
        if r >= 1:
            # Backpressure: the downstream slot we are about to fill
            # was drained two rounds ago (round 1: the post-seed
            # credit).
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
            if prog.brk[r]:
                # Segment boundary: the chain restarts from a fresh
                # input chunk. Our slot's previous arrival was drained
                # at round r-1 and the next remote write into it (round
                # r+1) is still credit-gated, so a plain store is safe.
                comm_buf[slot] = x_ref[t_schunk[r, me]]
        if prog.brk[r] and (prog.op == "allgather"
                            or (prog.op == "window"
                                and prog.mode[r] == 2)):
            # A rank's own chunk never travels the ring: the staged
            # value IS its final value, written straight to the output
            # (copy segments only — a window's reduce-scatter member
            # stages for the wire, not the output).
            out_ref[t_schunk[r, me]] = x_ref[t_schunk[r, me]]
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[slot],
            dst_ref=comm_buf.at[nslot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nslot],
            device_id=t_dst[r, me],
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if prog.mode[r] == 1:
            val = op.combine(comm_buf[nslot], x_ref[t_rchunk[r, me]])
            # The combined value only needs to persist in the comm
            # buffer when the next round forwards it down the chain.
            if r + 1 < rounds and not prog.brk[r + 1]:
                comm_buf[nslot] = val
        else:
            val = comm_buf[nslot]
        if prog.op == "reduce_scatter":
            if r == rounds - 1:
                out_ref[:] = val
        elif prog.op == "window" and prog.mode[r] == 1:
            # Reduce segment: only the segment-final receive is the
            # rank's fully-reduced own shard (see simulate).
            if r == rounds - 1 or prog.brk[r + 1]:
                out_ref[t_rchunk[r, me]] = val
        elif prog.last[r]:
            out_ref[t_rchunk[r, me]] = val
        # Drained comm_buf[nslot]; credit the rank that refills it at
        # round r+2.
        if r <= rounds - 3:
            pltpu.semaphore_signal(
                cap_sem.at[nslot], inc=1, device_id=t_src[r + 2, me],
                device_id_type=pltpu.DeviceIdType.LOGICAL)


def _pallas_call(prog: _Program, op, axis_name: str, state, seg: int):
    """Run the table program over ``state`` (nchunks, rows, 128), one
    kernel call per ``seg``-row segment."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import pallas_ring as pr

    kernel = functools.partial(_kernel, axis_name, op, prog)

    def call(b):
        r = b.shape[-2]
        out = (r, 128) if prog.op == "reduce_scatter" else b.shape
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(out, b.dtype,
                                           vma=frozenset({axis_name})),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4
            + [pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, r, 128), b.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR((2,)),
            ],
            compiler_params=pr._params(_COLLECTIVE_ID[prog.op]),
            interpret=pr._interpret(),
        )(prog.t_dst, prog.t_src, prog.t_schunk, prog.t_rchunk, b)

    return pr._by_segments(call, state, seg)


def _layout(flat2d, width: int):
    """(m, lanes) -> ((m, rows, 128), seg_rows): rows a whole number of
    kernel-call segments, each holding ``width`` (seg_rows, 128) blocks
    in VMEM."""
    from .. import pallas_ring as pr

    rows, seg = pr._plan_rows(flat2d.shape[1], flat2d.dtype, width)
    return pr._tile(flat2d, rows), seg


def _make_wrapper(prog: _Program, name: str) -> Callable:
    from .. import pallas_ring as pr

    def check(n):
        if n != prog.nranks:
            raise ArgumentError(
                f"schedule {name!r} compiled for {prog.nranks} ranks, "
                f"axis has {n}")

    if prog.op == "reduce_scatter":
        def run_rs(x, axis_name: str, op):
            import jax

            from ...ops import lookup as op_lookup

            op = op_lookup(op)
            n = jax.lax.axis_size(axis_name)
            check(n)
            if x.shape[0] != n:
                raise ArgumentError(
                    f"reduce_scatter input leading dim {x.shape[0]} != "
                    f"ranks {n}")
            if n == 1:
                return x[0]
            flat = x.reshape(n, -1)
            state, seg = _layout(flat, prog.nchunks + 3)
            out = _pallas_call(prog, op, axis_name, state, seg)
            return pr._untile(out[None], flat.shape[1])[0].reshape(
                x.shape[1:])

        return run_rs

    def run(x, axis_name: str, op):
        import jax

        from ...ops import lookup as op_lookup

        op = op_lookup(op)
        n = jax.lax.axis_size(axis_name)
        check(n)
        if n == 1:
            return x
        flat = x.reshape(-1)
        total = flat.shape[0]
        # The IR chunk plan sets the layout: nchunks equal slices, each
        # a (rows, 128) tile block.
        chunk = -(-total // prog.nchunks)
        if chunk * prog.nchunks != total:
            flat = jax.numpy.pad(flat, (0, chunk * prog.nchunks - total))
        state, seg = _layout(flat.reshape(prog.nchunks, chunk),
                             2 * prog.nchunks + 2)
        out = _pallas_call(prog, op, axis_name, state, seg)
        return pr._untile(out, chunk).reshape(-1)[:total].reshape(x.shape)

    return run


__all__ = ["analyze", "clear_compiled", "compile_schedule",
           "fuse_schedules", "fuse_window", "simulate"]
