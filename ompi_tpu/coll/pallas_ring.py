"""coll/pallas — hand-scheduled ICI ring collectives as Pallas kernels.

The TPU-native replacement for the reference's explicit algorithm
implementations (reference: ring allreduce coll_base_allreduce.c:341,
ring allgather coll_base_allgather.c, reduce_scatter ring
coll_base_reduce_scatter.c): instead of PML send/recv per round with a
CPU SIMD reduce (ompi/mca/op/avx) between rounds, each kernel drives the
inter-chip DMA engines directly (`pltpu.make_async_remote_copy` over
ICI) and fuses the per-step reduction on the VPU while the next block is
in flight — the compute/communication overlap the segmented-ring
algorithm (coll_base_allreduce.c:618) approximates in software.

Entry barrier: every kernel that talks to other devices first meets
its peers on the barrier semaphore (``pltpu.get_barrier_semaphore``,
one ``collective_id`` per kernel in ``COLLECTIVE_IDS``). Until a peer
has entered the kernel its VMEM scratch and semaphores may still belong
to the previous program, so no remote DMA or signal may reach it
earlier.

Flow control: the two-slot communication buffer is protected by a
capacity semaphore the consumer remote-signals back to its upstream
neighbor after draining a slot; the producer waits before re-filling.
(The reference's analog is the BTL flow-control window / fastbox
`in_use` flags, btl_sm_fbox.h:22-60 — without it a fast sender clobbers
a slot two steps ahead.)

Layout: every VMEM buffer is ``(..., rows, 128)`` with ``rows`` a
multiple of the dtype's sublane tile, so a dynamic index on a leading
axis selects whole (8, 128) tiles — Mosaic refuses a dynamic sublane
offset inside a tile. Payloads larger than the VMEM budget run the
kernel once per row segment (``_by_segments``); the chunked kernel
streams HBM->VMEM inside one kernel instead.

These kernels are selected by the `coll/pallas` component (opt-in via
``coll_select=pallas`` or per-op tuned rules); `coll/xla` remains the
default since XLA's own collectives are already ICI-optimal for the
common cases. The kernels run compiled on TPU meshes and in Mosaic
interpret mode on the CPU test mesh (tests/conftest.py's 8 virtual
devices), mirroring the reference's strategy of exercising transport
algorithms over loopback (SURVEY §4).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import config
from ..ops import lookup as op_lookup
from ..ops.op import Op

__all__ = [
    "COLLECTIVE_IDS", "ring_allgather", "ring_reduce_scatter",
    "ring_allreduce", "ring_allreduce_bidir", "ring_allreduce_chunked",
    "ring_allreduce_rd", "tree_bcast", "tree_reduce", "linear_gather",
    "linear_scatter", "ppermute_shift",
]

_interpret_var = config.register(
    "coll", "pallas", "interpret",
    type=bool, default=None,
    description="Force Mosaic interpret mode (auto: on for CPU backend)",
)
_bidir_var = config.register(
    "coll", "pallas", "bidir",
    type=bool, default=False,
    description="Use the bidirectional ring for pallas allreduce "
                "(both ICI link directions per step)",
)
_segment_var = config.register(
    "coll", "pallas", "segment_bytes",
    type=int, default=1 << 20,
    description="Segment size for the chunked HBM-streaming ring "
                "kernels (reference's segmented-ring knob: 1 MiB, "
                "coll_tuned_decision_fixed.c:73)",
)
_chunk_threshold_var = config.register(
    "coll", "pallas", "chunk_threshold_bytes",
    type=int, default=4 << 20,
    description="Per-shard payload size above which pallas allreduce "
                "streams segments HBM->VMEM (chunked kernel) instead "
                "of staging the whole payload in VMEM",
)
_rd_cutoff_var = config.register(
    "coll", "pallas", "rd_cutoff_bytes",
    type=int, default=10_000,
    description="Per-shard bytes below which pallas allreduce uses "
                "recursive doubling (reference: 10000B cutoff, "
                "coll_tuned_decision_fixed.c:53)",
)

#: One barrier-semaphore id per kernel: two kernels that shared an id
#: could satisfy each other's entry barrier.
COLLECTIVE_IDS = {
    "allgather": 0, "reduce_scatter": 1, "allreduce": 2, "shift": 3,
    "alltoall": 4, "bcast": 5, "bidir": 6, "chunked": 7, "rd": 8,
    "reduce": 9, "gather": 10, "scatter": 11,
    # sched/pallas_lower table programs, one id per program op
    "sched_allreduce": 12, "sched_reduce_scatter": 13,
    "sched_allgather": 14, "sched_window": 15,
    "quant": 16, "attn": 17,
}

#: VMEM bytes a whole-payload kernel call may hold (v5e has 16 MiB of
#: scoped VMEM; the rest is headroom for Mosaic's own temporaries).
_VMEM_BUDGET = 8 << 20

_LOGICAL = pltpu.DeviceIdType.LOGICAL


def _interpret():
    """False on TPU (compiled); Mosaic TPU-interpret params on CPU —
    the mode that emulates inter-device DMA + remote semaphore signals
    (plain ``interpret=True`` cannot discharge remote signals)."""
    forced = _interpret_var.value
    if forced is None:
        forced = jax.default_backend() == "cpu"
    return pltpu.InterpretParams() if forced else False


def _params(kernel: str | None) -> pltpu.CompilerParams:
    """Compiler params; ``kernel`` names the barrier-semaphore id of a
    kernel that meets its peers at entry (None: no remote peers)."""
    if kernel is None:
        return pltpu.CompilerParams(has_side_effects=True)
    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=COLLECTIVE_IDS[kernel])


def entry_barrier(peers) -> None:
    """Signal every peer on the barrier semaphore and wait until as
    many signals arrived: afterwards each listed peer has entered this
    kernel. The peer relation must be symmetric (each of my peers lists
    me as often as I list it)."""
    sem = pltpu.get_barrier_semaphore()
    for p in peers:
        pltpu.semaphore_signal(sem, 1, device_id=p, device_id_type=_LOGICAL)
    pltpu.semaphore_wait(sem, len(peers))


def _ring_peers(me, n):
    return [jax.lax.rem(me + 1, n), jax.lax.rem(me - 1 + n, n)]


def _all_peers(me, n):
    return [jax.lax.rem(me + k, n) for k in range(1, n)]


def _combine_blocks(op: Op, a, b):
    """Per-step reduction on the VPU (replaces ompi/mca/op/avx's CPU
    SIMD loops; reference dispatch: op_avx_functions.c:28-66)."""
    return op.combine(a, b)


def _sublane(dtype) -> int:
    """Minimum second-to-last-dim tile for the dtype (pallas_guide:
    (8,128) f32, (16,128) bf16, (32,128) int8)."""
    return max(8, 32 // max(1, jnp.dtype(dtype).itemsize))


def _plan_rows(lanes: int, dtype, width: int, quantum: int = 0
               ) -> tuple[int, int]:
    """(rows, seg_rows) for blocks of ``lanes`` elements laid out as
    (rows, 128): ``rows`` is a multiple of the sublane tile (or of
    ``quantum``) and of ``seg_rows``, the rows one kernel call takes so
    that ``width`` block-sized VMEM buffers fit the budget."""
    a = quantum or _sublane(dtype)
    rows = -(-max(lanes, 1) // 128)
    rows = -(-rows // a) * a
    row_bytes = width * 128 * jnp.dtype(dtype).itemsize
    seg = min(rows, max(a, _VMEM_BUDGET // row_bytes // a * a))
    return -(-rows // seg) * seg, seg


def _tile(flat: jax.Array, rows: int) -> jax.Array:
    """(m, lanes) -> (m, rows, 128), zero-padded."""
    pad = rows * 128 - flat.shape[1]
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(flat.shape[0], rows, 128)


def _untile(t: jax.Array, lanes: int) -> jax.Array:
    """(m, rows, 128) -> (m, lanes)."""
    return t.reshape(t.shape[0], -1)[:, :lanes]


def _by_segments(call: Callable, x: jax.Array, seg_rows: int
                 ) -> jax.Array:
    """Apply ``call`` to each ``seg_rows`` slice of the row axis (-2)
    of ``x`` and of its result — one kernel call per segment, each with
    its own entry barrier. Every kernel here works row-wise, so the
    segments are independent."""
    rows = x.shape[-2]
    if rows <= seg_rows:
        return call(x)
    nseg = rows // seg_rows
    xs = jnp.moveaxis(
        x.reshape(x.shape[:-2] + (nseg, seg_rows, 128)), -3, 0)
    ys = jax.lax.map(call, xs)
    ys = jnp.moveaxis(ys, 0, -3)
    return ys.reshape(ys.shape[:-3] + (rows, 128))


def _vmem_call(kernel, out_shape: tuple, dtype, axis_name: str,
               scratch_shapes: list, cid: str | None, n_in: int = 1):
    """pallas_call with whole-array VMEM operands."""
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n_in,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch_shapes,
        compiler_params=_params(cid),
        interpret=_interpret(),
    )


# ---------------------------------------------------------------------------
# Kernels. Block refs are (rows, 128); the leading axis of an (n, rows,
# 128) ref indexes ring positions (rank blocks).
# ---------------------------------------------------------------------------

def _allgather_kernel(axis_name: str, n: int, local_ref, out_ref,
                      comm_buf, send_sem, recv_sem, cap_sem):
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    entry_barrier(_ring_peers(me, n))

    out_ref[me] = local_ref[:]
    comm_buf[0] = local_ref[:]
    # Post-seed credit: gates the upstream neighbor's step-1 write into
    # comm_buf[0] so a fast neighbor cannot land it before the seed. A
    # 2-member ring has no step 1 in this n-1-step schedule — emitting
    # the credit would leave cap_sem[0] non-zero at kernel exit.
    if n > 2:
        pltpu.semaphore_signal(cap_sem.at[0], inc=1, device_id=left,
                               device_id_type=_LOGICAL)

    for step in range(n - 1):
        slot = step % 2
        nslot = (step + 1) % 2
        # Backpressure: wait for the downstream credit before filling
        # its slot (step 1: the post-seed credit; later steps: the
        # consumer drained the slot two steps ago).
        if step >= 1:
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[slot],
            dst_ref=comm_buf.at[nslot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nslot],
            device_id=right,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()
        src_block = jax.lax.rem(me - step - 1 + n, n)
        out_ref[src_block] = comm_buf[nslot]
        # Drained comm_buf[nslot]; let upstream reuse it at step+2.
        if step < n - 3:
            pltpu.semaphore_signal(cap_sem.at[nslot], inc=1,
                                   device_id=left, device_id_type=_LOGICAL)


def _reduce_scatter_kernel(axis_name: str, n: int, op: Op, x_ref, out_ref,
                           comm_buf, send_sem, recv_sem, cap_sem):
    """Ring reduce-scatter (the first phase of the reference's ring
    allreduce, coll_base_allreduce.c:341): at step s, pass the partial
    for block (me - s - 1) to the right, reducing on arrival; after
    n-1 steps each rank holds the full reduction of block me."""
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    entry_barrier(_ring_peers(me, n))

    # Send block (me - 1) first, so that block b circulates from rank
    # b+1 around to rank b, accumulating.
    first = jax.lax.rem(me - 1 + n, n)
    comm_buf[0] = x_ref[first]
    # Post-seed credit gating the upstream step-1 write (see allgather;
    # same n==2 exclusion — the n-1-step schedule has no step 1 there).
    if n > 2:
        pltpu.semaphore_signal(cap_sem.at[0], inc=1, device_id=left,
                               device_id_type=_LOGICAL)

    for step in range(n - 1):
        slot = step % 2
        nslot = (step + 1) % 2
        if step >= 1:
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[slot],
            dst_ref=comm_buf.at[nslot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nslot],
            device_id=right,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()
        # Arrived: left's partial for block (left - step - 1), i.e.
        # block (me - step - 2).
        blk = jax.lax.rem(me - step - 2 + 2 * n, n)
        reduced = _combine_blocks(op, comm_buf[nslot], x_ref[blk])
        if step < n - 2:
            comm_buf[nslot] = reduced
            if step < n - 3:
                pltpu.semaphore_signal(cap_sem.at[nslot], inc=1,
                                       device_id=left,
                                       device_id_type=_LOGICAL)
        else:
            out_ref[:] = reduced


def _allreduce_kernel(axis_name: str, n: int, op: Op, x_ref, out_ref,
                      comm_buf, send_sem, recv_sem, cap_sem):
    """Ring allreduce = reduce-scatter phase + allgather phase in one
    kernel (2(n-1) steps, the bandwidth-optimal schedule the tuned
    decision layer picks for large commutative reductions —
    coll_tuned_decision_fixed.c:45-87)."""
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    entry_barrier(_ring_peers(me, n))

    first = jax.lax.rem(me - 1 + n, n)
    comm_buf[0] = x_ref[first]
    # Post-seed credit gating the upstream step-1 write (see allgather).
    pltpu.semaphore_signal(cap_sem.at[0], inc=1, device_id=left,
                           device_id_type=_LOGICAL)

    for step in range(2 * (n - 1)):
        slot = step % 2
        nslot = (step + 1) % 2
        if step >= 1:
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[slot],
            dst_ref=comm_buf.at[nslot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nslot],
            device_id=right,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if step < n - 1:
            # reduce-scatter phase
            blk = jax.lax.rem(me - step - 2 + 2 * n, n)
            val = _combine_blocks(op, comm_buf[nslot], x_ref[blk])
            comm_buf[nslot] = val
            # The block completed at the last RS step (blk == me) is the
            # first fully-reduced one; store it before the AG phase.
            if step == n - 2:
                out_ref[blk] = val
        else:
            # allgather phase: circulate the fully-reduced blocks.
            blk = jax.lax.rem(me - (step - (n - 1)) - 1 + 2 * n, n)
            out_ref[blk] = comm_buf[nslot]
        if step < 2 * (n - 1) - 2:
            pltpu.semaphore_signal(cap_sem.at[nslot], inc=1,
                                   device_id=left, device_id_type=_LOGICAL)


# ---------------------------------------------------------------------------
# Chunked (HBM-streaming) ring allreduce: the reference's segmented ring
# (coll_base_allreduce.c:618-717 — 1 MiB segments pipelined through a
# bounded buffer) re-built for the TPU memory hierarchy. The payload
# stays in HBM; the VMEM working set is six segment-sized slots (input
# prefetch x2, comm buffer x2, output stage x2), so shard sizes are
# bounded by HBM, not VMEM.
#
# Flow control has two levels: within a segment, the capacity semaphore
# of the plain ring kernels; across segments, a credit semaphore — a
# device may start sending segment i+1 only after its downstream
# neighbor signals it has drained segment i (the reference's analog is
# the bounded num_segments pipeline in the segmented ring). One credit
# is primed at kernel start and the residue drained at kernel end so
# every segment's wait is unconditional (no predicated semaphore ops).
# ---------------------------------------------------------------------------

def _allreduce_chunked_kernel(axis_name: str, n: int, op: Op, seg: int,
                              n_segs: int, x_hbm, out_hbm,
                              comm_buf, x_buf, out_buf,
                              send_sem, recv_sem, cap_sem,
                              x_sem, out_sem, seg_sem):
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    entry_barrier(_ring_peers(me, n))

    # Prime one segment credit so every segment (incl. 0) waits uniformly.
    pltpu.semaphore_signal(seg_sem, inc=1, device_id=left,
                           device_id_type=_LOGICAL)

    def seg_body(si, _):
        off = si * seg

        # Credit from the right neighbor: it drained our previous
        # segment's sends from its comm buffer.
        pltpu.semaphore_wait(seg_sem, 1)

        def x_dma(j, slot):
            # j-th needed input block for this rank's ring schedule:
            # j=0 seeds the comm buffer, j=s+1 is combined at RS step s.
            blk = jax.lax.rem(me - 1 - j + 2 * n, n)
            return pltpu.make_async_copy(
                x_hbm.at[blk, pl.ds(off, seg)], x_buf.at[slot],
                x_sem.at[slot])

        def out_dma(blk, slot):
            return pltpu.make_async_copy(
                out_buf.at[slot], out_hbm.at[blk, pl.ds(off, seg)],
                out_sem.at[slot])

        x_dma(0, 0).start()
        x_dma(1, 1).start()
        x_dma(0, 0).wait()
        comm_buf[0] = x_buf[0]
        # Post-seed credit: the upstream neighbor's step-1 remote write
        # lands in comm_buf[0] — the slot the seed just filled. Without
        # this credit a fast left neighbor (already credited for the
        # next segment at our previous segment's end) could write
        # comm_buf[0] BEFORE the seed, which then silently overwrites
        # the delivered partial (the recv semaphore count would still
        # satisfy our step-1 wait). Gate every step-1 send on it.
        pltpu.semaphore_signal(cap_sem.at[0], inc=1, device_id=left,
                               device_id_type=_LOGICAL)

        writes = []  # in-flight VMEM->HBM output copies (unrolled)
        for step in range(2 * (n - 1)):
            slot = step % 2
            nslot = (step + 1) % 2
            if step >= 1:
                pltpu.semaphore_wait(cap_sem.at[nslot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=comm_buf.at[slot],
                dst_ref=comm_buf.at[nslot],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[nslot],
                device_id=right,
                device_id_type=_LOGICAL,
            )
            rdma.start()
            # Prefetch the input block for the NEXT reduce-scatter step
            # while the remote DMA is in flight; its slot held block
            # `step`, consumed at the previous step.
            if step + 2 < n:
                x_dma(step + 2, step % 2).start()
            rdma.wait()
            if step < n - 1:
                # reduce-scatter phase: fold our block into the arrival
                x_dma(step + 1, (step + 1) % 2).wait()
                blk = jax.lax.rem(me - step - 2 + 2 * n, n)
                val = _combine_blocks(op, comm_buf[nslot],
                                      x_buf[(step + 1) % 2])
                comm_buf[nslot] = val
                if step == n - 2:  # blk == me: first fully-reduced block
                    wslot = len(writes) % 2
                    if len(writes) >= 2:
                        writes[-2].wait()
                    out_buf[wslot] = val
                    writes.append(out_dma(blk, wslot))
                    writes[-1].start()
            else:
                # allgather phase: stream fully-reduced blocks out
                blk = jax.lax.rem(me - (step - (n - 1)) - 1 + 2 * n, n)
                wslot = len(writes) % 2
                if len(writes) >= 2:
                    writes[-2].wait()
                out_buf[wslot] = comm_buf[nslot]
                writes.append(out_dma(blk, wslot))
                writes[-1].start()
            if step < 2 * (n - 1) - 2:
                pltpu.semaphore_signal(cap_sem.at[nslot], inc=1,
                                       device_id=left,
                                       device_id_type=_LOGICAL)
        # Drained every send from the left neighbor: grant next credit.
        pltpu.semaphore_signal(seg_sem, inc=1, device_id=left,
                               device_id_type=_LOGICAL)
        # Out-copies must land before their slots are reused next segment.
        for w in writes[-2:]:
            w.wait()
        return 0

    jax.lax.fori_loop(0, n_segs, seg_body, 0)
    # Consume the residual credit (prime + n_segs signals, n_segs waits).
    pltpu.semaphore_wait(seg_sem, 1)


def _selfdma_chunked_kernel(axis_name: str, seg: int, n_segs: int,
                            x_hbm, out_hbm,
                            x_buf, comm_buf, x_sem, send_sem, recv_sem,
                            out_sem):
    """Degenerate 1-member ring of the chunked schedule: per segment,
    HBM->VMEM prefetch, one self-targeted remote DMA (the ICI machinery
    with device_id == me), VMEM->HBM writeback — double-buffered. A
    1-rank allreduce is the identity, but every DMA engine the n>1
    schedule uses runs for real: the one-chip proof that the kernel
    path compiles and executes.

    3-stage software pipeline: the remote DMA of segment si is waited
    only at iteration si+1, so IN(si+1), RDMA(si) and OUT(si-1) are all
    in flight together. Slot hazards: RDMA(si) needs comm_buf[si%2]
    free -> OUT(si-2) waited; IN(si+1) needs x_buf[(si+1)%2] free ->
    RDMA(si-1) waited; OUT(si) needs RDMA(si) waited."""
    def in_dma(si):
        return pltpu.make_async_copy(
            x_hbm.at[0, pl.ds(si * seg, seg)], x_buf.at[si % 2],
            x_sem.at[si % 2])

    def out_dma(si):
        return pltpu.make_async_copy(
            comm_buf.at[si % 2], out_hbm.at[0, pl.ds(si * seg, seg)],
            out_sem.at[si % 2])

    def rdma(si):
        slot = si % 2
        return pltpu.make_async_remote_copy(
            src_ref=x_buf.at[slot], dst_ref=comm_buf.at[slot],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
            device_id=jax.lax.axis_index(axis_name),
            device_id_type=_LOGICAL)

    in_dma(0).start()
    if n_segs > 1:
        in_dma(1).start()
    for si in range(n_segs):
        in_dma(si).wait()
        if si >= 2:
            out_dma(si - 2).wait()  # comm_buf[si%2] reader must finish
        rdma(si).start()
        if si >= 1:
            rdma(si - 1).wait()
            out_dma(si - 1).start()
            if si + 1 < n_segs:
                in_dma(si + 1).start()  # x_buf slot freed by the wait
    rdma(n_segs - 1).wait()
    out_dma(n_segs - 1).start()
    for si in range(max(0, n_segs - 2), n_segs):
        out_dma(si).wait()


def ring_allreduce_chunked(x: jax.Array, axis_name: str, op: Any = "sum",
                           seg_bytes: int | None = None) -> jax.Array:
    """Inside shard_map: this rank's full contribution (any shape) ->
    fully reduced buffer of the same shape, streamed through VMEM in
    double-buffered segments. Unlike the whole-payload kernels, handles
    shards far larger than VMEM in one kernel (the reference's
    segmented ring regime, coll_base_allreduce.c:618)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if seg_bytes is None:
        seg_bytes = _segment_var.value
    shape = x.shape
    flat = x.reshape(-1)
    itemsize = jnp.dtype(flat.dtype).itemsize
    a = _sublane(flat.dtype)

    # Lay out as (n, rows, 128): rows aligned to the sublane tile and
    # to a whole number of segments.
    rows = -(-flat.size // (n * 128))
    rows = -(-rows // a) * a
    seg_rows = max(a, min(-(-rows // a) * a,
                          (seg_bytes // (128 * itemsize) // a) * a or a))
    rows = -(-rows // seg_rows) * seg_rows
    n_segs = rows // seg_rows
    pad = n * rows * 128 - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(n, rows, 128)
    slots = [pltpu.VMEM((2, seg_rows, 128), flat.dtype)] * 3
    dma = [pltpu.SemaphoreType.DMA((2,))] * 4

    if n == 1:
        kernel = functools.partial(_selfdma_chunked_kernel, axis_name,
                                   seg_rows, n_segs)
        scratch = slots[:2] + dma
        cid = None  # no remote peer: no entry barrier
    else:
        kernel = functools.partial(_allreduce_chunked_kernel, axis_name,
                                   n, op, seg_rows, n_segs)
        scratch = (slots + dma[:2] + [pltpu.SemaphoreType.REGULAR((2,))]
                   + dma[2:] + [pltpu.SemaphoreType.REGULAR])
        cid = "chunked"
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, rows, 128), flat.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        compiler_params=_params(cid),
        interpret=_interpret(),
    )(blocks)
    flat_out = out.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(shape)


# ---------------------------------------------------------------------------
# Host-callable wrappers (shard_map bodies).
# ---------------------------------------------------------------------------

def _ring_sems():
    return [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
    ]


def ring_allgather(x: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: local block (chunk,) -> gathered (n, chunk)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[None]
    shape = x.shape
    flat = x.reshape(1, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, n + 3)
    kernel = functools.partial(_allgather_kernel, axis_name, n)

    def call(b):
        r = b.shape[-2]
        return _vmem_call(
            kernel, (n, r, 128), b.dtype, axis_name,
            scratch_shapes=[pltpu.VMEM((2, r, 128), b.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.REGULAR((2,))],
            cid="allgather")(b)

    out = _by_segments(call, _tile(flat, rows)[0], seg)
    return _untile(out, lanes).reshape((n,) + shape)


def ring_reduce_scatter(x: jax.Array, axis_name: str, op: Any = "sum"
                        ) -> jax.Array:
    """Inside shard_map: local (n, chunk) contributions -> own reduced
    block (chunk,)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[0]
    shape = x.shape[1:]
    flat = x.reshape(n, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, n + 3)
    kernel = functools.partial(_reduce_scatter_kernel, axis_name, n, op)

    def call(b):
        r = b.shape[-2]
        return _vmem_call(
            kernel, (r, 128), b.dtype, axis_name,
            [pltpu.VMEM((2, r, 128), b.dtype)] + _ring_sems(),
            "reduce_scatter")(b)

    out = _by_segments(call, _tile(flat, rows), seg)
    return _untile(out[None], lanes)[0].reshape(shape)


def ring_allreduce(x: jax.Array, axis_name: str, op: Any = "sum"
                   ) -> jax.Array:
    """Inside shard_map: local (n, chunk) contributions -> fully
    reduced (n, chunk) (every block identical across ranks only in the
    rank-major world view; here each rank returns all blocks)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape[1:]
    flat = x.reshape(n, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, 2 * n + 2)
    kernel = functools.partial(_allreduce_kernel, axis_name, n, op)

    def call(b):
        r = b.shape[-2]
        return _vmem_call(
            kernel, b.shape, b.dtype, axis_name,
            [pltpu.VMEM((2, r, 128), b.dtype)] + _ring_sems(),
            "allreduce")(b)

    out = _by_segments(call, _tile(flat, rows), seg)
    return _untile(out, lanes).reshape((n,) + shape)


def _allreduce_bidir_kernel(axis_name: str, n: int, op: Op,
                            x_ref, out_ref, buf_a, buf_b,
                            ssem_a, rsem_a, csem_a,
                            ssem_b, rsem_b, csem_b):
    """Bidirectional ring allreduce: the payload splits in half (axis 1
    of the (n, 2, rows, 128) refs) and the two halves run the
    2(n-1)-step ring schedule in OPPOSITE directions simultaneously, so
    both ICI directions of the torus link carry data every step — 2x
    the link bandwidth of the unidirectional ring (reference's
    algorithm space has only the one-direction ring,
    coll_base_allreduce.c:341; this is the TPU-topology upgrade). Both
    directions' DMAs are started before either is awaited."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_ring_peers(me, n))
    parts = (
        (1, buf_a, ssem_a, rsem_a, csem_a, 0),
        (-1, buf_b, ssem_b, rsem_b, csem_b, 1),
    )
    for d, buf, _ss, _rs, csem, h in parts:
        first = jax.lax.rem(me - d + n, n)
        buf[0] = x_ref[first, h]
        # Post-seed credit to this direction's upstream (see allgather).
        pltpu.semaphore_signal(
            csem.at[0], inc=1, device_id=jax.lax.rem(me - d + n, n),
            device_id_type=_LOGICAL,
        )

    for step in range(2 * (n - 1)):
        slot = step % 2
        nslot = (step + 1) % 2
        descs = []
        for d, buf, ssem, rsem, csem, h in parts:
            if step >= 1:
                pltpu.semaphore_wait(csem.at[nslot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[slot],
                dst_ref=buf.at[nslot],
                send_sem=ssem.at[slot],
                recv_sem=rsem.at[nslot],
                device_id=jax.lax.rem(me + d + n, n),
                device_id_type=_LOGICAL,
            )
            rdma.start()  # both directions in flight together
            descs.append(rdma)
        for (d, buf, ssem, rsem, csem, h), rdma in zip(parts, descs):
            rdma.wait()
            if step < n - 1:
                blk = jax.lax.rem(me - d * (step + 2) + 3 * n, n)
                val = _combine_blocks(op, buf[nslot], x_ref[blk, h])
                buf[nslot] = val
                if step == n - 2:
                    out_ref[blk, h] = val  # blk == me: first done block
            else:
                blk = jax.lax.rem(
                    me - d * (step - (n - 1) + 1) + 3 * n, n
                )
                out_ref[blk, h] = buf[nslot]
            if step < 2 * (n - 1) - 2:
                pltpu.semaphore_signal(
                    csem.at[nslot], inc=1,
                    device_id=jax.lax.rem(me - d + n, n),
                    device_id_type=_LOGICAL,
                )


def _allreduce_rd_kernel(axis_name: str, n: int, op: Op,
                         x_ref, out_ref, comm_buf, send_sems, recv_sems):
    """Recursive-doubling allreduce (reference:
    ompi_coll_base_allreduce_intra_recursivedoubling,
    coll_base_allreduce.c:130): log2(n) rounds, each exchanging the FULL
    payload with partner me^2^k — the latency-optimal schedule tuned
    picks below the 10 KB cutoff. Round k gets its own comm slot AND its
    own semaphore pair: partners of different rounds live in disjoint
    hypercube blocks until they meet, so a fast subtree can run rounds
    ahead — per-round semaphores keep its early DMA from satisfying an
    earlier round's wait (slot-mod-2 sharing would)."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    out_ref[:] = x_ref[:]
    rounds = (n - 1).bit_length()
    for k in range(rounds):
        bit = 1 << k
        partner = me ^ bit
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref,
            dst_ref=comm_buf.at[k],
            send_sem=send_sems.at[k],
            recv_sem=recv_sems.at[k],
            device_id=partner,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()

        # Rank-ordered combine so non-commutative user ops see the MPI
        # reduction order (the reference's is_commutative branch).
        @pl.when(partner < me)
        def _lower():
            out_ref[:] = _combine_blocks(op, comm_buf[k], out_ref[:])

        @pl.when(partner >= me)
        def _upper():
            out_ref[:] = _combine_blocks(op, out_ref[:], comm_buf[k])


def _flat_call(x: jax.Array, axis_name: str, kernel, width: int,
               scratch: Callable, cid: str) -> jax.Array:
    """Whole-payload kernel over one (rows, 128) block in and out:
    ``scratch(rows, dtype)`` builds the per-call scratch list."""
    shape = x.shape
    flat = x.reshape(1, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, width)

    def call(b):
        return _vmem_call(kernel, b.shape, b.dtype, axis_name,
                          scratch(b.shape[-2], b.dtype), cid)(b)

    out = _by_segments(call, _tile(flat, rows)[0], seg)
    return _untile(out[None], lanes)[0].reshape(shape)


def _round_scratch(rounds: int):
    def scratch(r, dtype):
        return [pltpu.VMEM((rounds, r, 128), dtype),
                pltpu.SemaphoreType.DMA((rounds,)),
                pltpu.SemaphoreType.DMA((rounds,))]
    return scratch


def ring_allreduce_rd(x: jax.Array, axis_name: str, op: Any = "sum"
                      ) -> jax.Array:
    """Inside shard_map: full local contribution -> fully reduced buffer
    via recursive doubling (power-of-two axis sizes only, like the
    reference's variant)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError(
            f"recursive doubling needs a power-of-two ring, got {n}"
        )
    rounds = (n - 1).bit_length()
    kernel = functools.partial(_allreduce_rd_kernel, axis_name, n, op)
    return _flat_call(x, axis_name, kernel, rounds + 2,
                      _round_scratch(rounds), "rd")


def _tree_reduce_kernel(axis_name: str, n: int, root: int, op: Op,
                        x_ref, out_ref, comm_buf, send_sems, recv_sems):
    """Binomial-tree reduce-to-root (reference:
    ompi_coll_base_reduce_intra_binomial, coll_base_reduce.c): the
    mirror of the bcast tree — in round k, every rank whose relative
    rank has lowest set bit 2^k sends its accumulated subtree to
    relative rank rel-2^k and leaves the game; receivers fold arrivals
    in ascending subtree order. Per-round buffers + semaphores for the
    same skew reason as the rd kernel."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    rel = jax.lax.rem(me - root + n, n)
    out_ref[:] = x_ref[:]
    rounds = (n - 1).bit_length()
    for k in range(rounds):
        bit = 1 << k
        low = rel & (2 * bit - 1)
        is_send = low == bit
        is_recv = jnp.logical_and(low == 0, rel + bit < n)
        dst = jax.lax.rem(me - bit + n, n)  # sender's parent
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref,
            dst_ref=comm_buf.at[k],
            send_sem=send_sems.at[k],
            recv_sem=recv_sems.at[k],
            device_id=dst,
            device_id_type=_LOGICAL,
        )

        @pl.when(is_send)
        def _send(rdma=rdma):
            rdma.start()
            rdma.wait_send()

        @pl.when(is_recv)
        def _recv(rdma=rdma):
            rdma.wait_recv()
            # arrival comes from rel+bit: higher relative rank, so the
            # accumulator stays on the left of the fold
            out_ref[:] = _combine_blocks(op, out_ref[:], comm_buf[k])


def tree_reduce(x: jax.Array, axis_name: str, op: Any = "sum",
                root: int = 0) -> jax.Array:
    """Inside shard_map: full local contribution -> the reduction at
    root (other ranks return their partial accumulator — MPI semantics:
    recvbuf significant only at root)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    rounds = (n - 1).bit_length()
    kernel = functools.partial(_tree_reduce_kernel, axis_name, n,
                               int(root), op)
    return _flat_call(x, axis_name, kernel, rounds + 2,
                      _round_scratch(rounds), "reduce")


def _tree_bcast_kernel(axis_name: str, n: int, root: int,
                       x_ref, out_ref, send_sem, recv_sem, ready_sem):
    """Binomial-tree bcast: in round k every rank that already holds
    the payload (relative rank < 2^k) pushes it one subtree over
    (relative +2^k) — ceil(log2 n) rounds total (reference:
    ompi_coll_base_bcast_intra_binomial, coll_base_bcast.c; tree shape
    coll_base_topo.c). Asymmetric DMA: senders wait send completion,
    receivers park on the recv semaphore (wait_recv). The receiver
    remote-signals readiness to its sender BEFORE parking — the DMA
    targets the same out_ref the receiver initializes at kernel start,
    so an unsynchronized send could land before that init."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    rel = jax.lax.rem(me - root + n, n)
    out_ref[:] = x_ref[:]
    rounds = max(1, (n - 1).bit_length())
    for k in range(rounds):
        bit = 1 << k
        dst = jax.lax.rem(me + bit, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref,
            dst_ref=out_ref,
            send_sem=send_sem.at[k % 2],
            recv_sem=recv_sem.at[k % 2],
            device_id=dst,
            device_id_type=_LOGICAL,
        )
        is_recv = jnp.logical_and(rel >= bit, rel < 2 * bit)

        @pl.when(is_recv)
        def _ready():
            # my sender is relative -bit: tell it my out_ref is ready
            pltpu.semaphore_signal(
                ready_sem.at[k % 2], inc=1,
                device_id=jax.lax.rem(me - bit + n, n),
                device_id_type=_LOGICAL,
            )

        @pl.when(jnp.logical_and(rel < bit, rel + bit < n))
        def _send(rdma=rdma):
            pltpu.semaphore_wait(ready_sem.at[k % 2], 1)
            rdma.start()
            rdma.wait_send()

        @pl.when(is_recv)
        def _recv(rdma=rdma):
            rdma.wait_recv()


def ring_allreduce_bidir(x: jax.Array, axis_name: str, op: Any = "sum"
                         ) -> jax.Array:
    """Inside shard_map: local (n, chunk) contributions -> fully
    reduced (n, chunk) via the bidirectional ring (both ICI link
    directions active every step)."""
    op = op_lookup(op)
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape[1:]
    flat = x.reshape(n, -1)
    lanes = flat.shape[1]
    half = -(-lanes // 2)
    rows, seg = _plan_rows(half, flat.dtype, 4 * n + 4)
    t = _tile(flat, 2 * rows).reshape(n, 2, rows, 128)
    kernel = functools.partial(_allreduce_bidir_kernel, axis_name, n, op)

    def call(b):
        r = b.shape[-2]
        return _vmem_call(
            kernel, b.shape, b.dtype, axis_name,
            [pltpu.VMEM((2, r, 128), b.dtype),
             pltpu.VMEM((2, r, 128), b.dtype)]
            + _ring_sems() + _ring_sems(),
            "bidir")(b)

    out = _by_segments(call, t, seg).reshape(n, 2 * rows, 128)
    return _untile(out, lanes).reshape((n,) + shape)


def tree_bcast(x: jax.Array, axis_name: str, root: int = 0
               ) -> jax.Array:
    """Inside shard_map: local block -> root's block, binomial tree."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    kernel = functools.partial(_tree_bcast_kernel, axis_name, n,
                               int(root))
    return _flat_call(x, axis_name, kernel, 2,
                      lambda r, dtype: _ring_sems(), "bcast")


def _alltoall_kernel(axis_name: str, n: int, x_ref, out_ref,
                     send_sem, recv_sem):
    """Pairwise-exchange alltoall (reference: coll_base_alltoall.c's
    pairwise variant): at step s every rank RDMA-writes block
    (me+s) directly into rank (me+s)'s out[me] — no intermediate
    buffering, each byte crosses ICI exactly once. The EP/Ulysses
    primitive (SURVEY §2.6, §5.7). Each step has its OWN semaphore
    pair: the writer of my out at step s is (me-s), a different device
    each step with no transitive ordering, so a 2-slot rotation would
    let a fast peer's later-step write satisfy an earlier step's wait
    and the kernel could exit before the straggler lands."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    out_ref[me] = x_ref[me]
    for step in range(1, n):
        dst = jax.lax.rem(me + step, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst],
            dst_ref=out_ref.at[me],
            send_sem=send_sem.at[step - 1],
            recv_sem=recv_sem.at[step - 1],
            device_id=dst,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()


def _peer_sems(n: int):
    return [pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,))]


def ring_alltoall(x: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: local (n, chunk) send blocks -> (n, chunk)
    received blocks (row s = block from rank s)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape[1:]
    flat = x.reshape(n, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, 2 * n)
    kernel = functools.partial(_alltoall_kernel, axis_name, n)

    def call(b):
        return _vmem_call(kernel, b.shape, b.dtype, axis_name,
                          _peer_sems(n), "alltoall")(b)

    out = _by_segments(call, _tile(flat, rows), seg)
    return _untile(out, lanes).reshape((n,) + shape)


def _gather_kernel(axis_name: str, n: int, root: int, x_ref, out_ref,
                   send_sems, recv_sems, ready_sem):
    """Linear gather-to-root (reference: coll_base_gather.c,
    ompi_coll_base_gather_intra_basic_linear): every non-root rank
    remote-DMAs its block into root's out[me]; root initializes its own
    row, grants a readiness credit to each sender (its out buffer is
    live), then parks on one recv semaphore per sender. Distinct
    semaphore slots per sender — the writers are unordered peers, so a
    shared slot could let one fast sender satisfy another's wait (same
    reasoning as the pairwise alltoall kernel)."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    rel = jax.lax.rem(me - root + n, n)

    @pl.when(rel == 0)
    def _root():
        out_ref[me] = x_ref[:]
        for s in range(1, n):
            pltpu.semaphore_signal(
                ready_sem, inc=1, device_id=jax.lax.rem(root + s, n),
                device_id_type=_LOGICAL,
            )
        for s in range(1, n):
            src_dev = jax.lax.rem(root + s, n)
            pltpu.make_async_remote_copy(
                src_ref=x_ref, dst_ref=out_ref.at[src_dev],
                send_sem=send_sems.at[s - 1],
                recv_sem=recv_sems.at[s - 1],
                device_id=src_dev,
                device_id_type=_LOGICAL,
            ).wait_recv()

    @pl.when(rel != 0)
    def _sender():
        pltpu.semaphore_wait(ready_sem, 1)
        # slot rel-1 matches the descriptor root waits on
        for s in range(1, n):
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref, dst_ref=out_ref.at[me],
                send_sem=send_sems.at[s - 1],
                recv_sem=recv_sems.at[s - 1],
                device_id=root,
                device_id_type=_LOGICAL,
            )

            @pl.when(rel == s)
            def _go(rdma=rdma):
                rdma.start()
                rdma.wait_send()


def linear_gather(x: jax.Array, axis_name: str, root: int = 0
                  ) -> jax.Array:
    """Inside shard_map: local block (chunk,) -> (n, chunk), rows
    defined at root only (MPI gather semantics)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[None]
    shape = x.shape
    flat = x.reshape(1, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, n + 1)
    kernel = functools.partial(_gather_kernel, axis_name, n, int(root))

    def call(b):
        return _vmem_call(
            kernel, (n,) + b.shape, b.dtype, axis_name,
            _peer_sems(n) + [pltpu.SemaphoreType.REGULAR], "gather")(b)

    out = _by_segments(call, _tile(flat, rows)[0], seg)
    return _untile(out, lanes).reshape((n,) + shape)


def _scatter_kernel(axis_name: str, n: int, root: int, x_ref, out_ref,
                    send_sems, recv_sems):
    """Linear scatter-from-root (reference: coll_base_scatter.c,
    ompi_coll_base_scatter_intra_basic_linear): root pushes row s of its
    buffer into rank (root+s)'s out. No readiness handshake needed —
    receivers never write their landing buffer, they only read it after
    the recv semaphore fires, so an early-landing DMA is harmless."""
    me = jax.lax.axis_index(axis_name)
    entry_barrier(_all_peers(me, n))
    rel = jax.lax.rem(me - root + n, n)

    @pl.when(rel == 0)
    def _root():
        out_ref[:] = x_ref[me]
        rdmas = []
        for s in range(1, n):
            dst_dev = jax.lax.rem(root + s, n)
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[dst_dev], dst_ref=out_ref,
                send_sem=send_sems.at[s - 1],
                recv_sem=recv_sems.at[s - 1],
                device_id=dst_dev,
                device_id_type=_LOGICAL,
            )
            rdma.start()
            rdmas.append(rdma)
        for rdma in rdmas:
            rdma.wait_send()

    @pl.when(rel != 0)
    def _receiver():
        for s in range(1, n):
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[me], dst_ref=out_ref,
                send_sem=send_sems.at[s - 1],
                recv_sem=recv_sems.at[s - 1],
                device_id=root,
                device_id_type=_LOGICAL,
            )

            @pl.when(rel == s)
            def _take(rdma=rdma):
                rdma.wait_recv()


def linear_scatter(x: jax.Array, axis_name: str, root: int = 0
                   ) -> jax.Array:
    """Inside shard_map: (n, chunk) buffer (significant at root) ->
    own block (chunk,)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[0]
    shape = x.shape[1:]
    flat = x.reshape(n, -1)
    lanes = flat.shape[1]
    rows, seg = _plan_rows(lanes, flat.dtype, n + 1)
    kernel = functools.partial(_scatter_kernel, axis_name, n, int(root))

    def call(b):
        return _vmem_call(kernel, b.shape[1:], b.dtype, axis_name,
                          _peer_sems(n), "scatter")(b)

    out = _by_segments(call, _tile(flat, rows), seg)
    return _untile(out[None], lanes)[0].reshape(shape)


def ppermute_shift(x: jax.Array, axis_name: str, shift: int = 1
                   ) -> jax.Array:
    """One ring hop as a Pallas remote DMA — the building block for
    ring attention's rotating KV blocks (SURVEY §5.7 plan: 'ring
    send-recv Pallas kernel with double-buffered ICI DMA')."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x

    def kernel(local_ref, out_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis_name)
        dst = jax.lax.rem(me + shift % n, n)
        # symmetric peer set: my destination and my source
        entry_barrier([dst, jax.lax.rem(me - shift % n + n, n)])
        rdma = pltpu.make_async_remote_copy(
            src_ref=local_ref,
            dst_ref=out_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=dst,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()

    return _flat_call(
        x, axis_name, kernel, 2,
        lambda r, dtype: [pltpu.SemaphoreType.DMA(()),
                          pltpu.SemaphoreType.DMA(())], "shift")


# ---------------------------------------------------------------------------
# Component: comm-vtable entry points over the kernels. Each rank's
# buffer is split into n ring segments so the schedule pipelines the
# whole payload (the reference's ring operates on per-rank blocks the
# same way, coll_base_allreduce.c:341).
# ---------------------------------------------------------------------------

from .framework import COLL, CollComponent, compile_plan, rank_major_check  # noqa: E402


def _split_ring(b: jax.Array, n: int) -> tuple[jax.Array, int, tuple]:
    shape = b.shape
    flat = b.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n, -1), pad, shape


def _unsplit_ring(blocks: jax.Array, pad: int, shape: tuple) -> jax.Array:
    flat = blocks.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def allreduce_block(b: jax.Array, axis_name: str, op: Any) -> jax.Array:
    """shard_map body: rank's contribution -> fully reduced buffer."""
    n = jax.lax.axis_size(axis_name)
    segs, pad, shape = _split_ring(b, n)
    out = ring_allreduce(segs, axis_name, op)
    return _unsplit_ring(out, pad, shape)


def allreduce_block_bidir(b: jax.Array, axis_name: str, op: Any
                          ) -> jax.Array:
    """shard_map body for the bidirectional ring."""
    n = jax.lax.axis_size(axis_name)
    segs, pad, shape = _split_ring(b, n)
    out = ring_allreduce_bidir(segs, axis_name, op)
    return _unsplit_ring(out, pad, shape)


def allreduce_block_chunked(b: jax.Array, axis_name: str, op: Any
                            ) -> jax.Array:
    """shard_map body for the chunked HBM-streaming ring (shards larger
    than VMEM; reference regime: segmented ring,
    coll_base_allreduce.c:618)."""
    return ring_allreduce_chunked(b, axis_name, op)


def allreduce_block_rd(b: jax.Array, axis_name: str, op: Any
                       ) -> jax.Array:
    """shard_map body for recursive doubling (small-message regime;
    reference: <10 KB cutoff, coll_tuned_decision_fixed.c:53)."""
    return ring_allreduce_rd(b, axis_name, op)


def reduce_block(b: jax.Array, axis_name: str, op: Any, root: int = 0
                 ) -> jax.Array:
    """shard_map body for binomial-tree reduce-to-root."""
    return tree_reduce(b, axis_name, op, root=root)


def allreduce_block_rsag(b: jax.Array, axis_name: str, op: Any
                         ) -> jax.Array:
    """Two-phase allreduce composed from the standalone reduce-scatter
    and allgather ring kernels. Communication-equivalent to the fused
    ring (2(n-1) steps, 1/n payload each) — NOT the reference's
    log(n) halving/doubling Rabenseifner (coll_base_allreduce.c:970) —
    but it exercises the standalone kernels as a pipeline stage pair,
    which is how TP layers consume them (psum_scatter + all_gather)."""
    n = jax.lax.axis_size(axis_name)
    segs, pad, shape = _split_ring(b, n)
    own = ring_reduce_scatter(segs, axis_name, op)
    out = ring_allgather(own, axis_name)
    return _unsplit_ring(out, pad, shape)


def bcast_block(b: jax.Array, axis_name: str, root: int = 0
                ) -> jax.Array:
    """shard_map body: every rank ends with root's block (binomial
    tree over ICI DMA)."""
    return tree_bcast(b, axis_name, root=root)


def gather_block(b: jax.Array, axis_name: str, root: int = 0
                 ) -> jax.Array:
    """shard_map body: own block -> (n, ...) gathered rows (defined at
    root), linear gather over ICI DMA."""
    return linear_gather(b, axis_name, root=root)


def scatter_block(b: jax.Array, axis_name: str, root: int = 0
                  ) -> jax.Array:
    """shard_map body: (n, ...) buffer (significant at root) -> own
    block, linear scatter over ICI DMA."""
    return linear_scatter(b, axis_name, root=root)


@COLL.register
class PallasColl(CollComponent):
    NAME = "pallas"
    PRIORITY = 30  # below coll/xla (40): opt-in via coll_select/priority
    DESCRIPTION = "hand-scheduled ICI ring kernels (Pallas remote DMA)"

    def allreduce(self, comm, x, op):
        op = op_lookup(op)
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x
        shard_bytes = (x.size // comm.size) * x.dtype.itemsize
        pof2 = comm.size & (comm.size - 1) == 0
        if shard_bytes > _chunk_threshold_var.value:
            # Large payloads stream HBM->VMEM in segments; the
            # whole-payload kernels would blow the ~16 MiB VMEM.
            body = allreduce_block_chunked
        elif shard_bytes < _rd_cutoff_var.value and pof2:
            # small-message latency regime: log2(n) rounds beats the
            # ring's 2(n-1) (reference 10 KB cutoff)
            body = allreduce_block_rd
        elif _bidir_var.value:
            body = allreduce_block_bidir
        else:
            body = allreduce_block
        key = ("allreduce", "pallas", body.__name__, op.cache_key,
               x.shape, str(x.dtype))
        if body is allreduce_block_chunked:
            # the segment size is baked into the traced kernel; a knob
            # change must not hit a stale plan
            key = key + (int(_segment_var.value),)
        plan = compile_plan(
            comm, key, lambda b: body(b, "ranks", op),
            check_vma=False,
        )
        return plan(x)

    def reduce(self, comm, x, op, root):
        """Binomial tree reduce over ICI DMA; result block at root
        (reference: coll_base_reduce.c binomial)."""
        op = op_lookup(op)
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x[0] if x.shape[0] == 1 else x[root]
        if not getattr(op, "commutative", True):
            # rank-ordered fallback (reference: non-commutative ops take
            # the linear path, coll_tuned_decision_fixed.c:85)
            return COLL.component("basic").reduce(comm, x, op, root)
        key = ("reduce", "pallas", "tree", op.cache_key, root, x.shape,
               str(x.dtype))
        plan = compile_plan(
            comm, key,
            lambda b: reduce_block(b, "ranks", op, root=root),
            check_vma=False,
        )
        return plan(x)[root]

    def bcast(self, comm, x, root):
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x
        key = ("bcast", "pallas", root, x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: bcast_block(b, "ranks", root=root),
            check_vma=False,
        )
        return plan(x)

    def allgather(self, comm, x):
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x[:, None]
        key = ("allgather", "pallas", x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: ring_allgather(b, "ranks"),
            check_vma=False,
        )
        return plan(x)

    def reduce_scatter_block(self, comm, x, op):
        op = op_lookup(op)
        x = rank_major_check(comm, x, min_ndim=2)
        if comm.size == 1:
            return x[:, 0]
        key = ("reduce_scatter_block", "pallas", op.cache_key, x.shape,
               str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: ring_reduce_scatter(b, "ranks", op),
            check_vma=False,
        )
        return plan(x)

    def gather(self, comm, x, root):
        """Linear gather over ICI DMA; rows defined at root
        (reference: coll_base_gather.c basic_linear)."""
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x[:, None][root]
        key = ("gather", "pallas", root, x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: gather_block(b, "ranks", root=root),
            check_vma=False,
        )
        return plan(x)[root]

    def scatter(self, comm, x, root):
        """Linear scatter over ICI DMA (reference: coll_base_scatter.c
        basic_linear). Root's (size, ...) buffer is staged rank-major
        (replicated rows) so the kernel sees it on-device."""
        from ..core.errors import ArgumentError

        arr = jnp.asarray(x)
        if arr.shape[0] != comm.size:
            raise ArgumentError(
                f"scatter needs (size, ...) buffer, got {arr.shape}"
            )
        if comm.size == 1:
            # rank-major (1,)+row result, matching XlaColl/TunedColl
            return comm.put_rank_major(arr)
        stacked = comm.put_rank_major(
            jnp.broadcast_to(arr[None], (comm.size,) + arr.shape)
        )
        key = ("scatter", "pallas", root, stacked.shape, str(stacked.dtype))
        plan = compile_plan(
            comm, key, lambda b: scatter_block(b, "ranks", root=root),
            check_vma=False,
        )
        return plan(stacked)

    def alltoall(self, comm, x):
        x = rank_major_check(comm, x, min_ndim=2)
        if x.shape[1] != comm.size:
            from ..core.errors import ArgumentError

            raise ArgumentError(
                f"alltoall needs (size, size, ...) buffer, got {x.shape}"
            )
        if comm.size == 1:
            return x
        key = ("alltoall", "pallas", x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: ring_alltoall(b, "ranks"),
            check_vma=False,
        )
        return plan(x)
