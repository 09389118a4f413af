"""coll/quant — block-scaled quantized allreduce wire tier.

Large allreduces are wire-bound: on an ICI ring the bytes each link
carries per step bound the achievable GB/s, so halving (bf16) or
quartering (int8) the bytes on the wire raises *effective* bandwidth by
the same factor at negligible accuracy cost for gradient-style sums
(EQuARX, arxiv 2506.17615; the reference MPI stack has no analog — its
wire format is always the user datatype).

Wire formats
  * ``int8``  — block-scaled: the flattened payload is cut into blocks
    of ``coll_quant_block`` elements (default 128, one VREG lane row);
    each block ships as int8 values plus ONE f32 scale
    ``max|x|_block / 127``.  Wire bytes per f32 element:
    ``1 + 4/block`` → 3.88x compression at block=128.
  * ``bf16``  — plain downcast, no scales, 2x compression.

Ring schedule (XLA fallback, runs on the CPU test mesh): the standard
bandwidth-optimal ring (coll/spmd.allreduce_ring) with the carried
partial kept in wire format between hops — each step dequantizes the
arriving block, accumulates the local contribution in f32, and requants
for the next hop ("dequant-accumulate-requant").  The allgather phase
circulates the final quantized block; every rank dequantizes once at
the end.  The fused Pallas variant runs the same schedule with the
int8 payload and the f32 scales as two parallel remote DMAs per step
(the bidirectional-ring two-DMA idiom, pallas_ring.py) and the
dequant/accumulate/requant on the VPU between hops.

Exactness rules: only unordered accumulations with bounded per-step
error go over the quantized wire — in practice SUM on floating-point
payloads.  Order statistics (MAX/MIN), non-commutative ops, joint ops
(MAXLOC) and integer dtypes are *refused* (``supports`` returns False)
and take the exact tier unchanged, so ``allreduce(max)`` through a
quant-enabled communicator stays bit-exact.  The tuned decision layer
(coll/tuned.decide_allreduce) enforces this plus the byte cutoff and
the user-rules veto; see DESIGN.md §12.

Error feedback (opt-in): quantization error is not lost — the residual
``e_t = (x + e_{t-1}) - roundtrip(x + e_{t-1})`` is carried host-side
across calls (EF-SGD lineage), so the *time-averaged* transmitted
signal converges to the exact one at O(1/t).  State lives outside the
compiled plans (they stay pure); see :class:`ErrorFeedback`.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..core import config
from ..core.counters import SPC
from ..ops import lookup as op_lookup
from ..ops.op import Op, _is_joint

__all__ = [
    "supports", "quantize_block_scaled", "dequantize_block_scaled",
    "allreduce_quant_ring", "allreduce_block_quant", "wire_bytes",
    "compression_ratio", "analytic_error_bound", "ErrorFeedback",
    "allreduce_error_feedback",
]

_V = functools.partial(config.register, "coll", "quant")
_enable_var = _V(
    "enable", type=bool, default=False,
    description="Let coll/tuned pick the quantized wire for large "
                "floating-point SUM allreduces",
)
_wire_var = _V(
    "wire", type=str, default="int8",
    description="Quantized wire format: int8 (block-scaled) or bf16 "
                "(downcast)",
)
_block_var = _V(
    "block", type=int, default=128,
    description="Elements per int8 scale block (one f32 scale each)",
)
_min_bytes_var = _V(
    "min_bytes", type=int, default=64 << 10,
    description="Per-rank payload bytes below which quant is refused "
                "(quant trades FLOPs for wire bytes; small messages "
                "are dispatch-bound, not wire-bound)",
)
_ef_var = _V(
    "error_feedback", type=bool, default=False,
    description="Carry the quantization residual across calls "
                "(opt-in; host-side state, see quant.ErrorFeedback)",
)

SPC.counter(
    "coll_quant_bytes_on_wire",
    "bytes actually shipped per hop by quantized allreduces "
    "(logical bytes land on coll_bytes via the normal path)",
    unit="bytes",
)
SPC.counter(
    "coll_quant_bytes_logical",
    "logical (unquantized) bytes the same payloads would have shipped",
    unit="bytes",
)
SPC.counter(
    "coll_quant_compression_ratio",
    "logical/wire byte ratio of the most recent quantized dispatch",
    unit="ratio",
)

_INT8_LEVELS = 127.0


def supports(op: Op | str | None, dtype: Any | None) -> bool:
    """True when (op, dtype) may take the quantized wire: a commutative
    non-joint accumulation with an XLA sum lowering over a floating
    payload.  MAX/MIN are order statistics — any representable-value
    change alters the result, so they are refused and stay exact."""
    if op is None or dtype is None:
        return False
    op = op_lookup(op)
    if not op.commutative or _is_joint(op):
        return False
    if op.xla_reduce != "psum":
        return False
    try:
        return bool(jnp.issubdtype(jnp.dtype(dtype), jnp.floating))
    except TypeError:
        return False


def wire_bytes(logical_bytes: int, itemsize: int = 4,
               wire: str | None = None, block: int | None = None) -> int:
    """Bytes on the wire for a logical payload of ``logical_bytes``."""
    wire = wire or _wire_var.value
    block = block or _block_var.value
    elems = max(1, logical_bytes // max(1, itemsize))
    if wire == "bf16":
        return elems * 2
    nblocks = -(-elems // block)
    return elems + 4 * nblocks


def compression_ratio(itemsize: int = 4, wire: str | None = None,
                      block: int | None = None) -> float:
    """Logical/wire ratio for the configured format (analytic)."""
    logical = 1 << 20
    return logical * itemsize / wire_bytes(logical * itemsize, itemsize,
                                           wire, block)


def record_wire_stats(logical_bytes: int, itemsize: int,
                      wire: str | None = None,
                      block: int | None = None) -> None:
    """SPC pvars for one quantized dispatch (host-side, at plan time)."""
    wb = wire_bytes(logical_bytes, itemsize, wire, block)
    SPC.record("coll_quant_bytes_on_wire", wb)
    SPC.record("coll_quant_bytes_logical", logical_bytes)
    SPC.counter("coll_quant_compression_ratio").set(
        logical_bytes / max(1, wb))
    from ..trace import span as tspan

    tspan.instant("quant.wire", cat="coll", logical=logical_bytes,
                  wire=wb, ratio=round(logical_bytes / max(1, wb), 3))


# ---------------------------------------------------------------------------
# Block-scaled codec (traced; used by the XLA ring, the tests and the
# error-feedback residual — the pallas kernel re-implements the same
# math on (rows, 128) tiles).
# ---------------------------------------------------------------------------

def quantize_block_scaled(x: jax.Array, block: int | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """Flat f32/bf16 ``(m,)`` payload (m % block == 0) -> (int8 ``(m,)``
    values, f32 ``(m/block,)`` scales).  scale = max|x|_block / 127;
    all-zero blocks get scale 1 so the roundtrip stays exact."""
    block = block or _block_var.value
    v = x.astype(jnp.float32).reshape(-1, block)
    m = jnp.max(jnp.abs(v), axis=1, keepdims=True)
    scale = jnp.where(m > 0, m / _INT8_LEVELS, 1.0)
    q = jnp.clip(jnp.round(v / scale), -_INT8_LEVELS, _INT8_LEVELS)
    return q.astype(jnp.int8).reshape(-1), scale.reshape(-1)


def dequantize_block_scaled(q: jax.Array, scales: jax.Array,
                            block: int | None = None) -> jax.Array:
    """Inverse of :func:`quantize_block_scaled` (f32 result)."""
    block = block or _block_var.value
    v = q.astype(jnp.float32).reshape(-1, block)
    return (v * scales.reshape(-1, 1)).reshape(-1)


def quant_roundtrip(x: jax.Array, wire: str | None = None,
                    block: int | None = None) -> jax.Array:
    """What the far side reconstructs from x's wire image (any shape)."""
    wire = wire or _wire_var.value
    if wire == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32).reshape(x.shape)
    block = block or _block_var.value
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, s = quantize_block_scaled(flat, block)
    out = dequantize_block_scaled(q, s, block)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# XLA ppermute ring (the fallback that runs on the CPU test mesh).
# Same schedule as spmd.allreduce_ring; the carried partial travels in
# wire format between hops.
# ---------------------------------------------------------------------------

def _flatten_pad_quant(x: jax.Array, n: int, block: int
                       ) -> tuple[jax.Array, int]:
    """Ravel and zero-pad so each of the n ring blocks is a whole
    number of scale blocks (element count divides n*block)."""
    flat = x.reshape(-1)
    total = flat.shape[0]
    quantum = n * block
    padded = -(-total // quantum) * quantum
    if padded != total:
        flat = jnp.pad(flat, (0, padded - total))
    return flat, total


def allreduce_quant_ring(x: jax.Array, axis_name: str, op: Any = "sum",
                         wire: str | None = None,
                         block: int | None = None) -> jax.Array:
    """Inside shard_map: quantized-wire ring allreduce of the local
    contribution ``x``.  Callers (coll/tuned, parallel/bucketer) gate
    on :func:`supports`; calling this with an unsupported op raises."""
    op = op_lookup(op)
    wire = wire or _wire_var.value
    block = block or _block_var.value
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if op.xla_reduce != "psum":
        raise ValueError(
            f"quant wire supports SUM only, got {op.name!r} "
            f"(tuned must refuse this op)"
        )
    rank = lax.axis_index(axis_name)
    flat, total = _flatten_pad_quant(x, n, block)
    blocks = flat.astype(jnp.float32).reshape(n, -1)
    m = blocks.shape[1]
    right = [(i, (i + 1) % n) for i in range(n)]

    if wire == "bf16":
        # Reduce-scatter: carry travels as bf16, accumulate in f32.
        carry = jnp.take(blocks, rank, axis=0).astype(jnp.bfloat16)
        for k in range(n - 1):
            recvd = lax.ppermute(carry, axis_name, right)
            idx = (rank - k - 1) % n
            acc = recvd.astype(jnp.float32) + jnp.take(blocks, idx, axis=0)
            carry = acc.astype(jnp.bfloat16)
        # Allgather: circulate the finished bf16 block.
        out = jnp.zeros((n, m), jnp.bfloat16)
        out = out.at[(rank + 1) % n].set(carry)
        cur = carry
        for k in range(n - 1):
            cur = lax.ppermute(cur, axis_name, right)
            out = out.at[(rank - k) % n].set(cur)
        deq = out.astype(jnp.float32)
    else:
        q, s = quantize_block_scaled(jnp.take(blocks, rank, axis=0), block)
        for k in range(n - 1):
            q = lax.ppermute(q, axis_name, right)
            s = lax.ppermute(s, axis_name, right)
            idx = (rank - k - 1) % n
            acc = dequantize_block_scaled(q, s, block) \
                + jnp.take(blocks, idx, axis=0)
            q, s = quantize_block_scaled(acc, block)
        out_q = jnp.zeros((n, m), jnp.int8)
        out_s = jnp.zeros((n, m // block), jnp.float32)
        out_q = out_q.at[(rank + 1) % n].set(q)
        out_s = out_s.at[(rank + 1) % n].set(s)
        for k in range(n - 1):
            q = lax.ppermute(q, axis_name, right)
            s = lax.ppermute(s, axis_name, right)
            out_q = out_q.at[(rank - k) % n].set(q)
            out_s = out_s.at[(rank - k) % n].set(s)
        deq = jax.vmap(
            lambda qq, ss: dequantize_block_scaled(qq, ss, block)
        )(out_q, out_s)

    return deq.reshape(-1)[:total].reshape(x.shape).astype(x.dtype)


def analytic_error_bound(per_rank: Any, axis_elems: int | None = None,
                         wire: str | None = None,
                         block: int | None = None) -> jax.Array:
    """Worst-case per-element |error| of the quantized-wire ring
    allreduce, from the GLOBAL ``(n, ...)`` stack of per-rank inputs.

    An element passes through at most n quantization events (the seed
    quantize + n-2 reduce-scatter requants + the final requant whose
    image the allgather circulates), each contributing at most half an
    int8 step of the then-current block scale.  Partial sums (and the
    errors already absorbed into them) are bounded by
    S_b = sum_r max|x_r|_block, so

        |err| <= 2 * n * S_b / 254          (int8; factor 2 absorbs the
                                             error-growth compounding)
        |err| <= 2 * n * S_b * 2**-9        (bf16 half-ulp)

    Returns the bound with the input's trailing shape.
    """
    wire = wire or _wire_var.value
    block = block or _block_var.value
    stack = jnp.asarray(per_rank, jnp.float32)
    n = stack.shape[0]
    flat = stack.reshape(n, -1)
    pad = (-flat.shape[1]) % (n * block)
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    nb = flat.shape[1] // block
    per_block_max = jnp.max(
        jnp.abs(flat).reshape(n, nb, block), axis=2
    )
    s = jnp.sum(per_block_max, axis=0)                       # (nb,)
    step = (1.0 / (2 * _INT8_LEVELS)) if wire != "bf16" else 2.0 ** -9
    bound = jnp.repeat(2.0 * n * s * step, block)
    if pad:
        bound = bound[:-pad]
    return bound.reshape(stack.shape[1:])


# ---------------------------------------------------------------------------
# Fused Pallas kernel: the same dequant-accumulate-requant ring with the
# int8 payload and the f32 scales as two parallel remote DMAs per step
# (the two-DMA-per-step idiom of pallas_ring._allreduce_bidir_kernel)
# under the same two-slot + capacity-semaphore credit flow control.
# Payload layout per ring block: (rows, 128) int8, rows % 128 == 0, one
# f32 scale per row kept as (rows/128, 128).  CPU testing requires
# Mosaic TPU-interpret mode (pallas_ring._interpret()).
# ---------------------------------------------------------------------------

def _quant_rows(x):
    """(rows, 128) f32 -> ((rows, 128) int8, (rows/128, 128) f32).

    Scale k of group g is row g*128+k's; the group axis stays leading
    so Mosaic never moves rows between sublanes and lanes."""
    g = x.shape[0] // 128
    x3 = x.reshape(g, 128, 128)
    m = jnp.max(jnp.abs(x3), axis=2)
    scale = jnp.where(m > 0, m / _INT8_LEVELS, 1.0)
    q = jnp.clip(jnp.round(x3 / scale[:, :, None]),
                 -_INT8_LEVELS, _INT8_LEVELS)
    return q.astype(jnp.int8).reshape(x.shape), scale


def _dequant_rows(q, s):
    g = s.shape[0]
    x3 = q.astype(jnp.float32).reshape(g, 128, 128) * s[:, :, None]
    return x3.reshape(q.shape)


def _quant_allreduce_kernel(axis_name, n, x_ref, out_ref,
                            buf_q, buf_s,
                            ssem_q, rsem_q, csem_q,
                            ssem_s, rsem_s, csem_s):
    """Ring allreduce over the quantized wire: 2(n-1) steps, each
    moving one int8 block + its scale row-group to the right neighbor
    as two DMAs issued back-to-back (both in flight before either is
    awaited), with dequant-accumulate-requant between hops."""
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_ring as pr

    me = lax.axis_index(axis_name)
    right = lax.rem(me + 1, n)
    left = lax.rem(me - 1 + n, n)
    pr.entry_barrier(pr._ring_peers(me, n))

    first = lax.rem(me - 1 + n, n)
    q0, s0 = _quant_rows(x_ref[first])
    buf_q[0] = q0
    buf_s[0] = s0
    # Post-seed credit for each buffer's slot 0 (pallas_ring credit
    # flow: gates the upstream step-1 write; no implicit entry barrier).
    for csem in (csem_q, csem_s):
        pltpu.semaphore_signal(csem.at[0], inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)

    for step in range(2 * (n - 1)):
        slot = step % 2
        nslot = (step + 1) % 2
        if step >= 1:
            pltpu.semaphore_wait(csem_q.at[nslot], 1)
            pltpu.semaphore_wait(csem_s.at[nslot], 1)
        dma_q = pltpu.make_async_remote_copy(
            src_ref=buf_q.at[slot], dst_ref=buf_q.at[nslot],
            send_sem=ssem_q.at[slot], recv_sem=rsem_q.at[nslot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        dma_s = pltpu.make_async_remote_copy(
            src_ref=buf_s.at[slot], dst_ref=buf_s.at[nslot],
            send_sem=ssem_s.at[slot], recv_sem=rsem_s.at[nslot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        dma_q.start()
        dma_s.start()
        dma_q.wait()
        dma_s.wait()
        if step < n - 1:
            blk = lax.rem(me - step - 2 + 2 * n, n)
            acc = _dequant_rows(buf_q[nslot], buf_s[nslot]) + x_ref[blk]
            qn, sn = _quant_rows(acc)
            comm_done = step == n - 2
            buf_q[nslot] = qn
            buf_s[nslot] = sn
            if comm_done:
                # First finished block: dequantized locally; its WIRE
                # image is what the allgather phase circulates, so all
                # ranks reconstruct identical values.
                out_ref[blk] = _dequant_rows(qn, sn)
        else:
            blk = lax.rem(me - (step - (n - 1)) - 1 + 2 * n, n)
            out_ref[blk] = _dequant_rows(buf_q[nslot], buf_s[nslot])
        if step < 2 * (n - 1) - 2:
            for csem in (csem_q, csem_s):
                pltpu.semaphore_signal(
                    csem.at[nslot], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )


def allreduce_block_quant(b: jax.Array, axis_name: str, op: Any = "sum"
                          ) -> jax.Array:
    """shard_map body: local contribution -> fully reduced buffer over
    the fused Pallas quantized ring (int8 wire, per-128-lane scales)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_ring as pr

    op = op_lookup(op)
    if op.xla_reduce != "psum":
        raise ValueError(f"quant pallas ring supports SUM only, "
                         f"got {op.name!r}")
    n = lax.axis_size(axis_name)
    if n == 1:
        return b
    shape = b.shape
    flat = b.astype(jnp.float32).reshape(-1)
    # Each ring block: (rows, 128) with rows % 128 == 0 so the f32
    # scale-per-row group lays out as whole (rows/128, 128) tiles.
    lanes = -(-flat.size // n)
    rows, seg = pr._plan_rows(lanes, jnp.float32, 2 * n + 1, quantum=128)
    pad = n * rows * 128 - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    kernel = functools.partial(_quant_allreduce_kernel, axis_name, n)

    def call(blocks):
        r = blocks.shape[-2]
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(blocks.shape, jnp.float32,
                                           vma=frozenset({axis_name})),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, r, 128), jnp.int8),
                pltpu.VMEM((2, r // 128, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR((2,)),
            ],
            compiler_params=pr._params("quant"),
            interpret=pr._interpret(),
        )(blocks)

    out = pr._by_segments(call, flat.reshape(n, rows, 128), seg)
    flat_out = out.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(shape).astype(b.dtype)


# ---------------------------------------------------------------------------
# Error feedback (opt-in, host-side state across calls).
# ---------------------------------------------------------------------------

class ErrorFeedback:
    """Carries the quantization residual across repeated allreduces of
    the same logical tensor (gradient steps): each call compensates the
    input with the previous residual, transmits the wire image of the
    compensated value, and keeps the new residual

        e_t = (x_t + e_{t-1}) - roundtrip(x_t + e_{t-1}).

    Telescoping gives sum_t transmitted = sum_t x_t + e_{-1} - e_T with
    ``e_T`` bounded by one quantization step — the time-averaged
    transmitted signal converges to the exact one at O(1/t).  State is
    per-instance and host-side; the compiled collective plans stay
    pure (DESIGN.md §12)."""

    def __init__(self, wire: str | None = None,
                 block: int | None = None) -> None:
        self.wire = wire
        self.block = block
        self.residual = None

    @staticmethod
    def enabled_by_config() -> bool:
        return bool(_ef_var.value)

    def compensate(self, x: jax.Array) -> jax.Array:
        """Return the value to transmit for ``x`` (the wire roundtrip
        of the residual-compensated input) and update the residual."""
        xc = jnp.asarray(x, jnp.float32)
        if self.residual is not None:
            xc = xc + self.residual
        sent = quant_roundtrip(xc, self.wire, self.block)
        self.residual = xc - sent
        return sent.astype(jnp.asarray(x).dtype)

    def residual_norm(self) -> float:
        if self.residual is None:
            return 0.0
        return float(jnp.linalg.norm(self.residual.reshape(-1)))


def allreduce_error_feedback(comm, x, state: ErrorFeedback,
                             op: Any = "sum"):
    """Vtable allreduce of the EF-compensated wire image of ``x`` (a
    rank-major ``(size, ...)`` buffer; the residual is elementwise, so
    one state instance covers all rank rows)."""
    return comm.allreduce(state.compensate(x), op)
