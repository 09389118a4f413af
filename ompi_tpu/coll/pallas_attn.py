"""Fused ring-attention Pallas kernel: compute/DMA overlap on ICI.

SURVEY §5.7's plan realized: "ring send-recv as a Pallas kernel with
double-buffered ICI DMA + per-step compute callback". The XLA-level
ring attention (parallel/sp.py) circulates KV blocks with ppermute and
*hopes* XLA overlaps the hop with the flash compute; this kernel
GUARANTEES the overlap — each step starts the remote DMA shipping the
current KV block to the right neighbor, runs the online-softmax block
update on the MXU/VPU while the block is in flight, then waits the DMA.

The communication protocol is the capacity-credit double-buffered ring
of coll/pallas_ring (reference lineage: the ring pass of
coll_base_allreduce.c:341 plus btl_sm_fbox.h:22-60-style flow control):
credits flow from each receiver to its upstream sender, granting reuse
of a KV slot only after the slot was both computed on and forwarded.

Shape constraints (compiled mode): T divisible by the dtype sublane
tile, Dh divisible by 128 — the wrapper falls back to the XLA
implementation otherwise. The whole (2*T, H, Dh) KV slot pair plus the
f32 accumulators must fit VMEM; long-context shards beyond that use
the XLA path (which streams through HBM).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ring import (_interpret, _params, _ring_peers, _sublane,
                          entry_barrier)

_NEG = -1e30


def _ring_attn_kernel(axis_name: str, n: int, causal: bool, scale: float,
                      nheads: int, tq: int,
                      q_ref, k_ref, v_ref, o_ref,
                      kv_buf, m_scr, l_scr, o_scr,
                      send_sem, recv_sem, cap_sem):
    me = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    if n > 1:
        entry_barrier(_ring_peers(me, n))

    # Seed slot 0 with the local KV block (K stacked over V).
    kv_buf[0, :tq] = k_ref[:]
    kv_buf[0, tq:] = v_ref[:]
    # Initial credit: my buf[1] is free — grant my upstream neighbor
    # its step-0 send (credits are about MY slots, granted to LEFT;
    # the ones I wait on come from RIGHT about ITS slots).
    if n > 1:
        pltpu.semaphore_signal(cap_sem.at[1], inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)

    # Online-softmax accumulators (f32).
    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    o_scr[...] = jnp.zeros_like(o_scr)

    row = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)

    def compute(slot: int, src):
        """Fold the KV block in `slot` (originally rank `src`'s) into
        the accumulators — the per-step compute that overlaps the DMA."""
        kb = kv_buf[slot, :tq]   # (T, H, Dh)
        vb = kv_buf[slot, tq:]
        for h in range(nheads):
            qh = q_ref[:, h, :].astype(jnp.float32)       # (Tq, Dh)
            kh = kb[:, h, :].astype(jnp.float32)          # (Tk, Dh)
            vh = vb[:, h, :].astype(jnp.float32)
            scores = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                     # (Tq, Tk)
            if causal:
                mask = (me * tq + row) >= (src * tq + col)
                scores = jnp.where(mask, scores, _NEG)
            mh = m_scr[h]                                 # (Tq,)
            blk_max = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(mh, blk_max)
            corr = jnp.exp(mh - m_new)
            p = jnp.exp(scores - m_new[:, None])
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1)
            o_scr[h] = o_scr[h] * corr[:, None] + jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new

    for step in range(n):
        slot = step % 2
        nslot = (step + 1) % 2
        src = jax.lax.rem(me - step + n, n)
        rdma = None
        if step < n - 1:
            # Permission to write RIGHT's buf[nslot] (its credit).
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=kv_buf.at[slot],
                dst_ref=kv_buf.at[nslot],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[nslot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
        compute(slot, src)            # overlaps the in-flight DMA
        if rdma is not None:
            rdma.wait()               # send drained + next block landed
            if step < n - 2:
                # buf[slot] fully consumed (computed + forwarded):
                # left may overwrite it at its step+1.
                pltpu.semaphore_signal(
                    cap_sem.at[slot], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )

    for h in range(nheads):
        denom = jnp.maximum(l_scr[h], 1e-30)[:, None]
        o_ref[:, h, :] = (o_scr[h] / denom).astype(o_ref.dtype)


# Conservative VMEM budget for the kernel's working set (~16 MiB real
# VMEM minus headroom for Mosaic's own staging).
_VMEM_BUDGET = 12 << 20


def supported(q: jax.Array) -> bool:
    """Whether the fused kernel can take this shape in compiled mode:
    tile alignment (T on the dtype sublane, Dh on the 128-lane tile)
    AND the whole working set — double-buffered KV pair, q/output, f32
    accumulators — fitting the VMEM budget. Callers fall back to the
    streaming XLA implementation otherwise (also applied in interpret
    mode, where the constraints are moot, to keep path selection
    deterministic across backends)."""
    t, h, dh = q.shape
    if t % _sublane(q.dtype) != 0 or dh % 128 != 0:
        return False
    itemsize = jnp.dtype(q.dtype).itemsize
    working = (
        2 * 2 * t * h * dh * itemsize   # kv_buf double buffer
        + 4 * t * h * dh * itemsize     # q, k, v inputs + output
        + h * t * dh * 4                # o accumulator (f32)
        + 2 * h * t * 4                 # m, l accumulators (f32)
    )
    return working <= _VMEM_BUDGET


def ring_attention_block(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, causal: bool = True
                         ) -> jax.Array:
    """Inside shard_map: (T, H, Dh) local q/k/v -> (T, H, Dh) outputs
    for this rank's query block, exact over the full ring."""
    n = jax.lax.axis_size(axis_name)
    t, h, dh = q.shape
    scale = 1.0 / float(dh) ** 0.5
    kernel = functools.partial(_ring_attn_kernel, axis_name, n,
                               bool(causal), scale, h, t)
    # a 1-member ring has no remote peer and so no entry barrier
    params = _params(None if n == 1 else "attn")
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, h, dh), q.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, 2 * t, h, dh), q.dtype),   # double-buffered KV
            pltpu.VMEM((h, t), jnp.float32),          # running max
            pltpu.VMEM((h, t), jnp.float32),          # running denom
            pltpu.VMEM((h, t, dh), jnp.float32),      # running output
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=params,
        interpret=_interpret(),
    )(q, k, v)
