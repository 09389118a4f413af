"""The Pallas kernels of the collective path, compiled by the TPU's own
compiler for a described (not attached) v5e:2x2 host.

Interpret mode on the CPU mesh checks what the kernels compute; only
the chip's compiler (Mosaic) checks that it takes them: tile-aligned
slices, VMEM within budget, barrier semaphores where ``collective_id``
is set. Each case lowers one kernel inside ``shard_map`` over the four
described devices and asserts the compiled program carries the kernel
(``tpu_custom_call``).

The topology is described in a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports
this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ompi_tpu.coll import pallas_ring, quant, sched
from ompi_tpu.core import config

MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU library would otherwise log under the system temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # commlint: allow(broadexcept)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_compiler(topo):
    """Compile for the chip: kernels lowered for Mosaic (not interpret
    mode, which ``_interpret()`` picks on the CPU backend), and no
    persistent cache (a TPU executable cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    var = config.VARS.lookup("coll_pallas_interpret")
    saved = (var.value, var.source)
    config.set("coll_pallas_interpret", False)
    try:
        yield topo
    finally:
        var.value, var.source = saved
        jax.config.update("jax_enable_compilation_cache", was_cache)
        compilation_cache.reset_cache()


def _compile(topo, body, per_rank_elems: int, dtype, n: int = 4):
    """Compile ``body(block, axis, op)`` per rank over ``n`` described
    devices; returns the compiled program's text."""
    mesh = Mesh(np.array(topo.devices[:n]), ("ranks",))
    fn = jax.shard_map(
        lambda b: body(b[0], "ranks", "sum")[None],
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
        check_vma=False)
    x = jax.ShapeDtypeStruct((n, per_rank_elems), dtype,
                             sharding=NamedSharding(mesh, P("ranks")))
    return jax.jit(fn).lower(x).compile().as_text()


def _sched_ring4(b, axis, op):
    return sched.allreduce_sched_pallas_ring(b, axis, op)


def _rs(b, axis, op):
    return pallas_ring.ring_reduce_scatter(b.reshape(4, -1), axis, op)


def _ag(b, axis, op):
    return pallas_ring.ring_allgather(b, axis).reshape(-1)


def _alltoall(b, axis, op):
    return pallas_ring.ring_alltoall(b.reshape(4, -1), axis).reshape(-1)


def _gather(b, axis, op):
    return pallas_ring.linear_gather(b, axis, root=1).reshape(-1)


def _scatter(b, axis, op):
    return pallas_ring.linear_scatter(b.reshape(4, -1), axis, root=2)


def _bcast(b, axis, op):
    return pallas_ring.tree_bcast(b, axis, root=3)


def _reduce(b, axis, op):
    return pallas_ring.tree_reduce(b, axis, op, root=1)


def _shift(b, axis, op):
    return pallas_ring.ppermute_shift(b, axis, -1)


_CASES = {
    "alltoall_f32_1MiB": (_alltoall, MiB // 4, jnp.float32),
    "gather_f32_1MiB": (_gather, MiB // 4, jnp.float32),
    "scatter_f32_1MiB": (_scatter, MiB // 4, jnp.float32),
    "bcast_bf16_1MiB": (_bcast, MiB // 2, jnp.bfloat16),
    "tree_reduce_f32_1MiB": (_reduce, MiB // 4, jnp.float32),
    "shift_f32_1MiB": (_shift, MiB // 4, jnp.float32),
    "chunked_f32_1MiB": (pallas_ring.ring_allreduce_chunked, MiB // 4,
                         jnp.float32),
    "vmem_ring_f32_1MiB": (pallas_ring.allreduce_block, MiB // 4,
                           jnp.float32),
    "vmem_ring_bf16_1MiB": (pallas_ring.allreduce_block, MiB // 2,
                            jnp.bfloat16),
    "vmem_ring_f32_64MiB": (pallas_ring.allreduce_block, 16 * MiB,
                            jnp.float32),
    "rd_f32_4KiB": (pallas_ring.allreduce_block_rd, 1024, jnp.float32),
    "bidir_f32_1MiB": (pallas_ring.allreduce_block_bidir, MiB // 4,
                       jnp.float32),
    "rsag_bf16_1MiB": (pallas_ring.allreduce_block_rsag, MiB // 2,
                       jnp.bfloat16),
    "reduce_scatter_f32_1MiB": (_rs, MiB // 4, jnp.float32),
    "allgather_f32_1MiB": (_ag, MiB // 4, jnp.float32),
    "sched_pallas_ring_f32_1MiB": (_sched_ring4, MiB // 4, jnp.float32),
    "sched_pallas_ring_bf16_1MiB": (_sched_ring4, MiB // 2,
                                    jnp.bfloat16),
    "quant_f32_1Mi_elems": (quant.allreduce_block_quant, MiB,
                            jnp.float32),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e_2x2(chip_compiler, case):
    body, elems, dtype = _CASES[case]
    text = _compile(chip_compiler, body, elems, dtype)
    assert "tpu_custom_call" in text, case


def test_selfdma_kernel_compiles_for_one_chip_at_64MiB(chip_compiler):
    """The one-chip proof kernel: the chunked ring's self-DMA variant
    (n == 1, no entry barrier) at 64 MiB."""
    dev = chip_compiler.devices[0]
    mesh = Mesh(np.array([dev]), ("x",))
    fn = jax.shard_map(
        lambda b: pallas_ring.ring_allreduce_chunked(b[0], "x")[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    x = jax.ShapeDtypeStruct((1, 16 * MiB), jnp.float32,
                             sharding=SingleDeviceSharding(dev))
    text = jax.jit(fn).lower(x).compile().as_text()
    assert "tpu_custom_call" in text


def test_every_kernel_has_its_own_barrier_id():
    ids = list(pallas_ring.COLLECTIVE_IDS.values())
    assert len(ids) == len(set(ids))


def test_ring_attention_kernel_compiles_for_v5e_2x2(chip_compiler):
    """The fused ring-attention kernel (sequence parallelism's KV ring)
    at T=256, H=4, Dh=128 per rank, bf16."""
    from ompi_tpu.coll import pallas_attn

    mesh = Mesh(np.array(chip_compiler.devices[:4]), ("ranks",))
    fn = jax.shard_map(
        lambda q, k, v: pallas_attn.ring_attention_block(
            q[0], k[0], v[0], "ranks")[None],
        mesh=mesh, in_specs=(P("ranks"),) * 3, out_specs=P("ranks"),
        check_vma=False)
    x = jax.ShapeDtypeStruct((4, 256, 4, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("ranks")))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
