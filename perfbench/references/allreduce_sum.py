"""Plain reference of MPI_Allreduce with MPI_SUM.

Every rank's answer is the sum of every rank's input. The reference
gathers each rank's block, a column block at a time, onto every chip
(``lax.all_gather``: it moves bits and adds nothing), sums it in rank
order in the configuration's type, and compares it with the answer
that chip holds. It uses nothing of the program under test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

BLOCK = 1 << 22  # elements per rank compared per step (16 MiB of f32)


def _row_devices(x) -> tuple:
    shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
    return tuple(s.device for s in shards)


@functools.lru_cache(maxsize=None)
def _gap_fn(devices: tuple, elems: int):
    mesh = Mesh(np.asarray(devices, dtype=object), ("r",))
    n = len(devices)
    blk = math.gcd(elems, BLOCK)
    steps = elems // blk

    def local(xr, outr):  # this chip's (1, elems) input and answer
        def body(i, worst):
            xb = lax.dynamic_slice_in_dim(xr, i * blk, blk, axis=1)
            every = lax.all_gather(xb, "r", axis=0, tiled=True)
            ref = every[0]
            for k in range(1, n):
                ref = ref + every[k]
            ob = lax.dynamic_slice_in_dim(outr, i * blk, blk, axis=1)[0]
            diff = jnp.abs(ob.astype(jnp.float32) - ref.astype(jnp.float32))
            return jnp.maximum(worst, jnp.max(diff))

        worst = lax.fori_loop(0, steps, body, jnp.zeros((), jnp.float32))
        return worst[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P("r"), P("r")),
                                 out_specs=P("r"), check_vma=False))


def gap(x, out) -> float:
    """Largest |answer - reference| over every element of every rank;
    inf where the answer has another shape, type or set of chips."""
    if (getattr(out, "shape", None) != x.shape
            or getattr(out, "dtype", None) != x.dtype
            or set(out.devices()) != set(x.devices())):
        return math.inf
    devices = _row_devices(x)
    per_chip = _gap_fn(devices, int(x.shape[1]))(x, out)
    return float(jnp.max(per_chip))


@functools.lru_cache(maxsize=None)
def _control_fn(devices: tuple):
    mesh = Mesh(np.asarray(devices, dtype=object), ("r",))

    def bf16(v):
        # an explicit rounding: XLA may keep a convert's excess precision
        return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def local(xr):
        every = lax.all_gather(bf16(xr), "r", axis=0, tiled=True)
        ref = every[0]
        for k in range(1, len(devices)):
            ref = bf16(ref + every[k])
        return ref[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("r"),
                                 out_specs=P("r"), check_vma=False))


def control(x):
    """The reference in bfloat16 arithmetic, standing in the program's
    place."""
    return _control_fn(_row_devices(x))(x)
