"""Plain reference of MPI_Allreduce with MPI_SUM in bfloat16.

``gap`` is ``allreduce_sum.gap``: the rank-order sum of every rank's
block, in the configuration's type, against every chip's answer. At
``value_bound`` 64 every partial sum is an integer in [-256, 252], exact
in bfloat16, so the limit is 0. The control is one precision below:
float8 e4m3, whose 3-bit significand rounds half of the integers in
[-64, 64) and more of the sums. It runs a column block at a time, as
the gap does: a rank-major bfloat16 block of 1 GiB a chip already takes
2 GiB in the chip's tiled layout, and a whole gather would not fit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from perfbench import harness

_sum = harness.load_module("references", "allreduce_sum")
gap = _sum.gap


def _e4m3(v):
    # an explicit rounding, in the array's own type
    return lax.reduce_precision(v, exponent_bits=4, mantissa_bits=3)


@functools.lru_cache(maxsize=None)
def _control_fn(devices: tuple, elems: int):
    mesh = Mesh(np.asarray(devices, dtype=object), ("r",))
    blk = math.gcd(elems, _sum.BLOCK)

    def local(xr):  # this chip's (1, elems) input
        def body(i, out):
            xb = lax.dynamic_slice_in_dim(xr, i * blk, blk, axis=1)
            every = lax.all_gather(_e4m3(xb), "r", axis=0, tiled=True)
            ref = every[0]
            for k in range(1, len(devices)):
                ref = _e4m3(ref + every[k])
            return lax.dynamic_update_slice_in_dim(out, ref[None], i * blk,
                                                   axis=1)

        return lax.fori_loop(0, elems // blk, body, jnp.zeros_like(xr))

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("r"),
                                 out_specs=P("r"), check_vma=False))


def control(x):
    """The reference in float8 e4m3 arithmetic, standing in the
    program's place."""
    return _control_fn(_sum._row_devices(x), int(x.shape[1]))(x)
