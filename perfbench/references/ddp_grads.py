"""Plain reference of the DDP cell's two calls: leaf by leaf, with no
bucketing, using nothing of the program under test.

- Four chips (the buffer is the gradient list, rank-major leaves):
  every rank's answer for each leaf is the sum of every rank's block
  of that leaf. ``lax.all_gather`` moves the leaf's blocks onto every
  chip (it moves bits and adds nothing), they are summed in rank order
  in the configuration's type, and compared with the answer that chip
  holds.
- One chip (the buffer is ``(micro-batch gradients, accumulator)``):
  each leaf of the answer is ``acc + g``.

Inputs are integers below 2**20, so every summation order is exact and
the limit is 0. A wrong shape, type, set of chips or number of leaves
reads inf; a leaf in another leaf's place reads its difference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _bf16(v):
    # an explicit rounding: XLA may keep a convert's excess precision
    return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _row_devices(x) -> tuple:
    shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
    return tuple(s.device for s in shards)


def _same_layout(xs: list, out) -> bool:
    return (isinstance(out, list) and len(out) == len(xs)
            and all(getattr(o, "shape", None) == x.shape
                    and getattr(o, "dtype", None) == x.dtype
                    and set(o.devices()) == set(x.devices())
                    for x, o in zip(xs, out)))


@functools.lru_cache(maxsize=None)
def _per_rank(devices: tuple, fn, with_answer: bool):
    """``fn(every, answer)`` (or ``fn(every)``) on each chip, where
    ``every`` is the leaf's blocks of every rank, gathered onto that
    chip, and ``answer`` the chip's own block of the answer."""
    mesh = Mesh(np.asarray(devices, dtype=object), ("r",))

    def local(xr, *outr):
        every = lax.all_gather(xr, "r", axis=0, tiled=True)
        return fn(every, *(o[0] for o in outr))[None]

    specs = (P("r"), P("r")) if with_answer else (P("r"),)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=specs,
                                 out_specs=P("r"), check_vma=False))


def _rank_sum_gap(every, answer):
    ref = every[0]
    for k in range(1, every.shape[0]):
        ref = ref + every[k]
    return jnp.max(jnp.abs(answer.astype(jnp.float32)
                           - ref.astype(jnp.float32)))


def _rank_sum_bf16(every):
    ref = _bf16(every[0])
    for k in range(1, every.shape[0]):
        ref = _bf16(ref + _bf16(every[k]))
    return ref


@jax.jit
def _accumulate_gap(grads, acc, out):
    return functools.reduce(jnp.maximum, [
        jnp.max(jnp.abs(o.astype(jnp.float32)
                        - (a + g).astype(jnp.float32)))
        for g, a, o in zip(grads, acc, out)])


@jax.jit
def _accumulate_bf16(grads, acc):
    return [_bf16(_bf16(a) + _bf16(g)) for g, a in zip(grads, acc)]


def gap(buf, out) -> float:
    """Largest |answer - reference| over every element of every leaf (of
    every rank)."""
    if isinstance(buf, tuple):
        grads, acc = buf
        if not _same_layout(acc, out):
            return math.inf
        return float(_accumulate_gap(grads, acc, out))
    if not _same_layout(buf, out):
        return math.inf
    per_leaf = [_per_rank(_row_devices(x), _rank_sum_gap, True)(x, o)
                for x, o in zip(buf, out)]
    return max(float(jnp.max(g)) for g in per_leaf)


def control(buf):
    """The reference in bfloat16 arithmetic, standing in the program's
    place."""
    if isinstance(buf, tuple):
        return _accumulate_bf16(*buf)
    return [_per_rank(_row_devices(x), _rank_sum_bf16, False)(x)
            for x in buf]
