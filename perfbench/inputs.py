"""Inputs made on the device from the run's seed.

Values are integers in [-bound, bound) stored in the configuration's
float type: any order of summing four of them is exact in float32, so
every reduction order the program may pick gives one answer, and a
lower precision does not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def base_key(seed: int):
    """A PRNG key for any non-negative seed, 64 bits of it used."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def derive(key, *path: int):
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def _mix(x):
    """A 32-bit integer hash (lowbias32): every input bit moves every
    output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def int_valued(key, shape: tuple, bound: int, dtype: str, sharding):
    """One integer-valued array in [-bound, bound), laid out by
    ``sharding``; traced into :func:`make_all`. Each value is a hash of
    its element index and ``key``: a few integer ops per element, where
    ``jax.random.randint``'s program takes seconds to compile and to load
    from the cache. ``bound`` is a power of two."""
    shape, bound = tuple(shape), int(bound)
    if bound <= 0 or bound & (bound - 1):
        raise ValueError(f"value bound {bound} is not a power of two")
    index = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in reversed(range(len(shape))):
        index = index + lax.broadcasted_iota(jnp.uint32, shape, d) \
            * jnp.uint32(stride)
        stride *= shape[d]
    words = jax.random.key_data(key).astype(jnp.uint32)
    bits = _mix((index * jnp.uint32(0x9E3779B1) + words[0]) ^ words[1])
    ints = (bits & jnp.uint32(2 * bound - 1)).astype(jnp.int32) - bound
    return lax.with_sharding_constraint(ints.astype(dtype), sharding)


def make_all(make, key, plan: list) -> list:
    """Every input of a run in one jitted call, born on its devices:
    ``make(derive(key, *path), nbytes)`` for each ``(path, nbytes)`` of
    ``plan``, in order."""
    def build(base):
        return [make(derive(base, *path), nbytes) for path, nbytes in plan]

    return jax.block_until_ready(jax.jit(build)(key))
