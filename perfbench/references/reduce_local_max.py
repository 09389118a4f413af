"""Plain reference of MPI_Reduce_local with MPI_MAX on int32: the answer
is ``max(inoutbuf, inbuf)``, elementwise, an exact integer compare, so
the limit is 0."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def _gap(inbuf, inout, out):
    # int32 differences wrap, so any wrong element reads at least 1;
    # below 2**31 apart the number is the true distance
    diff = out - jnp.maximum(inout, inbuf)
    return jnp.max(jnp.abs(diff.astype(jnp.float32)))


def gap(buf, out) -> float:
    """Largest |answer - reference| over every element; inf where the
    answer has another shape, type or chip."""
    inbuf, inout = buf
    if (getattr(out, "shape", None) != inout.shape
            or getattr(out, "dtype", None) != inout.dtype
            or set(out.devices()) != set(inout.devices())):
        return math.inf
    return float(_gap(inbuf, inout, out))


def _bf16(x):
    # an explicit rounding: XLA may keep a convert's excess precision
    return lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                mantissa_bits=7)


@jax.jit
def _control(inbuf, inout):
    return jnp.maximum(_bf16(inout), _bf16(inbuf)).astype(inout.dtype)


def control(buf):
    """The reference on inputs rounded to bfloat16, standing in the
    program's place: values up to 2**20 lose their low bits."""
    return _control(buf[0], buf[1])
