"""Plain reference of MPI_Reduce_local with MPI_SUM: the answer is
``inoutbuf + inbuf``, elementwise, in the configuration's type. One
IEEE add per element, so the answer is exact and the limit is 0."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def _gap(inbuf, inout, out):
    ref = inout + inbuf
    return jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))


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
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@jax.jit
def _control(inbuf, inout):
    return _bf16(_bf16(inout) + _bf16(inbuf))


def control(buf):
    """The reference in bfloat16 arithmetic, standing in the program's
    place."""
    return _control(buf[0], buf[1])
