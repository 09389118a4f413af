"""On-device reduction helpers — the execution engine behind the op
framework.

TPU-native replacement for the reference's CPU SIMD reduction loops
(reference: ompi/mca/op/avx/op_avx_functions.c:28-66 — per-(op × dtype)
AVX512/AVX2/SSE variants with runtime dispatch). Here the "dispatch
table" is the XLA compile cache: each (op, shape, dtype) combination jits
once and thereafter runs as a fused VPU/MXU kernel against HBM-resident
buffers.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from ..trace.span import Span
from .op import Op, lookup


def reduce_local(op: "Op | str", inbuf: Any, inoutbuf: Any) -> Any:
    """MPI_Reduce_local: combine two buffers on-device
    (reference: ompi/op + test/datatype/reduce_local.c)."""
    with Span("op.reduce_local", "op"):
        op = lookup(op)
        return op.combine(inoutbuf, inbuf)


def reduce_ranks(x, op: "Op | str"):
    """Reduce a (n_ranks, ...) stacked buffer down its leading axis with
    the op's combine — the compute kernel of every reduction collective
    (what the reference runs on CPU per segment, SURVEY §3.3 hot loop).
    Shares the rank-order-preserving tree fold the collectives execute.
    """
    op = lookup(op)
    if op.xla_reduce == "psum":
        return jnp.sum(x, axis=0)
    from ..coll.spmd import _tree_reduce_ranks  # lazy: avoids cycle

    return _tree_reduce_ranks(x, x.shape[0], op)
