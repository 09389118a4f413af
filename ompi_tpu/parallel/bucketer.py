"""parallel/bucketer — gradient bucket coalescing for data parallelism.

A transformer step produces hundreds of gradient leaves, and BENCH host
rows show per-call dispatch overhead dominating collectives below
~64 KiB — so reducing each leaf separately pays that overhead hundreds
of times per step.  The coalescer flattens the gradient pytree into a
few size-capped flat buckets (cvar ``parallel_dp_bucket_bytes``) and
issues ONE allreduce per bucket, so the whole decision stack — tuned's
algorithm table, hier's same-host split, the pallas kernels and the
quantized wire tier (coll/quant) — schedules per *bucket*, at bucket
size, instead of per leaf (the fusion T3/arxiv 2401.16677 motivates;
torch's DDP gradient buckets are the mainstream analog).

Determinism and ordering guarantees (DESIGN.md §12):
  * Bucket composition is a pure function of (pytree structure, leaf
    shapes/dtypes, bucket_bytes): leaves are taken in ``jax.tree``
    flatten order, grouped by dtype (preserving order inside each
    group), concatenated, and cut at element boundaries — never
    mid-element, never reordered.  Repeated calls with the same inputs
    bucket identically, so error-feedback residuals stay aligned.
  * Values are bit-identical to per-leaf dispatch for the exact tiers:
    an elementwise reduction of a concatenation is the concatenation of
    the reductions — per-element operation order is unchanged.

Two entry points mirror the two calling contexts:
  * :func:`allreduce_tree` — traced, inside shard_map/jit (the
    transformer train step); dispatches each bucket through
    ``coll.tuned.allreduce_by_decision``.
  * :func:`allreduce_pytree` — host-side, rank-major buffers through
    the comm vtable (``comm.allreduce`` per bucket), with optional
    error feedback.  It plans from the leaves' shapes and dtypes only,
    and keeps the plan with its jitted pack and unpack programs
    (``_pack_buckets`` / ``_unpack_buckets``, outputs on the
    communicator's rank sharding) per communicator and structure: a
    repeat step costs one dict lookup before the pack launch, and each
    bucket takes the communicator's allreduce lane.  Spans:
    ``coll.allreduce_pytree`` over the call, ``coll.pack`` and
    ``coll.unpack`` around the two launches, the buckets'
    ``coll.allreduce`` spans under the root.
"""

from __future__ import annotations

import math
import weakref
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import config
from ..core.counters import SPC
from ..trace.span import Span

_bucket_bytes_var = config.register(
    "parallel", "dp", "bucket_bytes",
    type=int, default=4 << 20,
    description="Max bytes per fused gradient-allreduce bucket "
                "(0 disables fusion: one dispatch per leaf)",
)

SPC.counter(
    "parallel_dp_bucket_dispatches",
    "fused gradient buckets dispatched (one collective each)",
)
SPC.counter(
    "parallel_dp_bucket_leaves",
    "gradient leaves coalesced into buckets",
)
SPC.counter(
    "parallel_dp_plan_builds",
    "host-side bucket plans built (a pytree structure seen for the "
    "first time on a communicator)",
)


class Bucket(NamedTuple):
    """One planned bucket: ``leaf_ids`` index the flattened pytree;
    ``elems`` is the flat element count of the bucket's payload."""
    dtype: Any
    elems: int
    #: (leaf_id, lo, hi): leaf's flat slice [lo, hi) lives in this
    #: bucket at the running offset (a leaf larger than the cap spans
    #: consecutive buckets).
    pieces: tuple


def _spec(leaf) -> tuple:
    """(shape, canonical dtype) of a leaf, read without touching its
    data."""
    if not hasattr(leaf, "dtype"):
        leaf = jnp.asarray(leaf)  # a Python scalar
    return tuple(np.shape(leaf)), jax.dtypes.canonicalize_dtype(leaf.dtype)


def _plan(specs: list, bucket_bytes: int) -> list[Bucket]:
    groups: dict = {}
    for i, (_, dt) in enumerate(specs):
        groups.setdefault(str(dt), (dt, []))[1].append(i)
    plans: list[Bucket] = []
    for _, (dt, ids) in sorted(groups.items()):
        fused = bucket_bytes > 0
        cap = max(1, bucket_bytes // dt.itemsize) if fused else 0
        pieces: list = []
        elems = 0
        for i in ids:
            size = math.prod(specs[i][0])
            lo = 0
            while lo < size or size == 0:
                take = min(size - lo, cap - elems) if fused else size
                pieces.append((i, lo, lo + take))
                elems += take
                lo += take
                if fused and elems >= cap:
                    plans.append(Bucket(dt, elems, tuple(pieces)))
                    pieces, elems = [], 0
                if size == 0:
                    break
            if not fused and pieces:
                # Fusion disabled: one bucket (dispatch) per leaf.
                plans.append(Bucket(dt, elems, tuple(pieces)))
                pieces, elems = [], 0
        if pieces:
            plans.append(Bucket(dt, elems, tuple(pieces)))
    return plans


def plan_buckets(tree: Any, bucket_bytes: Optional[int] = None
                 ) -> list[Bucket]:
    """Deterministic bucket plan for a pytree (shapes only, no data).
    The plan length IS the collective-dispatch count of a fused
    allreduce of ``tree``."""
    if bucket_bytes is None:
        bucket_bytes = _bucket_bytes_var.value
    return _plan([_spec(leaf) for leaf in jax.tree.leaves(tree)],
                 bucket_bytes)


def _gather_bucket(leaves: list, bucket: Bucket, flat_axis: int):
    parts = [
        leaves[i].reshape(leaves[i].shape[:flat_axis] + (-1,))[..., lo:hi]
        for i, lo, hi in bucket.pieces
    ]
    return jnp.concatenate(parts, axis=-1)


def _split_buckets(plan: list, reduced: list, shapes: list) -> list:
    """The leaves back from their buckets: each leaf's pieces joined in
    order and given ``shapes[i]`` (leading axes included)."""
    parts: dict = {}
    for bucket, r in zip(plan, reduced):
        off = 0
        for i, lo, hi in bucket.pieces:
            parts.setdefault(i, []).append(r[..., off:off + (hi - lo)])
            off += hi - lo
    return [jnp.concatenate(parts[i], axis=-1).reshape(shape)
            for i, shape in enumerate(shapes)]


def allreduce_tree(tree: Any, axis_name: str, op: Any = "sum",
                   bucket_bytes: Optional[int] = None,
                   allow_quant: Optional[bool] = None) -> Any:
    """Traced fused allreduce of a gradient pytree over ``axis_name``
    (inside shard_map/jit): one collective per planned bucket, each
    routed through coll/tuned's decision (so the quant tier and the
    explicit algorithms apply per bucket).  SPC bucket counters are
    recorded at trace time — they count collectives in the compiled
    program, not executions."""
    from ..coll import tuned

    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    leaves = [jnp.asarray(leaf) for leaf in leaves]
    plan = plan_buckets(leaves, bucket_bytes)
    SPC.record("parallel_dp_bucket_leaves", len(leaves))
    reduced = []
    for bucket in plan:
        payload = _gather_bucket(leaves, bucket, 0)
        reduced.append(tuned.allreduce_by_decision(
            payload, axis_name, op, allow_quant=allow_quant))
        SPC.record("parallel_dp_bucket_dispatches")
    return jax.tree.unflatten(
        treedef, _split_buckets(plan, reduced, [l.shape for l in leaves]))


class _Structure(NamedTuple):
    """What a host-side sync of one pytree structure needs, built once:
    the jitted pack and unpack programs of its plan, each with outputs
    pinned to the communicator's rank sharding."""
    pack: Callable
    unpack: Callable


#: comm -> {(treedef, leaf shapes and dtypes, bucket_bytes): _Structure}.
#: A DDP step syncs one structure every step: after the first, a call
#: costs one dict lookup before the pack launch.
_STRUCTURES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _build(comm, specs: tuple, bucket_bytes: int) -> _Structure:
    size = comm.size
    for shape, _ in specs:
        if len(shape) < 1 or shape[0] != size:
            raise ValueError(
                f"allreduce_pytree needs rank-major (size, ...) leaves,"
                f" got shape {shape}"
            )
    # Plan over the per-rank payload (axis 0 is the rank axis).
    plan = _plan([(shape[1:], dt) for shape, dt in specs], bucket_bytes)
    shapes = [shape for shape, _ in specs]
    sharding = comm.rank_sharding()

    def _pack_buckets(leaves):      # (size, elems) per bucket
        return [_gather_bucket(leaves, b, 1) for b in plan]

    def _unpack_buckets(reduced):
        return _split_buckets(plan, reduced, shapes)

    SPC.record("parallel_dp_plan_builds")
    return _Structure(
        jax.jit(_pack_buckets, out_shardings=[sharding] * len(plan)),
        jax.jit(_unpack_buckets, out_shardings=[sharding] * len(shapes)))


def allreduce_pytree(comm, tree: Any, op: Any = "sum",
                     bucket_bytes: Optional[int] = None,
                     error_feedback=None) -> Any:
    """Host-side fused allreduce of a pytree of rank-major ``(size,
    ...)`` buffers through the comm VTABLE: one ``comm.allreduce`` per
    bucket, so component selection (tuned/hier/pallas) and the quant
    tier run per bucket.  The plan is read from the leaves' shapes and
    dtypes, never their data, and kept per communicator and structure
    with its pack and unpack programs.  ``error_feedback`` is an
    optional dict used as a residual bank: one
    :class:`ompi_tpu.coll.quant.ErrorFeedback` per bucket index,
    created on first use and carried across calls (aligned because
    bucketing is deterministic — pass the same dict every step)."""
    with Span("coll.allreduce_pytree", "coll"):
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            return tree
        if bucket_bytes is None:
            bucket_bytes = _bucket_bytes_var.value
        try:
            specs = tuple((leaf.shape, leaf.dtype) for leaf in leaves)
        except AttributeError:
            specs = tuple(_spec(leaf) for leaf in leaves)
        key = (treedef, specs, bucket_bytes)
        memo = _STRUCTURES.setdefault(comm, {})
        st = memo.get(key)
        if st is None:
            st = memo[key] = _build(
                comm, tuple(_spec(leaf) for leaf in leaves), bucket_bytes)
        SPC.record("parallel_dp_bucket_leaves", len(leaves))
        with Span("coll.pack", "coll"):
            payloads = st.pack(leaves)
        reduced = []
        for bi in range(len(payloads)):
            # drop each packed bucket once it is handed on, so the
            # packed copy of the tree is not all resident at the unpack
            payload, payloads[bi] = payloads[bi], None
            if error_feedback is not None:
                from ..coll.quant import ErrorFeedback

                ef = error_feedback.setdefault(bi, ErrorFeedback())
                payload = ef.compensate(payload)
            reduced.append(comm.allreduce(payload, op))
            SPC.record("parallel_dp_bucket_dispatches")
        with Span("coll.unpack", "coll"):
            out = st.unpack(reduced)
        return jax.tree.unflatten(treedef, out)
