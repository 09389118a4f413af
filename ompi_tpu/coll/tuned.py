"""coll/tuned — algorithm decision layer.

TPU-native equivalent of ompi/mca/coll/tuned (reference:
coll_tuned_decision_fixed.c — fixed rules keyed on communicator size,
message size and op commutativity; coll_tuned_dynamic_file.c — rules
loadable from a file; per-op forced-algorithm MCA vars in
coll_tuned_*_decision.c).

The decision picks among the explicit algorithm space in coll/spmd plus
the XLA-native lowering. Defaults mirror the reference's fixed rules
(recursive doubling < 10 KB; ring ≤ 1 MB/rank; segmented ring above, 1 MB
segments — coll_tuned_decision_fixed.c:45-87) with one TPU-first change:
when the op maps onto the fabric's native reduction (`prefer_native`,
default on), XLA's own collective is used — it compiles to the ICI
schedule the explicit algorithms approximate.
"""

from __future__ import annotations

import json
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import config, dispatch_epoch
from ..core.errors import ArgumentError
from ..core.logging import get_logger
from ..ops import Op, lookup as op_lookup
from ..ops.op import _is_joint
from ..trace.span import Span
from . import spmd
from .framework import COLL, CollComponent, compile_plan, rank_major_check
from .xla import XlaColl, _dtype_key, _leaf_check

logger = get_logger("coll.tuned")

# Reference cutoffs (BASELINE.md): 10,000 B small-message cutoff, 1 MiB
# ring→segmented switch, 1 MiB segments.
_V = partial(config.register, "coll", "tuned")
_large = _V("bcast_large_cutoff", type=int, default=1 << 20,
            description="Bytes above which rooted ops take the "
                        "segmented pipeline tier (reference: 1MiB "
                        "segments, coll_tuned_decision_fixed.c:250-310)")
_small = _V("allreduce_small_cutoff", type=int, default=10_000,
            description="Allreduce: bytes/rank below which recursive "
                        "doubling is used (reference: 10000B)")
_ring_limit = _V("allreduce_ring_limit", type=int, default=1 << 20,
                 description="Allreduce: max bytes/rank for plain ring "
                             "before switching to segmented ring")
_seg_bytes = _V("segment_bytes", type=int, default=1 << 20,
                description="Segment size for segmented algorithms "
                            "(reference: 1MiB)")
_prefer_native = _V("prefer_native", type=bool, default=True,
                    description="Use XLA-native fabric collectives when "
                                "the op supports them")
_rules_file = _V("rules_file", type=str, default="",
                 description="JSON dynamic-rules file (reference: "
                             "coll_tuned_dynamic_file.c)")
_force_allreduce = _V("allreduce_algorithm", type=str, default="",
                      description="Force an allreduce algorithm by name")
_force_alltoall = _V("alltoall_algorithm", type=str, default="",
                     description="Force an alltoall algorithm by name")
_force_allgather = _V("allgather_algorithm", type=str, default="",
                      description="Force an allgather algorithm by name")
_force_bcast = _V("bcast_algorithm", type=str, default="",
                  description="Force a bcast algorithm by name")
_force_reduce = _V("reduce_algorithm", type=str, default="",
                   description="Force a reduce algorithm by name")
_force_scan = _V("scan_algorithm", type=str, default="",
                 description="Force the scan algorithm")
_force_exscan = _V("exscan_algorithm", type=str, default="",
                   description="Force the exscan algorithm")
_force_reduce_scatter = _V("reduce_scatter_algorithm", type=str, default="",
                           description="Force a reduce_scatter algorithm "
                                       "by name")
_force_gather = _V("gather_algorithm", type=str, default="",
                   description="Force a gather algorithm by name")
_force_scatter = _V("scatter_algorithm", type=str, default="",
                    description="Force a scatter algorithm by name")
_gather_binomial_max = _V("gather_binomial_max_bytes", type=int,
                          default=6 << 10,
                          description="Gather: per-rank bytes below which "
                                      "the binomial tree is used "
                                      "(reference: small-block binomial, "
                                      "coll_tuned_decision_fixed.c)")
_alltoall_small = _V("alltoall_small_msg", type=int, default=256,
                     description="Alltoall: bytes/dest below which bruck "
                                 "is used")
_alltoall_large = _V("alltoall_large_msg", type=int, default=32 << 10,
                     description="Alltoall: bytes/dest above which "
                                 "pairwise exchange is used")
_fast_cache_var = _V("fast_dispatch_cache", type=bool, default=True,
                     description="Route plain-array allreduces through "
                                 "the fast path (host tier for tiny "
                                 "payloads) and memoize the route in the "
                                 "communicator's lane per (shape, dtype, "
                                 "op): repeat calls skip the decision "
                                 "pipeline entirely. Invalidated by the "
                                 "dispatch epoch (config, breaker, "
                                 "health, sched cache, SLO, faultline, "
                                 "debug hooks, vtable re-selection)")
_host_small_max = _V("host_small_max_bytes", type=int, default=4096,
                     description="Fully-addressable allreduces at or "
                                 "below this many bytes reduce on the "
                                 "HOST (numpy over the rank axis + one "
                                 "device_put) instead of launching an "
                                 "XLA program — dispatch latency beats "
                                 "device compute at this size. 0 "
                                 "disables. Skipped under forced "
                                 "algorithms or a rules file")

# Quantized-wire cvars live in coll/quant (coll_quant_enable / _wire /
# _block / _min_bytes); decide_allreduce reads them through the quant
# module so the gate and the codec cannot disagree.

ALLREDUCE_ALGOS: dict[str, Callable] = {
    "native": spmd.allreduce_native,
    "recursive_doubling": spmd.allreduce_recursive_doubling,
    "ring": spmd.allreduce_ring,
    "ring_segmented": spmd.allreduce_ring_segmented,
    "rabenseifner": spmd.allreduce_reduce_scatter_allgather,
    "nonoverlapping": spmd.allreduce_nonoverlapping,
    "gather_reduce": spmd._allreduce_gather_reduce,
}


def _pallas_algos() -> None:
    """Extend the algorithm spaces with the Pallas kernel tier so the
    tuned rules (and tools/tune.py sweeps) can select pallas-vs-xla
    from measurement. Lazy: importing pallas pulls in Mosaic."""
    if "pallas_ring" in ALLREDUCE_ALGOS:
        return
    from . import pallas_ring as pr

    def _pallas_rd_guarded(b, axis_name, op):
        # recursive doubling needs a power-of-two ring; rules naming it
        # on other sizes degrade to the plain ring instead of failing at
        # trace time (the reference's decision functions guard the same
        # way before picking an algorithm)
        n = jax.lax.axis_size(axis_name)
        if n & (n - 1):
            return pr.allreduce_block(b, axis_name, op)
        return pr.allreduce_block_rd(b, axis_name, op)

    ALLREDUCE_ALGOS["pallas_ring"] = pr.allreduce_block
    ALLREDUCE_ALGOS["pallas_bidir"] = pr.allreduce_block_bidir
    ALLREDUCE_ALGOS["pallas_rd"] = _pallas_rd_guarded
    ALLREDUCE_ALGOS["pallas_ring_chunked"] = pr.allreduce_block_chunked
    ALLREDUCE_ALGOS["pallas_rsag"] = pr.allreduce_block_rsag
    BCAST_ALGOS["pallas_binomial"] = pr.bcast_block
    ALLGATHER_ALGOS["pallas_ring"] = pr.ring_allgather
    REDUCE_ALGOS["pallas_tree"] = pr.reduce_block
    REDUCE_SCATTER_ALGOS["pallas_ring"] = pr.ring_reduce_scatter
    GATHER_ALGOS["pallas_linear"] = pr.gather_block
    SCATTER_ALGOS["pallas_linear"] = pr.scatter_block


def _quant_algos() -> None:
    """Extend the allreduce space with the quantized-wire tier (lazy,
    like _pallas_algos: the names are selectable from rules files and
    forced vars before the module is imported)."""
    if "quant_ring" in ALLREDUCE_ALGOS:
        return
    from . import quant

    ALLREDUCE_ALGOS["quant_ring"] = quant.allreduce_quant_ring
    ALLREDUCE_ALGOS["quant_pallas"] = quant.allreduce_block_quant


def _sched_algos() -> None:
    """Extend the allreduce space with the schedule-compiler tier
    (coll/sched): IR programs lowered to fused jitted callables. Lazy
    like _pallas_algos — the names are selectable from rules files,
    forced vars and the schedule cache before the package is
    imported."""
    if "sched_ring" in ALLREDUCE_ALGOS:
        return
    from . import sched

    ALLREDUCE_ALGOS["sched_ring"] = sched.allreduce_sched_ring
    ALLREDUCE_ALGOS["sched_rd"] = sched.allreduce_sched_rd
    ALLREDUCE_ALGOS["sched_ring_seg"] = sched.allreduce_sched_ring_seg
    ALLREDUCE_ALGOS["sched_hier"] = sched.allreduce_sched_hier
    ALLREDUCE_ALGOS["sched_quant"] = sched.allreduce_sched_quant
    ALLREDUCE_ALGOS["sched_pallas_ring"] = sched.allreduce_sched_pallas_ring
    ALLREDUCE_ALGOS["sched_pallas_ring_seg"] = \
        sched.allreduce_sched_pallas_ring_seg
    REDUCE_SCATTER_ALGOS["sched_pallas_rs"] = sched.reduce_scatter_sched_pallas


def is_pallas_algo(name: str) -> bool:
    # quant_pallas is a Mosaic kernel too, as are the sched compiler's
    # fused device_pallas-tier kernels: same check_vma exemption.
    return name.startswith(("pallas", "sched_pallas")) \
        or name == "quant_pallas"


def is_quant_algo(name: str) -> bool:
    return name.startswith("quant")


def is_sched_algo(name: str) -> bool:
    """Schedule-compiler tier names (lowered IR programs)."""
    return name.startswith("sched_")


def _ensure_lazy(algo: str) -> None:
    """Trigger whichever lazy tier registration ``algo`` needs."""
    if is_pallas_algo(algo):
        _pallas_algos()
    if is_quant_algo(algo):
        _quant_algos()
    if is_sched_algo(algo):
        _sched_algos()


def _resolve_algo(opname: str, algo: str):
    """The callable behind an algorithm name (None if unknown),
    triggering lazy tier registrations on demand — how the sched
    autotuner and tools sweeps resolve candidates by name."""
    _ensure_lazy(algo)
    spaces = {
        "allreduce": ALLREDUCE_ALGOS,
        "alltoall": ALLTOALL_ALGOS,
        "allgather": ALLGATHER_ALGOS,
        "bcast": BCAST_ALGOS,
        "reduce": REDUCE_ALGOS,
        "scan": SCAN_ALGOS,
        "exscan": EXSCAN_ALGOS,
        "reduce_scatter": REDUCE_SCATTER_ALGOS,
        "gather": GATHER_ALGOS,
        "scatter": SCATTER_ALGOS,
    }
    space = spaces.get(opname)
    return None if space is None else space.get(algo)


#: Algorithm names that exist but are registered lazily (importing
#: pallas pulls in Mosaic; importing quant is cheap but kept symmetric).
#: Rules-file validation must know them without forcing the import.
_LAZY_ALGOS: dict[str, frozenset] = {
    "allreduce": frozenset({
        "pallas_ring", "pallas_bidir", "pallas_rd", "pallas_ring_chunked",
        "pallas_rsag", "quant_ring", "quant_pallas",
        "sched_ring", "sched_rd", "sched_ring_seg", "sched_hier",
        "sched_quant", "sched_pallas_ring", "sched_pallas_ring_seg",
    }),
    "bcast": frozenset({"pallas_binomial"}),
    "allgather": frozenset({"pallas_ring"}),
    "reduce": frozenset({"pallas_tree"}),
    "reduce_scatter": frozenset({"pallas_ring", "sched_pallas_rs"}),
    "gather": frozenset({"pallas_linear"}),
    "scatter": frozenset({"pallas_linear"}),
}

ALLGATHER_ALGOS: dict[str, Callable] = {
    "native": spmd.allgather_native,
    "ring": spmd.allgather_ring,
    "bruck": spmd.allgather_bruck,
}

ALLTOALL_ALGOS: dict[str, Callable] = {
    "native": spmd.alltoall_native,
    "pairwise": spmd.alltoall_pairwise,
    "bruck": spmd.alltoall_bruck,
}

BCAST_ALGOS: dict[str, Callable] = {
    "native": spmd.bcast_native,
    "binomial": spmd.bcast_binomial,
    "chain": spmd.bcast_chain,
    "binary": spmd.bcast_binary,
    "pipelined": spmd.bcast_pipelined,
}

REDUCE_ALGOS: dict[str, Callable] = {
    "native": spmd.reduce_native,
    "binomial": spmd.reduce_binomial,
    "pipelined": spmd.reduce_pipelined,
}

SCAN_ALGOS: dict[str, Callable] = {
    "native": spmd.scan_native,
    "recursive_doubling": spmd.scan_recursive_doubling,
    "linear_chain": spmd.scan_linear_chain,
}

EXSCAN_ALGOS: dict[str, Callable] = {
    "native": spmd.exscan_native,
    "recursive_doubling": spmd.exscan_recursive_doubling,
    "linear_chain": spmd.exscan_linear_chain,
}

REDUCE_SCATTER_ALGOS: dict[str, Callable] = {
    "native": spmd.reduce_scatter_native,
    "ring": spmd.reduce_scatter_ring,
    "recursive_halving": spmd.reduce_scatter_recursive_halving,
}

GATHER_ALGOS: dict[str, Callable] = {
    "native": spmd.gather_native,
    "binomial": spmd.gather_binomial,
}

SCATTER_ALGOS: dict[str, Callable] = {
    "native": spmd.scatter_native,
    "binomial": spmd.scatter_binomial,
}


def _algo_space(opname: str) -> set:
    """Every selectable algorithm name for ``opname``, including the
    lazily registered tiers (without importing them)."""
    spaces = {
        "allreduce": ALLREDUCE_ALGOS,
        "alltoall": ALLTOALL_ALGOS,
        "allgather": ALLGATHER_ALGOS,
        "bcast": BCAST_ALGOS,
        "reduce": REDUCE_ALGOS,
        "scan": SCAN_ALGOS,
        "exscan": EXSCAN_ALGOS,
        "reduce_scatter": REDUCE_SCATTER_ALGOS,
        "gather": GATHER_ALGOS,
        "scatter": SCATTER_ALGOS,
    }
    space = spaces.get(opname)
    if space is None:
        return set()
    return set(space) | set(_LAZY_ALGOS.get(opname, ()))


_KNOWN_OPNAMES = frozenset({
    "allreduce", "alltoall", "allgather", "bcast", "reduce", "scan",
    "exscan", "reduce_scatter", "gather", "scatter",
})


class Rules:
    """Dynamic decision rules loaded from a JSON file:
    {"allreduce": [{"max_bytes": N, "min_ranks": M, "algorithm": "ring"},
     ...], ...} — first matching entry wins.

    Band keys: min_bytes/max_bytes/min_ranks/max_ranks, plus the
    precision dimension: ``"dtype": "float32"`` restricts a rule to one
    payload dtype, and ``"allow_quant": false`` vetoes the automatic
    quantized-wire tier inside the rule's band (a rule carrying only
    the veto needs no "algorithm").

    Unknown opname keys and unknown algorithm names are NOT silent
    (reference regression: coll_tuned_dynamic_file.c ignores junk and
    users debug it for days) — each unknown key is logged ONCE through
    the monitoring layer, counted on the coll_tuned_rules_unknown pvar,
    and the rule is skipped, so a bogus rules file can never select a
    nonexistent algorithm."""

    def __init__(self, path: str) -> None:
        with open(path, "r", encoding="utf-8") as f:
            self._rules = json.load(f)
        self._warned: set = set()
        for opname in self._rules:
            if opname not in _KNOWN_OPNAMES:
                self._warn_once(
                    ("opname", opname),
                    "rules file names unknown operation %r "
                    "(known: %s)", opname, sorted(_KNOWN_OPNAMES),
                )

    def _warn_once(self, key: tuple, msg: str, *args) -> None:
        if key in self._warned:
            return
        self._warned.add(key)
        from ..core.counters import SPC

        SPC.record("coll_tuned_rules_unknown")
        logger.warning(msg, *args)

    def _matches(self, rule: dict, nbytes: int, nranks: int,
                 dtype) -> bool:
        if nbytes > rule.get("max_bytes", float("inf")):
            return False
        if nbytes < rule.get("min_bytes", 0):
            return False
        if nranks < rule.get("min_ranks", 0):
            return False
        if nranks > rule.get("max_ranks", float("inf")):
            return False
        want = rule.get("dtype")
        if want is not None and (dtype is None or str(dtype) != want):
            return False
        return True

    def decide(self, opname: str, nbytes: int, nranks: int,
               dtype=None) -> Optional[str]:
        known = _algo_space(opname)
        for rule in self._rules.get(opname, ()):
            if not self._matches(rule, nbytes, nranks, dtype):
                continue
            algo = rule.get("algorithm")
            if algo is None:
                continue  # veto-only rule (allow_quant band)
            if algo not in known:
                self._warn_once(
                    ("algo", opname, algo),
                    "rules file names unknown %s algorithm %r "
                    "(known: %s); rule skipped", opname, algo,
                    sorted(known),
                )
                continue
            return algo
        return None

    def allows_quant(self, opname: str, nbytes: int, nranks: int,
                     dtype=None) -> bool:
        """False when the first matching rule carries
        ``"allow_quant": false`` — the user-rules veto on the
        automatic quantized-wire tier."""
        for rule in self._rules.get(opname, ()):
            if not self._matches(rule, nbytes, nranks, dtype):
                continue
            if "allow_quant" in rule:
                return bool(rule["allow_quant"])
        return True


_rules_cache: dict[str, Rules] = {}


def _rules() -> Optional[Rules]:
    path = _rules_file.value
    if not path:
        return None
    r = _rules_cache.get(path)
    if r is None:
        try:
            r = Rules(path)
        except (OSError, ValueError, KeyError) as exc:
            logger.warning("cannot load rules file %s: %s", path, exc)
            r = Rules.__new__(Rules)
            r._rules = {}
        _rules_cache[path] = r
    return r


def _nbytes(x) -> int:
    """Bytes per rank of a rank-major pytree (block size, not total)."""
    total = 0
    for leaf in jax.tree.leaves(x):
        arr = jnp.asarray(leaf)
        total += (arr.size // max(arr.shape[0], 1)) * arr.dtype.itemsize
    return total


def _sched_lookup(opname: str, nbytes: int, nranks: int, dtype=None,
                  op=None, scope: Optional[str] = None) -> Optional[str]:
    """Compiled-schedule cache consult (the precedence slot between the
    correctness guards and the static priors). ``nbytes`` is bytes per
    rank — the same convention as Rules bands and the cache's size
    buckets. ``scope`` carries the communicator identity for SLO
    frontier selection."""
    from . import sched

    return sched.lookup(opname, nbytes, nranks, dtype=dtype, op=op,
                        scope=scope)


def decide_allreduce(op: Op, nbytes: int, nranks: int, dtype=None,
                     allow_quant: Optional[bool] = None,
                     scope: Optional[str] = None) -> str:
    """Pick the allreduce algorithm; precision-aware since the quant
    tier exists.  ``nbytes`` is BYTES PER RANK (the block size of the
    rank-major payload, see _nbytes) — the one byte convention shared
    by Rules bands, the schedule cache's size buckets and the priors.
    ``dtype`` is the payload element type (None = unknown → quant
    refused).  ``allow_quant`` overrides the coll_quant_enable cvar
    (True forces consideration, False vetoes); user rules can veto per
    band via ``"allow_quant": false``.

    Precedence: forced var > rules file > correctness guard
    (non-commutative/joint → ordered gather_reduce) > tuned
    compiled-schedule cache > static priors (sched/priors)."""
    forced = _force_allreduce.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("allreduce", nbytes, nranks, dtype)
        if got:
            return got
    if not op.commutative or _is_joint(op):
        return "gather_reduce"
    tuned_pick = _sched_lookup("allreduce", nbytes, nranks, dtype, op,
                               scope=scope)
    if tuned_pick:
        if allow_quant is False and (is_quant_algo(tuned_pick)
                                     or tuned_pick == "sched_quant"):
            tuned_pick = None  # caller's explicit lossy-wire veto wins
        if tuned_pick:
            return tuned_pick
    from .sched import priors

    return priors.prior_allreduce(op, nbytes, nranks, dtype,
                                  allow_quant, rules)


def decide_alltoall(nbytes_per_dest: int, nranks: int) -> str:
    forced = _force_alltoall.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("alltoall", nbytes_per_dest, nranks)
        if got:
            return got
    got = _sched_lookup("alltoall", nbytes_per_dest, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_alltoall(nbytes_per_dest, nranks)


def decide_allgather(nbytes: int, nranks: int) -> str:
    forced = _force_allgather.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("allgather", nbytes, nranks)
        if got:
            return got
    got = _sched_lookup("allgather", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_allgather(nbytes, nranks)


def decide_bcast(nbytes: int, nranks: int) -> str:
    """Reference regime (coll_tuned_decision_fixed.c:250-310): binomial
    for small messages, binary tree mid-size, segmented pipeline/chain
    for bulk. Native (XLA's own broadcast lowering) stays the default
    when preferred — XLA already emits the ICI-optimal schedule; the
    algorithm tiers are for rules-file/sweep selection and spanning
    reuse."""
    forced = _force_bcast.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("bcast", nbytes, nranks)
        if got:
            return got
    got = _sched_lookup("bcast", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_bcast(nbytes, nranks)


def decide_scan(op: Op, nbytes: int, nranks: int) -> str:
    """Scan space: the log-depth doubling exchange for small payloads,
    the associative-scan native plan otherwise; joint (paired-word)
    ops stay native — the variants exchange leaves positionally."""
    forced = _force_scan.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("scan", nbytes, nranks)
        if got:
            return got
    if _is_joint(op):
        return "native"
    got = _sched_lookup("scan", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_scan(op, nbytes, nranks)


def decide_exscan(op: Op, nbytes: int, nranks: int) -> str:
    forced = _force_exscan.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("exscan", nbytes, nranks)
        if got:
            return got
    if _is_joint(op):
        return "native"
    got = _sched_lookup("exscan", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_exscan(op, nbytes, nranks)


def decide_reduce(op: Op, nbytes: int, nranks: int) -> str:
    """Reference: coll_tuned_reduce_decision / decision_fixed — binomial
    for small messages, pipelined chains above; non-commutative ops take
    the ordered path. Here 'native' (the XLA allreduce + root slice) is
    the large-message answer: XLA already emits the ICI-optimal
    schedule."""
    forced = _force_reduce.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("reduce", nbytes, nranks)
        if got:
            return got
    if not op.commutative or _is_joint(op):
        return "native"  # ordered handling lives in the algo fallback
    got = _sched_lookup("reduce", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_reduce(op, nbytes, nranks)


def decide_reduce_scatter(op: Op, nbytes: int, nranks: int) -> str:
    """Reference: coll_base_reduce_scatter.c decision — recursive
    halving for small commutative power-of-two cases, ring for large."""
    forced = _force_reduce_scatter.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("reduce_scatter", nbytes, nranks)
        if got:
            return got
    if not op.commutative or _is_joint(op):
        # ring/halving accumulate out of rank order; the native path's
        # ordered gather-reduce fallback is the only correct one
        return "native"
    got = _sched_lookup("reduce_scatter", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_reduce_scatter(op, nbytes, nranks)


def decide_gather(nbytes: int, nranks: int) -> str:
    forced = _force_gather.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("gather", nbytes, nranks)
        if got:
            return got
    got = _sched_lookup("gather", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_gather(nbytes, nranks)


def decide_scatter(nbytes: int, nranks: int) -> str:
    """Default is ALWAYS native: on a single controller scatter is a
    pure reshard (put_rank_major), while the algorithm-form path must
    first stage the buffer replicated n-ways just to tear it apart
    again. The tree algorithms exist for parity with
    coll_base_scatter.c and are reachable only by forced var or rules
    file (e.g. for spanning-comm reuse where the staging is the
    transport anyway)."""
    forced = _force_scatter.value
    if forced:
        return forced
    rules = _rules()
    if rules is not None:
        got = rules.decide("scatter", nbytes, nranks)
        if got:
            return got
    got = _sched_lookup("scatter", nbytes, nranks)
    if got:
        return got
    from .sched import priors

    return priors.prior_scatter(nbytes, nranks)


def allreduce_by_decision(x: jax.Array, axis_name: str, op,
                          allow_quant: Optional[bool] = None
                          ) -> jax.Array:
    """Traced (inside shard_map/jit) allreduce of a plain array over
    ``axis_name``, routed through the same decision pipeline the comm
    vtable uses — this is how per-bucket dispatch (parallel/bucketer)
    gets tuned scheduling and the quant tier without a communicator
    object.  The decision runs at trace time (axis sizes are static)."""
    op = op_lookup(op)
    nranks = jax.lax.axis_size(axis_name)
    if nranks == 1:
        return x
    nbytes = x.size * x.dtype.itemsize
    algo = decide_allreduce(op, nbytes, nranks, dtype=x.dtype,
                            allow_quant=allow_quant)
    # Circuit breaker: route around tiers that tripped on a previous
    # kernel/transport fault. The decision runs at trace time, so this
    # is the only breaker hook the traced path gets (no runtime catch
    # is possible inside shard_map) — dispatch-time retry lives in
    # TunedColl.allreduce.
    from . import breaker

    algo = breaker.route("allreduce", algo)
    _ensure_lazy(algo)
    fn = ALLREDUCE_ALGOS.get(algo)
    if fn is None:
        raise ArgumentError(
            f"unknown allreduce algorithm {algo!r}; known: "
            f"{sorted(ALLREDUCE_ALGOS)}"
        )
    from ..core.counters import SPC

    SPC.record(f"coll_allreduce_algo_{algo}")
    # commtrace: one instant per decision shows *which* tier the tuned
    # table (plus breaker routing) actually picked on the timeline.
    from ..trace import span as tspan

    tspan.instant("tuned.tier", cat="coll", op="allreduce",
                  algo=algo, nbytes=nbytes)
    if is_quant_algo(algo) or algo == "sched_quant":
        from . import quant

        quant.record_wire_stats(nbytes, x.dtype.itemsize)
    if algo == "ring_segmented":
        seg_elems = max(1, _seg_bytes.value // x.dtype.itemsize)
        return fn(x, axis_name, op, segment_elems=seg_elems)
    return fn(x, axis_name, op)


def _probe_steps(comm, opname: str, algo: str) -> None:
    """Walk the chosen program's step count and probe faultline at
    each one (only ever called with a plan armed). sched_* algorithms
    report their real IR round count; the closed-form tiers use the
    ring-equivalent 2*(n-1) so ``after_step=`` has a stable meaning
    everywhere."""
    from ..ft import inject

    nsteps = 2 * (comm.size - 1)
    try:
        from . import sched as _sched

        if algo in _sched.ALGOS:
            nsteps = _sched.build_schedule(algo, comm.size).rounds()
    except Exception:  # commlint: allow(broadexcept)
        pass  # a schedule build error is the dispatch path's to raise
    for step in range(1, nsteps + 1):
        inject.coll_step(comm, opname, step)


@COLL.register
class TunedColl(XlaColl):
    """Decision layer over the full algorithm space. Inherits the
    XLA-native lowering for operations whose decision says 'native'."""

    NAME = "tuned"
    PRIORITY = 80
    DESCRIPTION = "algorithm decision layer (reference: coll/tuned)"

    def _allreduce_plan(self, comm, x, op, deny: tuple = ()):
        """Decision + compiled plan for allreduce; x is leaf-checked
        and comm.size > 1. The whole per-call decision pipeline lives
        here so persistent_program can resolve it once."""
        return self._allreduce_choice(comm, x, op, deny)[1]

    def _allreduce_choice(self, comm, x, op, deny: tuple = ()):
        """(algo, plan) so the dispatch-time breaker retry knows which
        tier it just ran. ``deny`` excludes tiers that already failed
        in this call."""
        is_plain_array = hasattr(x, "dtype") and hasattr(x, "shape")
        nbytes = _nbytes(x)
        algo = decide_allreduce(
            op, nbytes, comm.size,
            dtype=x.dtype if is_plain_array else None,
            scope=str(comm.cid),
        )
        from . import breaker

        algo = breaker.route("allreduce", algo, deny=deny,
                             scope=str(comm.cid))
        _ensure_lazy(algo)
        fn = ALLREDUCE_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown allreduce algorithm {algo!r}; known: "
                f"{sorted(ALLREDUCE_ALGOS)}"
            )
        leaves = jax.tree.leaves(x)
        # The explicit single-buffer algorithms (ring, rd, ...) operate
        # on one plain array; any pytree container (even single-leaf)
        # routes through the pytree-aware ordered gather+reduce.
        if algo not in ("native", "gather_reduce") and not is_plain_array:
            fn = ALLREDUCE_ALGOS["gather_reduce"]
            algo = "gather_reduce"
        key = ("allreduce", algo, op.cache_key, _dtype_key(x))
        if is_quant_algo(algo) or algo == "sched_quant":
            from . import quant

            wire = quant._wire_var.value
            blk = quant._block_var.value
            key = key + (wire, blk)
            quant.record_wire_stats(nbytes, x.dtype.itemsize, wire, blk)
        if algo == "ring_segmented":
            seg_elems = max(
                1, _seg_bytes.value // jnp.asarray(leaves[0]).dtype.itemsize
            )
            per_rank = lambda b: fn(b, "ranks", op, segment_elems=seg_elems)
            key = key + (seg_elems,)
        else:
            per_rank = lambda b: fn(b, "ranks", op)
        from ..core.counters import SPC

        SPC.record(f"coll_allreduce_algo_{algo}")
        from ..trace import span as tspan

        tspan.instant("tuned.tier", cat="coll", op="allreduce",
                      algo=algo, nbytes=nbytes,
                      denied=list(deny) if deny else None)
        return algo, compile_plan(comm, key, per_rank,
                                  check_vma=not is_pallas_algo(algo))

    # Host-reducible predefined ops: ufunc.reduce over the rank axis
    # preserves dtype and matches the device tier's combine.
    _HOST_NP_OPS = {
        "sum": np.add, "prod": np.multiply,
        "max": np.maximum, "min": np.minimum,
    }

    def _fast_allreduce(self, comm, x, op, given):
        """The fast route for a plain rank-major array: the host tier
        for tiny fully-addressable payloads (a numpy reduction over the
        rank axis plus one device_put beats an XLA program launch below
        ~4 KiB), else the routed compiled plan. The route is built here
        once and memoized in the communicator's allreduce lane, keyed
        on ``given`` (the op as the caller passed it); repeat calls
        never reach this method until the dispatch epoch moves.
        Returns the result, or None when the slow path must run (route
        disabled, pytree input, faultline armed, breaker or health
        ledger non-quiet: their lazy transitions are live state a memo
        would miss)."""
        if not _fast_cache_var.value or not isinstance(x, jax.Array):
            return None
        if x.ndim < 1 or x.shape[0] != comm.size:
            return None  # slow path raises the proper ArgumentError
        from ..ft import inject
        from ..health import ledger as health
        from . import breaker

        if inject.armed() or not breaker.quiet() \
                or not health.LEDGER.quiet():
            return None
        epoch = dispatch_epoch.value  # read before the route is built
        built = self._build_fast_allreduce(comm, x, op)
        if built is None:
            return None
        plan, counter = built
        key = comm._lane_store(TunedColl.allreduce, x, given,
                               (epoch, plan, counter, self._launch_fast))
        return self._launch_fast(comm, plan, x, given, key)

    def _launch_fast(self, comm, plan, x, op, key):
        """Run a fast-route plan (a lane hit, or the call that built
        it). A tier fault under it drops the lane entry and re-routes
        the call through the slow path, whose retry loop trips the
        breaker if the tier faults again; with the breaker off the
        fault is the caller's."""
        try:
            with Span("coll.launch", "coll"):
                return plan(x)
        except ArgumentError:
            raise
        except Exception:  # commlint: allow(broadexcept)
            from . import breaker

            if not breaker.enabled():
                raise
            comm._lane.pop(key, None)
            return self._routed_allreduce(comm, x, op_lookup(op))

    def _build_fast_allreduce(self, comm, x, op):
        """(plan, SPC counter name) for the lane, or None when the slow
        path must decide."""
        from ..core.counters import SPC
        from . import breaker

        limit = _host_small_max.value
        if (0 < limit >= x.size * x.dtype.itemsize and op.predefined
                and op.name in self._HOST_NP_OPS
                and x.is_fully_addressable
                and not _force_allreduce.value and _rules() is None):
            ufunc = self._HOST_NP_OPS[op.name]
            SPC.record("coll_allreduce_algo_host")

            def host_plan(buf):
                with Span("coll.host_fetch", "coll"):
                    a = np.asarray(buf)
                red = ufunc.reduce(a, axis=0)
                with Span("coll.host_put", "coll"):
                    return jax.device_put(np.broadcast_to(red, a.shape),
                                          buf.sharding)

            return host_plan, "coll_allreduce_algo_host"
        try:
            algo, plan = self._allreduce_choice(comm, x, op)
        except ArgumentError:
            raise
        except Exception:  # commlint: allow(broadexcept)
            if not breaker.enabled():
                raise
            return None  # slow path surfaces the real error
        return plan, f"coll_allreduce_algo_{algo}"

    def allreduce(self, comm, x, op):
        given, op = op, op_lookup(op)
        if comm.size > 1:
            out = self._fast_allreduce(comm, x, op, given)
            if out is not None:
                return out
        return self._routed_allreduce(comm, x, op)

    def _routed_allreduce(self, comm, x, op):
        """The decision pipeline on every call, with the breaker and
        sentinel retry loop: tier faults degrade to the next tier."""
        x = _leaf_check(comm, x)
        if comm.size == 1:
            return x
        from ..core.errors import RevokedError
        from ..ft import inject, lifeboat
        from ..health import ledger as health, sentinel
        from . import breaker

        scope = str(comm.cid)
        deny: tuple = ()
        while True:
            # Epoch/revocation fence at the top of the retry loop: a
            # comm revoked mid-degradation (a peer died while we were
            # falling tiers) must surface RevokedError, never keep
            # consuming tiers on a poisoned communicator.
            lifeboat.check(comm)
            algo, plan = self._allreduce_choice(comm, x, op, deny)

            def _run(algo=algo, plan=plan):
                # kernel_fault runs inside the bounded closure so an
                # injected wedge@coll stall is cancellable: the
                # sentinel abandons the wedged worker and the dispatch
                # falls to the next tier mid-flight. The per-step
                # probes give rank_kill@coll:after_step=k its
                # mid-collective firing point.
                if inject.armed():
                    inject.kernel_fault("allreduce", algo,
                                        cid=comm.cid)
                    _probe_steps(comm, "allreduce", algo)
                with Span("coll.launch", "coll"):
                    return plan(x)

            try:
                out = sentinel.maybe_bounded(
                    _run, what=f"allreduce[{algo}]")
            except ArgumentError:
                raise  # caller error, not a tier fault
            except RevokedError:
                raise  # recovery-surface error, not a tier fault
            except Exception as exc:  # commlint: allow(broadexcept)
                # Tier fault (kernel compile/launch failure, injected
                # FaultInjected, sentinel StallError on a wedged tier,
                # transport death inside the plan): trip the breaker,
                # report the transport tier to the health ledger, and
                # degrade to the next-cheaper tier instead of failing
                # the collective.
                #
                # StallError only *abandons* the wedged worker — the
                # stalled plan(x) keeps executing and may complete
                # concurrently with the retry below. Safe in a single
                # process because every tier is a pure function of its
                # input buffer and the late result is dropped; across
                # controllers a rank-local stall leaves ranks on
                # divergent tiers with an extra in-flight device
                # collective (hazard documented in DESIGN.md §17).
                #
                # On a revoked comm the fault is not a tier problem —
                # the peer is dead (sentinel StallError, injected
                # FaultInjected): convert to RevokedError so every
                # survivor exits the collective the same way instead
                # of burning tiers against a poisoned communicator.
                if lifeboat.revoked(comm):
                    raise RevokedError(
                        f"{comm.name} revoked during allreduce[{algo}]"
                        f" ({type(exc).__name__}: {exc})"
                    ) from exc
                if not breaker.enabled() \
                        or breaker.next_tier(algo) is None:
                    raise
                breaker.record_failure("allreduce", algo)
                health.report_failure(health.tier_of_algo(algo),
                                      scope=scope,
                                      cause=type(exc).__name__)
                from ..core.counters import SPC

                SPC.record("coll_tier_fallbacks")
                logger.warning(
                    "allreduce tier %r failed (%s: %s); degrading to "
                    "%r", algo, type(exc).__name__, exc,
                    breaker.next_tier(algo),
                )
                deny = deny + (algo,)
                continue
            if breaker.enabled():
                breaker.record_success("allreduce", algo)
                health.report_success(health.tier_of_algo(algo),
                                      scope=scope)
            return out

    def alltoall(self, comm, x):
        x = rank_major_check(comm, x, min_ndim=2)
        if x.shape[1] != comm.size:
            raise ArgumentError(
                f"alltoall needs (size, size, ...) buffer, got {x.shape}"
            )
        if comm.size == 1:
            return x
        per_dest = (x.size // (comm.size * comm.size)) * x.dtype.itemsize
        algo = decide_alltoall(per_dest, comm.size)
        fn = ALLTOALL_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(f"unknown alltoall algorithm {algo!r}")
        key = ("alltoall", algo, x.shape, str(x.dtype))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks"))
        return plan(x)

    def allgather(self, comm, x):
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x[:, None]
        algo = decide_allgather(_nbytes(x), comm.size)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = ALLGATHER_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(f"unknown allgather algorithm {algo!r}")
        key = ("allgather", algo, x.shape, str(x.dtype))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks"),
                            check_vma=not is_pallas_algo(algo))
        return plan(x)

    def bcast(self, comm, x, root):
        x = _leaf_check(comm, x)
        if comm.size == 1:
            return x
        algo = decide_bcast(_nbytes(x), comm.size)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = BCAST_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(f"unknown bcast algorithm {algo!r}")
        key = ("bcast", algo, root, _dtype_key(x))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks", root=root),
                            check_vma=not is_pallas_algo(algo))
        return plan(x)

    def reduce(self, comm, x, op, root):
        op = op_lookup(op)
        if comm.size == 1:
            return super().reduce(comm, x, op, root)
        algo = decide_reduce(op, _nbytes(x), comm.size)
        is_plain_array = hasattr(x, "dtype") and hasattr(x, "shape")
        if algo == "native" or not is_plain_array:
            return super().reduce(comm, x, op, root)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = REDUCE_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown reduce algorithm {algo!r}; known: "
                f"{sorted(REDUCE_ALGOS)}"
            )
        x = rank_major_check(comm, x)
        from ..core.counters import SPC

        SPC.record(f"coll_reduce_algo_{algo}")
        key = ("reduce", algo, op.cache_key, root, x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: fn(b, "ranks", op, root=root),
            check_vma=not is_pallas_algo(algo),
        )
        return plan(x)[root]

    def _prefix(self, comm, x, op, opname: str, decide, algos, native):
        """Shared scan/exscan dispatch over the tuned decision space
        (reference: the per-op decision functions of coll/tuned)."""
        op = op_lookup(op)
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return native(self, comm, x, op)
        algo = decide(op, _nbytes(x), comm.size)
        fn = algos.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown {opname} algorithm {algo!r}; known: "
                f"{sorted(algos)}"
            )
        key = (opname, algo, op.cache_key, x.shape, str(x.dtype))
        plan = compile_plan(
            comm, key, lambda b: fn(b, "ranks", op)
        )
        return plan(x)

    def scan(self, comm, x, op):
        return self._prefix(comm, x, op, "scan", decide_scan,
                            SCAN_ALGOS, XlaColl.scan)

    def exscan(self, comm, x, op):
        return self._prefix(comm, x, op, "exscan", decide_exscan,
                            EXSCAN_ALGOS, XlaColl.exscan)

    def reduce_scatter_block(self, comm, x, op):
        op = op_lookup(op)
        x = rank_major_check(comm, x, min_ndim=2)
        if x.shape[1] != comm.size:
            raise ArgumentError(
                f"reduce_scatter_block needs (size, size, ...) buffer, "
                f"got {x.shape}"
            )
        if comm.size == 1:
            return x[:, 0]
        per_rank = (x.size // (comm.size * comm.size)) * x.dtype.itemsize
        algo = decide_reduce_scatter(op, per_rank, comm.size)
        if algo == "native":
            return super().reduce_scatter_block(comm, x, op)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = REDUCE_SCATTER_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown reduce_scatter algorithm {algo!r}; known: "
                f"{sorted(REDUCE_SCATTER_ALGOS)}"
            )
        from ..core.counters import SPC

        SPC.record(f"coll_reduce_scatter_algo_{algo}")
        key = ("reduce_scatter_block", algo, op.cache_key, x.shape,
               str(x.dtype))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks", op),
                            check_vma=not is_pallas_algo(algo))
        return plan(x)

    def gather(self, comm, x, root):
        x = rank_major_check(comm, x)
        if comm.size == 1:
            return x[:, None][root]
        algo = decide_gather(_nbytes(x), comm.size)
        if algo == "native":
            return super().gather(comm, x, root)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = GATHER_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown gather algorithm {algo!r}; known: "
                f"{sorted(GATHER_ALGOS)}"
            )
        from ..core.counters import SPC

        SPC.record(f"coll_gather_algo_{algo}")
        key = ("gather", algo, root, x.shape, str(x.dtype))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks", root=root),
                            check_vma=not is_pallas_algo(algo))
        return plan(x)[root]

    def scatter(self, comm, x, root):
        arr = jnp.asarray(x)
        if arr.shape[0] != comm.size:
            raise ArgumentError(
                f"scatter needs (size, ...) buffer, got {arr.shape}"
            )
        if comm.size == 1:
            return comm.put_rank_major(arr)
        algo = decide_scatter(
            (arr.size // comm.size) * arr.dtype.itemsize, comm.size
        )
        if algo == "native":
            return super().scatter(comm, x, root)
        if is_pallas_algo(algo):
            _pallas_algos()
        fn = SCATTER_ALGOS.get(algo)
        if fn is None:
            raise ArgumentError(
                f"unknown scatter algorithm {algo!r}; known: "
                f"{sorted(SCATTER_ALGOS)}"
            )
        from ..core.counters import SPC

        SPC.record(f"coll_scatter_algo_{algo}")
        # Algorithm-form scatter runs inside the mesh: stage root's
        # buffer as replicated rank-major rows so the traced tree sees
        # it on-device (only root's copy is semantically significant).
        stacked = comm.put_rank_major(
            jnp.broadcast_to(arr[None], (comm.size,) + arr.shape)
        )
        key = ("scatter", algo, root, stacked.shape, str(stacked.dtype))
        plan = compile_plan(comm, key, lambda b: fn(b, "ranks", root=root),
                            check_vma=not is_pallas_algo(algo))
        return plan(stacked)
