"""Online schedule retune: the sched half of the watchtower loop.

watchtower (telemetry/) decides *when* a cached winner has drifted;
this module decides *what to do about it* — deterministically. Two
mechanisms:

``retune_key``
    Re-run the model-mode candidate sweep for exactly one cache key
    and install the new winner through ``cache.bump()`` — a
    version-bumped entry that retains the old winner one level deep
    (``rollback()`` restores it). On drift retunes the incumbent
    algorithm is *excluded* from the sweep: the live measurement just
    falsified the model's prediction for it, so re-scoring it with the
    same model would deterministically re-elect it. The bump raises
    the cache generation and the dispatch epoch, so memoized
    allreduce routes (the communicator's lane) re-consult at their
    next dispatch —
    a schedule is never mutated mid-flight.

topology penalties
    Persistent straggler findings reshape schedules instead of only
    marking tiers SUSPECT: ``set_topology_penalties`` records slow
    ranks and skew, and ``build_schedule`` consults
    ``reroot_groups``/``effective_segments``/``penalty_stamp`` so
    hierarchical trees re-root away from slow leaders and segmented
    rings shrink their chunks under skew. Penalties are inputs to the
    existing IR generators — the generated ``Schedule.digest()``
    stays a pure function of (algo, nranks, penalty state), keeping
    the byte-identity contract.

Determinism contract: every decision here is a pure function of the
cache key, the candidate pool, the seed, and the penalty state — no
wall-clock, no RNG beyond the model's seeded crc32 tie-break — so
same-seed controllers that observe the same drift install
byte-identical winners (the acceptance drill asserts this across two
subprocesses).
"""

from __future__ import annotations

import re
import threading
from typing import Optional, Sequence

from ...core.counters import SPC
from ...core.logging import get_logger
from . import cache as _cache

logger = get_logger("coll.sched")

#: ``cache_key`` grammar: op|b<bucket>|<dtype>|r<nranks>|<topo_fp>
_KEY_RE = re.compile(r"^([^|]+)\|b(\d+)\|([^|]+)\|r(\d+)\|(.*)$")


def parse_key(key: str) -> Optional[dict]:
    """Decompose a cache key back into its sweep coordinates (None for
    a key that doesn't match the grammar — e.g. a hand-edited file)."""
    m = _KEY_RE.match(key)
    if m is None:
        return None
    return {
        "opname": m.group(1),
        "bucket": int(m.group(2)),
        "dtype": m.group(3),
        "nranks": int(m.group(4)),
        "topo_fp": m.group(5),
    }


# ---------------------------------------------------------------------------
# topology penalties (straggler findings -> schedule shape)
# ---------------------------------------------------------------------------

_mu = threading.Lock()
_PENALTY = {"slow_ranks": frozenset(), "skew": False, "gen": 0}


def set_topology_penalties(slow_ranks: Sequence[int] = (),
                           skew: bool = False) -> bool:
    """Install the straggler-derived schedule penalties. Returns True
    when the state actually changed (the caller retunes only then)."""
    slow = frozenset(int(r) for r in slow_ranks)
    with _mu:
        if (_PENALTY["slow_ranks"] == slow
                and _PENALTY["skew"] == bool(skew)):
            return False
        _PENALTY["slow_ranks"] = slow
        _PENALTY["skew"] = bool(skew)
        _PENALTY["gen"] += 1
    from ...trace import span as tspan

    SPC.record("sched_topology_penalties")
    tspan.instant("sched.topology_penalty", cat="sched",
                  slow_ranks=sorted(slow), skew=bool(skew))
    logger.info("sched: topology penalties -> slow_ranks=%s skew=%s",
                sorted(slow) or "none", bool(skew))
    return True


def clear_topology_penalties() -> None:
    set_topology_penalties((), False)


def penalized_ranks() -> frozenset:
    return _PENALTY["slow_ranks"]


def skew_active() -> bool:
    return bool(_PENALTY["skew"])


def penalty_stamp() -> tuple:
    """Hashable content stamp for schedule memo keys: two identical
    penalty states always produce the same stamp (and digest)."""
    return (tuple(sorted(_PENALTY["slow_ranks"])),
            bool(_PENALTY["skew"]))


def reroot_groups(groups: Sequence[Sequence[int]]) -> list[list]:
    """Re-root a hierarchical group partition away from slow ranks:
    within each group the first non-slow member leads (leader = g[0]
    in ir.hierarchical), and groups whose every member is slow sink to
    the back of the leader chain (leaders[0] is the tree root).
    Relative order is otherwise preserved, so the result — and the
    schedule digest built from it — is deterministic."""
    slow = _PENALTY["slow_ranks"]
    out = [list(g) for g in groups]
    if not slow:
        return out
    rerooted = []
    for g in out:
        fast = [r for r in g if r not in slow]
        rerooted.append(fast + [r for r in g if r in slow])
    rerooted.sort(key=lambda g: 0 if (g and g[0] not in slow) else 1)
    return rerooted


def effective_segments(segments: int) -> int:
    """Segment count under the current penalties: skew doubles the
    segmentation (smaller chunks -> a slow hop stalls less pipeline)."""
    return int(segments) * 2 if _PENALTY["skew"] else int(segments)


# ---------------------------------------------------------------------------
# per-key retune
# ---------------------------------------------------------------------------

def _schedule_id(algo: str, nranks: int) -> str:
    """Like autotune._schedule_id but built through
    ``build_schedule`` so topology penalties reach the recorded
    digest (the generator-level reroot/segment shaping)."""
    from . import ALGOS, ScheduleError, build_schedule

    if algo not in ALGOS:
        return ""
    try:
        return build_schedule(algo, nranks).digest()
    except ScheduleError:
        return ""


def candidate_scores(key: str, *, seed: Optional[int] = None,
                     exclude: Sequence[str] = ()) -> list[dict]:
    """Deterministic model-mode scores for every currently-allowed
    candidate of ``key``, cheapest first. This doubles as the cached
    latency/bandwidth *frontier*: each point carries the step count
    (latency axis) and wire bytes (bandwidth axis) alongside the
    scalar score. Empty when the key doesn't parse or nothing is
    allowed (e.g. every candidate's tier quarantined)."""
    from ..tuned import _algo_space
    from ...ops import lookup as op_lookup
    from . import autotune

    parsed = parse_key(key)
    if parsed is None:
        return []
    seed = autotune._seed_var.value if seed is None else int(seed)
    nbytes = _cache.bucket_bytes(parsed["bucket"])
    nranks = parsed["nranks"]
    dtype = None if parsed["dtype"] == "any" else parsed["dtype"]
    allowed, _skipped = autotune.candidates(
        parsed["opname"], nranks, dtype=dtype, op=op_lookup("sum"))
    known = _algo_space(parsed["opname"])
    drop = set(exclude)
    out = []
    for algo in allowed:
        if algo in drop or algo not in known:
            continue
        steps, wire = autotune._steps_and_wire(algo, nbytes, nranks)
        out.append({
            "algo": algo,
            "score": autotune.model_cost(algo, nbytes, nranks, seed),
            "steps": float(steps),
            "wire": float(wire),
        })
    out.sort(key=lambda c: c["score"])
    return out


def retune_key(key: str, *, reason: str = "drift",
               seed: Optional[int] = None,
               exclude: Sequence[str] = (),
               live_p50_us: Optional[float] = None) -> Optional[dict]:
    """Re-sweep one cache key and install the winner as a
    version-bumped entry (old winner retained for rollback). Returns
    {"key","algorithm","version","previous","reason"} or None when no
    candidate is available. Every install emits a ``sched.retune``
    trace instant and counts ``sched_retunes`` — the retuneaudit lint
    evidence contract."""
    from ...trace import span as tspan

    frontier = candidate_scores(key, seed=seed, exclude=exclude)
    if not frontier:
        SPC.record("sched_retune_failed")
        return None
    parsed = parse_key(key)
    best = frontier[0]
    prev = _cache.CACHE.get(key) or {}
    version = _cache.CACHE.bump(
        key, best["algo"],
        schedule=_schedule_id(best["algo"], parsed["nranks"]),
        source=f"retune:{reason}", score=best["score"],
        frontier=frontier,
    )
    SPC.record("sched_retunes")
    tspan.instant("sched.retune", cat="sched", key=key, reason=reason,
                  algo=best["algo"],
                  prev=prev.get("algorithm", ""), version=version,
                  live_p50_us=live_p50_us)
    logger.info("sched: retuned %s (%s): %s -> %s (v%d)", key, reason,
                prev.get("algorithm", "?"), best["algo"], version)
    return {
        "key": key,
        "algorithm": best["algo"],
        "version": version,
        "previous": prev.get("algorithm", ""),
        "reason": reason,
    }


def reset_for_testing() -> None:
    with _mu:
        _PENALTY["slow_ranks"] = frozenset()
        _PENALTY["skew"] = False
        _PENALTY["gen"] = 0


__all__ = [
    "candidate_scores", "clear_topology_penalties",
    "effective_segments", "parse_key", "penalized_ranks",
    "penalty_stamp", "reroot_groups", "retune_key",
    "reset_for_testing", "set_topology_penalties", "skew_active",
]
