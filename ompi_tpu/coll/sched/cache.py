"""Versioned on-disk schedule cache.

The autotuner's winners persist as JSON keyed by
``op|size-bucket|dtype|nranks|topology-fingerprint`` so a fleet warms
once: the first controller (or an offline ``tools/sched warm`` run)
sweeps and writes the cache; every later process loads it and
dispatches winners with zero first-call tune cost. Size buckets are
log2 of the **bytes-per-rank** payload — the same convention
Rules._matches and decide_* use (DESIGN.md §18), so a rules band and a
cache entry keyed from the same payload always agree on the byte
count.

Determinism contract: ``digest()`` is the sha256 of the canonical JSON
of {version, entries → {algorithm, schedule}} — wall-clock timings and
scores are stored alongside for inspection but EXCLUDED, so a
same-seed autotune run produces a byte-identical digest on every
controller (the same reproducibility contract the health ledger's
transition digest carries). A version-mismatched file is ignored (and
counted), never migrated: stale schedules must lose to a fresh sweep,
not be reinterpreted.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from functools import partial
from typing import Optional

from ...core import config, dispatch_epoch
from ...core.logging import get_logger

logger = get_logger("coll.sched")

#: Bump when the entry format or the key grammar changes.
VERSION = 1

_V = partial(config.register, "coll", "sched")
_enable_var = _V(
    "cache_enable", type=bool, default=True,
    description="Consult the compiled-schedule cache in decide_* "
                "(static priors remain the cold-start fallback)",
)
_dir_var = _V(
    "cache_dir", type=str, default="",
    description="Directory for the persisted schedule cache "
                "(default: $OMPI_TPU_SCHED_CACHE or "
                "~/.cache/ompi_tpu/sched)",
)


def cache_dir() -> str:
    d = _dir_var.value
    if d:
        return d
    env = os.environ.get("OMPI_TPU_SCHED_CACHE", "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "ompi_tpu",
                        "sched")


def size_bucket(nbytes_per_rank: int) -> int:
    """log2 bucket of a bytes-per-rank payload (0 for <=1 byte)."""
    return max(0, int(nbytes_per_rank).bit_length() - 1)


def bucket_bytes(bucket: int) -> int:
    """Representative bytes-per-rank for a bucket (its lower edge)."""
    return 1 << bucket


def cache_key(opname: str, nbytes_per_rank: int, nranks: int,
              dtype=None, topo_fp: str = "") -> str:
    dt = str(dtype) if dtype is not None else "any"
    return (f"{opname}|b{size_bucket(nbytes_per_rank)}|{dt}"
            f"|r{nranks}|{topo_fp or 'none'}")


def default_path(topo_fp: str, nranks: int) -> str:
    return os.path.join(
        cache_dir(),
        f"sched_v{VERSION}_r{nranks}_{(topo_fp or 'none')[:16]}.json",
    )


class ScheduleCache:
    """In-memory view of the persisted winner table."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._entries: dict[str, dict] = {}
        # paths whose load was already attempted (hit or miss), so the
        # dispatch path stats a missing file at most once per config
        # generation.
        self._load_attempted: dict[str, bool] = {}
        self._config_gen = -1
        # bumped on every content change, with the dispatch epoch, so
        # a warm/tune invalidates the memoized allreduce routes.
        self._generation = 0
        # shared-read accounting by consumer scope ("tenant:<id>" /
        # "global"): the winner table warms ONCE per controller and
        # every daemon tenant reads the same entries — this meters
        # who benefits without ever scoping the entries themselves.
        self._scope_reads: dict[str, int] = {}

    # -- entries -------------------------------------------------------

    def put(self, key: str, algorithm: str, *, schedule: str = "",
            source: str = "autotune", tune_ms: Optional[float] = None,
            score: Optional[float] = None,
            frontier: Optional[list] = None,
            baseline_p50_us: Optional[float] = None,
            tile_bytes: Optional[int] = None,
            ag_deadline: Optional[int] = None,
            resident: Optional[bool] = None) -> None:
        ent = {"algorithm": algorithm, "schedule": schedule,
               "source": source, "version": 1}
        if tile_bytes is not None:
            ent["tile_bytes"] = int(tile_bytes)
        if ag_deadline is not None:
            ent["ag_deadline"] = int(ag_deadline)
        if resident is not None:
            ent["resident"] = bool(resident)
        if tune_ms is not None:
            ent["tune_ms"] = round(float(tune_ms), 3)
        if score is not None:
            ent["score"] = float(score)
        if frontier is not None:
            ent["frontier"] = list(frontier)
        if baseline_p50_us is not None:
            ent["baseline_p50_us"] = float(baseline_p50_us)
        with self._mu:
            self._entries[key] = ent
            self._generation += 1
            dispatch_epoch.bump()

    def bump(self, key: str, algorithm: str, *, schedule: str = "",
             source: str = "retune", tune_ms: Optional[float] = None,
             score: Optional[float] = None,
             frontier: Optional[list] = None,
             baseline_p50_us: Optional[float] = None,
             tile_bytes: Optional[int] = None,
             ag_deadline: Optional[int] = None,
             resident: Optional[bool] = None) -> int:
        """Install a new winner as a **version-bumped** entry: the
        prior winner survives one level deep under ``"previous"`` so a
        bad retune can be rolled back. Never mutates the old entry in
        place — a memoized allreduce route built under the previous
        dispatch epoch keeps running its old schedule until its next
        dispatch sees the bump. Returns the new version number."""
        new = {"algorithm": algorithm, "schedule": schedule,
               "source": source}
        if tile_bytes is not None:
            new["tile_bytes"] = int(tile_bytes)
        if ag_deadline is not None:
            new["ag_deadline"] = int(ag_deadline)
        if resident is not None:
            new["resident"] = bool(resident)
        if tune_ms is not None:
            new["tune_ms"] = round(float(tune_ms), 3)
        if score is not None:
            new["score"] = float(score)
        if frontier is not None:
            new["frontier"] = list(frontier)
        if baseline_p50_us is not None:
            new["baseline_p50_us"] = float(baseline_p50_us)
        with self._mu:
            old = self._entries.get(key)
            if old is None:
                new["version"] = 1
            else:
                # a retune must not silently drop the step-program tile
                # geometry or shard-residency plan tuned onto this key:
                # carry them forward unless the bump supplies fresh ones
                for carry in ("tile_bytes", "ag_deadline", "resident"):
                    if carry in old and carry not in new:
                        new[carry] = old[carry]
                new["version"] = int(old.get("version", 1)) + 1
                new["previous"] = {
                    "algorithm": old.get("algorithm", ""),
                    "schedule": old.get("schedule", ""),
                    "version": int(old.get("version", 1)),
                    "source": old.get("source", ""),
                }
            self._entries[key] = new
            self._generation += 1
            dispatch_epoch.bump()
            return new["version"]

    def rollback(self, key: str) -> bool:
        """Restore the ``"previous"`` winner a ``bump()`` retained.
        Returns False when there is nothing to roll back to."""
        with self._mu:
            ent = self._entries.get(key)
            prev = (ent or {}).get("previous")
            if not prev:
                return False
            restored = {"algorithm": prev.get("algorithm", ""),
                        "schedule": prev.get("schedule", ""),
                        "source": prev.get("source", "") or "rollback",
                        "version": int(ent.get("version", 1)) + 1}
            # rolling an algorithm winner back must not drop the
            # key-scoped tuning facts riding the entry (tile geometry,
            # shard-residency plan) — they are orthogonal to which
            # winner is installed, and a watchtower
            # bump-then-rollback cycle would otherwise silently erase
            # the residency decisions every same-seed controller
            # recompiles from
            for carry in ("tile_bytes", "ag_deadline", "resident"):
                if carry in ent:
                    restored[carry] = ent[carry]
            self._entries[key] = restored
            self._generation += 1
            dispatch_epoch.bump()
            return True

    def set_baseline(self, key: str, p50_us: float) -> None:
        """Stamp the live-measured p50 the watchtower drifts against.
        Non-semantic (excluded from the digest) so observation never
        perturbs the byte-identity contract; does not bump the
        generation for the same reason."""
        with self._mu:
            ent = self._entries.get(key)
            if ent is not None:
                ent["baseline_p50_us"] = float(p50_us)

    def get(self, key: str) -> Optional[dict]:
        return self._entries.get(key)

    def note_read(self, *, scope: str) -> None:
        """Meter one shared winner-table consult by a tenant scope
        (daemon dispatch calls this per collective) — billing-plane
        data, non-semantic: never in the digest."""
        with self._mu:
            self._scope_reads[scope] = \
                self._scope_reads.get(scope, 0) + 1

    def scope_reads(self) -> dict[str, int]:
        with self._mu:
            return dict(self._scope_reads)

    def entries(self) -> dict[str, dict]:
        with self._mu:
            return dict(self._entries)

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            self._load_attempted.clear()
            self._scope_reads.clear()
            self._config_gen = -1
            self._generation += 1
            dispatch_epoch.bump()

    def generation(self) -> int:
        """Content-change counter (see __init__)."""
        return self._generation

    # -- digest / persistence ------------------------------------------

    def digest(self) -> str:
        """sha256 over the semantic content only (version + winners);
        timings/scores excluded — the byte-identical-across-controllers
        contract."""
        with self._mu:
            canon = {
                "version": VERSION,
                "entries": {
                    k: {"algorithm": e["algorithm"],
                        "schedule": e.get("schedule", ""),
                        "version": int(e.get("version", 1)),
                        # semantic only when tuned: program tile
                        # geometry and shard-residency plans change
                        # what executes, so they join the digest — but
                        # only when present, keeping pre-program and
                        # pre-slipstream caches' digests byte-stable
                        **({"tile_bytes": int(e["tile_bytes"])}
                           if "tile_bytes" in e else {}),
                        **({"ag_deadline": int(e["ag_deadline"])}
                           if "ag_deadline" in e else {}),
                        **({"resident": bool(e["resident"])}
                           if "resident" in e else {})}
                    for k, e in sorted(self._entries.items())
                },
            }
        blob = json.dumps(canon, sort_keys=True,
                          separators=(",", ":")).encode()
        return hashlib.sha256(blob).hexdigest()

    def save(self, path: str) -> str:
        doc = {
            "version": VERSION,
            "digest": self.digest(),
            "entries": self.entries(),
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)  # atomic: readers never see a torn file
        logger.info("sched: saved %d schedule(s) to %s", len(self), path)
        return path

    def load(self, path: str) -> int:
        """Merge entries from ``path``; returns the number loaded.
        Version mismatches and unreadable files load nothing."""
        from ...core.counters import SPC

        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return 0
        if doc.get("version") != VERSION:
            SPC.record("sched_cache_version_mismatch")
            logger.warning(
                "sched: cache %s has version %r (want %d); ignored",
                path, doc.get("version"), VERSION,
            )
            return 0
        entries = doc.get("entries")
        if not isinstance(entries, dict):
            return 0
        loaded = 0
        with self._mu:
            for k, e in entries.items():
                if isinstance(e, dict) and e.get("algorithm"):
                    self._entries[k] = e
                    loaded += 1
            if loaded:
                self._generation += 1
                dispatch_epoch.bump()
        return loaded

    def ensure_loaded(self, topo_fp: str, nranks: int) -> None:
        """Attempt the default-path disk load once per (path, config
        generation) — a config mutation (cache_dir change, test reset)
        re-arms the attempt."""
        gen = config.generation()
        path = default_path(topo_fp, nranks)
        with self._mu:
            if self._config_gen != gen:
                self._load_attempted.clear()
                self._config_gen = gen
            if self._load_attempted.get(path):
                return
            self._load_attempted[path] = True
        n = self.load(path)
        if n:
            logger.info("sched: warmed %d schedule(s) from %s", n, path)

    def active(self) -> bool:
        """True once any entry exists — the gate for counting misses
        (an unconfigured process should not drown monitoring in
        sched_cache_misses)."""
        return bool(self._entries)


CACHE = ScheduleCache()

__all__ = [
    "CACHE", "VERSION", "ScheduleCache", "bucket_bytes", "cache_dir",
    "cache_key", "default_path", "size_bucket",
]
