"""watchtower (PR11): closed-loop drift retune, SLO selection, ratchet.

Covers: versioned cache bump/rollback and the digest's version field,
retune key parsing + deterministic candidate frontiers, topology
penalties reshaping hierarchical/segmented schedules, the watchtower
hysteresis (single-tick noise suppressed, sustained drift retunes
exactly once, cooldown and per-tick budget suppressions are counted),
the tier-1 closed-loop drill (faultline-injected drift on one key ->
one version-bumped retune within 3 ticks, new winner's measured p50
beats the drifted one), byte-identical retune logs + cache digests
across two same-seed controllers, the satellite straggler-reroot
drill, SLO frontier selection riding decide_*, violation-minute
accounting, the control-plane Prometheus lines, fleet stale-rank
degradation, the benchgate ratchet CLI, and the ``retuneaudit``
commlint rule (satellite 5)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu import telemetry
from ompi_tpu.analysis.lint import Linter
from ompi_tpu.core import config, counters, dispatch_epoch
from ompi_tpu.core.counters import SPC
from ompi_tpu.coll import sched, tuned
from ompi_tpu.coll.sched import autotune, ir, retune, slo
from ompi_tpu.coll.sched import cache as scache
from ompi_tpu.ft import inject
from ompi_tpu.health import ledger
from ompi_tpu.ops import lookup as op_lookup
from ompi_tpu.runtime import modex
from ompi_tpu.telemetry import export, fleet, sampler, straggler
from ompi_tpu.telemetry import watchtower
from ompi_tpu.tools import benchgate, mpit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: A small synthetic bench trajectory: five BENCH rounds (two clean,
#: three host-only with rc != 0) and five MULTICHIP rounds.
TRAJ = os.path.join(ROOT, "tests", "fixtures", "benchgate")


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


@pytest.fixture(autouse=True)
def _clean():
    yield
    telemetry.reset_for_testing()
    retune.reset_for_testing()
    slo.reset_for_testing()
    scache.CACHE.clear()
    sched.clear_schedules()
    mpit.clear_watches()
    inject.disarm()
    ledger.LEDGER.restore("fabric", cause="test_cleanup")


@pytest.fixture
def clean_cache(tmp_path):
    old_dir = config.get("coll_sched_cache_dir")
    config.set("coll_sched_cache_dir", str(tmp_path))
    scache.CACHE.clear()
    try:
        yield str(tmp_path)
    finally:
        scache.CACHE.clear()
        config.set("coll_sched_cache_dir", old_dir)


def _sample(us, bucket=12):
    """A sampler-shaped sample whose per-bucket allreduce p50 is
    ``us`` microseconds (histogram snapshots store seconds)."""
    return {"hists": {f"coll_allreduce_b{bucket}":
                      {"count": 8, "p50": us / 1e6}}}


def _snap(rank, p50_s):
    h = counters.Histogram("pml_send")
    for _ in range(8):
        h.record(p50_s)
    return {
        "format": "ompi_tpu.telemetry.v1", "rank": rank,
        "counters": {}, "hists": {"pml_send": h.snapshot()},
        "health": {}, "peers": {},
    }


# -- cache versioning -------------------------------------------------------

def test_cache_bump_retains_previous_and_rollback(clean_cache):
    key = scache.cache_key("allreduce", 1 << 12, 8, None, "fp")
    scache.CACHE.put(key, "sched_ring", schedule="s0")
    g0 = scache.CACHE.generation()
    d0 = scache.CACHE.digest()
    v = scache.CACHE.bump(key, "sched_rd", schedule="s1",
                          source="retune:test")
    assert v == 2
    ent = scache.CACHE.get(key)
    assert ent["algorithm"] == "sched_rd" and ent["version"] == 2
    assert ent["previous"]["algorithm"] == "sched_ring"
    assert ent["previous"]["version"] == 1
    assert scache.CACHE.generation() > g0  # memoized plans invalidate
    assert scache.CACHE.digest() != d0
    # rollback restores the retained winner as a fresh version (the
    # flip itself must invalidate plans too — no in-place mutation)
    assert scache.CACHE.rollback(key)
    ent = scache.CACHE.get(key)
    assert ent["algorithm"] == "sched_ring" and ent["version"] == 3
    assert not scache.CACHE.rollback(key)  # one level deep only
    # bump on an absent key is a plain v1 install
    assert scache.CACHE.bump("other|b4|any|r4|none", "sched_ring") == 1


def test_cache_digest_tracks_version_not_baseline():
    a, b = scache.ScheduleCache(), scache.ScheduleCache()
    a.put("k", "sched_ring", schedule="s")
    b.put("k", "sched_ring", schedule="s")
    assert a.digest() == b.digest()
    # same winner at a different version must not collide
    b.bump("k", "sched_rd", schedule="x")
    b.rollback("k")
    assert b.get("k")["algorithm"] == "sched_ring"
    assert a.digest() != b.digest()
    # observing a baseline is non-semantic: digest and generation hold
    g, d = a.generation(), a.digest()
    a.set_baseline("k", 123.4)
    assert a.get("k")["baseline_p50_us"] == 123.4
    assert a.generation() == g and a.digest() == d


# -- retune primitives ------------------------------------------------------

def test_parse_key_roundtrip():
    key = scache.cache_key("allreduce", 4096, 8, "float32", "fp16chars")
    got = retune.parse_key(key)
    assert got == {"opname": "allreduce", "bucket": 12,
                   "dtype": "float32", "nranks": 8,
                   "topo_fp": "fp16chars"}
    assert retune.parse_key("hand-edited-junk") is None


def test_candidate_scores_deterministic_frontier():
    key = scache.cache_key("allreduce", 1 << 12, 8, None, "none")
    a = retune.candidate_scores(key, seed=7)
    assert a and a == retune.candidate_scores(key, seed=7)
    assert [c["score"] for c in a] == sorted(c["score"] for c in a)
    assert all({"algo", "score", "steps", "wire"} <= set(c) for c in a)
    # excluding the winner removes it from the pool entirely
    b = retune.candidate_scores(key, seed=7, exclude=(a[0]["algo"],))
    assert a[0]["algo"] not in {c["algo"] for c in b}
    assert retune.candidate_scores("junk", seed=7) == []


def test_retune_key_version_bumps_and_counts(clean_cache):
    key = scache.cache_key("allreduce", 1 << 12, 8, None, "none")
    scache.CACHE.put(key, "sched_ring", schedule="s0")
    s0 = SPC.snapshot()
    got = retune.retune_key(key, seed=7, exclude=("sched_ring",),
                            live_p50_us=321.0)
    assert got is not None and got["version"] == 2
    assert got["previous"] == "sched_ring"
    assert got["algorithm"] != "sched_ring"
    exp = retune.candidate_scores(key, seed=7, exclude=("sched_ring",))
    assert got["algorithm"] == exp[0]["algo"]
    ent = scache.CACHE.get(key)
    assert ent["source"] == "retune:drift" and ent["frontier"]
    assert SPC.snapshot()["sched_retunes"] \
        == s0.get("sched_retunes", 0) + 1
    # a key outside the grammar can't be swept: counted, not crashed
    s1 = SPC.snapshot()
    assert retune.retune_key("junk", seed=7) is None
    assert SPC.snapshot()["sched_retune_failed"] \
        == s1.get("sched_retune_failed", 0) + 1


# -- topology penalties -----------------------------------------------------

def test_topology_penalties_reroot_and_segments():
    assert retune.set_topology_penalties([2], skew=True)
    assert not retune.set_topology_penalties([2], skew=True)  # no-op
    assert retune.penalized_ranks() == {2} and retune.skew_active()
    # slow non-leader sinks to the back of its group
    assert retune.reroot_groups([[0, 1], [2, 3]]) == [[0, 1], [3, 2]]
    assert retune.effective_segments(2) == 4
    retune.clear_topology_penalties()
    assert retune.reroot_groups([[0, 1], [2, 3]]) == [[0, 1], [2, 3]]
    assert retune.effective_segments(2) == 2
    # slow leader: group re-roots; an all-slow group sinks last
    retune.set_topology_penalties([0], skew=False)
    assert retune.reroot_groups([[0, 1], [2, 3]]) == [[1, 0], [2, 3]]
    assert retune.reroot_groups([[0], [1, 2]]) == [[1, 2], [0]]
    assert retune.penalty_stamp() == ((0,), False)


def test_build_schedule_digest_reshapes_under_penalties():
    d0 = sched.build_schedule("sched_hier", 4).digest()
    s0 = sched.build_schedule("sched_ring_seg", 8).digest()
    retune.set_topology_penalties([0], skew=True)
    # penalty state is part of the memo key: no stale hits
    d1 = sched.build_schedule("sched_hier", 4).digest()
    s1 = sched.build_schedule("sched_ring_seg", 8).digest()
    assert d1 != d0 and s1 != s0
    assert d1 == ir.hierarchical([[1, 2, 3, 0]]).digest()
    retune.clear_topology_penalties()
    assert sched.build_schedule("sched_hier", 4).digest() == d0
    assert sched.build_schedule("sched_ring_seg", 8).digest() == s0


# -- hysteresis -------------------------------------------------------------

def test_hysteresis_single_tick_noise_never_retunes(clean_cache):
    key = scache.cache_key("allreduce", 1 << 12, 8, None, "none")
    scache.CACHE.put(key, "sched_ring")
    wt = watchtower.Watchtower(seed=7, interval_ms=100)
    s0 = SPC.snapshot()
    out = []
    # noise, two clean ticks (streak resets), noise again: no retune
    for us in (100, 300, 100, 100, 300, 100, 100):
        out += wt.tick(_sample(us))
    assert out == []
    assert scache.CACHE.get(key)["version"] == 1
    snap = SPC.snapshot()
    assert snap["sched_drift_detected"] \
        == s0.get("sched_drift_detected", 0) + 2
    assert snap.get("sched_retunes", 0) == s0.get("sched_retunes", 0)
    # the first observation became the drift baseline on the entry
    assert scache.CACHE.get(key)["baseline_p50_us"] == 100.0


def test_sustained_drift_retunes_once_then_cooldown(clean_cache):
    key = scache.cache_key("allreduce", 1 << 12, 8, None, "none")
    scache.CACHE.put(key, "sched_ring")
    wt = watchtower.Watchtower(seed=7, interval_ms=100)
    s0 = SPC.snapshot()
    assert wt.tick(_sample(100)) == []          # baseline
    assert wt.tick(_sample(300)) == []          # drift 1/2
    got = wt.tick(_sample(300))                 # drift 2/2 -> retune
    assert len(got) == 1 and got[0]["version"] == 2
    assert got[0]["previous"] == "sched_ring"
    assert scache.CACHE.get(key)["version"] == 2
    # post-retune: fresh baseline, and the cooldown suppresses the
    # next sustained drift instead of thrashing
    assert wt.tick(_sample(400)) == []          # re-baseline at 400
    assert wt.tick(_sample(900)) == []          # drift 1/2
    assert wt.tick(_sample(900)) == []          # due, but cooling down
    snap = SPC.snapshot()
    assert snap["sched_retunes"] == s0.get("sched_retunes", 0) + 1
    assert snap["sched_retune_suppressed"] \
        >= s0.get("sched_retune_suppressed", 0) + 1
    assert scache.CACHE.get(key)["version"] == 2
    sup = [e for e in wt.log() if e.get("action") == "suppressed"]
    assert sup and sup[-1]["reason"] == "cooldown"


def test_budget_suppresses_but_streak_persists(clean_cache):
    k10 = scache.cache_key("allreduce", 1 << 10, 8, None, "none")
    k12 = scache.cache_key("allreduce", 1 << 12, 8, None, "none")
    scache.CACHE.put(k10, "sched_ring")
    scache.CACHE.put(k12, "sched_ring")
    wt = watchtower.Watchtower(seed=7, interval_ms=100)

    def both(us):
        s = _sample(us, bucket=10)
        s["hists"].update(_sample(us, bucket=12)["hists"])
        return s

    wt.tick(both(100))
    wt.tick(both(300))
    got = wt.tick(both(300))  # both due; budget=1 -> first key only
    assert [g["key"] for g in got] == [k10]
    sup = [e for e in wt.log() if e.get("action") == "suppressed"]
    assert sup and sup[-1] == {"tick": 3, "key": k12,
                               "action": "suppressed",
                               "reason": "budget"}
    # the suppressed key's streak persisted: next tick it fires
    got = wt.tick(both(300))
    assert [g["key"] for g in got] == [k12]
    assert scache.CACHE.get(k10)["version"] == 2
    assert scache.CACHE.get(k12)["version"] == 2


# -- the tier-1 closed-loop drill -------------------------------------------

def test_closed_loop_drill_faultline_drift(clean_cache):
    """Acceptance: faultline-injected drift on one key triggers
    exactly one version-bumped retune within 3 sampler ticks of the
    drift becoming sustained; single-tick noise is suppressed by the
    hysteresis; the new winner's measured p50 beats the drifted one."""
    world = mt.world()
    payload = np.arange(64, dtype=np.float32)  # 256 B -> bucket 8
    dst = 1 if world.size > 1 else 0

    def measured_block(tag, delayed):
        h = counters.Histogram("coll_allreduce_b8")
        if delayed:
            inject.arm(["delay@pml:op=send,ms=10,count=inf"], seed=0)
        comm = world.dup()
        try:
            for _ in range(6):
                t0 = time.perf_counter()
                comm.send(payload, dst, tag, source=0)
                h.record(time.perf_counter() - t0)
                comm.recv(0, tag, dest=dst)
        finally:
            comm.free()
            if delayed:
                inject.disarm()
        return h.snapshot()

    fast = measured_block(910, delayed=False)
    slow = measured_block(911, delayed=True)
    assert slow["p50"] >= 2.0 * fast["p50"]  # the injected drift

    key = scache.cache_key("allreduce", 256, 8, None, "drill")
    scache.CACHE.put(key, "sched_ring", schedule="s0")
    wt = watchtower.Watchtower(seed=7, interval_ms=100)
    s0 = SPC.snapshot()

    def tick(snap):
        return wt.tick({"hists": {"coll_allreduce_b8": snap}})

    assert tick(fast) == []   # baseline
    assert tick(slow) == []   # single-tick noise...
    assert tick(fast) == []
    assert tick(fast) == []   # ...suppressed (streak reset)
    assert scache.CACHE.get(key)["version"] == 1
    drift_onset = wt.ticks + 1
    results = []
    while wt.ticks < drift_onset + 2:  # within 3 ticks of onset
        results += tick(slow)
    assert len(results) == 1 and results[0]["version"] == 2
    ent = scache.CACHE.get(key)
    assert ent["version"] == 2
    assert ent["previous"]["algorithm"] == "sched_ring"
    assert ent["source"] == "retune:drift"
    snap = SPC.snapshot()
    assert snap["sched_retunes"] == s0.get("sched_retunes", 0) + 1
    # the loop's decisions are on the record
    acts = [e["action"] for e in wt.log()]
    assert acts.count("retune") == 1
    # with the fault gone, the installed winner's measured p50 beats
    # the drifted p50 that triggered the retune
    post = measured_block(912, delayed=False)
    assert post["p50"] < slow["p50"]


def test_retune_log_and_cache_digest_byte_identical(tmp_path):
    """Acceptance: two same-seed controller processes observing the
    same drift produce byte-identical retune logs and cache digests."""
    prog = (
        "import os\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from ompi_tpu.coll.sched import cache as scache\n"
        "from ompi_tpu.telemetry import watchtower\n"
        "scache.CACHE.clear()\n"
        "key = scache.cache_key('allreduce', 1 << 12, 8, None, 'fp0')\n"
        "scache.CACHE.put(key, 'sched_ring', schedule='s0')\n"
        "wt = watchtower.Watchtower(seed=3, interval_ms=50)\n"
        "def s(us):\n"
        "    return {'hists': {'coll_allreduce_b12':\n"
        "            {'count': 8, 'p50': us / 1e6}}}\n"
        "for us in (100.0, 320.0, 320.0, 90.0, 90.0):\n"
        "    wt.tick(s(us))\n"
        "print(wt.digest())\n"
        "print(scache.CACHE.digest())\n"
    )
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True,
            text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == 0, r.stderr[-1500:]
        outs.append(r.stdout)
    assert outs[0] == outs[1]
    wt_digest, cache_digest = outs[0].split()
    assert len(wt_digest) == 64 and len(cache_digest) == 64


# -- straggler findings -> reroot (satellite 3) -----------------------------

def test_straggler_drill_reroots_slow_host_within_two_ticks(clean_cache):
    """A persistently slow rank 0 (two ticks of findings) becomes a
    topology penalty: the hierarchical tree re-roots away from it, the
    cached sched_hier key is version-bumped so its recorded digest
    matches the reshaped program, and the old entry survives for
    rollback."""
    d0 = sched.build_schedule("sched_hier", 4).digest()
    key = scache.cache_key("allreduce", 1 << 10, 4, None, "fpY")
    scache.CACHE.put(key, "sched_hier", schedule=d0)
    wt = watchtower.Watchtower(seed=5, interval_ms=100)

    for tick in (1, 2):
        snaps = {r: _snap(r, 100e-6) for r in range(1, 4)}
        snaps[0] = _snap(0, 50e-3)  # rank 0 is the slow host
        assert straggler.analyze(snaps)
        mpit.check_watches()  # drain staged findings into the log
        wt.tick({"hists": {}})
        if tick == 1:  # one tick of findings is not persistence
            assert retune.penalized_ranks() == frozenset()

    assert retune.penalized_ranks() == {0} and retune.skew_active()
    # the reshaped generator output: rank 0 no longer roots the tree
    assert sched.build_schedule("sched_hier", 4).digest() \
        == ir.hierarchical([[1, 2, 3, 0]]).digest() != d0
    ent = scache.CACHE.get(key)
    assert ent["version"] == 2 and ent["source"] == "retune:straggler"
    assert ent["previous"]["algorithm"] == "sched_hier"
    assert ent["previous"]["schedule"] == d0
    # a bad reshape is recoverable: rollback restores the old winner
    assert scache.CACHE.rollback(key)
    assert scache.CACHE.get(key)["algorithm"] == "sched_hier"
    # penalties are sticky across ticks: no re-fire on the same set
    log_len = len(wt.log())
    wt.tick({"hists": {}})
    assert len(wt.log()) == log_len


# -- SLO selection ----------------------------------------------------------

def test_slo_frontier_pick_cheapest_wire_meeting_target():
    ent = {
        "baseline_p50_us": 10.0,
        "frontier": [
            {"algo": "sched_ring", "score": 1.0, "steps": 14, "wire": 200.0},
            {"algo": "sched_rd", "score": 1.5, "steps": 3, "wire": 50.0},
            {"algo": "sched_hier", "score": 4.0, "steps": 6, "wire": 30.0},
        ],
    }
    # est p50: ring 10, rd 15, hier 40. target 20 -> rd (least wire
    # among feasible), target 100 -> hier, target 9 -> nothing meets
    # it (the winner stands; the violation gets accounted instead)
    assert slo.frontier_pick(ent, 20.0) == "sched_rd"
    assert slo.frontier_pick(ent, 100.0) == "sched_hier"
    assert slo.frontier_pick(ent, 9.0) is None
    assert slo.frontier_pick({"frontier": ent["frontier"]}, 20.0) is None
    assert slo.frontier_pick(ent, 0.0) is None


def test_slo_targets_and_violation_minutes():
    old = config.get("coll_slo_p50_us")
    try:
        assert slo.target_for("7") == 0.0  # no SLO configured
        g0 = dispatch_epoch.value
        slo.set_target("7", 50.0)
        assert dispatch_epoch.value > g0  # memoized plans re-consult
        assert slo.target_for("7") == 50.0
        config.set("coll_slo_p50_us", 25.0)
        assert slo.target_for(None) == 25.0
        assert slo.target_for("other") == 25.0  # global fallback
        assert slo.targets() == {"7": 50.0, "world": 25.0}
        slo.set_target("7", None)
        assert slo.target_for("7") == 25.0
        slo.note_violation("tenant-a", 30.0)
        slo.note_violation("tenant-a", 30.0)
        assert slo.violation_minutes() == {"tenant-a": 1.0}
    finally:
        config.set("coll_slo_p50_us", old)


def test_decide_allreduce_slo_scope_picks_frontier(clean_cache):
    op = op_lookup("sum")
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", 1 << 12, 8, None, fp)
    scache.CACHE.put(
        key, "sched_ring",
        frontier=[
            {"algo": "sched_ring", "score": 1.0, "steps": 14,
             "wire": 200.0},
            {"algo": "sched_rd", "score": 1.5, "steps": 3,
             "wire": 50.0},
        ],
        baseline_p50_us=10.0,
    )
    # no SLO in force: the throughput winner stands
    assert tuned.decide_allreduce(op, 1 << 12, 8, None) == "sched_ring"
    slo.set_target("s1", 20.0)
    s0 = SPC.snapshot()
    # the scoped call swaps to the cheapest-wire point meeting 20us
    assert tuned.decide_allreduce(op, 1 << 12, 8, None,
                                  scope="s1") == "sched_rd"
    assert SPC.snapshot()["sched_slo_frontier_picks"] \
        == s0.get("sched_slo_frontier_picks", 0) + 1
    # other scopes keep the winner
    assert tuned.decide_allreduce(op, 1 << 12, 8, None,
                                  scope="s2") == "sched_ring"
    # an unmeetable target never downgrades below the winner
    slo.set_target("s1", 5.0)
    assert tuned.decide_allreduce(op, 1 << 12, 8, None,
                                  scope="s1") == "sched_ring"


def test_watchtower_slo_sweep_accounts_minutes():
    slo.set_target("t1", 50.0)
    wt = watchtower.Watchtower(seed=1, interval_ms=6000)
    wt.tick({"hists": {"coll_allreduce": {"count": 4, "p50": 200e-6}}})
    assert slo.violation_minutes() == {"t1": 0.1}  # one 6s tick over
    wt.tick({"hists": {"coll_allreduce": {"count": 4, "p50": 20e-6}}})
    assert slo.violation_minutes() == {"t1": 0.1}  # meeting it: flat


# -- exporter control-plane lines (satellite 1) -----------------------------

def test_prometheus_control_plane_series_guaranteed():
    slo.note_violation("tenant_b", 90.0)
    text = export.prometheus_text()
    for cname, _help in export.GUARANTEED_COUNTERS:
        assert f"ompi_tpu_{cname}" in text  # present even at zero
    assert "ompi_tpu_health_ledger_transitions_total" in text
    assert ('ompi_tpu_slo_violation_minutes{scope="tenant_b"} 1.5'
            in text)
    # a hand-built registry render carries none of the live-process
    # extras (the golden-file contract in test_telemetry)
    reg = counters.CounterRegistry()
    reg.counter("x_total", description="x").add(1)
    assert "sched_cache_hits" not in export.prometheus_text(reg)


# -- fleet stale-rank degradation (satellite 2) -----------------------------

def test_fleet_stale_ranks_degrade_to_last_seen():
    # isolate from samples other test modules published on the modex
    modex.clear_local()
    fleet.reset_for_testing()

    def pub(seq):
        modex.put("telemetry/9", {
            "format": "ompi_tpu.telemetry.v1", "rank": 9, "seq": seq,
            "counters": {"sm_send_bytes": seq}, "hists": {},
            "health": {}, "peers": {},
        })

    pub(1)
    s0 = SPC.snapshot().get("telemetry_fleet_stale_ranks", 0)
    g1 = fleet.gather(11)
    assert 9 in g1 and not g1[9].get("stale")
    assert 10 not in g1  # never published: absent, not stale
    # same seq next tick: the publisher missed its tick -> tagged
    g2 = fleet.gather(11)
    assert g2[9]["stale"] and g2[9]["counters"]["sm_send_bytes"] == 1
    assert SPC.snapshot()["telemetry_fleet_stale_ranks"] == s0 + 1
    # a fresh publication clears the tag
    pub(2)
    g3 = fleet.gather(11)
    assert not g3[9].get("stale")
    # key vanishes entirely (modex restart): last-seen sample fills in
    modex.clear_local()
    g4 = fleet.gather(11)
    assert g4[9]["stale"] and g4[9]["counters"]["sm_send_bytes"] == 2
    assert 10 not in g4  # never-published stays absent
    assert SPC.snapshot()["telemetry_fleet_stale_ranks"] == s0 + 2


# -- sampler hook -----------------------------------------------------------

def test_sampler_tick_drives_watchtower_when_enabled():
    old = config.get("telemetry_watchtower_enable")
    try:
        s = sampler.Sampler(seed=0, interval_ms=50)
        s.tick()
        assert watchtower._WT is None  # off by default: not even built
        config.set("telemetry_watchtower_enable", True)
        s.tick()
        assert watchtower.get().ticks == 1
    finally:
        config.set("telemetry_watchtower_enable", old)


# -- benchgate (the enforced ratchet) ---------------------------------------

def test_benchgate_direction_and_regression_semantics():
    assert benchgate.direction("busbw_gbps") == "higher"
    assert benchgate.direction("p50_64B_us") == "lower"
    assert benchgate.direction("overhead_pct") == "lower"  # not gbps
    assert benchgate.direction("mystery") is None
    assert benchgate._is_regression("p50_us", 130.0, 100.0, 0.25)
    assert not benchgate._is_regression("p50_us", 124.0, 100.0, 0.25)
    assert benchgate._is_regression("gbps", 70.0, 100.0, 0.25)
    assert not benchgate._is_regression("gbps", 80.0, 100.0, 0.25)
    # pct rows ratchet on absolute points near zero, not relative
    assert not benchgate._is_regression("overhead_pct", 1.9, 0.1, 0.25)
    assert benchgate._is_regression("overhead_pct", 2.3, 0.1, 0.25)
    assert not benchgate._is_regression("mystery", 9e9, 1.0, 0.25)


def test_benchgate_trajectory_loads_and_self_replay_passes():
    rounds = benchgate.load_trajectory(TRAJ)
    assert len(rounds) >= 10
    best = benchgate.baselines(rounds)
    assert ("fabric_loopback", "p50_64B_us") in best
    assert benchgate.main(["--root", TRAJ, "--dry-run"]) == 0
    # the recorded trajectory itself passes its own ratchet (host-only
    # rc!=0 rounds ride the degraded-row excusal)
    assert benchgate.main(["--root", TRAJ, "--self"]) == 0


def test_benchgate_fails_synthetic_regression(tmp_path, capsys):
    rounds = benchgate.load_trajectory(TRAJ)
    best = benchgate.baselines(rounds)[("fabric_loopback",
                                        "p50_64B_us")]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"rows": {"fabric_loopback": {"p50_64B_us": best * 10}}}))
    assert benchgate.main(["--root", TRAJ, "--current",
                           str(bad)]) == 1
    assert "RATCHET BREAK" in capsys.readouterr().out
    # the same regression tagged degraded is excused, not silent
    excused = tmp_path / "excused.json"
    excused.write_text(json.dumps(
        {"rows": {"fabric_loopback": {"p50_64B_us": best * 10,
                                      "degraded": True}}}))
    assert benchgate.main(["--root", TRAJ, "--current",
                           str(excused)]) == 0
    assert "excused" in capsys.readouterr().out
    # at the baseline: clean pass
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(
        {"rows": {"fabric_loopback": {"p50_64B_us": best}}}))
    assert benchgate.main(["--root", TRAJ, "--current",
                           str(ok)]) == 0
    # malformed current / empty trajectory: run failure, not a break
    broken = tmp_path / "broken.json"
    broken.write_text("not json {")
    assert benchgate.main(["--root", TRAJ, "--current",
                           str(broken)]) == 2
    assert benchgate.main(["--root", str(tmp_path / "nowhere")]) == 2


# -- retuneaudit commlint rule + CI seams (satellite 5) ---------------------

def test_retuneaudit_rule_flags_silent_installs():
    lin = Linter()
    bad = (
        "def silent(key):\n"
        "    CACHE.bump(key, 'ring')\n"
    )
    found = [f for f in lin.lint_source(bad) if f.rule == "retuneaudit"]
    assert len(found) == 1 and found[0].line == 2
    clean = (
        "def evidenced(key):\n"
        "    _cache.CACHE.put(key, 'ring')\n"
        "    SPC.record('sched_retunes')\n"
        "def allowed(key):\n"
        "    # commlint: allow(retuneaudit)\n"
        "    CACHE.bump(key, 'ring')\n"
        "def other_surface(key):\n"
        "    modex.put(key, {'x': 1})\n"  # not a schedule cache
        "    queue.put(key)\n"
    )
    assert [f for f in lin.lint_source(clean)
            if f.rule == "retuneaudit"] == []


def test_lint_baseline_and_benchgate_gate_from_tier1():
    """The CI seams run green from the suite itself: the commlint
    baseline ratchet and the bench ratchet's trajectory validation."""
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.lint", "ompi_tpu",
         "--baseline", "ompi_tpu/analysis/selfcheck_baseline.json"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-500:]
    r = subprocess.run(
        [sys.executable, "bench.py", "--gate", "--dry-run",
         "--root", TRAJ],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-500:]
    assert "trajectory ok" in r.stdout
