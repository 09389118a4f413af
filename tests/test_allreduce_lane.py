"""The communicator's allreduce lane: one memoized route per (shape,
dtype, op), served while the dispatch epoch holds.

Each epoch source gets one case: after the change, the next call is not
served by the stale entry, and the result is still the sum. Pytree and
wrong-leading-dim inputs keep the slow path and its errors; every call,
hit or miss, counts once in ``coll_allreduce_calls`` and once in its
``coll_allreduce_algo_<algo>``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu import Group
from ompi_tpu.analysis import sanitizer
from ompi_tpu.coll import breaker
from ompi_tpu.coll.sched import cache as scache
from ompi_tpu.coll.sched import slo
from ompi_tpu.core import config, dispatch_epoch
from ompi_tpu.core.counters import SPC
from ompi_tpu.core.errors import ArgumentError, CommError, RevokedError
from ompi_tpu.ft import inject, lifeboat
from ompi_tpu.health import ledger
from ompi_tpu.monitoring import MONITOR

N = 4


@pytest.fixture(scope="module")
def world():
    if not mt.initialized():
        mt.init()
    return mt.world()


@pytest.fixture
def comm(world):
    """A fresh 4-rank communicator (a 4-device CPU mesh) per test."""
    c = world.create(Group(range(N)))
    yield c
    if not c._freed:
        c.free()


def _counts() -> dict:
    snap = SPC.snapshot()
    return {k: snap.get(k, 0) for k in (
        "coll_allreduce_lane_hits", "coll_allreduce_lane_builds",
        "coll_allreduce_calls")}


def _delta(before: dict) -> dict:
    after = _counts()
    return {k: after[k] - before[k] for k in before}


def _buf(comm, elems: int = 1024):
    host = np.arange(N * elems, dtype=np.float32).reshape(N, elems)
    return comm.put_rank_major(host), host.sum(axis=0)


def _check_sum(out, ref) -> None:
    got = np.asarray(out)
    assert got.shape[0] == N
    for row in got:
        np.testing.assert_array_equal(row, ref)


def _warm(comm, x):
    """Fill the lane for x and prove the next call is a hit."""
    comm.allreduce(x, "sum")
    before = _counts()
    comm.allreduce(x, "sum")
    assert _delta(before)["coll_allreduce_lane_hits"] == 1


def _config_set():
    name = "coll_tuned_host_small_max_bytes"
    old = config.get(name)
    config.set(name, old)  # any mutation moves the epoch, same value too
    return lambda: config.set(name, old)


def _breaker_failure():
    breaker.record_failure("allreduce", "ring")
    breaker.record_success("allreduce", "ring")  # quiet again
    return breaker.reset


def _health_transition():
    ledger.report_failure("dcn", scope="lane", cause="t")
    ledger.report_success("dcn", scope="lane")  # quiet again
    return ledger.reset


def _sched_cache_put():
    key = scache.cache_key("allreduce", 1 << 30, 64, None, "lane")
    scache.CACHE.put(key, "sched_ring", schedule="s")
    return scache.CACHE.clear


def _slo_change():
    slo.set_target("lane", 50.0)
    return lambda: slo.set_target("lane", None)


def _faultline_armed():
    inject.arm("")
    return inject.disarm


def _memchecker_on():
    config.set("memchecker_base_enable", True)
    return lambda: config.set("memchecker_base_enable", False)


def _monitor_on():
    MONITOR.enable(True)
    return lambda: MONITOR.enable(False)


def _sanitizer_on():
    sanitizer.enable()
    return sanitizer.finalize_check


# (change, whether the lane may refill while the change holds)
SOURCES = {
    "config_set": (_config_set, True),
    "breaker_failure": (_breaker_failure, True),
    "health_transition": (_health_transition, True),
    "sched_cache_put": (_sched_cache_put, True),
    "slo_change": (_slo_change, True),
    "faultline_armed": (_faultline_armed, False),
    "memchecker_on": (_memchecker_on, False),
    "monitor_on": (_monitor_on, False),
    "sanitizer_on": (_sanitizer_on, False),
}


# 16 B rank-major takes the host tier, 16 KiB the compiled plan
@pytest.mark.parametrize("elems", [1, 1024], ids=["host", "plan"])
@pytest.mark.parametrize("source", list(SOURCES))
def test_epoch_source_invalidates_lane(comm, source, elems):
    change, refills = SOURCES[source]
    x, ref = _buf(comm, elems)
    _warm(comm, x)
    epoch = dispatch_epoch.value
    undo = change()
    try:
        assert dispatch_epoch.value > epoch
        before = _counts()
        _check_sum(comm.allreduce(x, "sum"), ref)
        d = _delta(before)
        assert d["coll_allreduce_lane_hits"] == 0  # not the stale entry
        assert d["coll_allreduce_lane_builds"] == (1 if refills else 0)
        assert d["coll_allreduce_calls"] == 1
    finally:
        undo()
    before = _counts()
    _check_sum(comm.allreduce(x, "sum"), ref)
    _check_sum(comm.allreduce(x, "sum"), ref)
    d = _delta(before)
    # the undo moved the epoch too: one build, then the lane serves
    assert d["coll_allreduce_lane_builds"] == 1
    assert d["coll_allreduce_lane_hits"] == 1


def test_vtable_reselection_invalidates_lane(comm):
    x, ref = _buf(comm)
    _warm(comm, x)
    comm._select_frameworks()
    before = _counts()
    _check_sum(comm.allreduce(x, "sum"), ref)
    d = _delta(before)
    assert d["coll_allreduce_lane_hits"] == 0
    assert d["coll_allreduce_lane_builds"] == 1


def test_revoked_comm_is_not_served(comm):
    x, _ = _buf(comm)
    _warm(comm, x)
    lifeboat.revoke(comm, cause="lane-test")
    before = _counts()
    with pytest.raises(RevokedError):
        comm.allreduce(x, "sum")
    assert _delta(before)["coll_allreduce_lane_builds"] == 0


def test_freed_comm_is_not_served(comm):
    x, _ = _buf(comm)
    _warm(comm, x)
    comm.free()
    assert comm._lane == {}
    before = _counts()
    with pytest.raises(CommError, match="freed"):
        comm.allreduce(x, "sum")
    d = _delta(before)
    assert d["coll_allreduce_lane_hits"] == 0
    assert d["coll_allreduce_lane_builds"] == 0


def test_pytree_input_takes_slow_path(comm):
    x, ref = _buf(comm)
    before = _counts()
    out = comm.allreduce({"a": x, "b": x}, "sum")
    _check_sum(out["a"], ref)
    _check_sum(out["b"], ref)
    d = _delta(before)
    assert d["coll_allreduce_lane_hits"] == 0
    assert d["coll_allreduce_lane_builds"] == 0
    assert comm._lane == {}


@pytest.mark.parametrize("shape", [(N - 1, 8), (N + 1, 8), ()],
                         ids=["short", "long", "scalar"])
def test_wrong_leading_dim_raises_argument_error(comm, shape):
    x = jnp.ones(shape, jnp.float32)
    before = _counts()
    for _ in range(2):  # a repeat is no hit either
        with pytest.raises(ArgumentError, match="leading dim"):
            comm.allreduce(x, "sum")
    d = _delta(before)
    assert d["coll_allreduce_lane_hits"] == 0
    assert d["coll_allreduce_lane_builds"] == 0


@pytest.mark.parametrize("elems,algo", [(1, "host"), (1024, "native")],
                         ids=["host", "plan"])
def test_calls_and_algo_counted_once_per_call(comm, elems, algo):
    x, ref = _buf(comm, elems)
    name = f"coll_allreduce_algo_{algo}"
    for expect_hit in (0, 1, 1):
        c0 = SPC.snapshot().get(name, 0)
        before = _counts()
        _check_sum(comm.allreduce(x, "sum"), ref)
        d = _delta(before)
        assert d["coll_allreduce_calls"] == 1
        assert SPC.snapshot().get(name, 0) - c0 == 1
        assert d["coll_allreduce_lane_hits"] == expect_hit
        assert d["coll_allreduce_lane_builds"] == 1 - expect_hit


def test_iallreduce_rides_the_lane(comm):
    x, ref = _buf(comm)
    _warm(comm, x)
    before = _counts()
    req = comm.iallreduce(x, "sum")
    req.wait()
    _check_sum(req.result(), ref)
    assert _delta(before)["coll_allreduce_lane_hits"] == 1


@pytest.mark.parametrize("breaker_on", [True, False], ids=["breaker", "off"])
def test_tier_fault_under_a_hit_drops_the_entry(comm, breaker_on):
    x, ref = _buf(comm)
    _warm(comm, x)
    (key, ent), = comm._lane.items()

    def faulty(buf):
        raise RuntimeError("tier fault")

    old = config.get("coll_breaker_enable")
    config.set("coll_breaker_enable", breaker_on)
    try:
        # after the config mutation, so the faulty entry is current
        comm._lane[key] = (dispatch_epoch.value, faulty) + ent[2:]
        if breaker_on:
            # re-routed through the slow path, which needs no memo
            _check_sum(comm.allreduce(x, "sum"), ref)
            assert key not in comm._lane
        else:
            with pytest.raises(RuntimeError, match="tier fault"):
                comm.allreduce(x, "sum")
    finally:
        config.set("coll_breaker_enable", old)
