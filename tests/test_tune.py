"""Auto-tuner: sweep, rules emission, round-trip through coll/tuned."""

import json

import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu.core import config


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


def test_tune_produces_valid_rules(tmp_path):
    from ompi_tpu.coll.tuned import ALLREDUCE_ALGOS
    from ompi_tpu.tools import tune

    comm = mt.world()
    rules = tune.tune(
        comm, ops=["allreduce"], min_bytes=256, max_bytes=4096, iters=1
    )
    assert "allreduce" in rules and rules["allreduce"]
    for rule in rules["allreduce"]:
        assert rule["algorithm"] in ALLREDUCE_ALGOS
    # last band must be open-ended
    assert "max_bytes" not in rules["allreduce"][-1]


def test_tuned_consumes_generated_rules(tmp_path):
    from ompi_tpu.tools import tune

    comm = mt.world()
    rules = tune.tune(
        comm, ops=["allreduce"], min_bytes=256, max_bytes=1024, iters=1
    )
    # force a recognizable winner so we can assert the dispatch
    rules["allreduce"] = [{"algorithm": "recursive_doubling"}]
    p = str(tmp_path / "rules.json")
    with open(p, "w") as f:
        json.dump(rules, f)
    config.set("coll_tuned_rules_file", p)
    try:
        from ompi_tpu.core.counters import SPC

        c = comm.dup()
        before = SPC.snapshot().get(
            "coll_allreduce_algo_recursive_doubling", 0
        )
        x = c.put_rank_major(np.ones((c.size, 64), np.float32))
        out = np.asarray(c.allreduce(x))
        np.testing.assert_allclose(
            out[0], np.full(64, c.size, np.float32)
        )
        after = SPC.snapshot().get(
            "coll_allreduce_algo_recursive_doubling", 0
        )
        assert after > before
    finally:
        config.set("coll_tuned_rules_file", "")


def test_tune_new_decision_spaces():
    """The sweep covers the reduce / reduce_scatter / gather / scatter
    spaces added for parity with coll_tuned_*_decision.c, and winners
    come from the registered algorithm sets."""
    from ompi_tpu.coll.tuned import (
        GATHER_ALGOS, REDUCE_ALGOS, REDUCE_SCATTER_ALGOS, SCATTER_ALGOS,
    )
    from ompi_tpu.tools import tune

    comm = mt.world()
    rules = tune.tune(
        comm, ops=["reduce", "reduce_scatter", "gather", "scatter"],
        min_bytes=256, max_bytes=1024, iters=1,
    )
    spaces = {
        "reduce": REDUCE_ALGOS,
        "reduce_scatter": REDUCE_SCATTER_ALGOS,
        "gather": GATHER_ALGOS,
        "scatter": SCATTER_ALGOS,
    }
    for opname, space in spaces.items():
        assert rules[opname], opname
        for rule in rules[opname]:
            assert rule["algorithm"] in space, (opname, rule)


def test_decide_defaults_mirror_reference_cutoffs():
    """The fixed decision rules (no forced var, no rules file) follow
    the reference's shape: small commutative reduces go binomial when
    the native path is disabled, reduce_scatter picks recursive halving
    only for small commutative power-of-two cases, ordered-required ops
    always route native, and scatter defaults native unconditionally."""
    from ompi_tpu import ops
    from ompi_tpu.coll import tuned

    config.set("coll_tuned_prefer_native", False)
    try:
        s = ops.lookup("sum")
        assert tuned.decide_reduce(s, 1024, 8) == "binomial"
        # >= the 1 MiB pipeline cutoff: segmented chain (round 4;
        # reference pipeline tier, coll_tuned_decision_fixed.c:250-310)
        assert tuned.decide_reduce(s, 1 << 20, 8) == "pipelined"
        assert tuned.decide_reduce(s, 256 << 10, 8) == "native"
        assert tuned.decide_reduce_scatter(s, 1024, 8) == \
            "recursive_halving"
        assert tuned.decide_reduce_scatter(s, 1024, 6) == "ring"  # !pof2
        assert tuned.decide_reduce_scatter(s, 1 << 20, 8) == "ring"
        maxloc = ops.lookup("maxloc")  # joint op: ordered path only
        assert tuned.decide_reduce_scatter(maxloc, 1024, 8) == "native"
        assert tuned.decide_gather(1024, 8) == "binomial"
        assert tuned.decide_gather(1 << 20, 8) == "native"
        assert tuned.decide_gather(1024, 2) == "native"  # tiny comm
        assert tuned.decide_scatter(1024, 8) == "native"
    finally:
        config.set("coll_tuned_prefer_native", True)
    # with prefer_native on (default), native wins for xla-reducible ops
    assert tuned.decide_reduce(ops.lookup("sum"), 1024, 8) == "native"


def test_rules_file_covers_new_spaces(tmp_path):
    """A dynamic rules file can steer the new decision spaces (reduce /
    reduce_scatter / gather / scatter), banded by size, first match
    wins — the coll_tuned_dynamic_file.c consumption model."""
    from ompi_tpu import ops
    from ompi_tpu.coll import tuned

    p = tmp_path / "rules.json"
    p.write_text(json.dumps({
        "reduce": [{"max_bytes": 4096, "algorithm": "binomial"},
                   {"algorithm": "native"}],
        "reduce_scatter": [{"algorithm": "ring"}],
        "gather": [{"min_ranks": 4, "algorithm": "binomial"}],
        "scatter": [{"algorithm": "binomial"}],
    }))
    config.set("coll_tuned_rules_file", str(p))
    try:
        s = ops.lookup("sum")
        assert tuned.decide_reduce(s, 1024, 8) == "binomial"
        # the rules file's catch-all entry outranks the fixed-rule
        # pipeline tier (dynamic rules win, decision_fixed is fallback)
        assert tuned.decide_reduce(s, 1 << 20, 8) == "native"
        assert tuned.decide_reduce_scatter(s, 1 << 20, 8) == "ring"
        assert tuned.decide_gather(1 << 20, 8) == "binomial"
        assert tuned.decide_gather(64, 2) == "native"  # min_ranks miss
        assert tuned.decide_scatter(64, 8) == "binomial"
    finally:
        config.set("coll_tuned_rules_file", "")


def test_tune_cli(tmp_path):
    from ompi_tpu.tools import tune

    p = str(tmp_path / "r.json")
    rc = tune.main([
        "--out", p, "--ops", "bcast", "--min-bytes", "256",
        "--max-bytes", "256", "--iters", "1",
    ])
    assert rc == 0
    with open(p) as f:
        doc = json.load(f)
    assert "bcast" in doc


def test_round4_algorithm_depth_spaces():
    """Chain/binary/pipelined bcast, pipelined reduce and the scan/
    exscan variants are selectable through the tuned decision layer
    (VERDICT r4 item 7; reference coll_tuned_decision_fixed.c:250-310)."""
    from ompi_tpu import ops as _ops
    from ompi_tpu.coll import tuned

    assert {"chain", "binary", "pipelined"} <= set(tuned.BCAST_ALGOS)
    assert "pipelined" in tuned.REDUCE_ALGOS
    assert {"recursive_doubling", "linear_chain"} <= set(tuned.SCAN_ALGOS)
    assert {"recursive_doubling", "linear_chain"} <= set(
        tuned.EXSCAN_ALGOS)

    s = _ops.lookup("sum")
    config.set("coll_tuned_prefer_native", False)
    try:
        # reference-shaped fixed rules: binomial small, binary mid,
        # pipelined bulk; scan flips to doubling below the small cutoff
        assert tuned.decide_bcast(1024, 8) == "binomial"
        assert tuned.decide_bcast(256 << 10, 8) == "binary"
        assert tuned.decide_bcast(4 << 20, 8) == "pipelined"
        assert tuned.decide_reduce(s, 4 << 20, 8) == "pipelined"
        assert tuned.decide_scan(s, 1024, 8) == "recursive_doubling"
        assert tuned.decide_scan(s, 4 << 20, 8) == "native"
        assert tuned.decide_exscan(s, 1024, 8) == "recursive_doubling"
    finally:
        config.set("coll_tuned_prefer_native", True)


def test_forced_depth_algorithms_through_vtable():
    """Forcing each new algorithm through the per-op MCA var runs it on
    the live comm and matches the oracle."""
    import numpy as np

    comm = mt.init()
    n = comm.size
    rng = np.random.default_rng(12)
    data = rng.standard_normal((n, 24)).astype(np.float32)
    x = comm.put_rank_major(data)

    for algo in ("chain", "binary", "pipelined"):
        config.set("coll_tuned_bcast_algorithm", algo)
        try:
            out = np.asarray(comm.bcast(x, root=3))
        finally:
            config.set("coll_tuned_bcast_algorithm", "")
        np.testing.assert_allclose(
            out, np.broadcast_to(data[3], out.shape), rtol=1e-6,
            err_msg=algo)

    config.set("coll_tuned_reduce_algorithm", "pipelined")
    try:
        out = np.asarray(comm.reduce(x, op="sum", root=0))
    finally:
        config.set("coll_tuned_reduce_algorithm", "")
    np.testing.assert_allclose(out, data.sum(0), rtol=1e-4, atol=1e-5)

    acc = np.cumsum(data, axis=0)
    for algo in ("recursive_doubling", "linear_chain"):
        config.set("coll_tuned_scan_algorithm", algo)
        try:
            out = np.asarray(comm.scan(x))
        finally:
            config.set("coll_tuned_scan_algorithm", "")
        np.testing.assert_allclose(out, acc, rtol=1e-4, atol=1e-5,
                                   err_msg=algo)
        config.set("coll_tuned_exscan_algorithm", algo)
        try:
            eout = np.asarray(comm.exscan(x))
        finally:
            config.set("coll_tuned_exscan_algorithm", "")
        np.testing.assert_allclose(eout[1:], acc[:-1], rtol=1e-4,
                                   atol=1e-5, err_msg=algo)
        np.testing.assert_allclose(eout[0], 0.0, atol=1e-6)


def test_tune_sweeps_scan_spaces(tmp_path):
    """tools/tune.py covers the scan/exscan spaces (VERDICT r4 item 7:
    'wired into tuned + tune.py')."""
    from ompi_tpu.tools import tune

    p = str(tmp_path / "scan.json")
    rc = tune.main([
        "--out", p, "--ops", "scan,exscan", "--min-bytes", "256",
        "--max-bytes", "1024", "--iters", "1",
    ])
    assert rc == 0
    with open(p) as f:
        doc = json.load(f)
    assert doc["scan"] and doc["exscan"]
    from ompi_tpu.coll import tuned as tuned_mod

    known = set(tuned_mod.SCAN_ALGOS) | set(tuned_mod.EXSCAN_ALGOS)
    for rules in (doc["scan"], doc["exscan"]):
        for rule in rules:
            assert rule["algorithm"] in known


def test_bogus_rules_file_cannot_select_nonexistent_algorithm(tmp_path):
    """ISSUE PR3 satellite 1: a user rules file naming an unknown
    algorithm or opname must not break dispatch — the bad entries are
    skipped (logged once via the monitoring layer, pvar
    coll_tuned_rules_unknown) and the default decision produces a
    correct result."""
    from ompi_tpu.core.counters import SPC

    p = str(tmp_path / "bogus.json")
    with open(p, "w") as f:
        json.dump({
            "allreduce": [{"algorithm": "warp_drive"}],
            "frobnicate": [{"algorithm": "ring"}],
        }, f)
    config.set("coll_tuned_rules_file", p)
    try:
        before = SPC.snapshot().get("coll_tuned_rules_unknown", 0)
        comm = mt.world().dup()
        x = comm.put_rank_major(np.ones((comm.size, 64), np.float32))
        out = np.asarray(comm.allreduce(x))
        np.testing.assert_allclose(
            out[0], np.full(64, comm.size, np.float32))
        after = SPC.snapshot().get("coll_tuned_rules_unknown", 0)
        # one warning for the unknown opname, one for the unknown algo
        assert after >= before + 2
        # warn-once: a second dispatch must not re-count
        mid = after
        np.asarray(comm.allreduce(x))
        assert SPC.snapshot().get("coll_tuned_rules_unknown", 0) == mid
    finally:
        config.set("coll_tuned_rules_file", "")


def test_rules_file_dtype_band_matches_only_that_dtype(tmp_path):
    """Precision-aware rules: a band with a "dtype" key steers only
    payloads of that dtype; others fall through to the defaults."""
    from ompi_tpu.core.counters import SPC

    p = str(tmp_path / "f32only.json")
    with open(p, "w") as f:
        json.dump({"allreduce": [
            {"dtype": "float32", "algorithm": "recursive_doubling"},
        ]}, f)
    config.set("coll_tuned_rules_file", p)
    try:
        comm = mt.world().dup()
        before = SPC.snapshot().get(
            "coll_allreduce_algo_recursive_doubling", 0)
        xf = comm.put_rank_major(np.ones((comm.size, 64), np.float32))
        np.asarray(comm.allreduce(xf))
        after = SPC.snapshot().get(
            "coll_allreduce_algo_recursive_doubling", 0)
        assert after > before, "f32 band must match f32 payload"
        xi = comm.put_rank_major(np.ones((comm.size, 64), np.int32))
        out = np.asarray(comm.allreduce(xi))
        np.testing.assert_array_equal(
            out[0], np.full(64, comm.size, np.int32))
        # int32 payload fell through: counter unchanged
        assert SPC.snapshot().get(
            "coll_allreduce_algo_recursive_doubling", 0) == after
    finally:
        config.set("coll_tuned_rules_file", "")


def test_tune_times_no_pallas_kernel_off_tpu(monkeypatch):
    """Off a TPU the Mosaic kernels would run in interpret mode, whose
    timings measure the emulator: the sweep leaves them out."""
    from ompi_tpu.coll import tuned
    from ompi_tpu.tools import tune

    tuned._pallas_algos()  # registered or not, they must not be timed
    seen = {}
    monkeypatch.setattr(
        tune, "sweep_op",
        lambda comm, opname, algos, *a: seen.setdefault(opname, set(algos))
        and [])
    tune.tune(mt.world(), min_bytes=256, max_bytes=256, iters=1)
    assert seen and "ring" in seen["allreduce"]
    for names in seen.values():
        assert not any(tuned.is_pallas_algo(n) for n in names), names
