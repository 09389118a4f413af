"""Schedule compiler (coll/sched): IR well-formedness, lowering
validity across the op/dtype algo space, the versioned winner cache,
deterministic autotune digests, cache-steered dispatch, and the
schedcutoff lint rule."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu.core import config
from ompi_tpu.core.counters import SPC
from ompi_tpu.coll import sched, tuned
from ompi_tpu.coll.sched import autotune, ir, lattice, lower, priors
from ompi_tpu.coll.sched import cache as scache
from ompi_tpu.ops import lookup as op_lookup


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


@pytest.fixture
def clean_cache(tmp_path):
    """Point the schedule cache at an empty tmp dir and restore."""
    old_dir = config.get("coll_sched_cache_dir")
    config.set("coll_sched_cache_dir", str(tmp_path))
    scache.CACHE.clear()
    try:
        yield str(tmp_path)
    finally:
        scache.CACHE.clear()
        config.set("coll_sched_cache_dir", old_dir)


# ---------------------------------------------------------------------------
# IR
# ---------------------------------------------------------------------------

def test_ring_ir_shape():
    s = ir.ring(8)
    ir.check(s)
    assert s.nranks == 8 and s.nchunks == 8
    assert s.rounds() == 2 * (8 - 1)
    # reduce-scatter phase reduces, allgather phase copies
    kinds = {st.kind for st in s.steps}
    assert kinds == {"send", "reduce", "copy"}
    assert s.digest() == ir.ring(8).digest()
    assert s.digest() != ir.ring(4).digest()


def test_generators_registry_and_params():
    assert set(ir.GENERATORS) >= {
        "ring", "recursive_doubling", "segmented_ring", "hierarchical",
        "quantized_wire",
    }
    rd = ir.generate("recursive_doubling", 8)
    ir.check(rd)
    assert rd.rounds() == 3
    seg = ir.generate("segmented_ring", 8, segments=4)
    ir.check(seg)
    assert seg.meta["segments"] == 4
    hier = ir.generate("hierarchical", 8)
    ir.check(hier)
    qw = ir.generate("quantized_wire", 8, wire="bf16")
    assert qw.meta["wire"] == "bf16"
    # the wire codec is lowering-relevant: it must reach the digest
    assert qw.digest() != ir.generate("quantized_wire", 8,
                                      wire="int8").digest()


def test_ir_rejects_malformed():
    with pytest.raises(ir.ScheduleError):
        ir.recursive_doubling(6)  # non power of two
    with pytest.raises(ir.ScheduleError):
        ir.segmented_ring(8, 0)
    with pytest.raises(ir.ScheduleError):
        ir.hierarchical([])
    with pytest.raises(ir.ScheduleError):
        ir.ring(4, order=[0, 1, 2, 2])  # not a permutation
    with pytest.raises(ir.ScheduleError):
        ir.generate("no_such_generator", 8)
    # hand-built violations caught by the checker
    bad = ir.Schedule(name="bad", op="allreduce", nranks=4, nchunks=4,
                      steps=(ir.Step(0, "send", 1, 1, 0),))
    with pytest.raises(ir.ScheduleError):
        ir.check(bad)  # self-send
    bad2 = ir.Schedule(name="bad2", op="allreduce", nranks=4, nchunks=4,
                      steps=(ir.Step(0, "send", 9, 1, 0),))
    with pytest.raises(ir.ScheduleError):
        ir.check(bad2)  # rank out of range


# ---------------------------------------------------------------------------
# lowering validity: the acceptance sweep
# ---------------------------------------------------------------------------

_EXACT_ALGOS = ("sched_ring", "sched_rd", "sched_ring_seg", "sched_hier")


@pytest.mark.parametrize("algo", _EXACT_ALGOS)
def test_lowered_schedule_bit_identical_across_op_dtype_space(algo):
    """Every lowered exact schedule must be BIT-IDENTICAL to the ring
    reference tier on every dtype/op in the algo space (the power-of-
    two validation payload makes every reduction order exact, so any
    deviation is a compiler bug, not float noise)."""
    comm = mt.world()
    s = sched.build_schedule(algo, comm.size)
    ir.check(s)
    for dtype in ("float32", "bfloat16", "float16", "int32"):
        for op in ("sum", "max", "min", "prod"):
            assert lower.validate_schedule(comm, s, op, dtype), \
                (algo, dtype, op)


def test_quantized_wire_validity_split():
    """bf16 wire (pure casts + adds, no division) is held to
    bit-identity; the int8 wire is lossy by design and validates
    against quant's analytic worst-case bound instead."""
    comm = mt.world()
    for wire, dtypes in (("bf16", ("float32", "bfloat16")),
                         ("int8", ("float32", "bfloat16"))):
        s = ir.quantized_wire(comm.size, wire=wire)
        ir.check(s)
        for dtype in dtypes:
            assert lower.validate_schedule(comm, s, "sum", dtype), \
                (wire, dtype)


def test_registered_sched_algos_dispatch():
    """The sched_* names register into ALLREDUCE_ALGOS lazily and run
    through the normal tuned dispatch (forced-algorithm cvar) with
    correct results."""
    comm = mt.world().dup()
    data = np.arange(8 * 64, dtype=np.float32).reshape(8, 64)
    x = comm.put_rank_major(data)
    ref = data.sum(0)
    try:
        for algo in ("sched_ring", "sched_ring_seg", "sched_hier"):
            config.set("coll_tuned_allreduce_algorithm", algo)
            got = np.asarray(comm.allreduce(x))[0]
            np.testing.assert_array_equal(got, ref, err_msg=algo)
    finally:
        config.set("coll_tuned_allreduce_algorithm", "")


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_digest(clean_cache, tmp_path):
    key = scache.cache_key("allreduce", 1024, 8, "float32", "fp0")
    assert "|b10|" in key
    scache.CACHE.put(key, "ring", schedule="abc123", source="model",
                     score=1.5)
    p = str(tmp_path / "c.json")
    scache.CACHE.save(p)
    d1 = scache.CACHE.digest()
    scache.CACHE.clear()
    assert scache.CACHE.load(p) == 1
    assert scache.CACHE.get(key)["algorithm"] == "ring"
    assert scache.CACHE.digest() == d1
    # timings never enter the digest: same entries, different scores
    scache.CACHE.clear()
    scache.CACHE.put(key, "ring", schedule="abc123", source="model",
                     score=99.9, tune_ms=123.0)
    assert scache.CACHE.digest() == d1


def test_cache_version_mismatch_ignored(clean_cache, tmp_path):
    p = str(tmp_path / "stale.json")
    with open(p, "w") as f:
        json.dump({"version": scache.VERSION + 999,
                   "entries": {"k": {"algorithm": "ring"}}}, f)
    before = SPC.snapshot().get("sched_cache_version_mismatch", 0)
    assert scache.CACHE.load(p) == 0
    assert len(scache.CACHE) == 0
    assert SPC.snapshot()["sched_cache_version_mismatch"] == before + 1


def test_same_seed_digest_byte_identical_across_controllers(tmp_path):
    """Two separate processes (two controllers), same seed, model mode:
    the persisted cache file must be byte-identical — digest AND file
    sha256."""
    prog = (
        "import json, hashlib, os\n"
        "from ompi_tpu.core import config\n"
        "from ompi_tpu.coll.sched import autotune, cache\n"
        "config.set('coll_sched_cache_dir', %r)\n"
        "cache.CACHE.clear()\n"
        "res = autotune.tune(8, mode='model', seed=7, topo_fp='ctrl')\n"
        "sha = hashlib.sha256(\n"
        "    open(res['path'], 'rb').read()).hexdigest()\n"
        "print(json.dumps({'digest': res['digest'], 'sha': sha}))\n"
    )
    outs = []
    for i in range(2):
        d = str(tmp_path / f"ctrl{i}")
        os.makedirs(d)
        r = subprocess.run(
            [sys.executable, "-c", prog % d], capture_output=True,
            text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == 0, r.stderr[-1500:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert outs[0]["digest"] == outs[1]["digest"]
    assert outs[0]["sha"] == outs[1]["sha"]


# ---------------------------------------------------------------------------
# dispatch precedence: cache first, priors as cold-start fallback
# ---------------------------------------------------------------------------

def test_cache_steers_decide_and_counts_spc(clean_cache):
    op = op_lookup("sum")
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", 4096, 8, "float32", fp)
    scache.CACHE.put(key, "recursive_doubling", source="test")
    try:
        s0 = SPC.snapshot()
        got = tuned.decide_allreduce(op, 4096, 8, "float32")
        assert got == "recursive_doubling"
        s1 = SPC.snapshot()
        assert s1.get("sched_cache_hits", 0) == \
            s0.get("sched_cache_hits", 0) + 1
        # a different bucket misses (counted: the cache is active) and
        # falls back to the static prior
        prior = priors.prior_allreduce(op, 64 << 20, 8, "float32")
        assert tuned.decide_allreduce(op, 64 << 20, 8, "float32") \
            == prior
        s2 = SPC.snapshot()
        assert s2.get("sched_cache_misses", 0) == \
            s1.get("sched_cache_misses", 0) + 1
        # cache disabled -> straight to the prior, no counters move
        config.set("coll_sched_cache_enable", False)
        assert tuned.decide_allreduce(op, 4096, 8, "float32") == \
            priors.prior_allreduce(op, 4096, 8, "float32")
        s3 = SPC.snapshot()
        assert s3.get("sched_cache_hits", 0) == \
            s2.get("sched_cache_hits", 0)
    finally:
        config.set("coll_sched_cache_enable", True)


def test_unusable_cached_winner_falls_through(clean_cache):
    """A cached quant winner is a miss when the current call lacks
    quant consent — the guard decides, not the cache."""
    op = op_lookup("sum")
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", 4096, 8, "float32", fp)
    scache.CACHE.put(key, "sched_quant", source="test")
    assert not config.get("coll_quant_enable")
    got = tuned.decide_allreduce(op, 4096, 8, "float32")
    assert got != "sched_quant"


def test_forced_and_rules_outrank_cache(clean_cache, tmp_path):
    op = op_lookup("sum")
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", 4096, 8, "float32", fp)
    scache.CACHE.put(key, "recursive_doubling", source="test")
    p = str(tmp_path / "rules.json")
    with open(p, "w") as f:
        json.dump({"allreduce": [{"algorithm": "ring"}]}, f)
    config.set("coll_tuned_rules_file", p)
    try:
        assert tuned.decide_allreduce(op, 4096, 8, "float32") == "ring"
    finally:
        config.set("coll_tuned_rules_file", "")


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------

def test_quarantined_tier_never_timed(clean_cache):
    from ompi_tpu.health import ledger as hl

    hl.LEDGER.quarantine("device", cause="test_sched")
    try:
        before = SPC.snapshot().get("sched_tune_skipped_quarantined", 0)
        allowed, skipped = autotune.candidates("allreduce", 8)
        # every device-tier candidate is refused...
        assert allowed == [a for a in allowed
                           if lattice.tier_of(a) != "device"]
        assert all(lattice.tier_of(a) == "device" for a in skipped)
        assert "sched_ring" in skipped and "native" in skipped
        # ...but the host-plane terminal keeps the sweep alive
        assert "gather_reduce" in allowed
        assert SPC.snapshot()["sched_tune_skipped_quarantined"] > before
        res = autotune.tune(8, mode="model", topo_fp="qtest",
                            save=False)
        assert set(res["skipped"]) == set(skipped)
        assert all(w == "gather_reduce" for w in res["winners"].values())
    finally:
        hl.LEDGER.reset()


def test_model_mode_deterministic_in_process(clean_cache):
    r1 = autotune.tune(8, mode="model", seed=3, topo_fp="det",
                       save=False)
    d1 = scache.CACHE.digest()
    scache.CACHE.clear()
    r2 = autotune.tune(8, mode="model", seed=3, topo_fp="det",
                       save=False)
    assert r1["winners"] == r2["winners"]
    assert scache.CACHE.digest() == d1


# ---------------------------------------------------------------------------
# bytes-per-rank convention (PR9 satellite fix)
# ---------------------------------------------------------------------------

def test_bytes_per_rank_convention_agrees(clean_cache, tmp_path):
    """Rules bands, decide_*, and the cache's size buckets must all
    consume the SAME number for one payload: bytes per rank, not total
    bytes. Regression: a (8, 256) f32 rank-major payload is 1 KiB per
    rank; a rules band capped at 2 KiB must match it, and the cache
    key built from the same _nbytes value must land in bucket b10."""
    comm = mt.world()
    data = np.ones((8, 256), np.float32)
    x = comm.put_rank_major(data)
    nbytes = tuned._nbytes(x)
    assert nbytes == 1024  # per rank — NOT 8192 total

    # cache side: same value -> bucket 10
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", nbytes, 8, "float32", fp)
    assert "|b10|" in key
    scache.CACHE.put(key, "recursive_doubling", source="test")
    op = op_lookup("sum")
    assert tuned.decide_allreduce(op, nbytes, 8, "float32") == \
        "recursive_doubling"

    # rules side: a <=2 KiB band matches the same per-rank value (it
    # would NOT match if decide passed total bytes), and rules outrank
    # the cache
    p = str(tmp_path / "band.json")
    with open(p, "w") as f:
        json.dump({"allreduce": [
            {"max_bytes": 2048, "algorithm": "ring"}]}, f)
    config.set("coll_tuned_rules_file", p)
    try:
        assert tuned.decide_allreduce(op, nbytes, 8, "float32") == "ring"
    finally:
        config.set("coll_tuned_rules_file", "")


def test_bucket_boundaries():
    assert scache.size_bucket(0) == 0
    assert scache.size_bucket(1) == 0
    assert scache.size_bucket(1023) == 9
    assert scache.size_bucket(1024) == 10
    assert scache.size_bucket(1025) == 10
    assert scache.bucket_bytes(scache.size_bucket(1 << 20)) == 1 << 20


# ---------------------------------------------------------------------------
# breaker/health deny-set over the lattice
# ---------------------------------------------------------------------------

def test_breaker_chain_derives_from_lattice():
    from ompi_tpu.coll import breaker

    assert breaker.NEXT_TIER == lattice.fallback_map()
    assert breaker.TERMINAL == lattice.TERMINAL
    # sched tiers degrade within the lattice before leaving it
    assert lattice.chain("sched_quant") == [
        "sched_quant", "sched_ring", "ring", "gather_reduce"]
    from ompi_tpu.health.ledger import tier_of_algo
    for algo in sched.ALGOS:
        assert tier_of_algo(algo) == lattice.tier_of(algo)


# ---------------------------------------------------------------------------
# schedcutoff lint rule
# ---------------------------------------------------------------------------

_CUTOFF_SRC = '''
def decide_allreduce(nbytes, nranks):
    if nbytes < 64 << 10:
        return "ring"
    return "segmented"

def decide_cvar_ok(nbytes, nranks):
    if nbytes < _small.value:
        return "ring"
    if nranks >= 8:
        return "rd"
    return "seg"

def helper(nbytes):
    return nbytes < 1 << 20

def decide_legacy(nbytes):
    if nbytes < 65536:  # commlint: allow(schedcutoff)
        return "a"
    return "b"
'''


def test_schedcutoff_rule():
    from ompi_tpu.analysis.lint import FileContext
    from ompi_tpu.analysis.rules import COMMLINT, ensure_rules
    ensure_rules()
    from ompi_tpu.analysis.rules.schedcutoff import SchedCutoffRule

    rule = SchedCutoffRule(COMMLINT)
    ctx = FileContext("ompi_tpu/coll/fake.py", _CUTOFF_SRC,
                      relpath="coll/fake.py")
    found = list(rule.check(ctx))
    # flags ONLY the literal threshold in the pick function: not the
    # cvar-backed compare, not the rank compare, not the helper, not
    # the allow()-escaped legacy line
    assert len(found) == 1 and found[0].line == 3, found
    assert "65536" in found[0].message
    # sched/priors.py is the sanctioned home — exempt
    ctx2 = FileContext("ompi_tpu/coll/sched/priors.py", _CUTOFF_SRC,
                       relpath="coll/sched/priors.py")
    assert list(rule.check(ctx2)) == []
    # outside coll/: not this rule's business
    ctx3 = FileContext("ompi_tpu/pml/fake.py", _CUTOFF_SRC,
                       relpath="pml/fake.py")
    assert list(rule.check(ctx3)) == []


# ---------------------------------------------------------------------------
# monitoring + CLI
# ---------------------------------------------------------------------------

def test_sched_counters_reach_monitoring_dump(clean_cache):
    from ompi_tpu.trace import recorder

    rec = recorder.configure(1024)
    try:
        fp = autotune.fingerprint()
        scache.CACHE.put(
            scache.cache_key("allreduce", 1024, 8, "float32", fp),
            "ring", source="test")
        op = op_lookup("sum")
        tuned.decide_allreduce(op, 1024, 8, "float32")
        tuned.decide_allreduce(op, 64 << 20, 8, "float32")
    finally:
        # the process ring back at its default size: a later test in
        # this process may read a traced slice longer than 1024 records
        recorder.configure()
    snap = SPC.snapshot()
    assert snap.get("sched_cache_hits", 0) >= 1
    assert snap.get("sched_cache_misses", 0) >= 1
    names = {r[3] for r in rec.records()}
    assert "sched.cache_hit" in names
    assert "sched.cache_miss" in names


def test_cli_dump_warm_list(clean_cache, capsys):
    from ompi_tpu.tools import sched as cli

    assert cli.main(["dump", "--name", "ring", "--nranks", "4"]) == 0
    out = capsys.readouterr().out
    assert "schedule ring" in out and "# digest" in out

    assert cli.main(["warm", "--nranks", "8", "--mode", "model"]) == 0
    out = capsys.readouterr().out
    assert "tuned" in out and "saved" in out and "digest" in out

    assert cli.main(["list"]) == 0
    out = capsys.readouterr().out
    assert "cached schedule(s)" in out and "allreduce|" in out
