"""Pallas lowering backend (coll/sched/pallas_lower): the dense
chained round-uniform contract, codegen bit-identity of the real
kernel under Mosaic interpret mode (and of the table simulator), the
device_pallas lattice tier with its medic
probe, autotuner quarantine discipline, the lowering-strategy
telemetry, and the devicesem lint rule."""

import dataclasses
import textwrap

import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu.core.counters import SPC
from ompi_tpu.core.errors import ArgumentError
from ompi_tpu.coll import pallas_ring, sched, tuned
from ompi_tpu.coll.sched import autotune, ir, lattice, lower, pallas_lower


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


@pytest.fixture
def clean_health():
    """Restore the health plane after quarantine/probe drills."""
    yield
    from ompi_tpu import health
    from ompi_tpu.health import prober

    health.reset_for_testing()
    prober.unregister_probe("device_pallas")


# ---------------------------------------------------------------------------
# analyze: the dense chained round-uniform contract
# ---------------------------------------------------------------------------

def test_analyze_ring_program_golden():
    p = pallas_lower.analyze(ir.ring(8))
    assert p.op == "allreduce" and p.nranks == 8 and p.nchunks == 8
    assert p.rounds == 14
    # reduce-scatter phase then allgather phase
    assert p.mode == (1,) * 7 + (2,) * 7
    # only round 0 stages from the input: one unbroken chain
    assert p.brk[0] is True and not any(p.brk[1:])
    # the final reduce round and every copy round deliver final values
    assert p.last == (False,) * 6 + (True,) * 8
    for t in (p.t_dst, p.t_src, p.t_schunk, p.t_rchunk):
        assert t.shape == (14, 8) and t.dtype == np.int32


def test_analyze_segment_boundaries_and_reduce_scatter():
    seg = pallas_lower.analyze(ir.segmented_ring(8, 2))
    assert seg.rounds == 28
    # one re-stage per segment: round 0 plus the one interior boundary
    assert sum(seg.brk) == 2 and seg.brk[0] is True
    rs = pallas_lower.analyze(ir.reduce_scatter(8))
    assert rs.op == "reduce_scatter"
    assert rs.rounds == 7 and rs.mode == (1,) * 7
    assert all(rs.last)


def test_analyze_rejects_hierarchical_not_dense():
    s = ir.hierarchical([[0, 1, 2, 3], [4, 5, 6, 7]])
    with pytest.raises(ArgumentError, match="not dense"):
        pallas_lower.analyze(s)


def test_analyze_rejects_quant_annotations():
    s = ir.quantized_wire(8)
    with pytest.raises(ArgumentError, match="annotations"):
        pallas_lower.analyze(s)


def test_analyze_rejects_mixed_receive_kinds():
    s = ir.ring(8)
    steps = list(s.steps)
    # flip ONE rank's round-0 reduce to a copy: round-uniformity breaks
    for i, st in enumerate(steps):
        if st.round == 0 and st.kind == "reduce" and st.rank == 0:
            steps[i] = dataclasses.replace(st, kind="copy")
            break
    bad = dataclasses.replace(s, steps=tuple(steps))
    with pytest.raises(ArgumentError, match="mixes receive kinds"):
        pallas_lower.analyze(bad)


# ---------------------------------------------------------------------------
# codegen bit-identity: simulator oracle (tier-1 on any jax build)
# ---------------------------------------------------------------------------

def _pallas_programs(n):
    return (ir.with_lowering(ir.ring(n), "pallas"),
            ir.with_lowering(ir.segmented_ring(n, 2), "pallas"),
            ir.with_lowering(ir.reduce_scatter(n), "pallas"))


def test_pallas_schedules_bit_identical_via_oracle():
    """Every pallas-lowered program must be bit-identical to the ring
    reference across dtypes and ops: the real kernel runs, under
    Mosaic's interpret mode on the CPU mesh."""
    comm = mt.world()
    for s in _pallas_programs(comm.size):
        ir.check(s)
        for dtype in ("float32", "bfloat16"):
            for op in ("sum", "max", "min"):
                assert lower.validate_schedule(comm, s, op, dtype), \
                    (s.name, dtype, op)


def test_oracle_catches_miscompiled_program():
    """Negative control: a round-uniform tamper (one whole reduce
    round demoted to copies) passes analyze but must FAIL validation —
    the oracle checks values, not just well-formedness."""
    comm = mt.world()
    s = ir.ring(8)
    steps = [dataclasses.replace(st, kind="copy")
             if st.round == 3 and st.kind == "reduce" else st
             for st in s.steps]
    bad = ir.with_lowering(dataclasses.replace(s, steps=tuple(steps)),
                           "pallas")
    pallas_lower.analyze(bad)  # well-formed by the contract
    assert not lower.validate_schedule(comm, bad, "sum", "float32")


def test_simulate_shapes_and_reduce_scatter_ownership():
    data = np.arange(8 * 8 * 16, dtype=np.float32).reshape(8, 8, 16)
    out = np.asarray(pallas_lower.simulate(ir.ring(8), data, "sum"))
    assert out.shape == (8, 8, 16)
    np.testing.assert_array_equal(out[0], data.sum(0))
    rs = np.asarray(pallas_lower.simulate(ir.reduce_scatter(8), data,
                                          "sum"))
    # REDUCE_SCATTER_ALGOS contract: rank k's result is chunk k
    assert rs.shape == (8, 16)
    np.testing.assert_array_equal(rs[3], data.sum(0)[3])
    with pytest.raises(ArgumentError, match="simulate expects"):
        pallas_lower.simulate(ir.ring(8), data[:, 0], "sum")


def test_pallas_kernels_execute_under_interpret_mode():
    comm = mt.world()
    for s in _pallas_programs(comm.size):
        assert lower.validate_schedule(comm, s, "sum", "float32"), s.name


# ---------------------------------------------------------------------------
# lowering strategies + memo + telemetry
# ---------------------------------------------------------------------------

def test_lower_strategy_selection_and_memo():
    before = SPC.snapshot().get("sched_lower_strategy_pallas", 0)
    s = ir.with_lowering(ir.ring(8), "pallas", tier="device_pallas")
    fn = lower.lower(s)
    assert callable(fn)
    # memoized on (digest, strategy); the counter ticks per selection
    assert lower.lower(s) is fn
    assert SPC.snapshot()["sched_lower_strategy_pallas"] == before + 2
    # explicit override beats meta
    assert lower.lower(s, strategy="interpret") is not fn
    with pytest.raises(ArgumentError, match="unknown lowering strategy"):
        lower.lower(s, strategy="mosaic2")


def test_lower_strategy_telemetry_series():
    from ompi_tpu.telemetry import export

    lower.lower(ir.ring(8))  # at least one interpret selection
    txt = export.prometheus_text()
    assert 'ompi_tpu_sched_lower_strategy_total{strategy="interpret"}' \
        in txt
    assert 'ompi_tpu_sched_lower_strategy_total{strategy="pallas"}' in txt
    # the compiled-kernel tier has a guaranteed health gauge series
    assert 'tier="device_pallas"' in txt


def test_compiled_wrapper_rejects_wrong_world_size():
    fn = pallas_lower.compile_schedule(
        ir.with_lowering(ir.ring(4), "pallas"))
    comm = mt.world()
    data = np.ones((comm.size, 64), np.float32)
    x = comm.put_rank_major(data)
    from ompi_tpu.coll.framework import compile_plan
    from ompi_tpu.ops import lookup

    plan = compile_plan(comm, ("test.pallas.wrongsize",),
                        lambda b: fn(b, "ranks", lookup("sum")),
                        check_vma=False)
    with pytest.raises(Exception, match="compiled for 4 ranks"):
        plan(x)


# ---------------------------------------------------------------------------
# device_pallas tier: lattice, dispatch registration, autotuner
# ---------------------------------------------------------------------------

def test_device_pallas_tops_the_tier_order():
    from ompi_tpu.health import ledger

    assert ledger.TIERS[0] == "device_pallas"
    assert ledger.TIERS.index("device_pallas") \
        < ledger.TIERS.index("device")


def test_lattice_chains_degrade_through_sched_tiers():
    assert lattice.tier_of("sched_pallas_ring") == "device_pallas"
    assert lattice.chain("sched_pallas_ring") == \
        ["sched_pallas_ring", "sched_ring", "ring", "gather_reduce"]
    assert lattice.chain("sched_pallas_ring_seg") == \
        ["sched_pallas_ring_seg", "sched_ring_seg", "sched_ring",
         "ring", "gather_reduce"]
    assert lattice.chain("sched_pallas_rs") == \
        ["sched_pallas_rs", "ring", "gather_reduce"]


def test_breaker_walks_device_pallas_to_device(clean_health):
    """A quarantined device_pallas tier degrades the fused kernel onto
    its interpret twin (the device tier), never a different algorithm
    family."""
    from ompi_tpu.health import ledger

    assert lattice.fallback("sched_pallas_ring") == "sched_ring"
    assert lattice.route("sched_pallas_ring",
                         denied={"sched_pallas_ring"}) == "sched_ring"
    ledger.LEDGER.quarantine("device_pallas", cause="drill")
    denied = {a for a in lattice.chain("sched_pallas_ring")
              if ledger.LEDGER.is_denied(lattice.tier_of(a),
                                         ledger.GLOBAL_SCOPE)}
    assert denied == {"sched_pallas_ring"}
    assert lattice.route("sched_pallas_ring", denied) == "sched_ring"
    assert lattice.tier_of("sched_ring") == "device"


def test_sched_pallas_algos_registered():
    for name in ("sched_pallas_ring", "sched_pallas_ring_seg"):
        assert name in sched.ALGOS
        s = sched.build_schedule(name, 8)
        assert s.meta["lowering"] == "pallas"
        assert s.meta["tier"] == "device_pallas"
    assert tuned.is_pallas_algo("sched_pallas_ring")
    assert tuned.is_pallas_algo("pallas_ring")
    assert tuned.is_pallas_algo("quant_pallas")
    assert not tuned.is_pallas_algo("sched_ring")


def test_autotuner_never_times_quarantined_device_pallas(clean_health):
    from ompi_tpu.health import ledger

    allowed, skipped = autotune.candidates("allreduce", 8,
                                           include_pallas=True)
    assert "sched_pallas_ring" in allowed
    assert "sched_pallas_ring_seg" in allowed
    before = SPC.snapshot().get("sched_tune_skipped_quarantined", 0)
    ledger.LEDGER.quarantine("device_pallas", cause="drill")
    allowed, skipped = autotune.candidates("allreduce", 8,
                                           include_pallas=True)
    assert "sched_pallas_ring" in skipped
    assert "sched_pallas_ring_seg" in skipped
    assert "sched_ring" in allowed  # only the pallas tier is denied
    assert SPC.snapshot()["sched_tune_skipped_quarantined"] >= before + 2


def test_model_mode_prefers_device_pallas_coefficients():
    """The alpha-beta model ranks the fused kernel above its interpret
    twin at every size: same step/wire structure, strictly better tier
    coefficients."""
    for nbytes in (1 << 10, 1 << 20, 64 << 20):
        fused = autotune.model_cost("sched_pallas_ring", nbytes, 8, 0)
        interp = autotune.model_cost("sched_ring", nbytes, 8, 0)
        assert fused < interp, (nbytes, fused, interp)


# ---------------------------------------------------------------------------
# medic: the device_pallas canary and the supervisor restore walk
# ---------------------------------------------------------------------------

def test_device_pallas_canary_registered_and_green(clean_health):
    from ompi_tpu.health import prober

    prober.ensure_builtin_probes()
    assert "device_pallas" in prober.probes()
    assert prober.probe_tier("device_pallas")


def test_device_pallas_canary_says_it_simulated_off_tpu(clean_health):
    """No Mosaic compile is possible on the CPU backend: the canary
    registered there is the table simulation, and every probe counts
    that it simulated rather than passing as if a kernel had run."""
    import jax

    from ompi_tpu.core.counters import SPC
    from ompi_tpu.health import prober

    assert jax.default_backend() == "cpu"
    prober.unregister_probe("device_pallas")
    prober.ensure_builtin_probes()
    assert prober.probes()["device_pallas"].startswith("simulated")
    before = SPC.snapshot().get("health_device_pallas_simulated", 0)
    assert prober.probe_tier("device_pallas")
    assert prober.probe_tier("device_pallas")
    after = SPC.snapshot().get("health_device_pallas_simulated", 0)
    assert after == before + 2


def test_supervisor_resurrects_quarantined_device_pallas(clean_health):
    import time

    from ompi_tpu.health import ledger, prober

    ledger.LEDGER.quarantine("device_pallas", cause="drill")
    assert ledger.LEDGER.is_denied("device_pallas",
                                   ledger.GLOBAL_SCOPE)
    prober.ensure_builtin_probes()
    sup = prober.Supervisor(seed=0)
    walked = False
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        sup.tick()
        state = ledger.state("device_pallas")
        walked = walked or state == ledger.PROBATION
        if state == ledger.HEALTHY:
            break
        time.sleep(0.02)
    assert ledger.state("device_pallas") == ledger.HEALTHY
    assert walked  # restore went through the PROBATION walk, no jump


# ---------------------------------------------------------------------------
# devicesem lint rule
# ---------------------------------------------------------------------------

def _lint(src, relpath="coll/fake.py"):
    from ompi_tpu.analysis.lint import FileContext
    from ompi_tpu.analysis.rules import COMMLINT, ensure_rules
    from ompi_tpu.analysis.rules.devicesem import DeviceSemRule

    ensure_rules()
    rule = DeviceSemRule(COMMLINT)
    ctx = FileContext("ompi_tpu/" + relpath, textwrap.dedent(src),
                      relpath=relpath)
    return list(rule.check(ctx))


_DMA_SCRATCH = """
    def call():
        pl.pallas_call(k, scratch_shapes=[pltpu.SemaphoreType.DMA((2,))])
"""


def test_devicesem_flags_start_without_wait():
    src = _DMA_SCRATCH + """
    def k(buf, sem):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
    """
    (f,) = _lint(src)
    assert f.rule == "devicesem" and "never wait" in f.message


def test_devicesem_flags_unbound_chained_start():
    src = _DMA_SCRATCH + """
    def k(buf, sem):
        pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf).start()
    """
    (f,) = _lint(src)
    assert "without binding" in f.message


def test_devicesem_flags_missing_dma_scratch():
    src = """
    def k(buf, sem):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
        rdma.wait()
    """
    (f,) = _lint(src)
    assert "scratch_shapes" in f.message


def test_devicesem_flags_conditional_only_wait():
    src = _DMA_SCRATCH + """
    def k(buf, sem, root):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
        if root:
            rdma.wait()
    """
    (f,) = _lint(src)
    assert "conditional" in f.message


def test_devicesem_accepts_balanced_and_guard_idioms():
    # straight start/wait; a None-guard on conditional creation; the
    # split-phase wait_send/wait_recv halves
    src = _DMA_SCRATCH + """
    def straight(buf):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
        rdma.wait()

    def guarded(buf, root):
        rdma = None
        if root:
            rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
            rdma.start()
        if rdma is not None:
            rdma.wait()

    def split(buf):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
        rdma.wait_send()
        rdma.wait_recv()
    """
    assert _lint(src) == []


def test_devicesem_suppression_and_scope():
    src = _DMA_SCRATCH + """
    def k(buf, sem):
        # commlint: allow(devicesem)
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
    """
    assert _lint(src) == []
    # host-side code outside coll/ never matches
    bare = """
    def k(buf):
        rdma = pltpu.make_async_remote_copy(src_ref=buf, dst_ref=buf)
        rdma.start()
    """
    assert _lint(bare, relpath="osc/fake.py") == []


def test_devicesem_repo_clean():
    """The real coll/ kernels (hand-written and generated) satisfy the
    rule without suppressions."""
    import glob
    import os

    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(base, "ompi_tpu")
    findings = []
    for path in glob.glob(os.path.join(pkg, "coll", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        findings += _lint(src, relpath=os.path.relpath(path, pkg))
    assert findings == [], [(f.path, f.line, f.message)
                            for f in findings]
