"""Whole-step comm compilation (ISSUE PR16): the multi-collective
sched IR Program, compile_step's program-level autotuning, the
StepExecutor/ShardedAllreduce transport binding, and the satellites
that ride along (jaxpr readiness ordering, the lifeboat rebuild drill,
the winner-cache tile-geometry override, the stepprogram lint rule,
and the guaranteed telemetry series).
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.coll.sched import ir
from ompi_tpu.coll.sched import pallas_lower
from ompi_tpu.coll.sched import stepprogram
from ompi_tpu.core.counters import SPC
from ompi_tpu.core.errors import ArgumentError


@pytest.fixture(scope="module")
def base():
    return ompi_tpu.init()


def _pow2_grads(base, sizes, dtype="float32", seed=7):
    """Rank-major leaves with values in {1, 2}: every arrival-order
    combine is exact in f32 and bf16, so cross-arm comparisons can be
    bitwise."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return {
        f"p{i}": jnp.asarray(
            rng.integers(1, 3, (base.size, n)).astype(np.float32),
            jnp.dtype(dtype))
        for i, n in enumerate(sizes)
    }


# -- the IR: multi-collective programs --------------------------------------

def test_program_check_render_digest():
    nodes = (
        ir.ProgramNode("b0", ir.ring(4)),
        *ir.zero_pair("b1", 4),
    )
    prog = ir.Program(name="step", nranks=4, nodes=nodes,
                      meta={"seed": 0, "tiles": "b0:1x64,b1:1x64"})
    ir.check_program(prog)
    txt = prog.render()
    assert txt.splitlines()[0].startswith("program step nranks=4 nodes=3")
    assert "node b0 deps=-" in txt
    assert "node b1.ag deps=b1.rs" in txt
    d = prog.digest()
    assert len(d) == 16 and int(d, 16) >= 0
    # meta feeds the digest: different tile geometry, different artifact
    other = ir.Program(name="step", nranks=4, nodes=nodes,
                       meta={"seed": 0, "tiles": "b0:2x32,b1:1x64"})
    assert other.digest() != d


def test_program_check_rejects_malformed():
    r = ir.ring(4)
    with pytest.raises(ir.ScheduleError):  # duplicate node name
        ir.check_program(ir.Program("p", 4, (
            ir.ProgramNode("a", r), ir.ProgramNode("a", r))))
    with pytest.raises(ir.ScheduleError):  # unknown dep
        ir.check_program(ir.Program("p", 4, (
            ir.ProgramNode("a", r, deps=("ghost",)),)))
    with pytest.raises(ir.ScheduleError):  # self-dep
        ir.check_program(ir.Program("p", 4, (
            ir.ProgramNode("a", r, deps=("a",)),)))
    with pytest.raises(ir.ScheduleError):  # cycle
        ir.check_program(ir.Program("p", 4, (
            ir.ProgramNode("a", r, deps=("b",)),
            ir.ProgramNode("b", r, deps=("a",)))))
    with pytest.raises(ir.ScheduleError):  # rank-count disagreement
        ir.check_program(ir.Program("p", 8, (ir.ProgramNode("a", r),)))


def test_allgather_generator_matches_oracle():
    """The standalone allgather phase: starting from the
    reduce_scatter ownership convention, every rank ends with every
    chunk — simulated with the kernel-semantics oracle."""
    import jax.numpy as jnp

    n = 4
    sched = ir.allgather(n)
    assert sched.op == "allgather" and sched.nchunks == n
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.standard_normal((n, n, 3)), jnp.float32)
    out = np.asarray(pallas_lower.simulate(sched, data, "sum"))
    # chunk c's owner is rank c (identity order): its copy replicates
    ref = np.stack([np.asarray(data)[c, c] for c in range(n)])
    for k in range(n):
        np.testing.assert_array_equal(out[k], ref)


def test_zero_pair_is_gated_rs_then_ag():
    rs, ag = ir.zero_pair("b3", 8)
    assert rs.name == "b3.rs" and rs.schedule.op == "reduce_scatter"
    assert ag.name == "b3.ag" and ag.schedule.op == "allgather"
    assert ag.deps == ("b3.rs",) and rs.deps == ()


# -- compile_step -----------------------------------------------------------

def test_compile_step_deterministic_and_complete():
    specs = [(4096, np.float32), (1024, np.float32), (2048, np.float32)]
    before = SPC.snapshot().get("sched_program_compiles_total", 0)
    a = stepprogram.compile_step(8, specs, seed=5, topo_fp="t")
    b = stepprogram.compile_step(8, specs, seed=5, topo_fp="t")
    assert SPC.snapshot()["sched_program_compiles_total"] == before + 2
    assert a.program.render() == b.program.render()
    assert a.digest() == b.digest()
    # the seed reaches the digest: same buckets, different artifact
    c = stepprogram.compile_step(8, specs, seed=6, topo_fp="t")
    assert c.digest() != a.digest()
    # one NodePlan per bucket, interleave biggest-first
    assert [n.elems for n in a.nodes] == [4096, 1024, 2048]
    assert a.interleave == (0, 2, 1)
    for n in a.nodes:
        assert n.tiles >= 1 and n.tile_elems >= 1
        assert n.tile_source in ("caller", "cache", "model")
    for key in ("seed", "topo", "choices", "tiles", "sources",
                "interleave"):
        assert key in a.program.meta
    assert a.compile_ms > 0.0
    with pytest.raises(ArgumentError):
        stepprogram.compile_step(8, [])


def test_compile_step_rs_ag_nodes_and_fusion():
    specs = [(512, np.float32)] * 4
    comp = stepprogram.compile_step(
        8, specs, node_choices=["allreduce", "rs_ag", "allreduce",
                                "rs_ag"])
    names = [n.name for n in comp.program.nodes]
    assert names == ["b0", "b1.rs", "b1.ag", "b2", "b3.rs", "b3.ag"]
    assert comp.program.node("b1.ag").deps == ("b1.rs",)
    # the two plain allreduces AND the two allgather halves fuse; the
    # reduce_scatter halves keep per-node kernels by contract
    assert set(comp.fused) == {"allreduce", "allgather"}
    assert comp.fused["allreduce"].meta["segments"] == 2
    assert comp.fused["allgather"].meta["segments"] == 2
    # single-rank comms have nothing to scatter: choice is forced
    solo = stepprogram.compile_step(1, specs, node_choices=["rs_ag"] * 4)
    assert all(n.choice == "allreduce" for n in solo.nodes)
    assert solo.program.nodes == ()


def test_fused_step_program_matches_simulator_oracle():
    """Tentpole acceptance: the step's fused multi-bucket allreduce
    table program is bit-faithful to the kernel-semantics simulator."""
    import jax.numpy as jnp

    comp = stepprogram.compile_step(
        8, [(256, np.float32)] * 3, node_choices=["allreduce"] * 3)
    fused = comp.fused["allreduce"]
    assert fused.nchunks == 24 and fused.meta["segments"] == 3
    rng = np.random.default_rng(1)
    data = jnp.asarray(rng.standard_normal((8, fused.nchunks, 4)),
                       jnp.float32)
    sim = np.asarray(pallas_lower.simulate(fused, data, "sum"))
    ref = np.broadcast_to(np.asarray(data).sum(axis=0),
                          np.asarray(data).shape)
    np.testing.assert_allclose(sim, ref, rtol=1e-5, atol=1e-5)


# -- transport binding ------------------------------------------------------

def test_sharded_allreduce_matches_reference(base):
    sh = stepprogram.ShardedAllreduce(
        base, 96, np.float32, tiles=8, tag_base=5100, label="t")
    assert sh.nshards == min(base.size, sh.tiles)
    rng = np.random.default_rng(2)
    x = rng.integers(1, 3, (base.size, 96)).astype(np.float32)
    sh.start()
    host = x
    for t in np.random.default_rng(0).permutation(sh.tiles):
        lo, hi = sh.tile_range(int(t))
        sh.ready(int(t), host[:, lo:hi])
    got = np.asarray(sh.wait())
    ref = np.broadcast_to(x.sum(axis=0), x.shape)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_step_program_session_bit_identical_vs_legacy(base, dtype):
    """Tentpole acceptance: a whole-step Program with >=2 buckets and
    an RS/AG pair executes bit-identically against the PR 15
    per-bucket session, on f32 and bf16."""
    from ompi_tpu.parallel.overlap import DpOverlapSession

    grads = _pow2_grads(base, [300, 200, 128], dtype=dtype)
    kw = dict(bucket_bytes=1024, tile_bytes=256)
    legacy = DpOverlapSession(base, grads, step_program=False,
                              tag_base=5200, **kw)
    nb = len(legacy.plan.buckets)
    assert nb >= 2
    choices = ["rs_ag" if i % 2 else "allreduce" for i in range(nb)]
    prog = DpOverlapSession(base, grads, step_program=True,
                            tag_base=5300, node_choices=choices, **kw)
    assert "rs_ag" in prog.compiled.program.meta["choices"]
    assert len(prog.compiled.program.nodes) > nb  # pairs split
    outs = []
    for sess in (legacy, prog):
        sess.begin_step()
        for nm in grads:
            sess.mark_ready(nm, grads[nm])
        out, report = sess.finish()
        assert report.buckets == nb
        outs.append(out)
    for nm in grads:
        a, b = np.asarray(outs[0][nm]), np.asarray(outs[1][nm])
        assert a.dtype == b.dtype
        assert (a == b).all(), f"{dtype} leaf {nm} diverged"


def test_session_binds_one_executor_and_stamps_plan(base):
    from ompi_tpu.coll.sched.stepprogram import StepExecutor
    from ompi_tpu.parallel.overlap import DpOverlapSession

    grads = _pow2_grads(base, [256, 256])
    sess = DpOverlapSession(base, grads, bucket_bytes=1024,
                            tag_base=5400)
    assert isinstance(sess._exec, StepExecutor)
    assert sess._pas is sess._exec.bindings
    nb = len(sess.plan.buckets)
    assert len(sess.compiled.nodes) == nb
    # the compiled geometry is stamped back into the plan
    assert sess.plan.tiles == [n.tiles for n in sess.compiled.nodes]
    assert sess.plan.tile_elems == [n.tile_elems
                                    for n in sess.compiled.nodes]
    assert sess.plan.tile_sources == [n.tile_source
                                      for n in sess.compiled.nodes]


# -- satellite 3: winner-cache tile geometry --------------------------------

def test_winner_cache_tile_geometry_reaches_plan(base):
    """A cached tile_bytes winner must reach plan_overlap's stamped
    geometry (no silent fallback to the static default), flagged
    'cache' and counted."""
    from ompi_tpu.coll.sched import autotune
    from ompi_tpu.coll.sched import cache as scache
    from ompi_tpu.parallel.overlap import DpOverlapSession

    grads = _pow2_grads(base, [512])  # one 2048-byte bucket
    fp = autotune.fingerprint()
    key = scache.cache_key("allreduce", 2048, base.size, "float32", fp)
    saved = scache.CACHE.get(key)
    scache.CACHE.put(  # commlint: allow(retuneaudit)
        key, "native", source="test", tile_bytes=512)
    before = SPC.snapshot().get("sched_program_tile_overrides_total", 0)
    try:
        sess = DpOverlapSession(base, grads, bucket_bytes=4096,
                                tag_base=5500)
        assert sess.plan.tile_sources == ["cache"]
        assert sess.plan.tiles == [4]           # 2048 B / 512 B
        assert sess.plan.tile_elems == [128]
        assert sess._pas[0].tile_elems == 128
        assert SPC.snapshot()["sched_program_tile_overrides_total"] \
            == before + 1
    finally:
        if saved is not None:
            scache.CACHE.put(  # commlint: allow(retuneaudit)
                key, saved["algorithm"],
                source=saved.get("source", "test"),
                tile_bytes=saved.get("tile_bytes"))


def test_tune_step_seeds_cache_for_program_compiles(base):
    from ompi_tpu.coll.sched import autotune

    out = autotune.tune_step(base.size, [2048, 4096], seed=3)
    assert len(out["keys"]) == 2 and out["digest"]
    comp = stepprogram.compile_step(
        base.size, [(512, np.float32), (1024, np.float32)], seed=3)
    assert [n.tile_source for n in comp.nodes] == ["cache", "cache"]


def test_tile_override_counter_guaranteed_in_exposition():
    from ompi_tpu.telemetry import export

    text = export.prometheus_text()
    for series in ("ompi_tpu_sched_program_tile_overrides_total",
                   "ompi_tpu_sched_program_compiles_total"):
        assert f"# TYPE {series} counter" in text
        assert any(ln.startswith(f"{series} ")
                   for ln in text.splitlines()), series


# -- satellite 1: jaxpr-ordering readiness ----------------------------------

def _block_stack_loss():
    """A transformer-block-shaped stack (rmsnorm + MLP residual, the
    model's _block dataflow without the mesh axes): one marker per
    block, one 3-leaf param group per block."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as T
    from ompi_tpu.parallel import overlap as ovl

    L, D, F = 4, 8, 16
    rng = np.random.default_rng(0)
    ws = [{"ln": jnp.ones((D,), jnp.float32),
           "w1": jnp.asarray(rng.standard_normal((D, F)) * 0.1,
                             jnp.float32),
           "w2": jnp.asarray(rng.standard_normal((F, D)) * 0.1,
                             jnp.float32)}
          for _ in range(L)]
    x = jnp.asarray(rng.standard_normal((2, D)), jnp.float32)

    def loss(ws, x):
        h = x
        for i, w in enumerate(ws):
            h = ovl.grad_marker(h, f"blk{i}")
            n = T._rmsnorm(h, w["ln"])
            h = h + jax.nn.gelu(n @ w["w1"]) @ w["w2"]
        return jnp.sum(h * h)

    return loss, ws, x


def test_jaxpr_and_marker_readiness_orders_agree():
    """The jaxpr ordering and the grad_marker capture must name the
    same backward schedule on the transformer block stack: last
    block's gradients first."""
    import jax

    from ompi_tpu.parallel import overlap as ovl

    loss, ws, x = _block_stack_loss()

    ovl.reset_capture()
    jax.grad(loss, argnums=(0, 1))(ws, x)
    marker_blocks = [int(m[3:]) for m in ovl.backward_order()]
    assert marker_blocks == [3, 2, 1, 0]

    kind, order = ovl.readiness_order(jax.grad(loss), args=(ws, x))
    assert kind == "jaxpr"
    assert sorted(order) == list(range(12))  # 4 blocks x 3 leaves
    jaxpr_blocks = []
    for leaf in order:           # 3 leaves per block, flatten order
        blk = leaf // 3
        if blk not in jaxpr_blocks:
            jaxpr_blocks.append(blk)
    assert jaxpr_blocks == marker_blocks
    ovl.reset_capture()


def test_readiness_order_falls_back_to_marker(monkeypatch):
    import jax

    from ompi_tpu.parallel import overlap as ovl

    loss, ws, x = _block_stack_loss()
    ovl.reset_capture()
    jax.grad(loss, argnums=(0, 1))(ws, x)

    def untraceable(*a, **k):
        raise TypeError("grad_fn does not trace")

    monkeypatch.setattr(ovl, "jaxpr_backward_order", untraceable)
    kind, order = ovl.readiness_order(jax.grad(loss), args=(ws, x))
    assert kind == "marker"
    assert order == ("blk3", "blk2", "blk1", "blk0")
    # no grad_fn at all: marker capture is the only source
    kind2, _ = ovl.readiness_order()
    assert kind2 == "marker"
    ovl.reset_capture()


# -- satellite 2: the lifeboat rebuild drill --------------------------------

@pytest.fixture
def _drill_clean():
    from ompi_tpu.ft import elastic, events, inject, lifeboat
    from ompi_tpu.health import ledger
    from ompi_tpu.telemetry import fleet

    yield
    inject.disarm()
    lifeboat.reset()
    elastic.reset()
    events.clear()
    fleet.reset_for_testing()
    ledger.reset()
    w = ompi_tpu.world()
    w._revoked = False
    w.epoch = 0


def test_rank_kill_mid_step_rebuilds_compiled_program(base, _drill_clean):
    """rank_kill mid-step with tiles in flight: the session's finish
    raises (no hang), abort tears the executor down, lifeboat.recover
    shrinks the comm across a revoked epoch, and a session rebuilt on
    the survivor comm compiles a fresh program whose next step is
    bit-identical to the survivor-only reference."""
    from ompi_tpu.core.errors import RevokedError
    from ompi_tpu.ft import elastic, inject, lifeboat
    from ompi_tpu.parallel.overlap import DpOverlapSession

    lifeboat.enable()
    inject.arm("rank_kill@coll:op=bcast,peer=3")
    c = base.dup()  # armed before dup: the coll vtable carries probes
    grads = _pow2_grads(base, [256, 192], seed=3)
    sess = DpOverlapSession(c, grads, bucket_bytes=1024, tag_base=5600)
    old_digest = sess.compiled.digest()
    sess.begin_step()
    for nm in grads:
        sess.mark_ready(nm, grads[nm])   # tiles in flight
    with pytest.raises((RevokedError, inject.FaultInjected)):
        sess.finish()                    # merged bcast hits the kill
    assert not sess._active and sess._pump_thread is None
    inject.disarm()
    assert elastic.failed_ranks() == {3}

    new = lifeboat.recover(c, seed=11)
    # The proc-failed auto-revoke poisons every comm containing rank 3,
    # WORLD included. Earlier suite tests may have left persistent
    # requests registered with the progress engine on WORLD; sess2's
    # pump would drain them and trip their iprobe liveness check on the
    # revoked WORLD. Un-revoke it here — the fixture restores the full
    # world state at teardown regardless.
    ompi_tpu.world()._revoked = False
    assert new.size == c.size - 1 and new.epoch == c.epoch + 1
    survivors = [r for r in range(c.size) if r != 3]
    g2 = {nm: np.asarray(grads[nm])[survivors] for nm in grads}
    sess2 = DpOverlapSession(new, g2, bucket_bytes=1024, tag_base=5600)
    assert sess2.compiled.program.nranks == new.size
    assert sess2.compiled.digest() != old_digest  # new epoch, new unit
    sess2.begin_step()
    for nm in g2:
        sess2.mark_ready(nm, g2[nm])
    out, _ = sess2.finish()
    for nm in g2:
        ref = np.broadcast_to(g2[nm].sum(axis=0), g2[nm].shape)
        assert (np.asarray(out[nm]) == ref).all(), nm


_DRILL_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import ompi_tpu as mt
    from ompi_tpu.core.errors import RevokedError
    from ompi_tpu.ft import inject, lifeboat
    from ompi_tpu.parallel.overlap import DpOverlapSession

    world = mt.init()
    lifeboat.enable()
    inject.arm("rank_kill@coll:op=bcast,peer=3")
    comm = world.dup()
    rng = np.random.default_rng(3)
    grads = {f"p{i}": rng.integers(1, 3, (8, n)).astype(np.float32)
             for i, n in enumerate((256, 192))}
    sess = DpOverlapSession(comm, grads, bucket_bytes=1024,
                            tag_base=5600, seed=5)
    d0 = sess.compiled.digest()
    sess.begin_step()
    for nm in grads:
        sess.mark_ready(nm, grads[nm])
    try:
        sess.finish()
    except (RevokedError, inject.FaultInjected):
        pass
    inject.disarm()
    new = lifeboat.recover(comm, seed=5)
    g2 = {nm: g[[r for r in range(8) if r != 3]]
          for nm, g in grads.items()}
    sess2 = DpOverlapSession(new, g2, bucket_bytes=1024,
                             tag_base=5600, seed=5)
    sess2.begin_step()
    for nm in g2:
        sess2.mark_ready(nm, g2[nm])
    out, _ = sess2.finish()
    ok = all((np.asarray(out[nm])
              == np.broadcast_to(g2[nm].sum(axis=0), g2[nm].shape)).all()
             for nm in g2)
    assert ok
    print("DIGESTS " + d0 + ":" + sess2.compiled.digest() + ":"
          + lifeboat.digest())
""")


@pytest.mark.slow
def test_step_program_digests_byte_identical_across_controllers():
    """Two same-seed controller processes running the kill/rebuild
    drill must agree byte-for-byte: the pre-kill program digest, the
    rebuilt program digest, and the recovery decision-log digest."""
    outs = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _DRILL_PROG],
            capture_output=True, text=True, timeout=300,
        )
        assert p.returncode == 0, p.stderr[-1500:]
        line = [l for l in p.stdout.splitlines()
                if l.startswith("DIGESTS ")][0]
        outs.append(line.split(" ", 1)[1])
    assert outs[0] == outs[1]
    pre, post, _boat = outs[0].split(":")
    assert pre != post and len(pre) == len(post) == 16


# -- satellite 4: the stepprogram lint rule ---------------------------------

def test_stepprogram_rule_fires_evidence_and_allow(tmp_path):
    from ompi_tpu.analysis import lint

    par = tmp_path / "parallel"
    par.mkdir()
    (par / "bad.py").write_text(textwrap.dedent("""
        def bind_buckets(comm, plans):
            pas = []
            for i, b in enumerate(plans):
                pas.append(PartitionedAllreduce(comm, b.template,
                                                tag=820 + i))
            return pas
    """))
    (par / "good.py").write_text(textwrap.dedent("""
        def bind_buckets(comm, plans):
            compiled = compile_step(comm.size,
                                    [(b.elems, b.dtype) for b in plans])
            pas = []
            for nd in compiled.nodes:
                pas.append(PartitionedAllreduce(comm, nd.template,
                                                tag=820 + nd.bucket))
            return pas
    """))
    (par / "allowed.py").write_text(textwrap.dedent("""
        def bench_arm(comm, plans):
            pas = []
            for i, b in enumerate(plans):
                pas.append(PartitionedAllreduce(  # commlint: allow(stepprogram)
                    comm, b.template, tag=820 + i))
            return pas
    """))
    other = tmp_path / "coll"
    other.mkdir()
    (other / "outside.py").write_text(textwrap.dedent("""
        def make(comm, plans):
            for b in plans:
                ShardedAllreduce(comm, b.elems, b.dtype)
    """))
    rep = lint.lint_tree(str(tmp_path), select="stepprogram")
    paths = [f.path for f in rep.findings]
    assert any("bad.py" in p for p in paths)
    assert not any("good.py" in p for p in paths)
    assert not any("allowed.py" in p for p in paths)
    assert not any("outside.py" in p for p in paths)  # not parallel/
