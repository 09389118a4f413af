"""commtrace (PR7): flight recorder, span tracing, Perfetto export.

Covers: ring wraparound + lock-free concurrent writers, the binary
record codec, deterministic cross-rank trace IDs, span nesting and
histogram feeding, the spans on the allreduce and reduce_local call
paths and their forwarding into a profiler session, the faultline
injected=true drill (satellite 2), the Histogram pvar class, the signal-handler post-mortem dump, the native
tracering bridge, the <5% recorder-overhead ratchet (satellite 3), the
Perfetto/merge exporters plus the 2-rank CLI acceptance run, and the
``tracespan`` commlint rule (satellite 5)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import ompi_tpu as mt
from ompi_tpu.core import config
from ompi_tpu.core.counters import SPC, Histogram
from ompi_tpu.trace import export, recorder
from ompi_tpu.trace import span as tspan

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test gets an empty ring; the enable cvar is restored. The
    native ring is process-global too — earlier suite files (fastpath,
    shm) leave park/spill events in it that rank_dump() would fold into
    dumps here, so it gets the same reset."""
    saved = config.get("trace_base_enable")
    recorder.configure(256)
    recorder.native_trace_reset()
    tspan.reset_for_testing()
    yield
    config.set("trace_base_enable", saved)
    recorder.configure()


def _records():
    return recorder.get().records()


# -- ring mechanics ---------------------------------------------------------

def test_ring_wraparound_keeps_newest():
    rec = recorder.configure(64)  # min capacity
    assert rec.capacity == 64
    for i in range(200):
        rec.emit("i", f"e{i}", cat="t")
    recs = rec.records()
    assert len(recs) == 64
    seqs = [r[0] for r in recs]
    # oldest-first, contiguous, ending at the last emitted seq
    assert seqs == list(range(136, 200))
    assert recs[-1][3] == "e199" and recs[0][3] == "e136"


def test_ring_capacity_rounds_to_power_of_two():
    assert recorder.configure(100).capacity == 128
    assert recorder.configure(1).capacity == 64


def test_disabled_recorder_emits_nothing():
    config.set("trace_base_enable", False)
    recorder.emit("i", "dropped")
    tspan.instant("also.dropped")
    with tspan.span("span.dropped"):
        pass
    assert _records() == []
    assert not recorder.enabled()


def test_concurrent_writers_unique_seqs():
    rec = recorder.configure(1024)
    n_threads, per = 8, 500

    def writer(t):
        for i in range(per):
            rec.emit("i", "w", cat="t", args={"t": t, "i": i})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = rec.records()
    assert len(recs) == 1024  # full ring survives the stampede
    seqs = [r[0] for r in recs]
    assert len(set(seqs)) == len(seqs)  # no slot ever double-counted
    assert max(seqs) == n_threads * per - 1
    # every surviving record is intact (no torn tuples)
    for r in recs:
        assert r[3] == "w" and 0 <= r[8]["t"] < n_threads


def test_codec_roundtrip():
    rec = recorder.configure(256)
    rec.emit("B", "coll.allreduce", cat="coll", span=7, parent=3,
             args={"trace_id": 42, "cid": 0})
    rec.emit("E", "coll.allreduce", cat="coll", span=7, parent=3)
    rec.emit("i", "tuned.tier", cat="coll", args={"algo": "ring"})
    recs = rec.records()
    blob = recorder.FlightRecorder.encode(recs)
    assert blob[:8] == b"OTTRACE1"
    back = recorder.FlightRecorder.decode(blob)
    assert len(back) == 3
    for orig, got in zip(recs, back):
        assert got[0] == orig[0] and got[1] == orig[1]  # seq, t_ns
        assert got[2] == orig[2] and got[3] == orig[3]  # ph, name
        assert got[4] == orig[4] and got[5] == orig[5]  # cat, span
        assert got[6] == orig[6]                        # parent
        assert got[8] == orig[8]                        # args
    assert recorder.FlightRecorder.encode([]) is not None
    with pytest.raises(ValueError):
        recorder.FlightRecorder.decode(b"NOTATRACE" * 2)


# -- spans ------------------------------------------------------------------

def test_span_nesting_parent_and_trace_id_inheritance():
    with tspan.span("outer", cat="coll", trace_id=99) as outer:
        tspan.instant("mark", cat="x", note=1)
        with tspan.span("inner", cat="pml") as inner:
            assert inner.trace_id == 99        # inherited
            assert inner.parent_id == outer.span_id
    recs = _records()
    phs = [(r[2], r[3]) for r in recs]
    assert phs == [("B", "outer"), ("i", "mark"), ("B", "inner"),
                   ("E", "inner"), ("E", "outer")]
    b_outer, mark, b_inner, e_inner, e_outer = recs
    assert b_outer[8]["trace_id"] == 99
    assert b_inner[8]["trace_id"] == 99
    assert mark[6] == b_outer[5]   # instant parented to open span
    assert mark[8]["trace_id"] == 99
    assert tspan.current() is None


def test_span_records_error_on_exception():
    with pytest.raises(RuntimeError):
        with tspan.span("boom"):
            raise RuntimeError("x")
    end = [r for r in _records() if r[2] == "E"][0]
    assert end[8] == {"error": "RuntimeError"}
    assert tspan.current() is None  # stack unwound


def test_span_feeds_histogram():
    SPC.reset_for_testing()
    with tspan.span("timed", histogram="test_span_hist"):
        time.sleep(0.002)
    snap = SPC.histogram_snapshots()["test_span_hist"]
    assert snap["count"] == 1
    assert snap["p50"] >= 0.002


def test_coll_trace_id_deterministic_and_namespaced():
    tspan.reset_for_testing()
    a = [tspan.coll_trace_id(3) for _ in range(3)]
    tspan.reset_for_testing()
    b = [tspan.coll_trace_id(3) for _ in range(3)]
    assert a == b  # same call order -> same IDs (the cross-rank claim)
    assert a == [(4 << 20) | k for k in range(3)]
    # different communicators never collide
    assert tspan.coll_trace_id(7) >> 20 == 8


# -- spans on the call paths -----------------------------------------------

@pytest.fixture(scope="module")
def world():
    if not mt.initialized():
        mt.init()
    return mt.world()


def _begins(recs) -> dict:
    """name -> (span_id, parent_id, trace_id) of each span begun."""
    return {r[3]: (r[5], r[6], r[8]["trace_id"]) for r in recs
            if r[2] == "B"}


_HOST = ["coll.host_fetch", "coll.host_put"]


@pytest.mark.parametrize("elems,fast,children,lane", [
    (2, True, _HOST, False),    # 64 B: host tier, the call that routes it
    (1024, True, [], False),    # 32 KiB rank-major: the routed plan
    (1024, False, [], False),   # the routed slow path's plan(x)
    (3, True, _HOST, True),     # the repeat call, served by the lane
    (1025, True, [], True),
], ids=["host", "plan", "slow", "lane-host", "lane-plan"])
def test_allreduce_span_tree(world, elems, fast, children, lane):
    import jax.numpy as jnp

    comp, fn = world._coll["allreduce"]
    assert fn == getattr(comp, "allreduce")  # the vtable is not wrapped
    saved = config.get("coll_tuned_fast_dispatch_cache")
    config.set("coll_tuned_fast_dispatch_cache", fast)
    try:
        x = world.put_rank_major(
            jnp.ones((world.size, elems), jnp.float32))
        if lane:
            world.allreduce(x, op="sum")
        hits = SPC.snapshot().get("coll_allreduce_lane_hits", 0)
        recorder.configure(256)
        world.allreduce(x, op="sum")
        assert SPC.snapshot().get("coll_allreduce_lane_hits", 0) \
            == hits + lane
    finally:
        config.set("coll_tuned_fast_dispatch_cache", saved)
    recs = _records()
    spans = _begins(recs)
    assert set(spans) == {"coll.allreduce", "coll.launch", *children}
    root, launch = spans["coll.allreduce"], spans["coll.launch"]
    assert launch[1] == root[0] and launch[2] == root[2]
    assert root[2] >> 20 == world.cid + 1
    for name in children:
        assert spans[name][1] == launch[0] and spans[name][2] == root[2]
    # the root opens first and closes last: it covers the whole call
    assert recs[0][2:4] == ("B", "coll.allreduce")
    assert recs[-1][2:4] == ("E", "coll.allreduce")
    assert [r for r in recs if r[2] == "E"][-2][3] == "coll.launch"


def test_reduce_local_span():
    import jax.numpy as jnp
    from ompi_tpu import ops

    a = jnp.arange(8, dtype=jnp.float32)
    out = ops.reduce_local("sum", a, a)
    assert float(out[-1]) == 14.0
    recs = _records()
    assert [(r[2], r[3], r[4]) for r in recs] == [
        ("B", "op.reduce_local", "op"), ("E", "op.reduce_local", "op")]
    assert recs[0][5] == recs[1][5] and recs[0][6] == 0


def test_spans_reach_the_profiler_only_while_it_records(world, tmp_path,
                                                       monkeypatch):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    opened = []
    real = tspan.TraceAnnotation

    class Spy(real):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(tspan, "TraceAnnotation", Spy)
    x = world.put_rank_major(jnp.ones((world.size, 2), jnp.float32))
    jax.block_until_ready(world.allreduce(x, op="sum"))
    assert opened == []  # no profiler session: nothing forwarded
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(world.allreduce(x, op="sum"))
    assert opened == ["coll.allreduce", "coll.launch", "coll.host_fetch",
                      "coll.host_put"]
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name in opened}
    assert set(events) == set(opened)
    # nested on the profiler's clock as in the ring
    for inner, outer in [("coll.launch", "coll.allreduce"),
                         ("coll.host_fetch", "coll.launch"),
                         ("coll.host_put", "coll.launch")]:
        assert events[outer][0] <= events[inner][0]
        assert events[inner][1] <= events[outer][1]
    ring = [r[3] for r in _records() if r[2] == "B"]
    assert ring[-4:] == opened


def test_pml_wrapper_delegates_name(world):
    from ompi_tpu.ft import lifeboat

    pml = world.pml
    # the revocation fence wraps outermost; the tracer sits just below
    assert isinstance(pml, lifeboat.LifeboatPml)
    assert isinstance(pml.host, tspan.TracePml)
    assert isinstance(pml.NAME, str) and pml.NAME  # delegated attr


def test_allreduce_emits_correlated_span(world):
    import jax.numpy as jnp

    tspan.reset_for_testing()
    x = jnp.arange(world.size * 2, dtype=jnp.float32).reshape(
        world.size, 2)
    world.allreduce(x, op="sum")
    recs = [r for r in _records()
            if r[4] == "coll" and r[3] == "coll.allreduce"]
    assert len(recs) >= 2
    begin = [r for r in recs if r[2] == "B"][0]
    tid = begin[8]["trace_id"]
    assert tid >> 20 == world.cid + 1  # cid-derived namespace
    end = [r for r in recs if r[2] == "E" and r[5] == begin[5]]
    assert end  # the span closed


def test_pml_send_recv_span_and_histogram(world):
    SPC.reset_for_testing()
    world.rank(0).send(np.float32(2.5), dest=1, tag=77)
    out = world.rank(1).recv(source=0, tag=77)
    assert float(np.asarray(out)) == 2.5
    names = {r[3] for r in _records() if r[4] == "pml"}
    assert "pml.send" in names and "pml.recv" in names
    hists = SPC.histogram_snapshots()
    assert hists["pml_send"]["count"] >= 1
    assert hists["pml_recv"]["count"] >= 1


# -- faultline drill (satellite 2) ------------------------------------------

def test_injected_fault_emits_tagged_event():
    from ompi_tpu.ft import inject

    plan = inject.FaultPlan("delay@pml:op=send,ms=1,count=1")
    fired = plan.decide("pml", "send", peer=1, tag=5)
    assert len(fired) == 1
    evs = [r for r in _records() if r[4] == "fault"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev[3] == "fault.delay"
    assert ev[8]["injected"] is True
    assert ev[8]["layer"] == "pml" and ev[8]["op"] == "send"
    assert ev[8]["peer"] == 1 and ev[8]["tag"] == 5
    # non-firing decisions stay silent
    plan.decide("pml", "send", peer=1, tag=5)  # count exhausted
    assert len([r for r in _records() if r[4] == "fault"]) == 1


# -- histogram pvar class ---------------------------------------------------

def test_histogram_buckets_and_percentiles():
    h = Histogram("t", "test")
    for _ in range(100):
        h.record_ns(1000)   # bucket 9 (512..1024)
    for _ in range(10):
        h.record_ns(1 << 20)
    s = h.snapshot()
    assert s["count"] == 110
    assert s["min"] == pytest.approx(1e-6)
    assert s["max"] == pytest.approx((1 << 20) * 1e-9)
    # p50 lands in the 512..1024 ns bucket, p99 in the 1 MiB-ns bucket
    assert 512e-9 <= s["p50"] <= 1024e-9
    assert (1 << 20) * 1e-9 <= s["p99"] <= (1 << 21) * 1e-9
    assert s["mean"] == pytest.approx(
        (100 * 1000 + 10 * (1 << 20)) / 110 * 1e-9)


def test_histogram_registry_and_reset():
    SPC.reset_for_testing()
    SPC.record_latency("reg_hist", 0.001)
    SPC.record_latency("reg_hist", 0.002)
    snap = SPC.histogram_snapshots()["reg_hist"]
    assert snap["count"] == 2
    SPC.reset_for_testing()
    assert "reg_hist" not in SPC.histogram_snapshots()


def test_histogram_empty_snapshot():
    s = Histogram("e", "empty").snapshot()
    assert s["count"] == 0 and s["p50"] == 0.0 and s["p99"] == 0.0


# -- post-mortem dumps ------------------------------------------------------

def test_dump_post_mortem_and_signal_handler(tmp_path):
    saved = config.get("trace_base_dir")
    config.set("trace_base_dir", str(tmp_path))
    try:
        recorder.emit("i", "pre.mortem", cat="t", args={"k": 1})
        path = recorder.dump_post_mortem("unit")
        assert path and os.path.exists(path)
        with open(path) as f:
            dump = json.load(f)
        assert dump["format"] == "ompi_tpu-trace-v1"
        assert dump["reason"] == "unit"
        assert any(e[3] == "pre.mortem" for e in dump["events"])

        # signal path: arm, raise, dump appears (handler runs on the
        # main thread at the next bytecode boundary)
        assert recorder.install_signal_handler()
        os.remove(path)
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.05)
        assert os.path.exists(path)
        with open(path) as f:
            assert "signal" in json.load(f)["reason"]
    finally:
        config.set("trace_base_dir", saved)
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


def test_unknown_signal_name_is_harmless():
    saved = config.get("trace_base_signal")
    config.set("trace_base_signal", "NOSUCHSIG")
    try:
        assert recorder.install_signal_handler() is False
    finally:
        config.set("trace_base_signal", saved)


# -- native tracering bridge ------------------------------------------------

def _native_available():
    from ompi_tpu.native import build

    return build.available()


@pytest.mark.skipif(not _native_available(),
                    reason="native library unavailable")
def test_native_ring_emit_drain_enable():
    from ompi_tpu.native import build

    lib = build.get_lib()
    recorder.native_trace_reset()
    lib.ompi_tpu_trace_emit(1, 3, 42, 43)   # fp_futex_park
    lib.ompi_tpu_trace_emit(4, 0, 7, 11)    # fp_crc_drop
    evs = recorder.drain_native()
    assert [e[3] for e in evs] == ["fp_futex_park", "fp_crc_drop"]
    for e in evs:
        assert e[2] == "i" and e[4] == "native"
    assert evs[0][8] == {"a": 3, "b": 42, "c": 43}
    # disabled ring drops writes; re-enabled ring records again
    recorder.native_trace_enable(False)
    lib.ompi_tpu_trace_emit(2, 0, 0, 0)
    assert len(recorder.drain_native()) == 2
    recorder.native_trace_enable(True)
    lib.ompi_tpu_trace_emit(2, 0, 0, 0)
    assert len(recorder.drain_native()) == 3
    recorder.native_trace_reset()
    assert recorder.drain_native() == []


@pytest.mark.skipif(not _native_available(),
                    reason="native library unavailable")
def test_native_events_fold_into_rank_dump():
    from ompi_tpu.native import build

    recorder.native_trace_reset()
    build.get_lib().ompi_tpu_trace_emit(3, 1, 64, 128)  # fp_slab_spill
    dump = export.rank_dump()
    native = [e for e in dump["events"] if e[4] == "native"]
    assert any(e[3] == "fp_slab_spill" for e in native)
    recorder.native_trace_reset()


# -- overhead ratchet (satellite 3) ----------------------------------------

@pytest.mark.skipif(not _native_available(),
                    reason="native library unavailable")
def test_trace_overhead_under_five_percent():
    """The always-on claim: recorder enabled (python cvar + native
    ring) costs <5% on the fastpath 64B RTT p50. Interleaved blocks,
    min-of-blocks on each side (monitoring_overhead discipline)."""
    sys.path.insert(0, HERE)
    try:
        import bench
    finally:
        sys.path.remove(HERE)
    row = bench._trace_overhead_row()
    assert "error" not in row, row
    assert row["p50_off_us"] > 0
    assert row["overhead_pct"] < 5.0, row
    assert row["pass"] is True


# -- exporters --------------------------------------------------------------

def test_perfetto_export_structure():
    with tspan.span("coll.allreduce", cat="coll", trace_id=11,
                    cid=0):
        tspan.instant("tuned.tier", cat="coll", algo="ring")
    dump = export.rank_dump()
    out = export.perfetto([dump])
    evs = out["traceEvents"]
    assert out["displayTimeUnit"] == "ms"
    assert out["otherData"]["ranks"] == 1
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"].startswith("rank")
    bs = [e for e in evs if e["ph"] == "B"]
    es = [e for e in evs if e["ph"] == "E"]
    ins = [e for e in evs if e["ph"] == "i"]
    assert len(bs) == len(es) == 1 and len(ins) == 1
    assert bs[0]["args"]["trace_id"] == 11
    assert ins[0]["s"] == "t"
    assert all(e.get("ts", 0.0) >= 0.0 for e in evs)
    assert bs[0]["ts"] <= ins[0]["ts"] <= es[0]["ts"]


def test_blob_roundtrip_matches_dump():
    recorder.emit("i", "blobbed", cat="t", args={"x": 1})
    blob = export.dump_to_blob()
    dump = export.blob_to_dump(blob)
    assert dump["format"] == "ompi_tpu-trace-v1"
    assert dump["clock"]["perf_ns"] == recorder.get().epoch_perf_ns
    assert any(e[3] == "blobbed" and e[8] == {"x": 1}
               for e in dump["events"])


def test_clock_alignment_shifts_events():
    rec = recorder.get()
    rec.emit("i", "tick", cat="t")
    d0 = export.rank_dump()
    d0["clock"]["offset_s"] = 0.5  # pretend this rank runs 500ms fast
    t_aligned = export._epoch_ns(d0, d0["events"][0][1], align=True)
    t_raw = export._epoch_ns(d0, d0["events"][0][1], align=False)
    assert t_raw - t_aligned == int(0.5e9)


def test_timeline_renders_cross_rank_lines():
    with tspan.span("coll.allreduce", cat="coll", trace_id=0x500001):
        pass
    d0 = export.rank_dump()
    d1 = json.loads(json.dumps(d0))
    d1["rank"] = 1
    text = export.timeline([d0, d1])
    assert "0x500001" in text
    assert "rank0" in text and "rank1" in text
    assert export.timeline([]) == "(no collective spans)"


# -- 2-rank merge acceptance (the ISSUE's checkable claim) ------------------

_RANK_PROG = """
import os, sys
import ompi_tpu
from ompi_tpu.trace import recorder
from ompi_tpu.core import config
config.set("trace_base_dir", sys.argv[1])
world = ompi_tpu.init()
import jax.numpy as jnp
x = jnp.arange(world.size * 4, dtype=jnp.float32).reshape(world.size, 4)
world.allreduce(x, op="sum")
world.allreduce(x, op="max")
ompi_tpu.finalize()
"""


def test_two_rank_merge_shares_trace_ids(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    for rank in (0, 1):
        env["OMPI_TPU_TRACE_RANK"] = str(rank)
        r = subprocess.run(
            [sys.executable, "-c", _RANK_PROG, str(tmp_path)],
            capture_output=True, text=True, timeout=240, cwd=HERE,
            env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
    merged = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.trace",
         "--dir", str(tmp_path), "-o", str(merged), "--timeline"],
        capture_output=True, text=True, timeout=120, cwd=HERE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "merged 2 rank dump(s)" in r.stdout
    out = json.loads(merged.read_text())
    begins = [e for e in out["traceEvents"]
              if e.get("cat") == "coll" and e["ph"] == "B"
              and e["name"] == "coll.allreduce"]
    by_rank = {}
    for e in begins:
        by_rank.setdefault(e["pid"], []).append(e["args"]["trace_id"])
    assert set(by_rank) == {0, 1}
    # the acceptance claim: each collective's spans share one trace ID
    # across both ranks, in issue order
    assert by_rank[0] == by_rank[1]
    assert len(by_rank[0]) == 2 and len(set(by_rank[0])) == 2


def test_cli_requires_input():
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.trace"],
        capture_output=True, text=True, timeout=120, cwd=HERE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert "no dump files" in r.stderr


# -- tracespan lint rule (satellite 5) --------------------------------------

def _tracespan_findings(src, relpath):
    from ompi_tpu.analysis.lint import Linter

    lin = Linter()
    out = [f for f in lin.lint_source(src, path=relpath,
                                      relpath=relpath)
           if f.rule == "tracespan"]
    assert not lin.errors, lin.errors
    return out


def test_tracespan_flags_unwrapped_entry_points():
    src = textwrap.dedent("""
        def allreduce(comm, x, op):
            return comm.do(x, op)

        class Helper:
            def send(self, comm, value, dest, tag):
                return comm.pml.send(comm, value, dest, tag)
    """)
    found = _tracespan_findings(src, "coll/custom.py")
    assert [f.line for f in found] == [2, 6]
    assert "trace span" in found[0].message


def test_tracespan_accepts_span_evidence_and_registered():
    src = textwrap.dedent("""
        from ompi_tpu.trace import span as tspan

        def allreduce(comm, x, op):
            with tspan.span("coll.allreduce", cat="coll"):
                return comm.do(x, op)

        @COLL.register
        class MyColl(CollComponent):
            def bcast(self, comm, x, root):
                return comm.do(x)  # selection-seam wrap covers this
    """)
    assert _tracespan_findings(src, "coll/custom.py") == []


def test_tracespan_scoping_and_suppression():
    src = textwrap.dedent("""
        def send(comm, value, dest, tag):
            return comm.pml.send(comm, value, dest, tag)
    """)
    # out-of-scope dirs and the seam files themselves are exempt
    assert _tracespan_findings(src, "io/custom.py") == []
    assert _tracespan_findings(src, "coll/framework.py") == []
    # builder methods without a comm parameter are out of scope
    nb = "def send(self, src, dst, buf):\n    return None\n"
    assert _tracespan_findings(nb, "coll/custom.py") == []
    sup = textwrap.dedent("""
        def send(comm, value, dest, tag):  # commlint: allow(tracespan)
            return comm.pml.send(comm, value, dest, tag)
    """)
    assert _tracespan_findings(sup, "coll/custom.py") == []


def test_tracespan_registered_with_repo():
    from ompi_tpu.analysis.rules import COMMLINT, ensure_rules

    ensure_rules()
    assert "tracespan" in COMMLINT._component_classes
