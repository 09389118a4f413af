"""The program-span readers: ``program_spans`` on small recorded lists of
records and calls (a lapped ring among them), the three metrics on
traced runs of each cell on the CPU mesh, and the idle-by-span view of
a profiler trace recorded here."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

import ompi_tpu
from ompi_tpu.trace import recorder
from perfbench import harness, program_spans

US = 1000  # ns


def _records(calls):
    """Ring records of calls given as (t0_us, [(name, parent, b_us, e_us)
    ...]); span ids number the spans in order, parent an index into the
    call's own list or None."""
    recs, sid = [], 0
    for _, spans in calls:
        ids = []
        for name, parent, b, e in spans:
            sid += 1
            ids.append(sid)
            pid = 0 if parent is None else ids[parent]
            recs.append((0, b * US, "B", name, "coll", sid, pid, 1,
                         {"trace_id": 7}))
            recs.append((0, e * US, "E", name, "coll", sid, pid, 1, None))
    recs.sort(key=lambda r: r[1])
    return [(i,) + r[1:] for i, r in enumerate(recs)]


CALLS = [
    (100, [("coll.allreduce", None, 101, 140),
           ("coll.launch", 0, 110, 138),
           ("coll.host_fetch", 1, 111, 120),
           ("coll.host_put", 1, 125, 137)]),
    (200, [("coll.allreduce", None, 201, 230),
           ("coll.launch", 0, 215, 229)]),
    (300, [("coll.allreduce", None, 301, 320),
           ("coll.launch", 0, 305, 318)]),
]


def _calls(group="lat"):
    calls = harness.Calls()
    for t0, _ in CALLS:
        calls.add(group, 8, t0 * 1e-6, (t0 + 50) * 1e-6, (t0 + 60) * 1e-6)
    return calls


def test_per_call_reads_each_call_and_its_children():
    got = program_spans.per_call(_records(CALLS), _calls(), "lat",
                                 "coll.allreduce", ("coll.launch",))
    assert len(got) == 3
    assert got[0] == pytest.approx({
        "coll.allreduce": 39e-6, "coll.launch": 28e-6,
        "coll.host_fetch": 9e-6, "coll.host_put": 12e-6})
    assert got[1] == pytest.approx({"coll.allreduce": 29e-6,
                                    "coll.launch": 14e-6})
    # another group, or a root the program never opens: nothing
    assert program_spans.per_call(_records(CALLS), _calls(), "bw",
                                  "coll.allreduce") is None
    assert program_spans.per_call(_records(CALLS), _calls(), "lat",
                                  "op.reduce_local") is None


def test_a_lapped_ring_or_a_missing_child_gives_none():
    recs = _records(CALLS)
    # the ring lost the first call's begin records: 2 of 3 calls left
    lapped = [r for r in recs if r[1] >= 140 * US]
    assert program_spans.per_call(lapped, _calls(), "lat",
                                  "coll.allreduce") is None
    # a program that opens the root but not the child (the parent
    # commit's vtable span): nothing rather than a number of another kind
    bare = [r for r in recs if r[3] == "coll.allreduce"]
    assert program_spans.per_call(bare, _calls(), "lat", "coll.allreduce",
                                  ("coll.launch",)) is None
    assert len(program_spans.per_call(bare, _calls(), "lat",
                                      "coll.allreduce")) == 3


def test_spans_outside_the_call_are_not_its_own():
    shifted = [(t0 + 45, spans) for t0, spans in CALLS]  # root ends past t1
    calls = harness.Calls()
    for t0, _ in shifted:
        calls.add("lat", 8, t0 * 1e-6, (t0 + 50) * 1e-6, (t0 + 60) * 1e-6)
    assert program_spans.per_call(_records(CALLS), calls, "lat",
                                  "coll.allreduce") is None


def test_segments_label_the_innermost_span_under_the_harness():
    events = [["call:lat.8B", 0, 100], ["coll.allreduce", 10, 80],
              ["coll.launch", 20, 60], ["coll.host_put", 40, 10],
              ["wait:lat.8B", 100, 50], ["call:lat.8B", 170, 10]]
    segs = program_spans.segments(events)
    assert [(s, e) for s, e, _ in segs] == [
        (0, 10), (10, 20), (20, 40), (40, 50), (50, 80), (80, 90),
        (90, 100), (100, 150), (150, 170), (170, 180)]
    assert [n for _, _, n in segs] == [
        "call:lat.8B", "call:lat.8B > coll.allreduce",
        "call:lat.8B > coll.launch", "call:lat.8B > coll.host_put",
        "call:lat.8B > coll.launch", "call:lat.8B > coll.allreduce",
        "call:lat.8B", "wait:lat.8B", "harness", "call:lat.8B"]
    idle = dict(program_spans.idle_by_span([["op", 45, 100]], events))
    assert idle == pytest.approx({
        "call:lat.8B": 20e-9, "call:lat.8B > coll.allreduce": 10e-9,
        "call:lat.8B > coll.launch": 20e-9,
        "call:lat.8B > coll.host_put": 5e-9, "wait:lat.8B": 5e-9,
        "harness": 20e-9})
    assert program_spans.idle_by_span([], []) == []


LAT = {"name": "lat", "share": 1.0, "order": "interleave",
       "sizes": [8, 4096], "buffers": 2, "samples": 3, "sample_below": 4,
       "trace_seconds": 0.1}
REDUCE = {"name": "reduce", "share": 1.0, "order": "slices",
          "sizes": [65536, 262144], "buffers": 1, "samples": 2,
          "sample_below": 2, "trace_seconds": 0.1}


@pytest.mark.parametrize("name,group,metrics", [
    ("imb_allreduce.4chip", LAT, ["route_us.lat", "launch_us.lat"]),
    ("imb_reduce_local.1chip", REDUCE, ["op_launch_us.reduce"]),
    ("imb_reduce_local_max_i32.1chip", LAT, ["op_launch_us.lat"])])
def test_traced_run_reports_the_span_metrics(name, group, metrics,
                                             tmp_path):
    cell = harness.load_cell(harness.load_bench(), name)
    cell.traffic = {"groups": [group]}
    r = harness.run_cell(cell, 2**31 + 777, 0.2, True,
                         devices=jax.devices()[:cell.chips], t_start=0.0,
                         work_dir=str(tmp_path), log=lambda line: None)
    assert r["correct"], r["checks"]
    for m in metrics:
        assert r["metrics"][m]["value"] > 0.0 and \
            r["metrics"][m]["unit"] == "us"


def test_readers_find_nothing_without_spans():
    empty = harness.Reading(nranks=4, setup_s=1.0, calls=harness.Calls(),
                            traces={}, peaks=None)
    calls = harness.Calls()
    calls.add("lat", 8, 1.0, 1.1, 1.2)
    calls.add("reduce", 8, 1.0, 1.1, 1.2)
    traced = harness.Reading(nranks=4, setup_s=1.0, calls=harness.Calls(),
                             traces={"lat": {"calls": calls},
                                     "reduce": {"calls": calls}},
                             peaks=None)
    recorder.configure()  # an empty ring: no span lies in those calls
    for metric in ("route_us.lat", "launch_us.lat", "op_launch_us.reduce",
                   "op_launch_us.lat"):
        mod = harness.load_module("metrics", metric)
        assert mod.read(empty) is None and mod.read(traced) is None


def test_cli_prints_idle_time_by_program_span(tmp_path, capsys):
    world = ompi_tpu.init()
    x = world.put_rank_major(jnp.ones((world.size, 2), jnp.float32))
    jax.block_until_ready(world.allreduce(x, "sum"))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("call:lat.8B"):
            out = world.allreduce(x, "sum")
        with jax.profiler.TraceAnnotation("wait:lat.8B"):
            jax.block_until_ready(out)
    assert program_spans.main([str(tmp_path)]) == 0
    (line,) = capsys.readouterr().out.splitlines()
    idle = dict(json.loads(line)["idle_by_span"])
    # the CPU has no device plane: the whole window is idle
    assert {"call:lat.8B > coll.launch", "call:lat.8B > coll.host_fetch",
            "call:lat.8B > coll.host_put", "wait:lat.8B"} <= set(idle)
    assert all(v > 0 for v in idle.values())
    assert program_spans.main([str(tmp_path / "none")]) == 1
