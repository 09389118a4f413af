"""Trace reduction on a small extract recorded from v5e traces (four
chips: an all-reduce slice and a latency slice; one chip: a
reduce_local slice), and on a trace recorded here on the CPU."""

from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, peaks, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_trace_extract.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def test_merge_is_a_union():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6.5)]) \
        == [[0, 4], [5, 7]]
    assert trace_reduce.merge([]) == []


def test_busy_counts_overlap_once_and_idle_is_averaged():
    ex = {"devices": {"/device:TPU:0": [["a", 0, 10], ["b", 5, 10]],
                      "/device:TPU:1": [["a", 0, 5]]},
          "host": []}
    busy = trace_reduce.busy_s(ex)
    assert busy == {"/device:TPU:0": pytest.approx(15e-9),
                    "/device:TPU:1": pytest.approx(5e-9)}
    assert trace_reduce.idle_share(ex, 20e-9) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        trace_reduce.mean_busy_s({"devices": {}, "host": []})


def test_idle_gaps_are_labelled_by_the_open_annotation():
    ex = {"devices": {"/device:TPU:0": [["op", 10, 10]]},
          "host": [["call:lat.8B", 0, 15], ["wait:lat.8B", 15, 25],
                   ["call:lat.8B", 50, 10]]}
    gaps = dict(trace_reduce.idle_gaps(ex))
    assert gaps == {"call:lat.8B": pytest.approx(20e-9),
                    "wait:lat.8B": pytest.approx(20e-9),
                    "harness": pytest.approx(10e-9)}


def test_collective_ops_are_selected_by_name(recorded):
    match = trace_reduce.name_matcher(("all-reduce",))
    bw, red = recorded["bw"], recorded["reduce"]
    assert trace_reduce.mean_busy_s(bw, match) == pytest.approx(
        trace_reduce.mean_busy_s(bw))
    assert all(v == 0 for v in trace_reduce.busy_s(red, match).values())
    assert len(bw["devices"]) == 4 and len(red["devices"]) == 1


def test_breakdown_of_the_recorded_slices(recorded):
    for name, ex in recorded.items():
        ops = trace_reduce.top_ops(ex, 10)
        assert 0 < len(ops) <= 10
        assert [s for _, s in ops] == sorted((s for _, s in ops),
                                             reverse=True)
        assert all("{" not in n and "(" not in n.split(" = ")[-1]
                   for n, _ in ops)
        gaps = trace_reduce.idle_gaps(ex, 10)
        idle = sum(s for _, s in gaps)
        # the gaps of the first chip are its idle time in the window
        lo, hi = 0.0, ex["window_s"] * 1e9
        busy0 = sum(e - s for s, e in trace_reduce.merge(
            (max(s, lo), min(s + d, hi))
            for _, s, d in ex["devices"][sorted(ex["devices"])[0]]))
        assert idle + busy0 * 1e-9 == pytest.approx(ex["window_s"],
                                                    rel=1e-6)
    assert recorded["reduce"]["host"][0][0].startswith("call:reduce.")
    assert "add" in trace_reduce.top_ops(recorded["reduce"])[0][0]


def _trace_reading(recorded, group, nranks):
    ex = recorded[group]
    calls = harness.Calls()
    for name, start, dur in ex["host"]:
        if name.startswith("call:"):
            nbytes = int(name.split(".")[-1][:-1])
            calls.add(group, nbytes, start, start, start + dur)
    trace = {"extract": ex, "window_s": ex["window_s"], "calls": calls}
    return harness.Reading(nranks=nranks, setup_s=1.0,
                           calls=harness.Calls(), traces={group: trace},
                           peaks=peaks.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("metric,group,nranks", [
    ("coll_roofline.bw", "bw", 4), ("idle_share.bw", "bw", 4),
    ("idle_share.lat", "lat", 4), ("op_roofline.reduce", "reduce", 1),
    ("idle_share.reduce", "reduce", 1)])
def test_trace_readers_on_the_recorded_slices(recorded, metric, group,
                                              nranks):
    value = harness.load_module("metrics", metric).read(
        _trace_reading(recorded, group, nranks))
    assert value is not None and 0.0 < value <= 100.0


def test_trace_readers_find_nothing_without_a_trace():
    r = harness.Reading(nranks=4, setup_s=1.0, calls=harness.Calls(),
                        traces={}, peaks=None)
    for metric in ("coll_roofline.bw", "idle_share.lat", "idle_share.bw",
                   "op_roofline.reduce", "idle_share.reduce",
                   "dispatch_us.lat"):
        assert harness.load_module("metrics", metric).read(r) is None


def test_read_xplane_keeps_the_harness_annotations(tmp_path):
    f = jax.jit(lambda a: a * 2 + 1)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("call:lat.8B"):
            y = f(x)
        with jax.profiler.TraceAnnotation("wait:lat.8B"):
            y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    ex = trace_reduce.read_xplane(path)
    assert [h[0] for h in ex["host"]] == ["call:lat.8B", "wait:lat.8B"]
    assert ex["host"][0][1] <= ex["host"][1][1]
    assert ex["devices"] == {}  # the CPU has no device plane
