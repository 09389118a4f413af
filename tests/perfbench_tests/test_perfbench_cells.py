"""Each benchmark cell driven end to end on the CPU mesh at a tiny size,
past the harness's look for a chip: a sound run is correct, and the
control (the reference one precision below the configuration's type, in
the program's place) and every fault the cell can have, planted under
the timed path, are not."""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import ompi_tpu
from ompi_tpu import ops
from ompi_tpu.communicator import Communicator
from perfbench import harness, inputs

TINY = {
    "imb_allreduce.4chip": {"groups": [
        {"name": "lat", "share": 0.5, "order": "interleave",
         "sizes": [8, 4096], "buffers": 2, "samples": 3,
         "sample_below": 4, "trace_seconds": 0.05},
        {"name": "bw", "share": 0.5, "order": "slices",
         "sizes": [65536, 262144], "buffers": 2, "samples": 2,
         "sample_below": 2, "trace_seconds": 0.05}]},
    "imb_reduce_local.1chip": {"groups": [
        {"name": "reduce", "share": 1.0, "order": "slices",
         "sizes": [65536, 262144], "buffers": 1, "samples": 2,
         "sample_below": 2, "trace_seconds": 0.05}]},
    "imb_reduce_local_max_i32.1chip": {"groups": [
        {"name": "lat", "share": 0.5, "order": "interleave",
         "sizes": [8, 4096], "buffers": 2, "samples": 3,
         "sample_below": 4, "trace_seconds": 0.05},
        {"name": "reduce", "share": 0.5, "order": "slices",
         "sizes": [65536, 262144], "buffers": 1, "samples": 2,
         "sample_below": 2, "trace_seconds": 0.05}]},
}
TINY["imb_allreduce_bf16.4chip"] = TINY["imb_allreduce.4chip"]
SEED = 2**31 + 12345


def _run(name: str, tmp_path, call=None, trace: bool = False,
         seed: int = SEED) -> dict:
    cell = harness.load_cell(harness.load_bench(), name)
    cell.traffic = TINY[name]
    lines = []
    result = harness.run_cell(
        cell, seed, 0.3, trace, devices=jax.devices()[:cell.chips],
        t_start=0.0, call=call, work_dir=str(tmp_path), log=lines.append)
    result["lines"] = lines
    return result


def test_inputs_come_from_the_seed_in_one_call():
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    plan = [((0, 0, 0), 64), ((0, 1, 1), 128)]

    def make(key, nbytes):
        return inputs.int_valued(key, (nbytes // 4,), 8, "float32", sharding)

    a, b, c = (inputs.make_all(make, inputs.base_key(s), plan)
               for s in (SEED, SEED, SEED + 2**32))
    assert [x.shape for x in a] == [(16,), (32,)]
    assert all(bool((x == y).all()) for x, y in zip(a, b))
    assert any(bool((x != y).any()) for x, y in zip(a, c))
    assert all(bool((x >= -8).all() and (x < 8).all()
                    and (x == jnp.round(x)).all()) for x in a)
    assert len(set(a[1].tolist())) == 16  # every value of the range
    with pytest.raises(ValueError):
        inputs.int_valued(inputs.base_key(1), (4,), 6, "float32", sharding)


def test_bf16_inputs_sum_exactly_in_every_order_and_e4m3_rounds_them():
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    x = inputs.int_valued(inputs.base_key(SEED), (4, 4096), 64, "bfloat16",
                          sharding)
    assert x.dtype == jnp.bfloat16
    exact = np.asarray(x, np.float32).sum(axis=0)
    for a, b, c, d in itertools.permutations(range(4)):
        chain = ((x[a] + x[b]) + x[c]) + x[d]
        tree = (x[a] + x[b]) + (x[c] + x[d])
        assert (np.asarray(chain, np.float32) == exact).all()
        assert (np.asarray(tree, np.float32) == exact).all()
    every = jnp.arange(-64, 64, dtype=jnp.bfloat16)
    e4m3 = lax.reduce_precision(every, exponent_bits=4, mantissa_bits=3)
    assert int(jnp.sum(e4m3 != every)) == 64


def test_reduce_local_max_reference():
    ref = harness.load_module("references", "reduce_local_max")
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    buf = tuple(inputs.int_valued(inputs.base_key(SEED + i), (1024,),
                                  1 << 20, "int32", sharding)
                for i in range(2))
    assert ref.gap(buf, ops.reduce_local("max", *buf)) == 0.0
    assert ref.gap(buf, ref.control(buf)) > 0.0
    assert ref.gap(buf, jnp.maximum(*buf).astype(jnp.float32)) == \
        float("inf")
    # off by 2**31: the int32 difference wraps to its minimum, whose int32
    # absolute value is negative; the gap still reads it
    wrapped = jnp.maximum(*buf).at[0].add(jnp.int32(-2**31))
    assert ref.gap(buf, wrapped) == 2.0**31


@pytest.mark.parametrize("name", sorted(TINY))
def test_sound_run_is_correct(name, tmp_path):
    r = _run(name, tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] == 0.0 for n, c in r["checks"].items()
               if n.startswith("gap."))
    assert {"setup_s"} <= set(r["metrics"])
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-2] == "checks"
    assert any(line.startswith("compilations in the window")
               for line in r["lines"])


def test_allreduce_metrics_and_tier_line(tmp_path):
    r = _run("imb_allreduce.4chip", tmp_path)
    assert set(r["metrics"]) == {"busbw_GBps", "lat_p50_us", "lat_p99_us",
                                 "setup_s"}
    assert r["metrics"]["lat_p99_us"]["value"] \
        >= r["metrics"]["lat_p50_us"]["value"] > 0
    tiers = [ln for ln in r["lines"] if ln.startswith("tier selection")]
    assert tiers and "coll_allreduce_algo_host" in tiers[0]


def test_reduce_local_max_metrics(tmp_path):
    r = _run("imb_reduce_local_max_i32.1chip", tmp_path)
    assert set(r["metrics"]) == {"lat_p50_us.local", "reduce_GBps",
                                 "setup_s"}
    assert r["metrics"]["lat_p50_us.local"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(tmp_path):
    r = _run("imb_reduce_local.1chip", tmp_path, trace=True)
    assert r["correct"], r["checks"]
    # no device ops in a CPU trace: the trace readers report nothing
    # rather than 0, and the host-clock reader still has its number
    assert "op_roofline.reduce" not in r["metrics"]
    assert "breakdown" in r


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_is_not_correct(name, tmp_path):
    cell = harness.load_cell(harness.load_bench(), name)
    reference = harness.load_module("references", cell.config["reference"])
    r = _run(name, tmp_path, call=reference.control)
    assert not r["correct"]
    assert all(c["value"] > c["limit"] for n, c in r["checks"].items()
               if n.startswith("gap."))


def _exchange_left_out(self, x, op="sum"):
    return x


def _half_the_ranks(self, x, op="sum"):
    half = jnp.sum(x[: self.size // 2], axis=0) * 2
    return jax.device_put(jnp.broadcast_to(half, x.shape), x.sharding)


_real_allreduce = Communicator.allreduce


def _answer_altered(self, x, op="sum"):
    out = _real_allreduce(self, x, op)
    return jax.device_put(out.at[self.size - 1, -1].add(1.0), out.sharding)


@pytest.mark.parametrize("fault", [_exchange_left_out, _half_the_ranks,
                                   _answer_altered])
def test_allreduce_fault_is_caught(fault, tmp_path, monkeypatch):
    ompi_tpu.init()
    monkeypatch.setattr(Communicator, "allreduce", fault)
    r = _run("imb_allreduce.4chip", tmp_path)
    assert not r["correct"]


_real_reduce_local = ops.reduce_local


def _state_unchanged(op, inbuf, inout):
    return inout


def _half_the_batch(op, inbuf, inout):
    h = inout.shape[0] // 2
    return jnp.concatenate([_real_reduce_local(op, inbuf[:h], inout[:h]),
                            inout[h:]])


def _local_answer_altered(op, inbuf, inout):
    return _real_reduce_local(op, inbuf, inout).at[-1].add(1)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _local_answer_altered])
def test_reduce_local_fault_is_caught(fault, tmp_path, monkeypatch):
    monkeypatch.setattr(ops, "reduce_local", fault)
    r = _run("imb_reduce_local.1chip", tmp_path)
    assert not r["correct"]


@pytest.mark.parametrize("fault", [_exchange_left_out, _half_the_ranks,
                                   _answer_altered])
def test_bf16_allreduce_fault_is_caught(fault, tmp_path, monkeypatch):
    ompi_tpu.init()
    monkeypatch.setattr(Communicator, "allreduce", fault)
    r = _run("imb_allreduce_bf16.4chip", tmp_path)
    assert not r["correct"]


def test_bf16_allreduce_takes_the_host_tier_at_8_bytes(tmp_path):
    r = _run("imb_allreduce_bf16.4chip", tmp_path)
    assert r["correct"], r["checks"]
    tiers = [ln for ln in r["lines"] if ln.startswith("tier selection")]
    assert tiers and "coll_allreduce_algo_host" in tiers[0]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _local_answer_altered])
def test_reduce_local_max_fault_is_caught(fault, tmp_path, monkeypatch):
    monkeypatch.setattr(ops, "reduce_local", fault)
    r = _run("imb_reduce_local_max_i32.1chip", tmp_path)
    assert not r["correct"]


def test_reduce_local_max_state_unchanged_in_the_window_is_caught(
        tmp_path, monkeypatch):
    # MAX saturates a chained inoutbuf in the warm-up, after which the
    # unchanged state is the right answer: the cell's entry is unchained
    name = "imb_reduce_local_max_i32.1chip"
    warm_up = 2 * sum(len(g["sizes"]) * g["buffers"]
                      for g in TINY[name]["groups"])
    calls = itertools.count()

    def planted(op, inbuf, inout):
        if next(calls) < warm_up:
            return _real_reduce_local(op, inbuf, inout)
        return inout

    monkeypatch.setattr(ops, "reduce_local", planted)
    r = _run(name, tmp_path)
    assert not r["correct"]
    assert all(c["value"] > 0 for n, c in r["checks"].items()
               if n.startswith("gap."))


def test_raising_call_counts_as_failed(tmp_path):
    def boom(buf):
        raise RuntimeError("tier fault")

    r = _run("imb_reduce_local.1chip", tmp_path, call=boom)
    assert not r["correct"] and r["failed"] == r["attempted"] > 0
