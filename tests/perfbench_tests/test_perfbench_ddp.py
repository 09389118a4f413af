"""The DDP cells (``dp_gradsync.4chip``, ``dp_gradaccum.1chip``) on the
CPU mesh, at a tiny preset of the same 153-leaf structure and a small
bucket cap, so that leaves span buckets: a sound run is correct; the
control and every fault planted under the timed path are not; a traced
run reports the span metrics. At published widths, the list the entry
builds has the configuration's counts, read from shapes alone."""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
import pytest

import ompi_tpu
from ompi_tpu import ops
from ompi_tpu.communicator import Communicator
from ompi_tpu.parallel import bucketer
from perfbench import harness

SYNC, ACCUM = "dp_gradsync.4chip", "dp_gradaccum.1chip"
TINY_WIDTHS = {"hidden_size": 32, "intermediate_size": 96,
               "moe_intermediate_size": 16, "kv_lora_rank": 16,
               "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
               "v_head_dim": 8, "num_attention_heads": 2,
               "vocab_size": 48, "bucket_bytes": 16384}
SEED = 2**31 + 4242

entry = harness.load_module("entries", "ddp_grads")
CONFIG = harness.load_cell(harness.load_bench(), SYNC).config


def _tiny_config() -> dict:
    return dict(CONFIG, **TINY_WIDTHS)


def _tiny_bytes() -> int:
    return 4 * sum(math.prod(s) for _, s in
                   entry.gradient_list(_tiny_config()))


def _tiny_plan() -> list:
    cfg = _tiny_config()
    return bucketer.plan_buckets(
        [jax.ShapeDtypeStruct(s, jnp.float32)
         for _, s in entry.gradient_list(cfg)], cfg["bucket_bytes"])


def _run(name: str, tmp_path, call=None, trace: bool = False) -> dict:
    cell = harness.load_cell(harness.load_bench(), name)
    cell.config = dict(cell.config, **TINY_WIDTHS)
    (group,) = cell.traffic["groups"]
    cell.traffic = {"groups": [dict(group, sizes=[_tiny_bytes()],
                                    sample_below=2, trace_seconds=0.1)]}
    lines = []
    result = harness.run_cell(
        cell, SEED, 0.3, trace, devices=jax.devices()[:cell.chips],
        t_start=0.0, call=call, work_dir=str(tmp_path), log=lines.append)
    result["lines"] = lines
    return result


# ---------------------------------------------------------------------------
# the list at published widths, from shapes alone


def test_the_list_has_the_configurations_counts():
    grads = entry.gradient_list(CONFIG)
    params = sum(math.prod(s) for _, s in grads)
    assert len(grads) == CONFIG["leaves"] == 153
    assert params == CONFIG["params_per_rank"] == 535_060_992
    assert 4 * params == CONFIG["bytes_per_rank"] == 2_140_243_968
    plan = bucketer.plan_buckets(
        [jax.ShapeDtypeStruct(s, jnp.float32) for _, s in grads],
        CONFIG["bucket_bytes"])
    assert len(plan) == CONFIG["buckets"] == 82
    assert [4 * b.elems for b in plan] == [26_214_400] * 81 + [16_877_568]
    # DDP's order: the reverse of registration, embedding last
    assert grads[0][0] == "lm_head.weight"
    assert grads[-1] == ("model.embed_tokens.weight", (12800, 2048))
    assert ("model.layers.0.self_attn.kv_a_layernorm.weight", (512,)) \
        in grads
    assert ("model.layers.4.mlp.gate.weight", (64, 2048)) in grads


def test_the_whole_model_counts_its_published_size():
    published = CONFIG["published"]
    whole = dict(CONFIG, **{k: published[k] for k in (
        "num_hidden_layers", "n_routed_experts", "vocab_size")})
    params = sum(math.prod(s) for _, s in entry.param_shapes(whole))
    assert params == published["parameters"] == 15_706_484_224


def test_the_ep_ranks_cover_every_expert_once():
    held = CONFIG["n_routed_experts"]
    ep = CONFIG["deployment"]["ep"]
    assert held * ep == CONFIG["published"]["n_routed_experts"]
    seen = []
    for rank in range(ep):
        for name, _ in entry.param_shapes(CONFIG, rank):
            if name.startswith("model.layers.1.mlp.experts.") \
                    and name.endswith(".gate_proj.weight"):
                seen.append(int(name.split(".")[5]))
    assert sorted(seen) == list(range(64))


def test_the_traffic_must_be_the_lists_size():
    target = entry._Accumulate(jax.devices()[0], _tiny_config())
    with pytest.raises(ValueError, match="gradient list"):
        target.make(jax.random.key(0), _tiny_bytes() + 4)


def test_the_tiny_preset_spans_buckets():
    plan = _tiny_plan()
    spanning = {i for b in plan for i, lo, hi in b.pieces if lo > 0}
    assert len(plan) > 4 and spanning


# ---------------------------------------------------------------------------
# the cells through the harness


@pytest.mark.parametrize("name", [SYNC, ACCUM])
def test_sound_run_is_correct(name, tmp_path):
    r = _run(name, tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] == 0.0 for n, c in r["checks"].items()
               if n.startswith("gap."))
    e2e = "busbw_GBps" if name == SYNC else "reduce_GBps"
    assert set(r["metrics"]) == {e2e, "setup_s"}


@pytest.mark.parametrize("name", [SYNC, ACCUM])
def test_control_is_not_correct(name, tmp_path):
    reference = harness.load_module("references", CONFIG["reference"])
    r = _run(name, tmp_path, call=reference.control)
    assert not r["correct"]
    assert all(c["value"] > c["limit"] for n, c in r["checks"].items()
               if n.startswith("gap."))


def _one_bucket_left_unreduced():
    calls = itertools.count()
    nb = len(_tiny_plan())
    real = Communicator.allreduce

    def allreduce(self, x, op="sum"):
        return x if next(calls) % nb == nb // 2 else real(self, x, op)

    return Communicator, "allreduce", allreduce


def _half_the_ranks():
    def allreduce(self, x, op="sum"):
        half = jnp.sum(x[: self.size // 2], axis=0) * 2
        return jax.device_put(jnp.broadcast_to(half, x.shape), x.sharding)

    return Communicator, "allreduce", allreduce


def _two_experts_swapped():
    names = [n for n, _ in entry.gradient_list(_tiny_config())]
    i = names.index("model.layers.2.mlp.experts.3.up_proj.weight")
    j = names.index("model.layers.2.mlp.experts.5.up_proj.weight")
    real = bucketer.allreduce_pytree

    def allreduce_pytree(comm, tree, op="sum", **kw):
        out = real(comm, tree, op, **kw)
        out[i], out[j] = out[j], out[i]
        return out

    return bucketer, "allreduce_pytree", allreduce_pytree


def _spanning_tail_misread():
    def split(plan, reduced, shapes):
        parts: dict = {}
        misread = False
        for bucket, r in zip(plan, reduced):
            off = 0
            for i, lo, hi in bucket.pieces:
                start = off
                if lo > 0 and not misread and hi - lo < r.shape[-1]:
                    start, misread = off + 1, True  # the first tail
                parts.setdefault(i, []).append(r[..., start:start + hi - lo])
                off += hi - lo
        return [jnp.concatenate(parts[i], axis=-1).reshape(shape)
                for i, shape in enumerate(shapes)]

    return bucketer, "_split_buckets", split


@pytest.mark.parametrize("fault", [
    _one_bucket_left_unreduced, _half_the_ranks, _two_experts_swapped,
    _spanning_tail_misread])
def test_sync_fault_is_caught(fault, tmp_path, monkeypatch):
    ompi_tpu.init()
    monkeypatch.setattr(*fault())
    r = _run(SYNC, tmp_path)
    assert r["failed"] == 0
    assert not r["correct"]


_real_reduce_local = ops.reduce_local


def _accumulator_unchanged(op, inbuf, inout):
    return inout


def _one_leaf_not_added(op, inbuf, inout):
    out = _real_reduce_local(op, inbuf, inout)
    out[7] = inout[7]
    return out


@pytest.mark.parametrize("fault", [_accumulator_unchanged,
                                   _one_leaf_not_added])
def test_accumulate_fault_is_caught(fault, tmp_path, monkeypatch):
    monkeypatch.setattr(ops, "reduce_local", fault)
    r = _run(ACCUM, tmp_path)
    assert r["failed"] == 0
    assert not r["correct"]


@pytest.mark.parametrize("name,metrics", [
    (SYNC, ["bucketer_us.bw", "bucket_launch_us.bw"]),
    (ACCUM, ["op_launch_us.reduce"])])
def test_traced_run_reports_the_span_metrics(name, metrics, tmp_path):
    r = _run(name, tmp_path, trace=True)
    assert r["correct"], r["checks"]
    for m in metrics:
        assert r["metrics"][m]["value"] > 0.0 and \
            r["metrics"][m]["unit"] == "us"
    # no device ops in a CPU trace: the trace readers report nothing
    assert "pack_roofline.bw" not in r["metrics"]
