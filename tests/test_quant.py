"""Quantized-wire allreduce tier: error bounds, exactness rules, error
feedback, and the tuned/vtable routing (ISSUE PR3 satellite 3).

Every reduction here runs on the 8-virtual-device mesh (conftest), so
the ring schedule executes all 2(n-1) hops and the measured error is
the real accumulated requantization error, checked against the
analytic block-scale bound from coll/quant.analytic_error_bound.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ompi_tpu as mt
from ompi_tpu.core import config
from ompi_tpu.core.counters import SPC
from ompi_tpu.coll import quant


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


@pytest.fixture
def quant_enabled():
    """Enable the quant tier with a tiny min_bytes so test payloads
    qualify; always restore defaults."""
    config.set("coll_quant_enable", True)
    config.set("coll_quant_min_bytes", 1 << 10)
    try:
        yield
    finally:
        config.set("coll_quant_enable", False)
        config.set("coll_quant_min_bytes", 64 << 10)
        config.set("coll_quant_wire", "int8")


def _rand(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


# ---------------------------------------------------------------------------
# codec + analytics
# ---------------------------------------------------------------------------

def test_block_scaled_roundtrip_error():
    x = jnp.asarray(_rand(4096))
    q, s = quant.quantize_block_scaled(x, 128)
    assert q.dtype == jnp.int8 and s.shape == (4096 // 128,)
    back = quant.dequantize_block_scaled(q, s, 128)
    # single quantization: error <= scale/2 = max|block|/254 per block
    err = np.abs(np.asarray(back - x)).reshape(-1, 128).max(axis=1)
    bound = np.abs(np.asarray(x)).reshape(-1, 128).max(axis=1) / 254.0
    assert (err <= bound + 1e-7).all()


def test_zero_block_is_exact():
    x = jnp.zeros(256, jnp.float32)
    q, s = quant.quantize_block_scaled(x, 128)
    assert np.asarray(
        quant.dequantize_block_scaled(q, s, 128) == 0).all()


def test_wire_bytes_and_ratio():
    # int8 wire: 1 byte/elem + 4-byte scale per 128 elems
    logical = 4 << 20
    elems = logical // 4
    assert quant.wire_bytes(logical, 4, wire="int8") == \
        elems + 4 * (elems // 128)
    assert quant.wire_bytes(logical, 4, wire="bf16") == logical // 2
    assert logical / quant.wire_bytes(logical, 4, wire="int8") > 1.9
    assert logical / quant.wire_bytes(logical, 4, wire="bf16") >= 1.9


def test_supports_refusals():
    from ompi_tpu import ops

    f32 = jnp.float32
    assert quant.supports(ops.lookup("sum"), f32)
    # order statistics must be exact: refused
    assert not quant.supports(ops.lookup("max"), f32)
    assert not quant.supports(ops.lookup("min"), f32)
    # joint (paired-word) ops: refused
    assert not quant.supports(ops.lookup("maxloc"), f32)
    # integer payloads: refused
    assert not quant.supports(ops.lookup("sum"), jnp.int32)
    assert not quant.supports(ops.lookup("band"), jnp.int32)


# ---------------------------------------------------------------------------
# ring allreduce within the analytic bound (both wires)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", ["int8", "bf16"])
def test_allreduce_within_analytic_bound(wire):
    from jax.sharding import Mesh, PartitionSpec as P

    n = 8
    data = _rand((n, 2048), seed=3)
    mesh = Mesh(np.array(jax.devices()[:n]), ("r",))
    fn = jax.jit(jax.shard_map(
        lambda b: quant.allreduce_quant_ring(
            b[0], "r", "sum", wire=wire)[None],
        mesh=mesh, in_specs=(P("r"),), out_specs=P("r"),
    ))
    out = np.asarray(fn(jnp.asarray(data)))
    exact = data.sum(axis=0)
    bound = np.asarray(quant.analytic_error_bound(data, wire=wire))
    err = np.abs(out - exact)
    # every rank's row identical (same wire image dequantized)
    for r in range(1, n):
        np.testing.assert_array_equal(out[r], out[0])
    assert (err[0] <= bound).all(), (
        f"max err {err[0].max()} vs bound min {bound.min()}"
    )


def test_allreduce_rejects_non_sum():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("r",))
    with pytest.raises(ValueError):
        jax.jit(jax.shard_map(
            lambda b: quant.allreduce_quant_ring(b[0], "r", "max")[None],
            mesh=mesh, in_specs=(P("r"),), out_specs=P("r"),
        ))(jnp.ones((8, 256), jnp.float32))


# ---------------------------------------------------------------------------
# vtable routing: sum quantized (within bound), max exact (refused)
# ---------------------------------------------------------------------------

def test_comm_sum_routes_through_quant_tier(quant_enabled):
    comm = mt.world().dup()
    data = _rand((comm.size, 4096), seed=5)
    before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    wire0 = SPC.snapshot().get("coll_quant_bytes_on_wire", 0)
    out = np.asarray(comm.allreduce(comm.put_rank_major(data), "sum"))
    after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    wire1 = SPC.snapshot().get("coll_quant_bytes_on_wire", 0)
    assert after > before, "quant tier not selected"
    assert wire1 > wire0, "bytes_on_wire pvar not recorded"
    bound = np.asarray(quant.analytic_error_bound(data))
    assert (np.abs(out[0] - data.sum(0)) <= bound).all()


def test_comm_max_stays_exact_under_quant(quant_enabled):
    """Order statistics must never quantize: with the tier enabled, max
    is refused by supports() and lands on an exact algorithm."""
    comm = mt.world().dup()
    data = _rand((comm.size, 4096), seed=6)
    before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    out = np.asarray(comm.allreduce(comm.put_rank_major(data), "max"))
    after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    assert after == before, "max must not route through the quant tier"
    np.testing.assert_array_equal(out[0], data.max(axis=0))


def test_small_message_stays_exact(quant_enabled):
    """Below coll_quant_min_bytes the gate refuses: tiny payloads are
    latency-bound, compression buys nothing."""
    config.set("coll_quant_min_bytes", 64 << 10)
    comm = mt.world().dup()
    data = _rand((comm.size, 64), seed=7)
    before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    out = np.asarray(comm.allreduce(comm.put_rank_major(data), "sum"))
    after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    assert after == before
    np.testing.assert_allclose(out[0], data.sum(0), rtol=1e-5,
                               atol=1e-5)


def test_rules_file_can_veto_quant(tmp_path, quant_enabled):
    """A user rules band with ``"allow_quant": false`` forces the exact
    tiers even when the cvar enables quantization."""
    import json

    p = str(tmp_path / "noquant.json")
    with open(p, "w") as f:
        json.dump({"allreduce": [{"allow_quant": False}]}, f)
    config.set("coll_tuned_rules_file", p)
    try:
        comm = mt.world().dup()
        data = _rand((comm.size, 4096), seed=8)
        before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
        out = np.asarray(comm.allreduce(comm.put_rank_major(data)))
        after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
        assert after == before, "rules veto ignored"
        np.testing.assert_allclose(out[0], data.sum(0), rtol=1e-5,
                                   atol=1e-5)
    finally:
        config.set("coll_tuned_rules_file", "")


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_converges():
    """EF residual carry: the time-averaged transmitted signal converges
    to the true input — avg error over 16 compensated roundtrips of the
    SAME gradient is much smaller than one uncompensated roundtrip.
    The reduction itself stays exact here: EF compensates the SOURCE
    quantization (the roundtrip compensate() applies); in-ring requant
    noise is deterministic per input and is bounded separately by
    analytic_error_bound."""
    comm = mt.world()
    data = _rand((comm.size, 2048), seed=9)
    exact = data.sum(0)
    ef = quant.ErrorFeedback()
    acc = np.zeros_like(exact)
    errs = []
    for t in range(1, 17):
        payload = ef.compensate(jnp.asarray(data))
        out = np.asarray(comm.allreduce(payload, "sum"))
        acc += out[0]
        errs.append(np.abs(acc / t - exact).mean())
    # average error at t=16 beats t=1 by at least 4x (observed ~16x)
    assert errs[-1] < errs[0] / 4.0, (errs[0], errs[-1])
    assert float(ef.residual_norm()) > 0.0


def test_error_feedback_identity_when_exact():
    """With no quantization error (exact roundtrip impossible here, so
    use zeros) the residual stays zero."""
    ef = quant.ErrorFeedback()
    x = jnp.zeros(256, jnp.float32)
    out = ef.compensate(x)
    assert np.asarray(out == 0).all()
    assert float(ef.residual_norm()) == 0.0


# ---------------------------------------------------------------------------
# partitioned BucketedAllreduce rides the same tier (satellite 2)
# ---------------------------------------------------------------------------

def test_partitioned_buckets_route_through_quant(quant_enabled):
    """coll/partitioned's BucketedAllreduce dispatches each bucket via
    comm.allreduce — the SAME vtable path — so the quant tier applies
    per bucket with no second quantization implementation."""
    from ompi_tpu.coll.partitioned import BucketedAllreduce

    comm = mt.world().dup()
    data = _rand((comm.size, 16384), seed=10)
    before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    br = BucketedAllreduce(comm, comm.put_rank_major(data), "sum",
                           nbuckets=4)
    br.ready_all()
    out = np.asarray(br.wait())
    after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
    assert after >= before + 4, "buckets did not route through quant"
    # each bucket quantizes independently: bound per bucket slab
    for b in range(4):
        lo, hi = br.bucket_range(b)
        bound = np.asarray(quant.analytic_error_bound(data[:, lo:hi]))
        assert (np.abs(out[0, lo:hi] - data[:, lo:hi].sum(0))
                <= bound).all()


# ---------------------------------------------------------------------------
# pallas fused kernel (Mosaic interpret mode on the CPU mesh)
# ---------------------------------------------------------------------------

def test_pallas_quant_allreduce_within_bound():
    from jax.sharding import Mesh, PartitionSpec as P

    # Four ranks in this process; eight run in a subprocess below.
    n = 4
    data = _rand((n, 128 * 128), seed=11)  # one quantum per rank
    mesh = Mesh(np.array(jax.devices()[:n]), ("r",))
    fn = jax.jit(jax.shard_map(
        lambda b: quant.allreduce_block_quant(b[0], "r", "sum")[None],
        mesh=mesh, in_specs=(P("r"),), out_specs=P("r"),
        check_vma=False,
    ))
    out = np.asarray(fn(jnp.asarray(data)))
    bound = np.asarray(quant.analytic_error_bound(data))
    assert (np.abs(out[0] - data.sum(0)) <= bound).all()
    for r in range(1, n):
        np.testing.assert_array_equal(out[r], out[0])


_QUANT_8_RANKS = """
import jax
jax.config.update("jax_num_cpu_devices", 8)
import jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from ompi_tpu.coll import quant
n = 8
data = np.random.default_rng(11).standard_normal((n, 128 * 128)).astype(
    np.float32)
mesh = Mesh(np.array(jax.devices()[:n]), ("r",))
fn = jax.jit(jax.shard_map(
    lambda b: quant.allreduce_block_quant(b[0], "r", "sum")[None],
    mesh=mesh, in_specs=(P("r"),), out_specs=P("r"), check_vma=False))
out = np.asarray(fn(jnp.asarray(data)))
bound = np.asarray(quant.analytic_error_bound(data))
assert (np.abs(out[0] - data.sum(0)) <= bound).all()
assert all((out[r] == out[0]).all() for r in range(1, n))
print("QUANT8 OK")
"""


def test_pallas_quant_allreduce_8_ranks_with_a_wider_host_pool():
    """The kernel's barrier and credit protocol at eight ranks.

    In interpret mode every emulated device's program holds one thread
    of XLA:CPU's host pool while it sits in an emulator callback, and
    the emulator's buffer allocation (``np.array`` of a device value)
    needs one more. A pool of exactly eight threads under eight emulated
    devices therefore deadlocks with all eight parked in
    ``_allocate_buffer``, while seven ranks on the same pool finish.
    ``NPROC`` sizes that pool, so eight ranks run in a subprocess whose
    pool has sixteen threads."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, NPROC="16", JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    proc = subprocess.run([sys.executable, "-c", _QUANT_8_RANKS], env=env,
                          cwd=root, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0 and "QUANT8 OK" in proc.stdout, \
        proc.stdout[-2000:] + proc.stderr[-4000:]
