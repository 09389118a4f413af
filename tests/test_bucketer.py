"""Gradient bucket coalescer: plan determinism, dispatch counts, value
equality vs per-leaf reduction, and both calling contexts (traced
shard_map + host-side comm vtable)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import ompi_tpu as mt
from ompi_tpu.core import config
from ompi_tpu.core.counters import SPC
from ompi_tpu.parallel import bucketer


@pytest.fixture(scope="module", autouse=True)
def _init():
    if not mt.initialized():
        mt.init()
    yield


def _tree(n_leaves=8, elems=1000, seed=0, lead=()):
    rng = np.random.default_rng(seed)
    return {
        f"g{i:03d}": jnp.asarray(
            rng.standard_normal(lead + (elems + i,)).astype(np.float32))
        for i in range(n_leaves)
    }


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def test_plan_fuses_issue_workload():
    """The ISSUE acceptance shape: 256 x 32 KiB f32 leaves fuse into 2
    buckets at the default 4 MiB cap — a 128x dispatch reduction."""
    tree = {f"g{i:03d}": jnp.zeros(8192, jnp.float32)
            for i in range(256)}
    plan = bucketer.plan_buckets(tree)
    assert len(plan) == 2
    assert sum(b.elems for b in plan) == 256 * 8192
    # fusion off: one dispatch per leaf
    assert len(bucketer.plan_buckets(tree, 0)) == 256


def test_plan_is_deterministic_and_ordered():
    tree = _tree(12, 500)
    p1 = bucketer.plan_buckets(tree, 4096)
    p2 = bucketer.plan_buckets(tree, 4096)
    assert p1 == p2
    # pieces cover every leaf exactly once, in flatten order
    seen = [i for b in p1 for (i, lo, hi) in b.pieces]
    assert seen == sorted(seen)


def test_plan_groups_by_dtype_and_splits_large_leaves():
    tree = {
        "a": jnp.zeros((3, 5), jnp.float32),
        "big": jnp.zeros(3_000_000, jnp.float32),  # > 4 MiB: spans
        "c": jnp.zeros(7, jnp.int32),
        "empty": jnp.zeros(0, jnp.float32),
    }
    plan = bucketer.plan_buckets(tree)
    dtypes = {str(b.dtype) for b in plan}
    assert dtypes == {"float32", "int32"}
    leaves = jax.tree.leaves(tree)
    for b in plan:
        # buckets are dtype-pure
        for i, _lo, _hi in b.pieces:
            assert jnp.asarray(leaves[i]).dtype == b.dtype
    f32_elems = sum(b.elems for b in plan if str(b.dtype) == "float32")
    assert f32_elems == 15 + 3_000_000 + 0


# ---------------------------------------------------------------------------
# traced context (shard_map): bitwise equality with per-leaf psum
# ---------------------------------------------------------------------------

def test_allreduce_tree_matches_per_leaf_psum():
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    tree = _tree(6, 300, seed=2, lead=(8,))

    def run(f):
        return jax.jit(jax.shard_map(
            lambda t: jax.tree.map(
                lambda y: y[None], f(jax.tree.map(lambda x: x[0], t))),
            mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        ))(tree)

    fused = run(lambda t: bucketer.allreduce_tree(t, "dp"))
    ref = run(lambda t: jax.tree.map(
        lambda g: jax.lax.psum(g, "dp"), t))
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transformer_sync_grads_unchanged_by_bucketing():
    """The MULTICHIP gradient path: bucketed _sync_grads is bitwise
    identical to the seed's per-leaf psums (no gradient-value
    regression, ISSUE acceptance)."""
    from jax import lax

    from ompi_tpu.models import transformer as T

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("dp", "pp", "tp"))
    rng = np.random.default_rng(11)

    def mk(*shape):
        return jnp.asarray(
            rng.standard_normal((8,) + shape).astype(np.float32))

    grads = {
        "embed": mk(64, 32), "pos": mk(8, 32), "head": mk(32, 64),
        "ln_f": mk(32),
        "blocks": {
            "ln1": mk(2, 32), "wq": mk(2, 32, 32), "wk": mk(2, 32, 32),
            "wv": mk(2, 32, 32), "wo": mk(2, 32, 32), "ln2": mk(2, 32),
            "router": mk(2, 32, 4), "w1": mk(2, 32, 64),
            "w2": mk(2, 64, 32),
        },
    }

    def seed_semantics(g):
        out = {}
        for name in ("embed", "pos", "head", "ln_f"):
            t = lax.psum(g[name], "tp")
            out[name] = lax.psum(lax.psum(t, "pp"), "dp")
        out["blocks"] = {
            n: lax.psum(
                lax.psum(v, "tp") if n in T._TP_REPLICATED else v, "dp")
            for n, v in g["blocks"].items()
        }
        return out

    def run(f):
        return jax.jit(jax.shard_map(
            lambda t: jax.tree.map(
                lambda y: y[None], f(jax.tree.map(lambda x: x[0], t))),
            mesh=mesh, in_specs=(P(("dp", "pp", "tp")),),
            out_specs=P(("dp", "pp", "tp")),
        ))(grads)

    a = run(lambda g: T._sync_grads(g, None))
    b = run(seed_semantics)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# host context (comm vtable)
# ---------------------------------------------------------------------------

def test_allreduce_pytree_vtable_exact():
    comm = mt.world()
    tree = _tree(5, 700, seed=3, lead=(comm.size,))
    before = SPC.snapshot().get("parallel_dp_bucket_dispatches", 0)
    out = bucketer.allreduce_pytree(comm, tree)
    after = SPC.snapshot().get("parallel_dp_bucket_dispatches", 0)
    assert after > before
    for k, v in tree.items():
        np.testing.assert_allclose(
            np.asarray(out[k][0]), np.asarray(v).sum(0),
            rtol=1e-5, atol=1e-5)


def test_allreduce_pytree_rejects_non_rank_major():
    comm = mt.world()
    with pytest.raises(ValueError):
        bucketer.allreduce_pytree(
            comm, {"a": jnp.zeros(comm.size + 1, jnp.float32)})


def test_allreduce_pytree_quant_and_error_feedback():
    """Fused buckets route through the quant tier when enabled, and the
    dict residual bank carries one ErrorFeedback per bucket across
    steps (deterministic bucketing keeps shapes aligned)."""
    comm = mt.world().dup()
    tree = _tree(6, 4000, seed=4, lead=(comm.size,))
    config.set("coll_quant_enable", True)
    config.set("coll_quant_min_bytes", 1 << 10)
    try:
        before = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
        bank = {}
        out1 = bucketer.allreduce_pytree(comm, tree,
                                         error_feedback=bank)
        after = SPC.snapshot().get("coll_allreduce_algo_quant_ring", 0)
        assert after > before
        assert len(bank) >= 1
        out2 = bucketer.allreduce_pytree(comm, tree,
                                         error_feedback=bank)
        for k in tree:
            assert np.isfinite(np.asarray(out1[k])).all()
            assert np.isfinite(np.asarray(out2[k])).all()
    finally:
        config.set("coll_quant_enable", False)
        config.set("coll_quant_min_bytes", 64 << 10)


def test_dp_module_routes_through_bucketer():
    """parallel/dp.allreduce_gradients is the bucketer front door."""
    from ompi_tpu.parallel import dp

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    tree = _tree(4, 200, seed=5, lead=(8,))
    out = jax.jit(jax.shard_map(
        lambda t: jax.tree.map(
            lambda y: y[None],
            dp.allreduce_gradients(
                jax.tree.map(lambda x: x[0], t), "dp")),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
    ))(tree)
    for k, v in tree.items():
        np.testing.assert_allclose(
            np.asarray(out[k][0]), np.asarray(v).sum(0),
            rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# host context: planning from shapes, one memo per structure
# ---------------------------------------------------------------------------

def _sub_comm(n=4):
    from ompi_tpu.group import Group

    return mt.world().create(Group(list(range(n))))


def _rank_major_list(comm, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [comm.put_rank_major(rng.integers(-64, 64, (comm.size,) + s)
                                .astype(np.float32)) for s in shapes]


# a small list of the DDP cell's kinds of leaves: norm vectors, low-rank
# projections, same-shape expert matrices; at a 2 KiB cap leaves span
# buckets and the last bucket is short
_SHAPES = [(40, 16), (16,), (24, 8), (8,), (12, 16), (16, 12), (12, 16),
           (16, 12), (64, 16), (16,)]


def _counts():
    snap = SPC.snapshot()
    return {k: snap.get(k, 0) for k in (
        "parallel_dp_plan_builds", "parallel_dp_bucket_dispatches",
        "coll_allreduce_lane_hits")}


def test_allreduce_pytree_list_is_exact_leaf_by_leaf():
    comm = _sub_comm()
    grads = _rank_major_list(comm, _SHAPES)
    plan = bucketer.plan_buckets([g[0] for g in grads], 2048)
    assert any(lo > 0 for b in plan for _, lo, _ in b.pieces)
    out = bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=2048)
    assert isinstance(out, list) and len(out) == len(grads)
    for g, o in zip(grads, out):
        assert o.shape == g.shape and o.dtype == g.dtype
        assert o.sharding.is_equivalent_to(comm.rank_sharding(), o.ndim)
        want = np.broadcast_to(np.asarray(g).sum(0), g.shape)
        np.testing.assert_array_equal(np.asarray(o), want)


def test_repeat_sync_builds_no_plan_and_takes_the_lane():
    comm = _sub_comm()
    grads = _rank_major_list(comm, _SHAPES, seed=1)
    nb = len(bucketer.plan_buckets([g[0] for g in grads], 2048))
    c0 = _counts()
    bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=2048)
    c1 = _counts()
    assert c1["parallel_dp_plan_builds"] == c0["parallel_dp_plan_builds"] + 1
    assert c1["parallel_dp_bucket_dispatches"] \
        == c0["parallel_dp_bucket_dispatches"] + nb
    out = bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=2048)
    c2 = _counts()
    assert c2["parallel_dp_plan_builds"] == c1["parallel_dp_plan_builds"]
    assert c2["parallel_dp_bucket_dispatches"] \
        == c1["parallel_dp_bucket_dispatches"] + nb
    # every packed bucket lies on the rank sharding: each takes the lane
    assert c2["coll_allreduce_lane_hits"] \
        == c1["coll_allreduce_lane_hits"] + nb
    np.testing.assert_array_equal(np.asarray(out[0]), np.broadcast_to(
        np.asarray(grads[0]).sum(0), grads[0].shape))
    # another cap, or another structure, is another plan
    bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=4096)
    bucketer.allreduce_pytree(comm, grads[1:], "sum", bucket_bytes=2048)
    assert _counts()["parallel_dp_plan_builds"] \
        == c2["parallel_dp_plan_builds"] + 2


def test_allreduce_pytree_plans_without_reading_leaves(monkeypatch):
    """The plan comes from shapes and dtypes: no leaf is indexed or
    copied to plan, on a first call or a repeat."""
    comm = _sub_comm()
    grads = _rank_major_list(comm, _SHAPES, seed=2)

    def no_index(self, idx):
        raise AssertionError("a leaf was indexed to plan")

    monkeypatch.setattr(type(grads[0]), "__getitem__", no_index)
    for _ in range(2):
        out = bucketer.allreduce_pytree(comm, grads, "sum",
                                        bucket_bytes=2048)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(out[3]), np.broadcast_to(
        np.asarray(grads[3]).sum(0), grads[3].shape))


def test_allreduce_pytree_spans_nest_the_buckets():
    from ompi_tpu.trace import recorder

    comm = _sub_comm()
    grads = _rank_major_list(comm, _SHAPES, seed=3)
    nb = len(bucketer.plan_buckets([g[0] for g in grads], 2048))
    bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=2048)
    recorder.configure()
    try:
        bucketer.allreduce_pytree(comm, grads, "sum", bucket_bytes=2048)
        begins = [r for r in recorder.get().records() if r[2] == "B"]
    finally:
        recorder.configure()
    root = [r for r in begins if r[3] == "coll.allreduce_pytree"]
    assert len(root) == 1
    under = {}
    for r in begins:
        if r[6] == root[0][5]:
            under[r[3]] = under.get(r[3], 0) + 1
    assert under == {"coll.pack": 1, "coll.allreduce": nb, "coll.unpack": 1}
