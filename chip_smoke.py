#!/usr/bin/env python3
"""Chip smoke: drive the communicator's collective path once on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the cross-chip path on four chips

One chip: ``ompi_tpu.init()`` -> ``ops.reduce_ranks`` over 8 rank blocks
of 64 MiB (the op tier of every reducing collective), the communicator
entry points at 4 B, 1 MiB and 256 MiB, and the one-chip Pallas kernel
(the self-DMA variant of the chunked ring) at 64 MiB, each checked
against numpy or against its input; then the health supervisor's
``device_pallas`` canary, which must compile and pass.

Four chips: ``comm.allreduce`` at 4 KiB, 1 MiB and 64 MiB per rank in
f32 and bf16 with the default selection and with each Pallas allreduce
kernel forced (``pallas_ring_chunked``, ``pallas_ring``,
``sched_pallas_ring``, ``pallas_bidir``, ``pallas_rd``, ``pallas_rsag``
and the int8-wire ``quant_pallas``), plus ``reduce_scatter_block`` and
``allgather``, each compared with a jitted ``shard_map`` reference
(``lax.psum`` and friends; ``quant_pallas`` within quant's analytic
error bound).

The circuit breaker is off throughout, so a tier fault raises instead
of degrading, and the run requires zero tier fallbacks. Everything runs
in this one process. The phase timings printed along the way are smoke
timings, not metrics. The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import threading
import time

MiB = 1 << 20
SEED = 0  # PRNG seed of every phase's random inputs
# seconds before the watchdog exits with code 124 (a wedged collective)
WATCHDOG_S = 1100.0


def _log(msg: str) -> None:
    print(msg, flush=True)


class SmokeError(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def _watchdog(seconds: float) -> None:
    """Exit hard when the run outlives its budget: a wedged collective
    must not hold the chip."""
    def fire():
        print(f"chip_smoke: watchdog fired after {seconds:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(124)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


class Phases:
    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def run(self, name: str, fn, *args):
        t = time.perf_counter()
        _log(f"phase {name}: start")
        out = fn(*args)
        _log(f"smoke timing: {name} {time.perf_counter() - t:.3f} s "
             "(wall, compile included; not a metric)")
        return out


def _versions() -> None:
    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    _log(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
         f"libtpu {libtpu}")


def _on_tpu(x) -> bool:
    return all(d.platform == "tpu" for d in x.devices())


def _sample(x, stride: int):
    """Host copy of every ``stride``-th element of the last axis."""
    import numpy as np

    return np.asarray(x[..., ::stride]).astype(np.float64)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def _reduce_ranks_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu import ops

    ranks = 8
    key = jax.random.PRNGKey(SEED)
    cases = (("sum", jnp.float32), ("max", jnp.int32),
             ("sum", jnp.bfloat16))
    for op, dtype in cases:
        elems = 64 * MiB // jnp.dtype(dtype).itemsize
        make = jax.jit(lambda k, d=dtype, e=elems: (
            jax.random.randint(k, (ranks, e), -1000, 1000).astype(d)
            if jnp.issubdtype(d, jnp.integer)
            else jax.random.normal(k, (ranks, e), jnp.float32).astype(d)))
        x = make(key)
        out = jax.block_until_ready(ops.reduce_ranks(x, op))
        _check(out.shape == (elems,) and out.dtype == dtype,
               f"reduce_ranks {op} {dtype}: shape {out.shape} {out.dtype}")
        _check(_on_tpu(out), f"reduce_ranks {op}: result left the TPU")
        xs, got = _sample(x, 4099), _sample(out, 4099)
        if op == "max":
            _check(np.array_equal(got, xs.max(axis=0)),
                   "reduce_ranks max int32 != numpy")
        else:
            # one rounding per partial sum of 8 terms, in the dtype
            eps = float(jnp.finfo(dtype).eps)
            tol = 8 * eps * np.abs(xs).sum(axis=0) + 1e-30
            err = np.abs(got - xs.sum(axis=0))
            _check(bool((err <= tol).all()),
                   f"reduce_ranks sum {jnp.dtype(dtype).name}: max err "
                   f"{err.max():.3g} over tolerance")
        _log(f"  reduce_ranks {op} {jnp.dtype(dtype).name} 8 x 64 MiB: ok")
        del x, out


def _entry_points_phase(comm) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.PRNGKey(SEED + 1)
    for nbytes in (4, MiB, 256 * MiB):
        elems = nbytes // 4
        x = jax.jit(lambda k, e=elems: jax.random.normal(
            k, (comm.size, e), jnp.float32),
            out_shardings=comm.rank_sharding())(key)
        stride = max(1, elems // 4096)
        want = _sample(x, stride)
        calls = (
            ("allreduce", lambda: comm.allreduce(x, "sum"), want),
            ("bcast", lambda: comm.bcast(x, root=0), want),
            ("allgather", lambda: comm.allgather(x), want[:, None]),
            ("reduce_scatter_block",
             lambda: comm.reduce_scatter_block(x[:, None], "sum"), want),
        )
        for name, call, ref in calls:
            out = jax.block_until_ready(call())
            _check(_on_tpu(out), f"{name} {nbytes} B: result left the TPU")
            got = _sample(out, stride)
            _check(got.shape == ref.shape and np.array_equal(got, ref),
                   f"{name} {nbytes} B: wrong result {got.shape}")
            del out
        _log(f"  comm allreduce/bcast/allgather/reduce_scatter_block "
             f"{nbytes} B: ok")
        del x


def _selfdma_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ompi_tpu.coll import pallas_ring

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    fn = jax.jit(jax.shard_map(
        lambda b: pallas_ring.ring_allreduce_chunked(b[0], "x")[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    elems = 64 * MiB // 4
    x = jax.random.normal(jax.random.PRNGKey(SEED + 2), (1, elems),
                          jnp.float32)
    lowered = fn.lower(x)
    _check("tpu_custom_call" in lowered.as_text(),
           "self-DMA kernel: no tpu_custom_call in the lowered program")
    out = jax.block_until_ready(lowered.compile()(x))
    _check(_on_tpu(out), "self-DMA kernel: result left the TPU")
    _check(bool(jnp.array_equal(out, x)),
           "self-DMA kernel: output != input")
    _log("  pallas self-DMA chunked ring 64 MiB: ok (tpu_custom_call)")


def _canary_phase() -> None:
    """The health supervisor's device_pallas canary: on a TPU it must
    be the compiled kernel, not the CPU simulation, and it must pass."""
    from ompi_tpu.core.counters import SPC
    from ompi_tpu.health import prober

    prober.ensure_builtin_probes()
    desc = prober.probes().get("device_pallas", "")
    _check("self-DMA" in desc, f"device_pallas canary is {desc!r}")
    _check(prober.probe_tier("device_pallas"),
           "device_pallas canary failed")
    _check(SPC.snapshot().get("health_device_pallas_simulated", 0) == 0,
           "device_pallas canary simulated on a TPU")
    _log("  health device_pallas canary (compiled self-DMA kernel): ok")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _spans_devices(x, n: int) -> bool:
    return len({s.device.id for s in x.addressable_shards}) == n \
        and len(x.devices()) == n


def _allreduce_4chip_phase(comm) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.coll import quant
    from ompi_tpu.core import config

    n = comm.size
    mesh = comm.mesh
    spec = comm.rank_sharding()
    psum = jax.jit(jax.shard_map(lambda b: jax.lax.psum(b, "ranks"),
                                 mesh=mesh, in_specs=P("ranks"),
                                 out_specs=P("ranks")))
    algos = ("", "pallas_ring_chunked", "pallas_ring", "sched_pallas_ring",
             "pallas_bidir", "pallas_rd", "pallas_rsag", "quant_pallas")
    key = jax.random.PRNGKey(SEED + 3)
    for nbytes in (4 << 10, MiB, 64 * MiB):
        for dtype in (jnp.float32, jnp.bfloat16):
            elems = nbytes // jnp.dtype(dtype).itemsize
            if dtype == jnp.float32:
                # integer-valued: every summation order is exact
                make = lambda k, e=elems: jax.random.randint(
                    k, (n, e), -64, 64).astype(jnp.float32)
            else:
                make = lambda k, e=elems: jax.random.normal(
                    k, (n, e), jnp.float32).astype(jnp.bfloat16)
            x = jax.jit(make, out_shardings=spec)(key)
            _check(_spans_devices(x, n), "input does not span the chips")
            ref = psum(x)
            # bf16 tolerance: each of the n-1 partial sums rounds once
            # to bf16, i.e. at most n * 2^-8 of the sum of magnitudes.
            tol = (n * 2.0 ** -8) * jnp.sum(
                jnp.abs(x.astype(jnp.float32)), axis=0)
            if dtype == jnp.float32:
                tol = jnp.zeros_like(tol)
            # the int8 wire is lossy: quant's own worst-case bound, plus
            # the bf16 rounding of its result and of the reference
            qtol = quant.analytic_error_bound(x, wire="int8") + 2 * tol
            for algo in algos:
                config.set("coll_tuned_allreduce_algorithm", algo)
                try:
                    out = jax.block_until_ready(comm.allreduce(x, "sum"))
                finally:
                    config.set("coll_tuned_allreduce_algorithm", "")
                label = (f"allreduce[{algo or 'default'}] "
                         f"{nbytes} B/rank {jnp.dtype(dtype).name}")
                _check(out.shape == x.shape and out.dtype == x.dtype,
                       f"{label}: shape {out.shape} {out.dtype}")
                _check(_spans_devices(out, n),
                       f"{label}: output does not span the chips")
                err = jnp.abs(out.astype(jnp.float32)
                              - ref.astype(jnp.float32))
                bound = qtol if algo == "quant_pallas" else tol
                _check(bool(jnp.all(err <= bound[None])),
                       f"{label}: differs from lax.psum (max err "
                       f"{float(jnp.max(err))})")
                _log(f"  {label}: matches lax.psum"
                     + (" within quant's bound"
                        if algo == "quant_pallas" else ""))
                del out, err
            del x, ref, tol, qtol


def _rs_ag_4chip_phase(comm) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = comm.size
    mesh = comm.mesh
    spec = comm.rank_sharding()
    rs_ref = jax.jit(jax.shard_map(
        lambda b: jax.lax.psum_scatter(b[0], "ranks", scatter_dimension=0,
                                       tiled=True),
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks")))
    ag_ref = jax.jit(jax.shard_map(
        lambda b: jax.lax.all_gather(b[0], "ranks")[None],
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks")))
    key = jax.random.PRNGKey(SEED + 4)
    for nbytes in (4 << 10, MiB, 64 * MiB):
        elems = nbytes // 4
        x = jax.jit(lambda k, e=elems: jax.random.randint(
            k, (n, n, e // n), -64, 64).astype(jnp.float32),
            out_shardings=spec)(key)
        out = jax.block_until_ready(comm.reduce_scatter_block(x, "sum"))
        ref = rs_ref(x)
        _check(out.shape == ref.shape and bool(jnp.array_equal(out, ref)),
               f"reduce_scatter_block {nbytes} B/rank: differs from "
               f"lax.psum_scatter ({out.shape} vs {ref.shape})")
        _check(_spans_devices(out, n),
               "reduce_scatter_block: output does not span the chips")
        del x, out, ref
        y = jax.jit(lambda k, e=elems: jax.random.randint(
            k, (n, e), -64, 64).astype(jnp.float32),
            out_shardings=spec)(key)
        out = jax.block_until_ready(comm.allgather(y))
        ref = ag_ref(y)
        _check(out.shape == ref.shape and bool(jnp.array_equal(out, ref)),
               f"allgather {nbytes} B/rank: differs from lax.all_gather "
               f"({out.shape} vs {ref.shape})")
        _check(_spans_devices(out, n),
               "allgather: output does not span the chips")
        _log(f"  reduce_scatter_block + allgather {nbytes} B/rank: "
             "match lax.psum_scatter / lax.all_gather")
        del y, out, ref


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the cross-chip "
                    "path and its references only")
    args = ap.parse_args(argv)
    _watchdog(WATCHDOG_S)

    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "ompi_tpu")):
        print("chip_smoke: the ompi_tpu package is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, here)

    from ompi_tpu.core import compile_cache

    _log(f"compile cache: {compile_cache.enable()}")
    _versions()
    import jax

    devs = jax.devices()
    dev = devs[0]
    _log(f"platform {dev.platform}  device_kind {dev.device_kind}  "
         f"device count {len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 3

    import ompi_tpu
    from ompi_tpu.coll import breaker, pallas_ring
    from ompi_tpu.core import config
    from ompi_tpu.core.counters import SPC
    from ompi_tpu.native import build

    if pallas_ring._interpret():
        print("chip_smoke: coll_pallas_interpret is set; the kernels "
              "must run compiled", file=sys.stderr)
        return 3
    _log("native library: "
         + ("built" if build.get_lib() is not None else "NOT built"))
    config.set("coll_breaker_enable", False)
    _check(not breaker.enabled(), "the circuit breaker is still on")

    phases = Phases()
    comm = phases.run("init", ompi_tpu.init)
    _check(comm.size == len(devs), f"world size {comm.size}")
    if args.chips == 1:
        phases.run("reduce_ranks", _reduce_ranks_phase)
        phases.run("comm entry points", _entry_points_phase, comm)
        phases.run("pallas self-DMA", _selfdma_phase)
        phases.run("device_pallas canary", _canary_phase)
    else:
        phases.run("allreduce 4-chip", _allreduce_4chip_phase, comm)
        phases.run("reduce_scatter/allgather 4-chip", _rs_ag_4chip_phase,
                   comm)
    fallbacks = SPC.snapshot().get("coll_tier_fallbacks", 0)
    _check(fallbacks == 0, f"{fallbacks} tier fallbacks")
    _log(f"tier fallbacks: {fallbacks}")
    _log(f"smoke timing: total {time.perf_counter() - phases.t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SmokeError as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        rc = 1
    sys.stdout.flush()
    os._exit(rc)
