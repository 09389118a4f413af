"""IMB-MPI1-style benchmark harness.

The reference delegates perf measurement to the Intel MPI Benchmarks
(reference: NEWS:249 lists IMB among the external suites; BASELINE.md's
target metric is "IMB-MPI1 Allreduce GB/s + p50 latency vs message size
4B-1GB"). This is that harness for ompi_tpu: sweep message sizes per
collective, report p50/min latency and effective bandwidth.

    python -m ompi_tpu.tools.imb --ops allreduce,bcast --max-bytes 4194304

Timing notes: each (op, size) is run `--iters` times after a warmup
call that triggers plan compilation; latency includes the full
framework dispatch path (what a user sees per call), so host dispatch
dominates small sizes — use bench.py's chained-iteration method for
pure device throughput.
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass

import numpy as np

OPS = ("allreduce", "bcast", "reduce", "allgather",
       "reduce_scatter_block", "alltoall", "barrier",
       "gather", "scatter", "scan", "exscan")


@dataclass
class Row:
    op: str
    nbytes: int
    p50_us: float
    min_us: float
    gbps: float


def _buffer(comm, op: str, nbytes: int):
    n = comm.size
    elems = max(1, nbytes // 4)
    if op in ("alltoall", "reduce_scatter_block"):
        data = np.ones((n, n, max(1, elems // n)), np.float32)
    else:
        data = np.ones((n, elems), np.float32)
    return comm.put_rank_major(data)


def _traffic_bytes(op: str, nbytes: int, n: int) -> float:
    """Algorithmic bus bytes per rank (IMB conventions)."""
    if op == "allreduce":
        return 2 * (n - 1) / n * nbytes
    if op in ("bcast", "reduce"):
        return nbytes
    if op in ("allgather", "alltoall"):
        return (n - 1) / n * nbytes
    if op == "reduce_scatter_block":
        return (n - 1) / n * nbytes
    if op in ("gather", "scatter", "scan", "exscan"):
        return nbytes
    return 0.0


def run_one(comm, op: str, nbytes: int, iters: int) -> Row:
    import jax

    x = None if op == "barrier" else _buffer(comm, op, nbytes)

    def call():
        if op == "barrier":
            comm.barrier()
            return None
        if op in ("gather", "scatter"):
            return getattr(comm, op)(x, root=0)
        return getattr(comm, op)(x)

    out = call()  # warmup/compile
    if out is not None:
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = call()
        if out is not None:
            jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    p50 = float(np.median(times))
    tmin = float(np.min(times))
    traffic = _traffic_bytes(op, nbytes, comm.size)
    gbps = traffic / tmin / 1e9 if traffic else 0.0
    return Row(op, nbytes, p50 * 1e6, tmin * 1e6, gbps)


def sweep(comm, ops, min_bytes: int, max_bytes: int, iters: int
          ) -> list[Row]:
    rows = []
    for op in ops:
        if op == "barrier":
            rows.append(run_one(comm, op, 0, iters))
            continue
        size = min_bytes
        while size <= max_bytes:
            rows.append(run_one(comm, op, size, iters))
            size *= 4
    return rows


def render(rows: list[Row]) -> str:
    lines = [
        f"{'op':>22} {'bytes':>12} {'p50 us':>10} {'min us':>10} "
        f"{'GB/s':>8}"
    ]
    for r in rows:
        lines.append(
            f"{r.op:>22} {r.nbytes:>12} {r.p50_us:>10.1f} "
            f"{r.min_us:>10.1f} {r.gbps:>8.2f}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ompi_tpu.tools.imb")
    ap.add_argument("--ops", default="allreduce,bcast,alltoall,barrier")
    ap.add_argument("--min-bytes", type=int, default=4)
    ap.add_argument("--max-bytes", type=int, default=1 << 22)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    ops = [o.strip() for o in args.ops.split(",") if o.strip()]
    bad = [o for o in ops if o not in OPS]
    if bad:
        raise SystemExit(f"unknown ops {bad}; known: {OPS}")

    import ompi_tpu

    comm = ompi_tpu.init()
    rows = sweep(comm, ops, args.min_bytes, args.max_bytes, args.iters)
    if args.json:
        print(json.dumps([r.__dict__ for r in rows]))
    else:
        print(f"# ompi_tpu IMB-style sweep, {comm.size} ranks")
        print(render(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
