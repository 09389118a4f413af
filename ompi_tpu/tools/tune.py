"""Auto-tuner: measure the algorithm space, emit a tuned rules file.

TPU-native equivalent of generating coll/tuned's dynamic-rules input
(reference: coll_tuned_dynamic_file.c consumes rules files that HPC
sites produce by sweeping; the fixed rules in
coll_tuned_decision_fixed.c:45-87 are the shipped defaults). This tool
closes the loop on-device: time every registered algorithm per
(operation, message size) on the actual hardware, pick winners, and
write the JSON that `coll_tuned_rules_file` consumes — per-system
tuning without touching code.

    python -m ompi_tpu.tools.tune --out rules.json --max-bytes 1048576
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable

import numpy as np


def _time_plan(comm, key: tuple, per_rank: Callable, x, iters: int,
               check_vma: bool = True) -> float:
    import jax

    from ..coll.framework import compile_plan

    plan = compile_plan(comm, key, per_rank, check_vma=check_vma)
    jax.block_until_ready(plan(x))  # warmup/compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(plan(x))
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_op(comm, opname: str, algos: dict, min_bytes: int,
             max_bytes: int, iters: int) -> list[dict]:
    """Time each algorithm per size; return winner rules sorted by
    size band (first-match format of coll/tuned's Rules)."""
    from ..coll.tuned import is_pallas_algo
    from ..ops import lookup as op_lookup

    op = op_lookup("sum")
    n = comm.size
    winners: list[tuple[int, str, dict]] = []
    size = min_bytes
    while size <= max_bytes:
        elems = max(1, size // 4)
        if opname in ("alltoall", "reduce_scatter", "scatter"):
            # Per-destination layout (ranks, dests/rows, chunk). The
            # decide_* functions for these ops consult rules with the
            # PER-CHUNK byte count, so the emitted band must be keyed
            # by the chunk size actually measured — not the total —
            # or the rules would select winners measured at n-times-
            # larger messages.
            chunk = max(1, elems // n)
            data = np.ones((n, n, chunk), np.float32)
            band = chunk * 4
        else:
            data = np.ones((n, elems), np.float32)
            band = size
        x = comm.put_rank_major(data)
        times = {}
        for name, fn in algos.items():
            key = ("tune", opname, name, x.shape, str(x.dtype))
            try:
                if opname in ("allreduce", "reduce_scatter", "scan",
                              "exscan"):
                    per_rank = lambda b, f=fn: f(b, "ranks", op)
                elif opname == "reduce":
                    per_rank = lambda b, f=fn: f(b, "ranks", op, root=0)
                elif opname in ("bcast", "gather", "scatter"):
                    per_rank = lambda b, f=fn: f(b, "ranks", root=0)
                else:
                    per_rank = lambda b, f=fn: f(b, "ranks")
                times[name] = _time_plan(
                    comm, key, per_rank, x, iters,
                    check_vma=not is_pallas_algo(name),
                )
            except Exception:
                continue  # algorithm invalid for this shape/rank count
        if times:
            best = min(times, key=times.get)
            winners.append((band, best, times))
        size *= 4
    # collapse consecutive same-winner bands into max_bytes rules
    rules: list[dict] = []
    for size, best, times in winners:
        if rules and rules[-1]["algorithm"] == best:
            rules[-1]["max_bytes"] = size
        else:
            rules.append({"max_bytes": size, "algorithm": best})
    if rules:
        del rules[-1]["max_bytes"]  # last band is open-ended
    return rules


def tune(comm, ops=None, min_bytes: int = 256,
         max_bytes: int = 1 << 20, iters: int = 5) -> dict:
    from ..coll.tuned import (
        ALLGATHER_ALGOS,
        ALLREDUCE_ALGOS,
        ALLTOALL_ALGOS,
        BCAST_ALGOS,
        GATHER_ALGOS,
        REDUCE_ALGOS,
        REDUCE_SCATTER_ALGOS,
        SCAN_ALGOS,
        EXSCAN_ALGOS,
        SCATTER_ALGOS,
        _pallas_algos,
        is_pallas_algo,
    )

    import jax

    # pallas-vs-xla selection from measurement, on a TPU only: on any
    # other backend the Mosaic kernels run in interpret mode, whose
    # timings measure the emulator and not the chip.
    _pallas_algos()
    on_tpu = jax.default_backend() == "tpu"

    def space(algos, drop=()):
        return {k: v for k, v in algos.items()
                if k not in drop and (on_tpu or not is_pallas_algo(k))}

    spaces = {
        "allreduce": space(ALLREDUCE_ALGOS,
                           ("gather_reduce", "ring_segmented")),
        "allgather": space(ALLGATHER_ALGOS),
        "alltoall": space(ALLTOALL_ALGOS),
        "bcast": space(BCAST_ALGOS),
        "reduce": space(REDUCE_ALGOS),
        "reduce_scatter": space(REDUCE_SCATTER_ALGOS),
        "gather": space(GATHER_ALGOS),
        "scatter": space(SCATTER_ALGOS),
        "scan": space(SCAN_ALGOS),
        "exscan": space(EXSCAN_ALGOS),
    }
    ops = ops or list(spaces)
    out = {}
    for opname in ops:
        out[opname] = sweep_op(
            comm, opname, spaces[opname], min_bytes, max_bytes, iters
        )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ompi_tpu.tools.tune")
    ap.add_argument("--out", required=True)
    ap.add_argument("--ops", default="allreduce,allgather,alltoall,bcast,"
                                     "reduce,reduce_scatter,gather,"
                                     "scatter,scan,exscan")
    ap.add_argument("--min-bytes", type=int, default=256)
    ap.add_argument("--max-bytes", type=int, default=1 << 20)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)

    import ompi_tpu

    comm = ompi_tpu.init()
    rules = tune(
        comm, [o.strip() for o in args.ops.split(",")],
        args.min_bytes, args.max_bytes, args.iters,
    )
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(rules, f, indent=2)
    print(f"wrote {args.out}; activate with "
          f"OMPITPU_MCA_coll_tuned_rules_file={args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
