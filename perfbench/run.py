#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 perfbench/run.py --workload imb_allreduce.4chip --seed 7 \
        --seconds 10 --trace 0

From the root of a checkout. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics (the same window, then a
short profiled slice of each traffic group). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit. The lines before it
say which tier ran, how many compilations fell inside the window and
how many tier fallbacks there were.

Exit codes: 0 with a result; 2 when a file the cell needs is missing;
3 without a TPU or with fewer chips than the cell asks for; 124 when
the run outlives its watchdog. Caches stay inside the checkout, in
``.bench_cache/`` (JAX's persistent compilation cache, unless
``JAX_COMPILATION_CACHE_DIR`` names one, and the collective schedule
cache, emptied at each start so no run steers the next).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WATCHDOG_S = 1150.0  # a first run compiles; a wedged chip must not hang


def _watchdog(seconds: float) -> None:
    def fire():
        print(f"perfbench: watchdog fired after {seconds:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(124)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    _watchdog(WATCHDOG_S)

    sys.path.insert(0, ROOT)
    cache = os.path.join(ROOT, ".bench_cache")
    sched = os.path.join(cache, "sched")
    shutil.rmtree(sched, ignore_errors=True)
    os.makedirs(sched)
    os.environ["OMPI_TPU_SCHED_CACHE"] = sched
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from perfbench import harness

    try:
        cell = harness.load_cell(harness.load_bench(), args.workload)
    except (harness.BenchError, OSError, KeyError) as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(cache, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.BenchError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 3
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices=devices,
                              t_start=T_START,
                              work_dir=cache)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:  # e.g. the program under test is missing
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # no library thread may keep the chip past the result
