#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 perfbench/proof.py --workload imb_allreduce.4chip \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 4

In one process, on the chips of this machine: the cell at its own sizes
and load for a short window on each program seed, then with the control
(the cell's reference in the precision below, in the program's place)
on each control seed. Prints one JSON line per run with every number
the check compares, then the lower reading (the largest a sound run
gave) and the upper one (the smallest the control gave) of each. The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from perfbench import harness

    cell = harness.load_cell(harness.load_bench(), args.workload)
    devices = harness.chip_devices(cell.chips)
    reference = harness.load_module("references", cell.config["reference"])
    readings = {"program": {}, "control": {}}
    runs = [("program", s, None) for s in args.seeds]
    runs += [("control", s, reference.control) for s in args.control_seeds]
    for side, seed, call in runs:
        result = harness.run_cell(
            cell, seed, args.seconds, False, devices=devices,
            t_start=time.perf_counter(), call=call,
            work_dir=os.path.join(ROOT, ".bench_cache"),
            log=lambda line: None)
        checks = {k: c["value"] for k, c in result["checks"].items()}
        print(json.dumps({"side": side, "seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": checks}), flush=True)
        for k, v in checks.items():
            readings[side].setdefault(k, []).append(v)
    summary = {k: {"lower": max(readings["program"].get(k, [0.0])),
                   "upper": min(readings["control"].get(k, [0.0]))}
               for k in readings["program"]}
    print(json.dumps({"readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
