"""One run of one benchmark cell: set-up, a measured window, the check,
and one result line.

Everything particular to a cell is data or a small file found by name:

- ``BENCHMARK.json`` names each workload's configuration and traffic mix;
- ``configs/<config>.json``: the deployment (op, dtype, value bound,
  the limit of the check) and the names of its ``entry`` driver and its
  ``reference``;
- ``traffic/<traffic>.json``: groups of message sizes, each with its
  share of the window, its order (``interleave``: rounds of every size
  in a seeded order; ``slices``: one time slice per size, in a seeded
  order), the number of input buffers per size, how many answers per
  size are kept for the check, and the seconds of its traced slice;
- ``entries/<entry>.py``: ``open(env)`` returns a target with
  ``make(key, nbytes)`` (one input, traced into the one jitted call
  that makes every input of the run on the device), ``call(buf)``
  (the timed call), ``next(buf, out)`` (the next call's input) and
  ``counters()``;
- ``references/<reference>.py``: ``gap(buf, out)``, the largest
  deviation of an answer from the plain reference, and ``control(buf)``,
  the reference in the precision below, to stand in the program's place;
- ``metrics/<metric>.py``: ``read(reading)`` returns the metric's value
  from a :class:`Reading`, or None where there is nothing to read.

A later cell, mix, entry or metric is new files plus new entries in
``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The cell cannot be run here: no chip, or a file it names is
    missing."""


# ---------------------------------------------------------------------------
# finding a cell's files


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_bench() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(bench: dict, name: str) -> Cell:
    workloads = {w["name"]: w for w in bench["workloads"]}
    if name not in workloads:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    wl = workloads[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = dict(load_json(os.path.join(ROOT, configs[wl["config"]]["file"])))
    config["name"] = wl["config"]
    traffic = load_json(os.path.join(HERE, "traffic", f"{wl['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, int(wl["chips"]), config, traffic, e2e, per_layer)


def chip_devices(chips: int) -> list:
    """The first ``chips`` accelerator devices; no accelerator, or too
    few, is a BenchError (the run prints no result)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# what a metric reader reads


@dataclass
class Group:
    """Host-clock record of one group's calls: ``t0`` the call, ``t1``
    its return, ``t2`` its result ready (perf_counter seconds)."""
    nbytes: np.ndarray
    t0: np.ndarray
    t1: np.ndarray
    t2: np.ndarray


@dataclass
class Calls:
    rows: list = field(default_factory=list)  # (group, nbytes, t0, t1, t2)

    def add(self, group: str, nbytes: int, t0: float, t1: float,
            t2: float) -> None:
        self.rows.append((group, nbytes, t0, t1, t2))

    def group(self, name: str) -> Optional[Group]:
        rows = [r for r in self.rows if r[0] == name]
        if not rows:
            return None
        cols = list(zip(*rows))
        return Group(np.asarray(cols[1], dtype=np.int64),
                     *(np.asarray(c, dtype=np.float64) for c in cols[2:]))


@dataclass
class Reading:
    """Everything a metric reader may use from one run."""
    nranks: int
    setup_s: float
    calls: Calls                  # the measured window
    traces: dict                  # group -> {"extract", "window_s", "calls"}
    peaks: Optional[dict]         # perfbench.peaks entry of the device


@dataclass
class Env:
    devices: list                 # the cell's chips, in rank order
    config: dict


# ---------------------------------------------------------------------------
# compilations inside the window


class _CompileCount:
    """Counts compilations and compile-cache loads while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self) -> None:
        self.armed = False
        self.count = 0
        import jax.monitoring as mon

        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, *args, **kw) -> None:
        if self.armed and name in self.EVENTS:
            self.count += 1

    def _duration(self, name, secs, *args, **kw) -> None:
        self._event(name)


_compiles: Optional[_CompileCount] = None


def _compile_counter() -> _CompileCount:
    global _compiles
    if _compiles is None:
        _compiles = _CompileCount()
    return _compiles


# ---------------------------------------------------------------------------
# the window


class _Driver:
    """Drives the timed call through a traffic group's schedule."""

    def __init__(self, target, call: Callable, slots: dict, rng) -> None:
        self.target = target
        self.call = call
        self.slots = slots            # (group, nbytes) -> [buf, ...]
        self.rng = rng
        self.attempted = 0
        self.failed = 0
        self.first_error: Optional[str] = None
        self.kept: dict = {}          # (group, nbytes) -> [(buf, out)]
        self.last: dict = {}          # (group, nbytes) -> (buf, out)
        self.want: dict = {}          # (group, nbytes) -> call indices kept

    def warm_up(self) -> None:
        """Each buffer of each size through the timed call twice, so
        that everything the window runs is compiled. A call that raises
        counts as failed."""
        import jax

        for bufs in self.slots.values():
            for _ in range(2):
                for b, buf in enumerate(bufs):
                    self.attempted += 1
                    try:
                        out = jax.block_until_ready(self.call(buf))
                    except Exception as exc:  # counted, not fatal
                        self._failure(exc)
                        continue
                    bufs[b] = self.target.next(buf, out)

    def _failure(self, exc: Exception) -> None:
        self.failed += 1
        if self.first_error is None:
            self.first_error = f"{type(exc).__name__}: {exc}"

    def plan_samples(self, group: dict) -> None:
        below = int(group["sample_below"])
        k = max(0, int(group["samples"]) - 1)
        for nbytes in group["sizes"]:
            idx = self.rng.choice(below, size=min(k, below), replace=False)
            self.want[(group["name"], nbytes)] = {int(i) for i in idx}

    def drive(self, group: dict, seconds: float, calls: Calls) -> dict:
        """Run the group for ``seconds``; returns calls made per size."""
        import jax
        from jax.profiler import TraceAnnotation

        name = group["name"]
        sizes = [int(s) for s in group["sizes"]]
        counts = {s: 0 for s in sizes}
        labels = {s: (f"call:{name}.{s}B", f"wait:{name}.{s}B")
                  for s in sizes}
        perf = time.perf_counter

        def one(nbytes: int) -> None:
            key = (name, nbytes)
            bufs = self.slots[key]
            i = counts[nbytes]
            counts[nbytes] = i + 1
            self.attempted += 1
            buf = bufs[i % len(bufs)]
            call_label, wait_label = labels[nbytes]
            try:
                t0 = perf()
                with TraceAnnotation(call_label):
                    out = self.call(buf)
                t1 = perf()
                with TraceAnnotation(wait_label):
                    jax.block_until_ready(out)
                t2 = perf()
            except Exception as exc:  # a failed call is counted, not fatal
                self._failure(exc)
                return
            calls.add(name, nbytes, t0, t1, t2)
            bufs[i % len(bufs)] = self.target.next(buf, out)
            if i in self.want.get(key, ()):
                self.kept.setdefault(key, []).append((buf, out))
            self.last[key] = (buf, out)

        order = [int(s) for s in self.rng.permutation(sizes)]
        if group["order"] == "interleave":
            t_end = perf() + seconds
            while perf() < t_end:
                for nbytes in order:
                    one(nbytes)
                order = [int(s) for s in self.rng.permutation(sizes)]
        elif group["order"] == "slices":
            per = seconds / len(sizes)
            for nbytes in order:
                t_stop = perf() + per
                one(nbytes)
                while perf() < t_stop:
                    one(nbytes)
        else:
            raise BenchError(f"unknown order {group['order']!r}")
        return counts


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _trace_group(driver: _Driver, group: dict, work_dir: str) -> dict:
    import jax

    from . import trace_reduce

    out_dir = os.path.join(work_dir, "trace", group["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    calls = Calls()
    jax.profiler.start_trace(out_dir)
    try:
        t0 = time.perf_counter()
        driver.drive(group, float(group["trace_seconds"]), calls)
        window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise BenchError(f"the profiler wrote no trace under {out_dir}")
    extract = trace_reduce.read_xplane(max(paths, key=os.path.getmtime))
    return {"extract": extract, "window_s": window_s, "calls": calls}


def _counter_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _check(driver: _Driver, groups: list, reference, limit: float) -> dict:
    """Per size, the largest gap of its kept answers from the reference
    (inf where no call of that size answered), beside ``limit``."""
    checks = {}
    for g in groups:
        for nbytes in g["sizes"]:
            key = (g["name"], int(nbytes))
            answers = driver.kept.pop(key, [])
            if key in driver.last:
                answers.append(driver.last.pop(key))
            gaps = [float(reference.gap(b, o)) for b, o in answers]
            del answers
            checks[f"gap.{g['name']}.{int(nbytes)}B"] = (
                max(gaps) if gaps else math.inf, limit)
    return checks


def _breakdown(traces: dict) -> dict:
    """The ops with most device time over every traced slice, and the
    idle time of the first chip by what the host was doing."""
    from . import trace_reduce

    gaps: dict = {}
    merged: dict = {"devices": {}, "host": []}
    for t in traces.values():
        for label, sec in trace_reduce.idle_gaps(t["extract"], 10**6):
            gaps[label] = gaps.get(label, 0.0) + sec
        for dev, evs in t["extract"]["devices"].items():
            merged["devices"].setdefault(dev, []).extend(evs)
    return {"device_ops": trace_reduce.top_ops(merged, 10),
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:10]]}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices: list, t_start: float,
             call: Optional[Callable] = None,
             work_dir: Optional[str] = None,
             log: Callable[[str], None] = print) -> dict:
    """Run ``cell`` once and return its result object.

    ``call`` replaces the entry's timed call (the control, or a fault
    planted by a test); ``log`` prints the lines before the result.
    """
    import jax

    from . import inputs, peaks, trace_reduce

    t_enter = time.perf_counter()
    work_dir = work_dir or os.path.join(ROOT, ".bench_cache")
    env = Env(list(devices), cell.config)
    entry = load_module("entries", cell.config["entry"])
    reference = load_module("references", cell.config["reference"])
    metrics = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: load_module("metrics", m["name"]) for m in metrics}
    kind = env.devices[0].device_kind
    peak = peaks.peaks_for(kind) if env.devices[0].platform == "tpu" \
        else None

    target = entry.open(env)
    t_open = time.perf_counter()
    rng = np.random.default_rng(seed)
    groups = cell.traffic["groups"]
    plan = [((gi, si, b), int(nbytes))
            for gi, g in enumerate(groups)
            for si, nbytes in enumerate(g["sizes"])
            for b in range(int(g["buffers"]))]
    slots: dict = {}  # (group, nbytes) -> [buf, ...]
    made = inputs.make_all(target.make, inputs.base_key(seed), plan)
    for ((gi, _, _), nbytes), buf in zip(plan, made):
        slots.setdefault((groups[gi]["name"], nbytes), []).append(buf)
    del made
    t_inputs = time.perf_counter()
    driver = _Driver(target, call or target.call, slots, rng)
    driver.warm_up()
    t_warm = time.perf_counter()
    for g in groups:
        driver.plan_samples(g)
    compiles = _compile_counter()
    before = target.counters()
    calls = Calls()
    setup_s = time.perf_counter() - t_start
    compiles.count, compiles.armed = 0, True
    counts = {}
    for g in groups:
        counts[g["name"]] = driver.drive(
            g, seconds * float(g["share"]), calls)
    compiles.armed = False
    delta = _counter_delta(before, target.counters())
    memory_peak = _memory_peak(env.devices)

    traces = {}
    if trace:
        for g in groups:
            if g.get("trace_seconds"):
                traces[g["name"]] = _trace_group(driver, g, work_dir)

    # the check, after the window, with the inputs no answer needs freed
    del slots
    driver.slots = None
    t_check = time.perf_counter()
    checks = _check(driver, groups, reference, float(cell.config["limit"]))
    t_check = time.perf_counter() - t_check
    fallbacks = int(delta.get("coll_tier_fallbacks", 0))
    checks["failed_calls"] = (float(driver.failed), 0.0)
    checks["tier_fallbacks"] = (float(fallbacks), 0.0)
    attempted = driver.attempted
    correct = attempted > 0 and all(
        v <= lim for v, lim in checks.values())

    log(f"set-up: start to harness {t_enter - t_start:.3f} s, entry "
        f"open {t_open - t_enter:.3f} s, inputs {t_inputs - t_open:.3f} s,"
        f" warm-up {t_warm - t_inputs:.3f} s")
    log(f"calls per size: {json.dumps(counts, sort_keys=True)}")
    log("tier selection in the window: " + json.dumps(
        {k: v for k, v in sorted(delta.items())
         if k.startswith("coll_allreduce_algo_")}))
    log(f"compilations in the window: {compiles.count}")
    log(f"tier fallbacks: {fallbacks}")
    log(f"check against the reference: {t_check:.3f} s")
    if driver.first_error:
        log(f"first failed call: {driver.first_error}")

    reading = Reading(nranks=cell.chips, setup_s=setup_s, calls=calls,
                      traces=traces, peaks=peak)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(reading)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    dev0 = env.devices[0]
    device = {"platform": dev0.platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": driver.failed + fallbacks,
              "metrics": values, "device": device}
    traced = [t for t in traces.values() if t["extract"]["devices"]]
    if traced:
        device["busy_s"] = sum(trace_reduce.mean_busy_s(t["extract"])
                               for t in traced)
        device["window_s"] = sum(t["window_s"] for t in traced)
    if trace and traces:
        result["breakdown"] = _breakdown(traces)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """Each number compared beside its limit as the last lines on
    standard error, then the result as the last line on standard output."""
    for name, c in result["checks"].items():
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])  # JSON has no inf or nan
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
