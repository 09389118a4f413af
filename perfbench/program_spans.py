#!/usr/bin/env python3
"""The program's own spans around the benchmark's calls.

ompi_tpu records a span at each layer boundary of a call
(``coll.allreduce`` over the whole ``Communicator`` call, ``coll.launch``
around handing the plan to JAX, ``coll.host_fetch`` / ``coll.host_put``
inside the host tier, ``op.reduce_local``) into its flight recorder,
stamped with ``perf_counter_ns``: the clock of the harness's ``Calls``.
While a profiler session records, the same spans are also host
annotations in the ``.xplane.pb``. Two readings:

- ``per_call(records, calls, group, root, need)``: the spans of each
  traced call of one group, from the recorder's records. It is pure, so
  it is tested on small recorded lists; ``traced`` applies it to the
  ring, in the process that ran the calls, for the metric readers.
- ``python3 perfbench/program_spans.py <trace dir>``: for each
  ``.xplane.pb`` under the directory, the first chip's idle time by the
  innermost program span open at each instant (under the harness's
  ``call:`` / ``wait:`` annotation that holds it), falling back to the
  harness label. One JSON line per trace.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from typing import Optional

#: Share of a group's traced calls whose spans the ring must still hold.
COVERED = 0.9
#: Host annotations that are program spans.
PROGRAM_PREFIXES = ("coll.", "op.", "pml.")


def ring_records() -> list:
    """The program's flight-recorder records, oldest first."""
    from ompi_tpu.trace import recorder

    return recorder.get().records()


def closed_spans(records) -> list[tuple]:
    """``(name, span_id, parent_id, t0_ns, t1_ns)`` of every span whose
    begin and end records are both in ``records``."""
    opened: dict = {}
    out = []
    for r in records:
        t_ns, ph, name, span, parent = r[1], r[2], r[3], r[5], r[6]
        if ph == "B":
            opened[span] = (name, parent, t_ns)
        elif ph == "E":
            begun = opened.pop(span, None)
            if begun is not None:
                out.append((begun[0], span, begun[1], begun[2], t_ns))
    return out


def per_call(records, calls, group: str, root: str,
             need: tuple = ()) -> Optional[list[dict]]:
    """For each call of ``group`` in ``calls`` (a harness ``Calls``,
    perf_counter seconds), the seconds of the ``root`` span lying inside
    the call's ``[t0, t1]`` and of every span under it, by name (spans of
    one name add up). A call counts where its root span and a span of
    each name in ``need`` are there. None where fewer than ``COVERED``
    of the group's calls count: the ring was lapped, or the program
    records no such spans."""
    g = calls.group(group)
    if g is None or not len(g.t0):
        return None
    spans = closed_spans(records)
    children: dict = {}
    for s in spans:
        children.setdefault(s[2], []).append(s)
    roots = sorted((s for s in spans if s[0] == root), key=lambda s: s[3])
    starts = [s[3] for s in roots]
    out = []
    for t0, t1 in zip(g.t0, g.t1):
        lo, hi = t0 * 1e9, t1 * 1e9
        i = bisect.bisect_left(starts, lo)
        if i == len(roots) or roots[i][4] > hi:
            continue
        found: dict = {}
        todo = [roots[i]]
        while todo:
            s = todo.pop()
            found[s[0]] = found.get(s[0], 0.0) + (s[4] - s[3]) * 1e-9
            todo.extend(children.get(s[1], ()))
        if all(n in found for n in need):
            out.append(found)
    if len(out) < COVERED * len(g.t0):
        return None
    return out


def traced(reading, group: str, root: str,
           need: tuple = ()) -> Optional[list[dict]]:
    """``per_call`` over the recorder's ring for the traced slice of
    ``group`` in a harness ``Reading``; None without that slice."""
    t = reading.traces.get(group)
    if t is None:
        return None
    return per_call(ring_records(), t["calls"], group, root, need)


# ---------------------------------------------------------------------------
# idle time by program span, from a profiler trace


def host_events(path: str) -> list[list]:
    """``[name, start_ns, dur_ns]`` of the host annotations of one
    ``.xplane.pb`` that are the harness's or the program's spans."""
    from jax.profiler import ProfileData

    from perfbench import trace_reduce

    keep = trace_reduce.HOST_PREFIXES + PROGRAM_PREFIXES
    return sorted(([e.name, float(e.start_ns), float(e.duration_ns)]
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(keep)), key=lambda e: e[1])


def segments(events) -> list[tuple]:
    """``(start, end, label)`` pieces of the time the events cover, each
    labelled ``outer > innermost`` by the outermost and the innermost
    event open in it (the event alone where one is open), and
    ``harness`` between events."""
    out: list = []
    stack: list = []  # (end, name), outermost first
    t = min((s for _, s, _ in events), default=0.0)

    def label() -> str:
        if not stack:
            return "harness"
        if len(stack) == 1:
            return stack[0][1]
        return f"{stack[0][1]} > {stack[-1][1]}"

    def advance(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            if stack[-1][0] > t:
                out.append((t, stack[-1][0], label()))
                t = stack[-1][0]
            stack.pop()
        if x > t:
            out.append((t, x, label()))
            t = x

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        advance(s)
        stack.append((s + d, name))
    advance(max((s + d for _, s, d in events), default=t))
    return out


def idle_by_span(device_events, events) -> list[list]:
    """Idle time (s) of one device inside the harness's annotated window,
    summed by the label of ``segments`` open at each instant, largest
    first."""
    from perfbench import trace_reduce

    window = [e for e in events if e[0].startswith(
        trace_reduce.HOST_PREFIXES)] or events
    if not window:
        return []
    lo = window[0][1]
    hi = max(s + d for _, s, d in window)
    busy = trace_reduce.merge((max(s, lo), min(s + d, hi))
                              for _, s, d in device_events
                              if s + d > lo and s < hi)
    idle, t = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    # the pieces cover the window without a hole ("harness" between
    # annotations), so every idle instant gets a label
    total: dict = {}
    segs = segments([e for e in events if lo <= e[1] < hi])
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            ns = min(e, b) - max(s, a)
            total[name] = total.get(name, 0.0) + ns * 1e-9
            k += 1
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: program_spans.py <trace dir>", file=sys.stderr)
        return 2
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench import trace_reduce

    paths = sorted(glob.glob(os.path.join(argv[0], "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    for path in paths:
        devices = trace_reduce.read_xplane(path)["devices"]
        chip0 = devices[sorted(devices)[0]] if devices else []
        print(json.dumps({"trace": os.path.relpath(path, argv[0]),
                          "idle_by_span": idle_by_span(
                              chip0, host_events(path))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
