"""From a profiler trace to device busy time, op time and idle gaps.

Two stages. ``read_xplane`` turns one ``.xplane.pb`` into an *extract*,
a plain dict that JSON can hold::

    {"devices": {"/device:TPU:0": [[op_name, start_ns, dur_ns], ...], ...},
     "host": [[annotation, start_ns, dur_ns], ...]}

with the events of each device's "XLA Ops" line and the host's
``call:``/``wait:`` annotations that the harness puts around each timed
call. Every other function works on an extract, so it is tested on a
small recorded one without a chip.
"""

from __future__ import annotations

import bisect
import re
from typing import Callable, Iterable

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("call:", "wait:")


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES))
    host.sort(key=lambda ev: ev[1])
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _spans(events, match: Callable[[str], bool] | None = None):
    return [(s, s + d) for name, s, d in events
            if match is None or match(name)]


def busy_s(extract: dict, match: Callable[[str], bool] | None = None
           ) -> dict[str, float]:
    """Per device, seconds covered by at least one op (that ``match``
    accepts): nested or overlapping ops count once."""
    return {dev: sum(e - s for s, e in merge(_spans(evs, match))) * 1e-9
            for dev, evs in extract["devices"].items()}


def mean_busy_s(extract: dict, match=None) -> float:
    per = busy_s(extract, match)
    if not per:
        raise ValueError("the trace holds no device")
    return sum(per.values()) / len(per)


def idle_share(extract: dict, window_s: float) -> float:
    """1 - busy/window, averaged over the devices."""
    return 1.0 - mean_busy_s(extract) / window_s


def name_matcher(patterns: Iterable[str]) -> Callable[[str], bool]:
    pats = tuple(p.lower() for p in patterns)
    return lambda name: any(p in name.lower() for p in pats)


def short_name(name: str) -> str:
    """An XLA op's name without its operands and layouts:
    ``%add.1 = f32[4194304] add``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    rest = re.sub(r"\{[^}]*\}", "", rest)
    return f"{head} = {rest.split('(')[0]}"


def top_ops(extract: dict, n: int = 10) -> list[list]:
    """The ops that took most device time, seconds averaged over the
    devices."""
    total: dict[str, float] = {}
    for evs in extract["devices"].values():
        for name, _, dur in evs:
            key = short_name(name)
            total[key] = total.get(key, 0.0) + dur
    ndev = max(1, len(extract["devices"]))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / ndev] for name, ns in ranked]


def _attribute(host: list, starts: list, s: float, e: float,
               by_label: dict) -> None:
    """Share the idle span [s, e) out among the harness annotations that
    cover it (they do not nest); the rest is the harness's own."""
    def add(label, ns):
        by_label[label] = by_label.get(label, 0.0) + ns * 1e-9

    t = s
    for name, hs, hd in host[max(0, bisect.bisect_right(starts, s) - 1):]:
        if hs >= e:
            break
        a, b = max(hs, t), min(hs + hd, e)
        if b > a:
            if a > t:
                add("harness", a - t)
            add(name, b - a)
            t = b
    if e > t:
        add("harness", e - t)


def idle_gaps(extract: dict, n: int = 10) -> list[list]:
    """Idle time of the first device inside the annotated window, summed
    by the host annotation open at each instant of it: what the host
    was doing while the device waited."""
    devs = sorted(extract["devices"])
    host = extract["host"]
    if not devs or not host:
        return []
    lo = host[0][1]
    hi = max(s + d for _, s, d in host)
    busy = merge((max(s, lo), min(e, hi))
                 for s, e in _spans(extract["devices"][devs[0]])
                 if e > lo and s < hi)
    starts = [s for _, s, _ in host]
    by_label: dict[str, float] = {}
    t = lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            _attribute(host, starts, t, s, by_label)
        t = max(t, e)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[label, sec] for label, sec in ranked]
