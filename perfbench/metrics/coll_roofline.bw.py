"""Roofline share (%) of the collective in the bandwidth group's traced
slice: the least time of its calls over the device time of the
collective's ops.

A call's least time is the larger of its bus bytes per rank over the
chip's ICI peak and its HBM bytes (S read, S written) over the HBM
peak; on a v5e the ICI term bounds. The device time is, per chip, the
union of the ops whose names mark a collective, averaged over the
chips."""

from perfbench import arith, trace_reduce

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")


def read(r):
    t = r.traces.get("bw")
    if t is None or r.peaks is None:
        return None
    g = t["calls"].group("bw")
    if g is None:
        return None
    ici = r.peaks["ici_GBps"] * 1e9
    hbm = r.peaks["hbm_GBps"] * 1e9
    least = sum(max(arith.allreduce_bus_bytes(int(s), r.nranks) / ici,
                    2.0 * int(s) / hbm) for s in g.nbytes)
    busy = trace_reduce.mean_busy_s(
        t["extract"], trace_reduce.name_matcher(COLLECTIVE_OPS))
    if busy <= 0.0:
        return None
    return 100.0 * least / busy
