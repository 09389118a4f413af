"""Roofline share (%) of the op tier in the reduce group's traced slice:
3*S bytes per call (read inbuf and inoutbuf, write the result) over the
HBM peak, over the device time of the slice's ops, which run nothing
but the reduction."""

from perfbench import trace_reduce


def read(r):
    t = r.traces.get("reduce")
    if t is None or r.peaks is None:
        return None
    g = t["calls"].group("reduce")
    if g is None or not t["extract"]["devices"]:
        return None
    least = sum(3.0 * int(s) for s in g.nbytes) / (r.peaks["hbm_GBps"] * 1e9)
    busy = trace_reduce.mean_busy_s(t["extract"])
    if busy <= 0.0:
        return None
    return 100.0 * least / busy
