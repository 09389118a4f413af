"""99th percentile (nearest rank) of the call-to-ready time (us) over
every call of the latency group."""

from perfbench import arith


def read(r):
    g = r.calls.group("lat")
    if g is None:
        return None
    return arith.percentile(list(g.t2 - g.t0), 0.99) * 1e6
