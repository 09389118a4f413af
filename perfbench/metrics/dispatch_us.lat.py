"""Host dispatch (us) of the latency group: median over its calls of
the time from the call to its return, before the wait for the result.
Host clock of the measured window, read in the traced run."""

from perfbench import arith


def read(r):
    g = r.calls.group("lat")
    if g is None:
        return None
    return arith.median(list(g.t1 - g.t0)) * 1e6
