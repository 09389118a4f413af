"""Reduce_local rate (GB/s): the sum of the message bytes S of every
call of the reduce group over the sum of their call-to-ready times
(IMB Reduce_local convention)."""

from perfbench import arith


def read(r):
    g = r.calls.group("reduce")
    if g is None:
        return None
    return arith.rate([float(s) for s in g.nbytes], g.t2 - g.t0) / 1e9
