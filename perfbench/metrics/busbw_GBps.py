"""Bus bandwidth of the bandwidth group (GB/s): the sum over its calls
of 2(n-1)/n * S bus bytes per rank, over the sum of their call-to-ready
times (IMB / nccl-tests convention)."""

from perfbench import arith


def read(r):
    g = r.calls.group("bw")
    if g is None:
        return None
    bus = [arith.allreduce_bus_bytes(int(s), r.nranks) for s in g.nbytes]
    return arith.rate(bus, g.t2 - g.t0) / 1e9
