"""Roofline share (%) of the bucketer's pack and unpack in the ``bw``
group's traced slice of a gradient sync: their HBM bytes over the HBM
peak, over their device time.

A sync of S bytes a rank packs the leaves into buckets (read S, write
S) and unpacks the reduced buckets into leaves (read S, write S):
``hbm_bytes(S)`` = 4 S a chip. The slice runs nothing on the device but
the pack, the buckets' collectives and the unpack, so the device time
of the pack and unpack is, per chip, the union of the ops that are not
collectives, averaged over the chips."""

from perfbench import trace_reduce

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")


def hbm_bytes(nbytes: int) -> float:
    """HBM bytes a chip of one pack and one unpack of ``nbytes``."""
    return 4.0 * nbytes


def read(r):
    t = r.traces.get("bw")
    if t is None or r.peaks is None or not t["extract"]["devices"]:
        return None
    g = t["calls"].group("bw")
    if g is None:
        return None
    collective = trace_reduce.name_matcher(COLLECTIVE_OPS)
    busy = trace_reduce.mean_busy_s(t["extract"],
                                    lambda name: not collective(name))
    if busy <= 0.0:
        return None
    least = sum(hbm_bytes(int(s)) for s in g.nbytes) \
        / (r.peaks["hbm_GBps"] * 1e9)
    return 100.0 * least / busy
