"""Device idle share (%) in the bandwidth group's traced slice: 1 minus
busy over window, averaged over the chips."""

from perfbench import trace_reduce


def read(r):
    t = r.traces.get("bw")
    if t is None or not t["extract"]["devices"]:
        return None
    return 100.0 * trace_reduce.idle_share(t["extract"], t["window_s"])
