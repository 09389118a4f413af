"""Op tier per latency call (us): median over the traced ``lat`` calls
of the program's ``op.reduce_local`` span, from the call into the op to
the dispatched combine. Host-clock spans from the program's flight
recorder; nothing where the ring lacks the spans of 90% of the calls."""

from perfbench import arith, program_spans


def read(r):
    calls = program_spans.traced(r, "lat", "op.reduce_local")
    if calls is None:
        return None
    return arith.median([c["op.reduce_local"] for c in calls]) * 1e6
