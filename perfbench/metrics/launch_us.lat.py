"""Plan launch per latency call (us): median over the traced ``lat``
calls of the program's ``coll.launch`` span, handing the compiled
program to JAX, or the whole host tier on the calls that take it.
Host-clock spans from the program's flight recorder; nothing where the
ring lacks the spans of 90% of the calls."""

from perfbench import arith, program_spans


def read(r):
    calls = program_spans.traced(r, "lat", "coll.allreduce",
                                 ("coll.launch",))
    if calls is None:
        return None
    return arith.median([c["coll.launch"] for c in calls]) * 1e6
