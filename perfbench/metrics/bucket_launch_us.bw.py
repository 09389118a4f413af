"""Host time issuing a gradient sync's buckets (us): median over the
traced ``bw`` calls of the sum of the ``coll.allreduce`` spans under the
program's ``coll.allreduce_pytree`` span, one a bucket. Host-clock
spans from the program's flight recorder; nothing where the ring lacks
the spans of 90% of the calls."""

from perfbench import arith, program_spans


def read(r):
    calls = program_spans.traced(r, "bw", "coll.allreduce_pytree",
                                 ("coll.allreduce",))
    if calls is None:
        return None
    return arith.median([c["coll.allreduce"] for c in calls]) * 1e6
