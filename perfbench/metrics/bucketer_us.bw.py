"""The bucketer's own Python per gradient sync (us): median over the
traced ``bw`` calls of the program's ``coll.allreduce_pytree`` span less
its ``coll.pack``, its ``coll.unpack`` and the sum of its buckets'
``coll.allreduce`` spans. That is the structure lookup, the counters
and the loop over the buckets, without the launches. Host-clock spans
from the program's flight recorder; nothing where the ring lacks the
spans of 90% of the calls."""

from perfbench import arith, program_spans

PARTS = ("coll.pack", "coll.unpack", "coll.allreduce")


def read(r):
    calls = program_spans.traced(r, "bw", "coll.allreduce_pytree", PARTS)
    if calls is None:
        return None
    return arith.median([c["coll.allreduce_pytree"]
                         - sum(c[p] for p in PARTS) for c in calls]) * 1e6
