"""ompi_tpu's own Python per latency call (us): median over the traced
``lat`` calls of the program's ``coll.allreduce`` span less its
``coll.launch`` child. That is the communicator's preamble and the
tuned memo check, without handing the plan to JAX. Host-clock spans
from the program's flight recorder; nothing where the ring lacks the
spans of 90% of the calls."""

from perfbench import arith, program_spans


def read(r):
    calls = program_spans.traced(r, "lat", "coll.allreduce",
                                 ("coll.launch",))
    if calls is None:
        return None
    return arith.median([c["coll.allreduce"] - c["coll.launch"]
                         for c in calls]) * 1e6
