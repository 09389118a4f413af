"""Every benchmark test starts with tracing on and an empty ring of the
default size.

The span readers read a traced slice back from the process's flight
recorder and read nothing where the ring lost a tenth of the slice's
calls. Under ``pytest -n <workers> --dist loadfile`` a worker runs
other test files first, in the same process, and one that leaves a
1,024-record ring behind (or tracing off) would make a traced latency
slice of a few thousand records read nothing.
"""

import pytest

from ompi_tpu.core import config
from ompi_tpu.trace import recorder


@pytest.fixture(autouse=True)
def _default_ring():
    saved = config.get("trace_base_enable")
    config.set("trace_base_enable", True)
    recorder.configure()
    yield
    config.set("trace_base_enable", saved)
