"""Entry: ``ompi_tpu.ops.reduce_local(op, inbuf, inoutbuf)`` on one chip,
every call of one size on the same seeded pair, its answer dropped.

Not chained: an idempotent op such as MAX saturates a chain after one
call (the next ``inoutbuf`` is already ``max(inoutbuf, inbuf)``), so
every later answer would equal its ``inoutbuf`` and a call that returned
``inoutbuf`` unchanged would pass the check. The device work per call is
the chained entry's: read two buffers, write one.
"""

from __future__ import annotations

from perfbench import harness

_chained = harness.load_module("entries", "ops_reduce_local")


def open(env):
    return _Unchained(env.devices[0], env.config)


class _Unchained(_chained._ReduceLocal):
    def next(self, buf, out):
        return buf
