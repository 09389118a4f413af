"""Entry: ``ompi_tpu.ops.reduce_local(op, inbuf, inoutbuf)`` on one chip.

Chained as IMB-MPI1 Reduce_local runs it: each call's output is the
next call's ``inoutbuf``, so no two calls see the same input.
"""

from __future__ import annotations

from perfbench import inputs


def open(env):
    return _ReduceLocal(env.devices[0], env.config)


class _ReduceLocal:
    def __init__(self, device, config: dict) -> None:
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        from ompi_tpu import ops

        self.reduce_local = ops.reduce_local
        self.sharding = SingleDeviceSharding(device)
        self.op = config["op"]
        self.dtype = config["dtype"]
        self.itemsize = jnp.dtype(self.dtype).itemsize
        self.bound = int(config["value_bound"])

    def make(self, key, nbytes: int):
        shape = (nbytes // self.itemsize,)
        inbuf, inout = (
            inputs.int_valued(inputs.derive(key, i), shape, self.bound,
                              self.dtype, self.sharding)
            for i in range(2))
        return inbuf, inout

    def call(self, buf):
        return self.reduce_local(self.op, buf[0], buf[1])

    def next(self, buf, out):
        return buf[0], out

    def counters(self) -> dict:
        from ompi_tpu.core.counters import SPC

        return SPC.snapshot()
