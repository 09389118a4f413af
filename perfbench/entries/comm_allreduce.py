"""Entry: ``Communicator.allreduce(x, op)`` over the cell's chips.

The buffer is rank-major, ``(n, S / itemsize)``, block i on rank i's
chip (``comm.rank_sharding()``), as a user's device-resident tensor is.
Selection is the program's default: no algorithm is forced and no cvar
is set. Every call of one size reads the same buffers, as IMB's do.
"""

from __future__ import annotations

from perfbench import inputs


def open(env):
    import ompi_tpu
    from ompi_tpu.group import Group

    world = ompi_tpu.init()
    ranks = [world.devices.index(d) for d in env.devices]
    comm = world if ranks == list(range(world.size)) \
        else world.create(Group(ranks))
    return _Allreduce(comm, env.config)


class _Allreduce:
    def __init__(self, comm, config: dict) -> None:
        import jax.numpy as jnp

        self.comm = comm
        self.op = config["op"]
        self.dtype = config["dtype"]
        self.itemsize = jnp.dtype(self.dtype).itemsize
        self.bound = int(config["value_bound"])

    def make(self, key, nbytes: int):
        shape = (self.comm.size, nbytes // self.itemsize)
        return inputs.int_valued(key, shape, self.bound, self.dtype,
                                 self.comm.rank_sharding())

    def call(self, buf):
        return self.comm.allreduce(buf, self.op)

    def next(self, buf, out):
        return buf

    def counters(self) -> dict:
        from ompi_tpu.core.counters import SPC

        return SPC.snapshot()
