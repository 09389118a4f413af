"""Entry: a data-parallel training step's gradient work on one chip's
share of DeepSeek-V2-Lite's parameters.

The gradient list has one leaf per parameter of the share, under the
Hugging Face ``modeling_deepseek`` names and shapes, in the reverse of
registration order (the order DDP buckets them), as one list. The
cell's chip count picks the call:

- four chips: the DDP gradient sync, ``bucketer.allreduce_pytree(comm,
  grads, "sum", bucket_bytes=...)``; each leaf rank-major, ``(4,
  *shape)`` with block i on chip i (``comm.rank_sharding()``). Every
  call syncs the same list, as IMB's calls reuse their buffers;
- one chip: DDP's ``no_sync()`` micro-batch accumulation,
  ``ops.reduce_local("sum", grads, acc)``, chained as
  ``ops_reduce_local`` is: each call's answer is the next call's
  accumulator.

Selection is the program's default: no cvar is set.
"""

from __future__ import annotations

from perfbench import inputs


def param_shapes(config: dict, ep_rank: int = 0) -> list[tuple]:
    """``(name, shape)`` of every parameter of the share, in Hugging Face
    registration order: ``num_hidden_layers`` layers (the first
    ``first_k_dense_replace`` dense), ``n_routed_experts`` routed experts
    a MoE layer held here (EP rank ``ep_rank``'s, named by their global
    index), ``vocab_size`` rows of the embedding and the head. The
    router keeps the published expert count."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    lora, v = config["kv_lora_rank"], config["v_head_dim"]
    held = config["n_routed_experts"]
    routed = config["published"]["n_routed_experts"]
    if config.get("q_lora_rank") is not None:
        raise ValueError("the list is written for q_lora_rank null")

    def mlp(prefix, width):
        return [(f"{prefix}.gate_proj.weight", (width, h)),
                (f"{prefix}.up_proj.weight", (width, h)),
                (f"{prefix}.down_proj.weight", (h, width))]

    out = [("model.embed_tokens.weight", (config["vocab_size"], h))]
    for layer in range(config["num_hidden_layers"]):
        p = f"model.layers.{layer}"
        out += [(f"{p}.self_attn.q_proj.weight", (heads * (nope + rope), h)),
                (f"{p}.self_attn.kv_a_proj_with_mqa.weight", (lora + rope, h)),
                (f"{p}.self_attn.kv_a_layernorm.weight", (lora,)),
                (f"{p}.self_attn.kv_b_proj.weight", (heads * (nope + v), lora)),
                (f"{p}.self_attn.o_proj.weight", (h, heads * v))]
        if layer < config["first_k_dense_replace"]:
            out += mlp(f"{p}.mlp", config["intermediate_size"])
        else:
            for e in range(ep_rank * held, (ep_rank + 1) * held):
                out += mlp(f"{p}.mlp.experts.{e}",
                           config["moe_intermediate_size"])
            out.append((f"{p}.mlp.gate.weight", (routed, h)))
            out += mlp(f"{p}.mlp.shared_experts",
                       config["moe_intermediate_size"]
                       * config["n_shared_experts"])
        out += [(f"{p}.input_layernorm.weight", (h,)),
                (f"{p}.post_attention_layernorm.weight", (h,))]
    out += [("model.norm.weight", (h,)),
            ("lm_head.weight", (config["vocab_size"], h))]
    return out


def gradient_list(config: dict) -> list[tuple]:
    """The share's ``(name, shape)`` in DDP's bucketing order."""
    ep_rank = config.get("deployment", {}).get("ep_rank", 0)
    return param_shapes(config, ep_rank)[::-1]


def open(env):
    if len(env.devices) == 1:
        return _Accumulate(env.devices[0], env.config)
    import ompi_tpu
    from ompi_tpu.group import Group

    world = ompi_tpu.init()
    ranks = [world.devices.index(d) for d in env.devices]
    comm = world if ranks == list(range(world.size)) \
        else world.create(Group(ranks))
    return _Sync(comm, env.config)


class _Grads:
    def __init__(self, config: dict) -> None:
        import math

        import jax.numpy as jnp

        self.op = config["op"]
        self.dtype = config["dtype"]
        self.bound = int(config["value_bound"])
        self.shapes = [s for _, s in gradient_list(config)]
        self.sizes = [math.prod(s) for s in self.shapes]
        self.nbytes = jnp.dtype(self.dtype).itemsize * sum(self.sizes)

    def _list(self, key, lead: tuple, sharding) -> list:
        """The list's leaves, cut from one seeded array of all their
        elements: one hash over the whole list compiles in a fraction
        of the time of one a leaf."""
        from jax import lax

        flat = inputs.int_valued(key, lead + (sum(self.sizes),),
                                 self.bound, self.dtype, sharding)
        out, off = [], 0
        for shape, size in zip(self.shapes, self.sizes):
            leaf = flat[..., off:off + size].reshape(lead + shape)
            out.append(lax.with_sharding_constraint(leaf, sharding))
            off += size
        return out

    def _check_size(self, nbytes: int) -> None:
        if nbytes != self.nbytes:
            raise ValueError(f"the traffic's size {nbytes} B is not the "
                             f"gradient list's {self.nbytes} B")

    def counters(self) -> dict:
        from ompi_tpu.core.counters import SPC

        return SPC.snapshot()


class _Sync(_Grads):
    def __init__(self, comm, config: dict) -> None:
        from ompi_tpu.parallel import bucketer

        super().__init__(config)
        self.comm = comm
        self.allreduce_pytree = bucketer.allreduce_pytree
        self.bucket_bytes = int(config["bucket_bytes"])

    def make(self, key, nbytes: int):
        self._check_size(nbytes)
        return self._list(key, (self.comm.size,), self.comm.rank_sharding())

    def call(self, buf):
        return self.allreduce_pytree(self.comm, buf, self.op,
                                     bucket_bytes=self.bucket_bytes)

    def next(self, buf, out):
        return buf


class _Accumulate(_Grads):
    def __init__(self, device, config: dict) -> None:
        from jax.sharding import SingleDeviceSharding
        from ompi_tpu import ops

        super().__init__(config)
        self.sharding = SingleDeviceSharding(device)
        self.reduce_local = ops.reduce_local

    def make(self, key, nbytes: int):
        """A tuple: (micro-batch gradients, accumulator)."""
        self._check_size(nbytes)
        return tuple(self._list(inputs.derive(key, side), (), self.sharding)
                     for side in range(2))

    def call(self, buf):
        return self.reduce_local(self.op, buf[0], buf[1])

    def next(self, buf, out):
        return buf[0], out
