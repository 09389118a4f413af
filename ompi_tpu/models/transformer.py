"""Flagship model: a 3-D-parallel MoE transformer LM built entirely on
ompi_tpu's collective substrate.

This is the framework's "one model running end-to-end" (SURVEY §7 step 4
analog, extended to every §2.6 parallelism row):

- **dp**: batch sharded over the 'dp' mesh axis; gradients psum'd
  (parallel/dp).
- **pp**: transformer blocks split into stages over 'pp'; activations
  hop stages through ppermute edge channels in a GPipe schedule
  (parallel/pp).
- **tp**: Megatron column/row-sharded MLPs with sequence-parallel
  allgather / reduce_scatter transitions (parallel/tp).
- **sp**: the sequence dimension lives sharded over the 'tp' axis
  between blocks; attention is exact causal *ring attention* — KV blocks
  circulate the tp ring (parallel/sp).
- **ep**: alternating blocks use MoE MLPs whose experts are sharded over
  the same axis, dispatched by capacity-based all_to_all (parallel/ep).

Gradient synchronization rules (encoded in `_sync_grads`):
- every param: mean over dp;
- tp-replicated params (attn, norms, router, embed/head): psum over tp
  (each tp rank saw only its sequence shard);
- tp-sharded params (MLP shards, experts): no tp sync — each rank owns
  its slice;
- stage-stacked params: no pp sync; embed/head/final-norm (used by one
  stage, stored replicated): psum over pp.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import dp as dp_mod
from ..parallel import overlap as overlap_mod
from ..parallel import ep as ep_mod
from ..parallel import pp as pp_mod
from ..parallel import sp as sp_mod
from ..parallel import tp as tp_mod
from ..parallel.mesh_utils import factorize, make_mesh


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128
    layers_per_stage: int = 2
    seq_len: int = 64
    n_experts: int = 4  # total experts (0 = dense-only)
    expert_ff: int = 64
    moe_every: int = 2  # every k-th layer is MoE (0 = never)
    capacity_factor: float = 1.25
    microbatches: int = 2
    lr: float = 1e-2
    dtype: Any = jnp.float32

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig, pp_size: int) -> dict:
    """Global (unsharded) parameter pytree; block params stacked over
    (stage, layer). Sharding is applied by the mesh specs at jit time."""
    k = jax.random.split(rng, 16)
    D, V, S = cfg.d_model, cfg.vocab, cfg.seq_len
    L, Pn = cfg.layers_per_stage, pp_size
    QKV, F = cfg.qkv_dim, cfg.d_ff
    E, Fe = max(cfg.n_experts, 1), cfg.expert_ff

    def norm(key, *shape, scale=0.02):
        return (jax.random.normal(key, shape) * scale).astype(cfg.dtype)

    return {
        "embed": norm(k[0], V, D),
        "pos": norm(k[1], S, D),
        "head": norm(k[2], D, V),
        "ln_f": jnp.ones((D,), cfg.dtype),
        "blocks": {
            "ln1": jnp.ones((Pn, L, D), cfg.dtype),
            "wq": norm(k[3], Pn, L, D, QKV),
            "wk": norm(k[4], Pn, L, D, QKV),
            "wv": norm(k[5], Pn, L, D, QKV),
            "wo": norm(k[6], Pn, L, QKV, D),
            "ln2": jnp.ones((Pn, L, D), cfg.dtype),
            "w1": norm(k[7], Pn, L, D, F),
            "w2": norm(k[8], Pn, L, F, D),
            "router": norm(k[9], Pn, L, D, E),
            "we1": norm(k[10], Pn, L, E, D, Fe),
            "we2": norm(k[11], Pn, L, E, Fe, D),
        },
    }


def param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpecs: stage axis over 'pp'; Megatron shards over 'tp';
    experts sharded over 'tp' (= the ep axis)."""
    return {
        "embed": P(),
        "pos": P(),
        "head": P(),
        "ln_f": P(),
        "blocks": {
            "ln1": P("pp"),
            "wq": P("pp"),
            "wk": P("pp"),
            "wv": P("pp"),
            "wo": P("pp"),
            "ln2": P("pp"),
            "w1": P("pp", None, None, "tp"),
            "w2": P("pp", None, "tp", None),
            "router": P("pp"),
            "we1": P("pp", None, "tp", None, None),
            "we2": P("pp", None, "tp", None, None),
        },
    }


# Leaves whose gradients need a tp psum (saw only a sequence shard).
_TP_REPLICATED = {"ln1", "wq", "wk", "wv", "wo", "ln2", "router"}
# Leaves used by a single pipeline stage but stored replicated over pp.
_PP_REPLICATED_TOP = {"embed", "pos", "head", "ln_f"}


# ---------------------------------------------------------------------------
# Model math (per-rank block code, runs inside shard_map)
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * scale


def _attention(x, wq, wk, wv, wo, cfg: ModelConfig):
    """Ring attention over the tp axis; x is (B, T_local, D)."""
    B, T, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    q = (x @ wq).reshape(B, T, H, Dh)
    kk = (x @ wk).reshape(B, T, H, Dh)
    v = (x @ wv).reshape(B, T, H, Dh)
    attn = jax.vmap(
        lambda qq, kkk, vv: sp_mod.ring_attention(
            qq, kkk, vv, axis_name="tp", causal=True
        )
    )(q, kk, v)
    return attn.reshape(B, T, H * Dh) @ wo


def _dense_mlp(x, w1, w2):
    """Megatron TP MLP with sequence-parallel transitions; x (B,T,D)."""
    B = x.shape[0]
    flat = x.reshape(-1, x.shape[-1])  # (B*T_local, D)
    out = tp_mod.tp_mlp(flat, w1, w2, axis_name="tp")
    return out.reshape(x.shape)


def _moe_mlp(x, router, we1, we2, cfg: ModelConfig):
    """Expert-parallel MoE over the tp(=ep) axis; x (B,T,D)."""
    n_local = we1.shape[0]  # experts this rank owns (E_total/ntp)
    flat = x.reshape(-1, x.shape[-1])
    logits = flat @ router

    def expert_fn(e, toks):
        h = jax.nn.gelu(toks @ we1[e])
        return h @ we2[e]

    out = ep_mod.moe_dispatch_combine(
        flat, logits, expert_fn, n_local, axis_name="tp",
        capacity_factor=cfg.capacity_factor,
    )
    return out.reshape(x.shape)


def _block(x, bp, layer: int, cfg: ModelConfig, use_moe: bool):
    g = lambda leaf: leaf[layer]
    h = x + _attention(
        _rmsnorm(x, g(bp["ln1"])), g(bp["wq"]), g(bp["wk"]), g(bp["wv"]),
        g(bp["wo"]), cfg,
    )
    norm2 = _rmsnorm(h, g(bp["ln2"]))
    if use_moe:
        return h + _moe_mlp(
            norm2, g(bp["router"]), g(bp["we1"]), g(bp["we2"]), cfg
        )
    return h + _dense_mlp(norm2, g(bp["w1"]), g(bp["w2"]))


def _stage_fn(stage_blocks, x, cfg: ModelConfig):
    """Apply this stage's layers_per_stage blocks to (B, T_local, D).

    Each block's input carries a grad_marker: its backward rule fires
    once every gradient inside the block has been produced, so the
    captured order is the true per-layer backprop tile schedule
    (parallel/overlap replays it for tile-granular Pready firing)."""
    for layer in range(cfg.layers_per_stage):
        use_moe = (
            cfg.n_experts > 0
            and cfg.moe_every > 0
            and (layer % cfg.moe_every) == (cfg.moe_every - 1)
        )
        x = overlap_mod.grad_marker(x, f"blk{layer}")
        x = _block(x, stage_blocks, layer, cfg, use_moe)
    return x


# ---------------------------------------------------------------------------
# The SPMD training step
# ---------------------------------------------------------------------------

def _forward_loss(params, tokens, targets, cfg: ModelConfig):
    """Per-rank forward+loss. tokens/targets: (B_local, S) replicated
    over pp/tp; returns global-mean scalar loss (same on every rank)."""
    B, S = tokens.shape
    ntp = lax.axis_size("tp")
    T = S // ntp  # local sequence shard

    # Embed + positional, then shard the sequence over tp. The marker's
    # backward rule fires last — embed/pos grads close the backprop.
    x = overlap_mod.grad_marker(
        params["embed"][tokens] + params["pos"][None, :S], "embed"
    )
    tp_idx = lax.axis_index("tp")
    x = lax.dynamic_slice_in_dim(x, tp_idx * T, T, axis=1)  # (B, T, D)

    # Microbatch split for the pipeline.
    M = cfg.microbatches
    mb = B // M
    micro = x.reshape(M, mb, T, x.shape[-1])

    # params["blocks"] is already this rank's stage slice (shard_map
    # delivered the 'pp'-sharded leading axis, squeezed by the wrapper).
    outs = pp_mod.pipeline(
        lambda bp, h: _stage_fn(bp, h, cfg), params["blocks"], micro,
        axis_name="pp",
    )  # (M, mb, T, D), valid on last pp stage

    h = outs.reshape(B, T, -1)
    h = _rmsnorm(h, params["ln_f"])
    logits = h @ params["head"]  # (B, T, V)

    tgt = lax.dynamic_slice_in_dim(targets, tp_idx * T, T, axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    local_sum = jnp.sum(nll)

    npp = lax.axis_size("pp")
    stage = lax.axis_index("pp")
    ndp = lax.axis_size("dp")
    # Only the last stage's activations are real; mask the others. This
    # is the LOCAL loss share: no collective here — differentiating a
    # psum under shard_map (rep-checking off) multiplies cotangents by
    # the group size, so the cross-rank reduction of both loss and
    # grads happens explicitly outside the grad (_sync_grads / the
    # caller's psum), keeping per-rank cotangents exactly 1.
    local_sum = jnp.where(stage == npp - 1, local_sum, 0.0)
    ntokens_global = B * S * ndp
    return local_sum / ntokens_global


def _sync_grads(grads, cfg: ModelConfig):
    """Apply the gradient synchronization rules (module docstring).

    The local loss already carries the 1/(global tokens) normalization,
    so every cross-rank combination is a SUM: over dp for all params
    (each dp rank saw a batch shard), over tp for tp-replicated params
    (each tp rank saw a sequence shard), over pp for the stage-shared
    top-level params (only one stage's copy received gradient).

    The tp/pp sums are few and stay per-leaf; the dp sum — every
    parameter, the DDP-style gradient reduction — goes through the
    bucket coalescer (parallel/dp.allreduce_gradients): leaves fuse
    into size-capped flat buckets with one collective per bucket, so
    tuned scheduling and the quantized wire tier apply at bucket
    granularity.  Values match the per-leaf psums exactly — an
    elementwise sum of a concatenation is the concatenation of the
    sums.
    """
    from ..parallel import dp as _dp

    pre = {}
    for name in ("embed", "pos", "head", "ln_f"):
        g = grads[name]
        g = lax.psum(g, "tp")
        pre[name] = lax.psum(g, "pp")
    pre["blocks"] = {
        name: lax.psum(g, "tp") if name in _TP_REPLICATED else g
        for name, g in grads["blocks"].items()
    }
    # Capture the readiness schedule of the exact tree handed to the dp
    # reduction — the tile order parallel/overlap's mark_ready replays.
    pre = overlap_mod.capture_ready_schedule(pre)
    return _dp.allreduce_gradients(pre, "dp")


def build_train_step(cfg: ModelConfig, mesh):
    """Compile the full SPMD training step over a ('dp','pp','tp') mesh.

    Returns step(params, tokens, targets) -> (loss, new_params); params
    enter/leave sharded per param_specs.
    """
    specs = param_specs(cfg)

    def per_rank(params, tokens, targets):
        local_loss, grads = jax.value_and_grad(
            lambda p: _forward_loss(p, tokens, targets, cfg)
        )(params)
        grads = _sync_grads(grads, cfg)
        # Reported loss: sum the local shares OUTSIDE the grad.
        loss = lax.psum(
            lax.psum(lax.psum(local_loss, "tp"), "pp"), "dp"
        )
        new_params = jax.tree.map(
            lambda p, g: (p - cfg.lr * g).astype(p.dtype), params, grads
        )
        return loss, new_params

    # shard_map hands each rank a (1, L, ...) slice of every
    # 'pp'-sharded blocks leaf; squeeze that stage axis so the block code
    # sees its own stage's (L, ...) params directly, and restore it on
    # the way out.
    def per_rank_wrapped(params, tokens, targets):
        params = dict(params)
        params["blocks"] = jax.tree.map(
            lambda l: l[0], params["blocks"]
        )
        loss, new_params = per_rank(params, tokens, targets)
        new_params["blocks"] = jax.tree.map(
            lambda l: l[None], new_params["blocks"]
        )
        return loss, new_params

    in_specs = (
        specs,
        P("dp"),  # tokens: batch sharded over dp
        P("dp"),
    )
    out_specs = (P(), specs)

    fn = jax.shard_map(
        per_rank_wrapped,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,))


def build_forward(cfg: ModelConfig, mesh):
    """Compile the forward+loss only (no grad, no donation) — the
    compile-check entry point."""
    specs = param_specs(cfg)

    def per_rank(params, tokens, targets):
        params = dict(params)
        params["blocks"] = jax.tree.map(lambda l: l[0], params["blocks"])
        local = _forward_loss(params, tokens, targets, cfg)
        return lax.psum(lax.psum(lax.psum(local, "tp"), "pp"), "dp")

    fn = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(specs, P("dp"), P("dp")),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_init(cfg: ModelConfig, mesh, seed: int = 0):
    """Init params and place them according to param_specs."""
    pp_size = mesh.shape["pp"]
    params = init_params(jax.random.PRNGKey(seed), cfg, pp_size)
    specs = param_specs(cfg)
    # PartitionSpec is itself a pytree (tuple), so flatten the spec tree
    # with specs-as-leaves and zip against the param leaves.
    leaves, treedef = jax.tree.flatten(params)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, P)
    )
    placed = [
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(leaves, spec_leaves)
    ]
    return jax.tree.unflatten(treedef, placed)


def demo_mesh(n_devices: Optional[int] = None, devices=None):
    """A (dp, pp, tp) mesh factorizing the available devices."""
    import jax as _jax

    if devices is None:
        devices = _jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    dims = factorize(n, 3)
    return make_mesh(
        {"dp": dims[0], "pp": dims[1], "tp": dims[2]}, devices
    )


def make_batch(cfg: ModelConfig, batch: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, size=(batch, cfg.seq_len))
    targets = np.roll(tokens, -1, axis=1)
    return jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
