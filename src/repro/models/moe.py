"""Expert-parallel Mixture-of-Experts with TensorDash-style structured sparsity.

The router's top-k one-hot IS the paper's Z-vector at expert granularity:
most (expert, token) pairs are ineffectual and the dispatch machinery —
sort-free capacity bucketing + all-to-all — advances effectual work into
their slots, exactly the paper's advance-in-time/space mechanism one level
up the hierarchy (DESIGN.md §5).

Parallel layout (production mesh):
  * experts sharded over the ``model`` axis (EP),
  * each expert's FFN dim additionally FSDP-sharded over ``data`` and
    all-gathered per layer inside ``shard_map`` (reduce-scattered in the
    backward pass automatically by shard_map's AD),
  * tokens sharded over every mesh axis during training (sequence over
    ``model``), dispatched via tiled ``all_to_all``;
  * decode (tiny token counts) uses the replicated-token + psum path so
    expert weights never move.

Gather-based dispatch (no [T, E, C] one-hot einsums): a [T, E] one-hot would
cost O(T*E*C*d) MAC-counted FLOPs in XLA and wreck the compute roofline; the
bucketing below is pure integer work + takes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import runtime as rtm
from repro.models.common import ACTIVATIONS, Spec

__all__ = ["MoEConfig", "moe_specs", "moe_ffn"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    num_experts: int
    top_k: int
    d_ff: int  # per-expert hidden dim
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    activation: str = "silu"
    router_scale: bool = True  # normalize top-k weights to sum to 1
    a2a_quant: bool = True  # int8 dispatch/combine payloads (§Perf iter. 5)


def _qa2a(x, split_axis, concat_axis):
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    q = jax.lax.all_to_all(q, "model", split_axis=split_axis, concat_axis=concat_axis, tiled=True)
    s = jax.lax.all_to_all(scale, "model", split_axis=split_axis, concat_axis=concat_axis, tiled=True)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _quantized_all_to_all(x, split_axis, concat_axis):
    """all_to_all with int8 payload + per-row fp32 scales (~2x fewer ICI
    bytes than bf16; the DeepSeek-V3 fp8-dispatch recipe).  The gradient
    takes the mirrored quantized all_to_all."""
    return _qa2a(x, split_axis, concat_axis)


def _qa2a_fwd(x, split_axis, concat_axis):
    return _qa2a(x, split_axis, concat_axis), None


def _qa2a_bwd(split_axis, concat_axis, _, g):
    # transpose of tiled all_to_all = all_to_all with swapped axes
    return (_qa2a(g, concat_axis, split_axis),)


_quantized_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


def _a2a(cfg: MoEConfig, x, split_axis, concat_axis):
    if cfg.a2a_quant:
        return _quantized_all_to_all(x, split_axis, concat_axis)
    return jax.lax.all_to_all(x, "model", split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def moe_specs(cfg: MoEConfig) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.d_ff
    specs = {
        "router": Spec((d, e), ("embed", None), init="scaled", scale=0.02, dtype=jnp.float32),
        "w_gate": Spec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_up": Spec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_down": Spec((e, f, d), ("experts", "expert_mlp", "expert_embed")),
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * cfg.d_ff
        specs["shared"] = {
            "w_gate": Spec((d, fs), ("embed", "mlp")),
            "w_up": Spec((d, fs), ("embed", "mlp")),
            "w_down": Spec((fs, d), ("mlp", "embed")),
        }
    return specs


def _route(cfg: MoEConfig, x2, router_w):
    """x2 [T, d] -> (weights [T, k] f32, experts [T, k] i32, probs [T, E])."""
    logits = (x2.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_scale:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return top_p, top_e.astype(jnp.int32), probs


def _bucket(cfg: MoEConfig, top_e, n_experts: int, capacity: int, t: int):
    """Capacity bucketing: (slot_table [E, C] token-flat-id or T*k sentinel,
    pos [T, k] slot-within-expert, fits [T, k])."""
    flat_e = top_e.reshape(-1)  # [T*k]
    # position of each assignment within its expert (stable, FIFO like the
    # paper's in-order scheduler)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)  # [T*k, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T*k, E]
    pos = jnp.sum(pos, axis=-1)  # [T*k]
    fits = pos < capacity
    slot = jnp.where(fits, flat_e * capacity + pos, n_experts * capacity)
    table = jnp.full((n_experts * capacity + 1,), t * cfg.top_k, jnp.int32)
    table = table.at[slot].set(jnp.arange(t * cfg.top_k, dtype=jnp.int32), mode="drop")
    return table[:-1].reshape(n_experts, capacity), pos.reshape(-1, cfg.top_k), fits.reshape(-1, cfg.top_k)


def _expert_ffn(cfg: MoEConfig, xe, w_gate, w_up, w_down):
    """xe [E_local, C, d] -> [E_local, C, d] (grouped gated FFN)."""
    act = ACTIVATIONS[cfg.activation]
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xe, w_up)
    h = act(g) * u
    rt = rtm.resolve(None)
    if rt.wants_sparse and cfg.activation in ("relu", "squared_relu"):
        # relu-family gates leave exact zeros in h, so each expert's
        # down-projection is a planned block-sparse product.  Routed
        # per-expert (not one fused einsum) so every expert resolves its
        # own tuned cell — expert capacity C, not the merged E*C shape,
        # is the bucket a ``geometry="auto"`` runtime tunes for.
        ys = [rt.matmul(h[e], w_down[e], op="moe_expert")
              for e in range(h.shape[0])]
        return jnp.stack(ys)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _shared_ffn(cfg: MoEConfig, params, x):
    act = ACTIVATIONS[cfg.activation]
    h = act(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


def _moe_local(cfg: MoEConfig, params, x2):
    """Single-device path (smoke tests, no mesh): all experts local."""
    t = x2.shape[0]
    e = cfg.num_experts
    cap = max(1, int(t * cfg.top_k / e * cfg.capacity_factor))
    top_p, top_e, _ = _route(cfg, x2, params["router"])
    table, pos, fits = _bucket(cfg, top_e, e, cap, t)
    x_pad = jnp.concatenate([x2, jnp.zeros((1, x2.shape[1]), x2.dtype)], 0)
    token_of = jnp.minimum(table // cfg.top_k, t)  # sentinel -> pad row
    xe = x_pad[token_of]  # [E, C, d]
    ye = _expert_ffn(cfg, xe, params["w_gate"], params["w_up"], params["w_down"])
    ye_flat = jnp.concatenate([ye.reshape(e * cap, -1), jnp.zeros((1, x2.shape[1]), ye.dtype)], 0)
    slot = jnp.where(fits, top_e * cap + pos, e * cap)  # [T, k]
    y = jnp.einsum("tkd,tk->td", ye_flat[slot], top_p.astype(ye.dtype))
    return y


def _moe_sharded(cfg: MoEConfig, ep_size: int, seq_sharded: bool, params, x2):
    """shard_map body.  x2 [t_local, d]; expert weights [E_local, d, f_shard]."""
    e = cfg.num_experts
    e_local = e // ep_size
    t = x2.shape[0]
    # FSDP: gather the expert FFN shard over the data axis
    w_gate = jax.lax.all_gather(params["w_gate"], "data", axis=2, tiled=True)
    w_up = jax.lax.all_gather(params["w_up"], "data", axis=2, tiled=True)
    w_down = jax.lax.all_gather(params["w_down"], "data", axis=1, tiled=True)
    top_p, top_e, _ = _route(cfg, x2, params["router"])

    if seq_sharded:
        cap = max(1, int(t * cfg.top_k / e * cfg.capacity_factor))
        table, pos, fits = _bucket(cfg, top_e, e, cap, t)
        x_pad = jnp.concatenate([x2, jnp.zeros((1, x2.shape[1]), x2.dtype)], 0)
        xe = x_pad[jnp.minimum(table // cfg.top_k, t)]  # [E, C, d]
        # dispatch: tokens travel to their experts' shard
        xe = _a2a(cfg, xe, 0, 1)
        ye = _expert_ffn(cfg, xe, w_gate, w_up, w_down)  # [E_local, ep*C, d]
        ye = _a2a(cfg, ye, 1, 0)
        ye_flat = jnp.concatenate(
            [ye.reshape(e * cap, -1), jnp.zeros((1, x2.shape[1]), ye.dtype)], 0
        )
        slot = jnp.where(fits, top_e * cap + pos, e * cap)
        y = jnp.einsum("tkd,tk->td", ye_flat[slot], top_p.astype(ye.dtype))
    else:
        # decode path: tokens replicated over `model`; each shard runs only
        # its local experts and the combine is a psum. Weights never move.
        my = jax.lax.axis_index("model") * e_local
        cap = max(1, int(t * cfg.top_k / e * cfg.capacity_factor) * 4)
        cap = min(cap, t * cfg.top_k)
        local = (top_e >= my) & (top_e < my + e_local)
        loc_e = jnp.where(local, top_e - my, e_local)  # e_local = drop bucket
        table, pos, fits = _bucket(cfg, loc_e, e_local + 1, cap, t)
        table = table[:e_local]
        x_pad = jnp.concatenate([x2, jnp.zeros((1, x2.shape[1]), x2.dtype)], 0)
        xe = x_pad[jnp.minimum(table // cfg.top_k, t)]
        ye = _expert_ffn(cfg, xe, w_gate, w_up, w_down)
        ye_flat = jnp.concatenate(
            [ye.reshape(e_local * cap, -1), jnp.zeros((1, x2.shape[1]), ye.dtype)], 0
        )
        slot = jnp.where(fits & local, loc_e * cap + pos, e_local * cap)
        y = jnp.einsum("tkd,tk->td", ye_flat[slot], top_p.astype(ye.dtype))
        y = jax.lax.psum(y, "model")
    return y


def moe_ffn(params, cfg: MoEConfig, x, *, mesh=None, seq_sharded: bool = True):
    """MoE FFN.  x [B, S, d].  With a mesh (explicit, or from the ambient
    ``repro.runtime.Runtime``), runs expert-parallel via shard_map; without
    one, the single-device reference path."""
    mesh = rtm.active_mesh(mesh)
    b, s, d = x.shape
    shared = _shared_ffn(cfg, params["shared"], x) if cfg.num_shared_experts else 0.0

    if mesh is None:
        y = _moe_local(cfg, {k: v for k, v in params.items() if k != "shared"}, x.reshape(-1, d))
        return y.reshape(b, s, d) + shared

    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    seq_ax = "model" if (seq_sharded and s % mesh.shape["model"] == 0 and s > 1) else None
    x_spec = P(dp, seq_ax, None)
    w_specs = {
        "router": P(None, None),
        "w_gate": P("model", None, "data"),
        "w_up": P("model", None, "data"),
        "w_down": P("model", "data", None),
    }
    body = functools.partial(
        _moe_sharded, cfg, mesh.shape["model"], seq_ax is not None
    )

    def flat_body(p, xl):
        t_local = xl.shape[0] * xl.shape[1]
        y = body(p, xl.reshape(t_local, d))
        return y.reshape(xl.shape)

    y = jax.shard_map(
        flat_body,
        mesh=mesh,
        in_specs=(w_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )({k: params[k] for k in w_specs}, x)
    return y + shared
