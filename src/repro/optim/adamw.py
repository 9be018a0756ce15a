"""Pure-pytree AdamW with global-norm clipping and warmup-cosine schedule.

Moments are fp32 regardless of param dtype (bf16 params + fp32 optimizer is
the production mixed-precision recipe); all state is elementwise and thus
inherits the parameters' (FSDP+TP) sharding — ZeRO-style optimizer-state
sharding falls out of the param PartitionSpecs for free.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["OptConfig", "OptState", "init_opt_state", "apply_updates", "global_norm", "lr_at"]


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1


class OptState(NamedTuple):
    step: jax.Array
    m: dict
    v: dict


def init_opt_state(params) -> OptState:
    # zeros_like keeps each parameter's sharding: the moments are born
    # sharded like their parameters, never whole on one device
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return OptState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
    )


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def lr_at(cfg: OptConfig, step) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0
    )
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * warm * cos


def apply_updates(params, grads, state: OptState, cfg: OptConfig):
    """One AdamW step.  Returns (params, state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    step = state.step + 1
    lr = lr_at(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, OptState(step=step, m=new_m, v=new_v), metrics
