"""Train step factory: microbatch gradient accumulation, AdamW, metrics,
optional TensorDash sparsity taps and cross-pod int8 gradient compression.

Microbatch accumulation runs as a ``lax.scan`` so XLA overlaps each
microbatch's gradient reduce with the next microbatch's compute (the
standard compute/comm overlap at scale); a straggler therefore costs at most
one microbatch of work.

``sparsity_taps=True`` instruments the three TensorDash training streams
(paper Eq. 1-3): every step's metrics gain per-layer non-zero fractions of
the FFN activations (``A_density``) and of the output-gradient streams at
each layer's MLP output (``G_density``, via the zero-probe trick), plus a
``modeled_speedup`` scalar — the work-skipping bound over the three
training convolutions.  :func:`modeled_speedup` refines the same densities
through the cycle-accurate ``core.perf_model`` simulator host-side (the
paper's Fig. 14 view).

Kernel-backend selection rides on the ambient ``repro.runtime.Runtime``
(``with runtime.use(rt):``), which also supplies the mesh; the PR-1 era
explicit ``mesh=`` parameters completed their deprecation cycle and are gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import runtime as rtm
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.optim.adamw import OptConfig, apply_updates, global_norm, init_opt_state

__all__ = ["make_train_step", "make_loss_fn", "init_train_state", "modeled_speedup"]


def _make_loss(cfg: ModelConfig, mesh):
    def loss_fn(params, batch, probes=None, taps=None):
        return M.loss_fn(params, cfg, batch, mesh=mesh, probes=probes, taps=taps)

    return loss_fn


def make_loss_fn(cfg: ModelConfig):
    """Loss closure over ``cfg``; the mesh comes from the ambient runtime."""
    return _make_loss(cfg, rtm.active_mesh())


def init_train_state(cfg: ModelConfig, params):
    return init_opt_state(params)


def _tap_stacks(cfg: ModelConfig) -> dict[str, int]:
    """Probe-able layer stacks of this config (name -> layer count)."""
    if cfg.family == "moe":
        stacks = {}
        if cfg.first_dense_layers:  # insertion order = execution order
            stacks["dense_layers"] = cfg.first_dense_layers
        stacks["layers"] = cfg.num_layers - cfg.first_dense_layers
        return stacks
    return {"layers": cfg.num_layers}


def _density(x) -> jax.Array:
    """Non-zero fraction per layer: collapse all but the leading axis."""
    return jnp.mean((x != 0).astype(jnp.float32), axis=tuple(range(1, x.ndim)))


def _tap_metrics(cfg: ModelConfig, taps: dict, gprobes: dict) -> dict:
    """Per-layer A/G densities + the in-graph modeled speedup.

    ``modeled_speedup`` is the ideal work-skipping bound: each of the three
    training convolutions performs the same MACs, and TensorDash at best
    prices a stream at its density — FWD at ``dA``, BWD_INPUT at ``dG``,
    BWD_WEIGHT at ``min(dA, dG)`` (the sparser operand wins, Eq. 3).  The
    cycle-accurate estimate (staging-depth limits, row imbalance) is the
    host-side :func:`modeled_speedup` helper over the same densities.
    """
    a_parts = [
        1.0 - taps[name]["ffn_act"].zeros / jnp.maximum(taps[name]["ffn_act"].total, 1.0)
        for name in _tap_stacks(cfg)
    ]
    g_parts = [_density(gprobes[name]) for name in _tap_stacks(cfg)]
    a_density = jnp.concatenate([jnp.atleast_1d(a) for a in a_parts])
    g_density = jnp.concatenate([jnp.atleast_1d(g) for g in g_parts])
    ideal = 3.0 / (a_density + g_density + jnp.minimum(a_density, g_density))
    return {
        "A_density": a_density,
        "G_density": g_density,
        "modeled_speedup": jnp.mean(ideal),
    }


def modeled_speedup(metrics, cfg: ModelConfig, **kw) -> dict[str, float]:
    """Refine one step's tapped densities through ``core.perf_model``.

    Host-side (call on fetched metrics, not inside jit): maps the step's
    per-layer A/G densities onto the FFN contraction layers and runs the
    tile simulator — one point of the paper's Fig. 14 speedup-over-training
    curve.  ``kw`` forwards to ``perf_model.speedup_from_densities``
    (``tile=``, ``clustering=``, ``max_t=`` ...).
    """
    from repro.core import perf_model as pm

    a = jax.device_get(metrics["A_density"])
    g = jax.device_get(metrics["G_density"])
    layers = pm.ffn_layers_from_config(cfg, n_layers=len(a))
    return pm.speedup_from_densities(a, g, layers, **kw)


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptConfig,
    *,
    microbatches: int = 1,
    sparsity_taps: bool = False,
    dynamic_sparsity=None,
    guard_nonfinite: bool = False,
):
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)``.  ``batch`` is the global batch; with ``microbatches > 1`` it
    is split on the leading axis and gradients are accumulated in fp32.
    The mesh comes from the ambient runtime (``with runtime.use(rt):``).

    ``sparsity_taps=True`` (dense/moe token-LM families) adds per-layer
    ``A_density`` / ``G_density`` vectors and a ``modeled_speedup`` scalar
    to the metrics; with microbatches the densities are averaged.

    ``dynamic_sparsity`` threads RigL mask state through the step: pass a
    ``repro.sparse_train.DynamicSparsityController`` (or its ``spec()``
    dict) and the step signature becomes ``train_step(params, opt_state,
    batch, masks)`` with ``masks = controller.masks()``.  Each step then
    (1) applies the block masks to the weights (so the planned kernels see
    exactly-zero blocks — the mask *is* the ``SparsityPlan``), (2) takes
    gradients at the masked point (RigL's dense gradients), (3) emits the
    controller's block-score trees as ``dst_w_scores`` / ``dst_g_scores``
    metrics plus a live ``dst_density`` scalar, and (4) masks the gradients
    before the optimizer so pruned weights stay pinned at zero between
    refreshes — regrown blocks restart from zero, no straight-through
    estimator needed.

    ``guard_nonfinite=True`` hardens the step: the signature gains a traced
    ``poison`` scalar (the fault-injection hook: 0 clean, 1 NaN loss, 2 NaN
    grads — same trust boundary a numerically-diverged model poisons), the
    step checks ``isfinite(loss) & isfinite(grad_norm)`` in-graph, and a
    non-finite step is *skipped*: params and optimizer state pass through
    unchanged (elementwise select — a clean guarded step stays bit-identical
    to an unguarded one) and ``metrics["nonfinite"]`` is 1.  The launcher
    layers exponential backoff + checkpoint-before-abort on top
    (``launch/train.py``).
    """
    rt = rtm.resolve(None)
    if rt.geometry == "auto" and (rt.tuning_db is None or len(rt.tuning_db) == 0):
        import warnings

        warnings.warn(
            "make_train_step under Runtime(geometry='auto') with an empty "
            "TuningDB: every cell resolves cold to the hand-tuned defaults. "
            "Pre-populate with `python -m repro.tune --configs <arch>` "
            "(see README #autotuning).",
            stacklevel=2,
        )
    policy = rtm.active_policy()
    mesh = policy.mesh
    loss_fn = _make_loss(cfg, mesh)
    dst_spec = None
    if dynamic_sparsity is not None:
        dst_spec = (
            dynamic_sparsity.spec()
            if hasattr(dynamic_sparsity, "spec")
            else dict(dynamic_sparsity)
        )
    if sparsity_taps and (cfg.family not in ("dense", "moe") or cfg.frontend is not None):
        raise ValueError(
            f"sparsity_taps: unsupported family {cfg.family!r} / frontend "
            f"{cfg.frontend!r} (taps probe the transformer MLP stacks)"
        )

    def _constrain_grads(grads):
        # pin gradient shardings to the parameter layout right at the
        # backward boundary so the partitioner can shard the reduction
        if mesh is None:
            return grads
        return jax.tree.map(
            jax.lax.with_sharding_constraint,
            grads,
            policy.param_shardings(M.param_specs(cfg)),
        )

    def _zero_probes(batch):
        b, s = batch["tokens"].shape
        return {
            name: jnp.zeros((n, b, s, cfg.d_model), jnp.float32)
            for name, n in _tap_stacks(cfg).items()
        }

    def grads_of(params, batch):
        if not sparsity_taps:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            return loss, grads, {}

        def loss_with_taps(params, probes, b):
            taps: dict = {}
            return loss_fn(params, b, probes=probes, taps=taps), taps

        (loss, taps), (grads, gprobes) = jax.value_and_grad(
            loss_with_taps, argnums=(0, 1), has_aux=True
        )(params, _zero_probes(batch), batch)
        return loss, grads, _tap_metrics(cfg, taps, gprobes)

    def train_step(params, opt_state, batch, masks=None, poison=None):
        from repro.sparse_train.masks import (
            apply_block_masks, block_scores, mask_density,
        )

        params_in, opt_state_in = params, opt_state
        if dst_spec is not None:
            if masks is None:
                raise TypeError(
                    "dynamic_sparsity train step takes masks: "
                    "train_step(params, opt_state, batch, controller.masks())"
                )
            params = apply_block_masks(params, masks, dst_spec)
        if microbatches == 1:
            loss, grads, tapm = grads_of(params, batch)
            grads = _constrain_grads(grads)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]),
                batch,
            )
            acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            tap0: dict = {}
            if sparsity_taps:  # abstract trace only needed to size the tap carry
                _, _, tap0 = jax.eval_shape(
                    lambda b: grads_of(params, b), jax.tree.map(lambda x: x[0], mb)
                )
                tap0 = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), tap0)

            def body(acc, b):
                acc_g, acc_l, acc_t = acc
                l, g, t = grads_of(params, b)
                acc_g = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                acc_t = jax.tree.map(lambda a, x: a + x / microbatches, acc_t, t)
                return (acc_g, acc_l + l, acc_t), None

            (grads, loss, tapm), _ = jax.lax.scan(
                body, (acc0, jnp.zeros((), jnp.float32), tap0), mb
            )
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        if guard_nonfinite:
            # fault-injection hook at the loss/grad trust boundary: a traced
            # poison code so chaos replays never retrace the step program
            pc = jnp.asarray(0 if poison is None else poison, jnp.int32)
            loss = loss + jnp.where(pc == 1, jnp.float32(jnp.nan),
                                    jnp.float32(0.0))
            gnan = jnp.where(pc == 2, jnp.float32(jnp.nan), jnp.float32(0.0))
            grads = jax.tree.map(lambda g: g + gnan.astype(g.dtype), grads)
        dstm = {}
        if dst_spec is not None:
            # scores before the grad mask: RigL regrows on the *dense*
            # gradient's block mass; prune scores come from the (already
            # masked) weights.  Masking the grads afterwards pins pruned
            # weights (and their optimizer updates) at exactly zero.
            dstm = {
                "dst_w_scores": block_scores(params, dst_spec),
                "dst_g_scores": block_scores(grads, dst_spec),
                "dst_density": mask_density(masks, dst_spec),
            }
            grads = apply_block_masks(grads, masks, dst_spec)
        params, opt_state, metrics = apply_updates(params, grads, opt_state, opt_cfg)
        if dst_spec is not None:
            # stale Adam momentum would drift just-pruned entries off zero;
            # re-mask so stored weights always carry exactly-zero blocks
            # (what makes value planning recover the mask by construction)
            params = apply_block_masks(params, masks, dst_spec)
        if guard_nonfinite:
            # skip-step: a non-finite loss or gradient leaves params and
            # optimizer state untouched (the poisoned update is computed —
            # static program — and deselected; a clean step's select is the
            # identity, so guarding costs no numerics)
            ok = jnp.isfinite(loss) & jnp.isfinite(metrics["grad_norm"])
            keep = lambda new, old: jnp.where(ok, new, old)
            params = jax.tree.map(keep, params, params_in)
            opt_state = jax.tree.map(keep, opt_state, opt_state_in)
            metrics["nonfinite"] = (~ok).astype(jnp.int32)
        metrics["loss"] = loss
        metrics["param_norm"] = global_norm(params)
        metrics.update(tapm)
        metrics.update(dstm)
        return params, opt_state, metrics

    return train_step
