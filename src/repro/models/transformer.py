"""Decoder-only transformer backbone (dense + MoE families).

One scanned homogeneous block keeps the HLO size independent of depth (the
94-layer MoE compiles as fast as the 26-layer dense model); per-layer
differences (Gemma-2 local/global alternation) ride along as scanned flags.

Execution policy (kernel backend, block geometry, mesh) is resolved through
``repro.runtime``: pass a mesh explicitly or install a ``Runtime`` with
``with repro.runtime.use(rt):``.  Under a sparse runtime the block geometry
auto-clamps to the operand shapes — there is no dense fallback path.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import runtime as rtm
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import ACTIVATIONS, Spec, rms_norm, softcap
from repro.core import sparsity as sps
from repro.parallel.sharding import DP, constrain


def _seq_ax(cfg):
    # Sequence parallelism pays off where the layout feeds the MoE dispatch
    # directly; on dense archs under the CPU partitioner (no AR->RS rewrite)
    # it only adds all-gathers, and it breaks the static-causal KV slicing
    # (gemma2 prefill +255%) -- measured in EXPERIMENTS.md SS Perf iter. 8.
    return "model" if cfg.family == "moe" else None

__all__ = [
    "attn_config",
    "mla_config",
    "moe_config",
    "block_specs",
    "backbone_specs",
    "stack_specs",
    "head_matmul",
    "forward",
    "prefill",
    "decode_step",
    "init_layer_caches",
]


def stack_specs(specs, n: int):
    """Prepend a scanned 'layers' dim to every Spec in a tree."""
    return jax.tree.map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, init=s.init, scale=s.scale, dtype=s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, Spec),
    )


def attn_config(cfg: ModelConfig) -> attn.AttnConfig:
    return attn.AttnConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm,
        attn_softcap=cfg.attn_softcap,
        sliding_window=cfg.sliding_window,
        mrope_sections=cfg.mrope_sections,
        q_chunk=cfg.q_chunk,
        unroll=cfg.unroll,
        kv_quant=cfg.kv_cache_quant,
    )


def mla_config(cfg: ModelConfig) -> mla_mod.MLAConfig:
    return mla_mod.MLAConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank,
        q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta,
        q_chunk=cfg.q_chunk,
        unroll=cfg.unroll,
    )


def moe_config(cfg: ModelConfig) -> moe_mod.MoEConfig:
    return moe_mod.MoEConfig(
        d_model=cfg.d_model,
        num_experts=cfg.num_experts,
        top_k=cfg.top_k,
        d_ff=cfg.moe_d_ff,
        num_shared_experts=cfg.num_shared_experts,
        capacity_factor=cfg.capacity_factor,
        activation=cfg.activation,
        a2a_quant=cfg.moe_a2a_quant,
    )


def mlp_specs(cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    if cfg.mlp_gated:
        return {
            "w_gate": Spec((d, d_ff), ("embed", "mlp")),
            "w_up": Spec((d, d_ff), ("embed", "mlp")),
            "w_down": Spec((d_ff, d), ("mlp", "embed")),
        }
    return {
        "w_up": Spec((d, d_ff), ("embed", "mlp")),
        "w_down": Spec((d_ff, d), ("mlp", "embed")),
    }


def mlp_fwd(params, cfg: ModelConfig, x, taps: dict | None = None, mesh=None, rt=None):
    act = ACTIVATIONS[cfg.activation]
    rt = rtm.resolve(rt)
    mesh = mesh if mesh is not None else rt.mesh
    if cfg.mlp_gated:
        if rt.wants_sparse and cfg.activation == "relu":
            # TensorDash fused + emitted-plan path: the gate matmul applies
            # ReLU in its store step and emits its output's block-nonzero
            # mask.  Gating is a pointwise product, so a block the gate
            # zeroed stays zero in h — the emitted mask is a valid
            # (conservative) plan for the w_down matmul, which therefore
            # never re-scans h's values; the plan's CSR work queue (built in
            # the same fused replanning dispatch) then lets the v3 ragged
            # grid skip those blocks in time at per-row granularity — token
            # rows ReLU zeroed heavily finish early instead of riding
            # behind the densest row's max(nnz) bound (v2).  The runtime
            # clamps block geometry to the operand shapes, so odd token
            # counts plan at a finer granularity instead of silently
            # running dense.
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1])
            g, gmask = rt.matmul_fused(
                x2, params["w_gate"], activation="relu", assume_dense=True
            )
            h2 = g * (x2 @ params["w_up"])
            if taps is not None:
                taps["ffn_act"] = sps.measure(h2.reshape(*lead, -1))
            plan_h = rt.plan_for_fused_output(gmask, h2, params["w_down"],
                                              k=x2.shape[1])
            return rt.matmul(h2, params["w_down"], plan=plan_h).reshape(*lead, -1)
        h = act(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = act(x @ params["w_up"])
    h = constrain(h, mesh, (DP, None, "model"))
    if taps is not None:
        taps["ffn_act"] = sps.measure(h)
    return h @ params["w_down"]


@jax.named_scope("head")
def head_matmul(cfg: ModelConfig, h, lm_head):
    """``h @ lm_head`` through the active runtime.

    Under a sparse runtime (e.g. a block-pruned head), the weight-side plan
    is computed once and replayed from the runtime's plan cache on every
    subsequent call — prefill plans, decode steps cache-hit (the software
    analogue of the paper's amortized backside scheduler, §3.7).  Weights
    are static across a generation, so the replay is numerically exact; the
    cache validates hits by array identity.  Inside a jitted decode loop the
    plan is part of the traced program instead (``PlanCache.traced``): XLA
    hoists it out of the scan, so it is still computed once per call, not
    per token.

    Execution lands on the v3 ragged work-queue kernel (the runtime
    default): the decode-path LM-head matmul issues exactly one grid step
    per effectual block — ``sum(nnz)``, not ``Mb * max(nnz)`` — so a
    block-pruned head with *uneven* per-row pruning (the realistic case)
    still decodes at its true density; under ``compact_grid=True`` (v2) a
    single dense vocabulary row would drag every row back to dense cost.
    The cached plan carries its CSR work queue, so decode steps hand the
    kernel a precomputed schedule with zero planning dispatches.

    XLA cannot partition a Pallas kernel, so on a mesh of several devices
    the head runs under ``shard_map``: each device takes its own tokens
    (split over the policy's data axes when they divide the token count)
    against the whole head, and the head's gradient is summed over them.
    """
    del cfg
    rt = rtm.resolve()
    b, s, d = h.shape
    if not rt.wants_sparse:
        return h @ lm_head

    def head(h2, w):
        return rt.matmul(h2, w, plan_key=("lm_head", id(lm_head)), side="B")

    mesh = rt.mesh
    if mesh is not None and mesh.size > 1:
        names, n = rt.sharding.spmm_axes("M")
        tok = names if names and (b * s) % n == 0 else None
        head = jax.shard_map(
            head, mesh=mesh, in_specs=(P(tok, None), P()),
            out_specs=P(tok, None), check_vma=False,
        )
    return head(h.reshape(b * s, d), lm_head).reshape(b, s, -1)


def block_specs(cfg: ModelConfig, *, moe: bool) -> dict:
    d = cfg.d_model
    specs: dict[str, Any] = {"ln1": Spec((d,), (None,), init="ones"), "ln2": Spec((d,), (None,), init="ones")}
    if cfg.use_mla:
        specs["attn"] = mla_mod.mla_specs(mla_config(cfg))
    else:
        specs["attn"] = attn.attention_specs(attn_config(cfg))
    specs["mlp"] = moe_mod.moe_specs(moe_config(cfg)) if moe else mlp_specs(cfg, cfg.d_ff)
    if cfg.post_norms:
        specs["post_attn_norm"] = Spec((d,), (None,), init="ones")
        specs["post_mlp_norm"] = Spec((d,), (None,), init="ones")
    return specs


def _block_fwd(params, cfg: ModelConfig, h, positions, is_global, mesh, probe=None, taps=False):
    """One block forward -> ``(h, tap_stats | None)``.

    ``probe`` is a zero array added at the MLP output (the zero-probe trick:
    ``jax.grad`` w.r.t. it is exactly this layer's output-gradient stream
    G_O, the paper's Eq. 2/3 sparse operand); ``taps=True`` additionally
    returns the FFN activation's measured :class:`SparsityStats` (the Eq. 1
    A stream)."""
    zero_centered = cfg.post_norms  # gemma-style norms
    with jax.named_scope("attention"):
        a = rms_norm(h, params["ln1"], zero_centered=zero_centered)
        if cfg.use_mla:
            a = mla_mod.mla_fwd(params["attn"], mla_config(cfg), a, positions, mesh=mesh)
        else:
            a = attn.attention_fwd(params["attn"], attn_config(cfg), a, positions, is_global=is_global, mesh=mesh)
        # pin the projection outputs themselves: lets GSPMD reduce-scatter
        # the partial sums (sequence parallelism) instead of all-reducing
        # the full activation before the residual add (§Perf iteration 6)
        a = constrain(a, mesh, (DP, _seq_ax(cfg), None))
        if cfg.post_norms:
            a = rms_norm(a, params["post_attn_norm"], zero_centered=True)
    h = h + a
    with jax.named_scope("mlp"):
        m = rms_norm(h, params["ln2"], zero_centered=zero_centered)
        stats = None
        if cfg.num_experts and "router" in params["mlp"]:
            m = moe_mod.moe_ffn(params["mlp"], moe_config(cfg), m, mesh=mesh)
            if taps:  # no hidden tap inside expert dispatch: measure the output
                stats = {"ffn_act": sps.measure(m)}
        else:
            t = {} if taps else None
            m = mlp_fwd(params["mlp"], cfg, m, taps=t, mesh=mesh)
            stats = t
        m = constrain(m, mesh, (DP, _seq_ax(cfg), None))
        if cfg.post_norms:
            m = rms_norm(m, params["post_mlp_norm"], zero_centered=True)
    if probe is not None:
        # zero probe: d loss / d probe == G_O at the MLP output; cast so the
        # add never promotes the activation dtype (bf16 models stay bf16)
        m = m + probe.astype(m.dtype)
    return constrain(h + m, mesh, (DP, _seq_ax(cfg), None)), stats


def _block_decode(params, cfg: ModelConfig, h, cache, pos, is_global, mesh):
    zero_centered = cfg.post_norms
    with jax.named_scope("attention"):
        a = rms_norm(h, params["ln1"], zero_centered=zero_centered)
        if cfg.use_mla:
            a, cache = mla_mod.mla_decode(params["attn"], mla_config(cfg), a, cache, pos, mesh=mesh)
        else:
            a, cache = attn.attention_decode(
                params["attn"], attn_config(cfg), a, cache, pos, is_global=is_global, mesh=mesh
            )
        if cfg.post_norms:
            a = rms_norm(a, params["post_attn_norm"], zero_centered=True)
    h = h + a
    with jax.named_scope("mlp"):
        m = rms_norm(h, params["ln2"], zero_centered=zero_centered)
        if cfg.num_experts and "router" in params["mlp"]:
            m = moe_mod.moe_ffn(params["mlp"], moe_config(cfg), m, mesh=mesh, seq_sharded=False)
        else:
            m = mlp_fwd(params["mlp"], cfg, m, mesh=mesh)
        if cfg.post_norms:
            m = rms_norm(m, params["post_mlp_norm"], zero_centered=True)
    return constrain(h + m, mesh, (DP, _seq_ax(cfg), None)), cache


# ---------------------------------------------------------------------------
# Backbone
# ---------------------------------------------------------------------------


def backbone_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    specs: dict[str, Any] = {}
    if cfg.frontend is None:
        specs["embed"] = Spec((v, d), ("vocab", "embed"), init="embed")
    n_moe = cfg.num_layers - cfg.first_dense_layers
    is_moe = cfg.family == "moe"
    specs["layers"] = stack_specs(block_specs(cfg, moe=is_moe), n_moe if is_moe else cfg.num_layers)
    if is_moe and cfg.first_dense_layers:
        specs["dense_layers"] = stack_specs(block_specs(cfg, moe=False), cfg.first_dense_layers)
    specs["final_norm"] = Spec((d,), (None,), init="ones")
    if cfg.frontend == "audio":
        specs["lm_head"] = Spec((cfg.num_codebooks, d, v), (None, "embed", "vocab"))
    else:
        specs["lm_head"] = Spec((d, v), ("embed", "vocab"))
    return specs


def _global_flags(cfg: ModelConfig, n: int):
    if cfg.local_global_alternate:
        return (jnp.arange(n) % 2) == 1
    return jnp.ones((n,), bool)


def _static_flags(cfg: ModelConfig, n: int):
    if cfg.local_global_alternate:
        return [i % 2 == 1 for i in range(n)]
    return [True] * n


def _embed_in(params, cfg: ModelConfig, batch):
    if cfg.frontend is not None:
        h = batch["inputs_embeds"].astype(jnp.bfloat16)
    else:
        embed, ids = params["embed"], batch["tokens"]
        if ids.shape[1] == 1 and cfg.vocab_size % 16 == 0:
            # decode: one-hot matmul instead of gather — GSPMD partitions the
            # matmul over the vocab-sharded table cleanly (a gather triggers
            # "involuntary full rematerialization" = replicating the table).
            # Only for model-axis-divisible vocabs: non-divisible tables
            # (mamba2's 50280) are replicated anyway and the gather is free
            # (§Perf iteration 8 follow-up).
            onehot = jax.nn.one_hot(ids, embed.shape[0], dtype=embed.dtype)
            h = onehot @ embed
        else:
            h = embed[ids]
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model**0.5, h.dtype)
    return h


def _positions(cfg: ModelConfig, batch, s: int):
    if cfg.mrope_sections is not None and "positions" in batch:
        return batch["positions"]
    return jnp.arange(s)


def _scan_layers(cfg, body, h, stacked_params, flags, probes=None, collect=False):
    """Run ``body(h, p, g, probe) -> (h, taps)`` over a layer stack.

    ``probes`` (optional) is scanned along with the params — one zero probe
    slice per layer; ``collect=True`` stacks each layer's tap stats into the
    second return value (leaves gain a leading ``[n_layers]`` axis)."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]
    if cfg.remat:
        body = jax.checkpoint(body, static_argnums=(2,)) if cfg.unroll else jax.checkpoint(body)
    if cfg.unroll:
        # python loop with STATIC per-layer flags: enables static-causal
        # attention slicing (and static sliding windows for gemma-2)
        outs = []
        for i, g in enumerate(_static_flags(cfg, n)):
            p = jax.tree.map(lambda x: x[i], stacked_params)
            h, t = body(h, p, g, probes[i] if probes is not None else None)
            outs.append(t)
        stats = jax.tree.map(lambda *xs: jnp.stack(xs), *outs) if collect else None
        return h, stats

    def scan_fn(carry, inp):
        p, g, pr = inp
        return body(carry, p, g, pr)

    h, stats = jax.lax.scan(scan_fn, h, (stacked_params, flags, probes))
    return h, (stats if collect else None)


def forward(params, cfg: ModelConfig, batch, mesh=None, probes=None, taps=None):
    """Full-sequence forward -> logits (train / eval).

    ``probes`` maps stack names (``"layers"``, ``"dense_layers"``) to
    ``[n_layers, B, S, D]`` zero arrays added at each layer's MLP output —
    gradients w.r.t. them are the per-layer G_O streams.  Passing a dict as
    ``taps`` fills it (same keys) with per-layer measured FFN-activation
    :class:`SparsityStats` — together the A/G densities TensorDash training
    instrumentation feeds into ``core.perf_model``.
    """
    mesh = rtm.active_mesh(mesh)
    h = constrain(_embed_in(params, cfg, batch), mesh, (DP, _seq_ax(cfg), None))
    s = h.shape[1]
    positions = _positions(cfg, batch, s)
    collect = taps is not None
    probes = probes or {}

    def body(h, p, g, pr):
        return _block_fwd(p, cfg, h, positions, g, mesh, probe=pr, taps=collect)

    if cfg.family == "moe" and cfg.first_dense_layers:
        h, dstats = _scan_layers(
            cfg, body, h, params["dense_layers"],
            _global_flags(cfg, cfg.first_dense_layers),
            probes=probes.get("dense_layers"), collect=collect,
        )
        if collect:
            taps["dense_layers"] = dstats
    n = params["layers"]["ln1"].shape[0]
    h, stats = _scan_layers(
        cfg, body, h, params["layers"], _global_flags(cfg, n),
        probes=probes.get("layers"), collect=collect,
    )
    if collect:
        taps["layers"] = stats
    h = rms_norm(h, params["final_norm"], zero_centered=cfg.post_norms)
    if cfg.frontend == "audio":
        logits = constrain(jnp.einsum("bsd,kdv->bskv", h, params["lm_head"]), mesh, (DP, None, None, "model"))
    else:
        logits = constrain(head_matmul(cfg, h, params["lm_head"]), mesh, (DP, None, "model"))
    return softcap(logits, cfg.final_softcap)


def init_layer_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Zero-filled stacked decode caches for the backbone."""
    n_moe = cfg.num_layers - cfg.first_dense_layers
    n_scan = n_moe if cfg.family == "moe" else cfg.num_layers

    def one(n):
        if cfg.use_mla:
            c = mla_mod.init_mla_cache(mla_config(cfg), batch, max_len)
        else:
            c = attn.init_cache(attn_config(cfg), batch, max_len)
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), c)

    caches = {"layers": one(n_scan)}
    if cfg.family == "moe" and cfg.first_dense_layers:
        caches["dense_layers"] = one(cfg.first_dense_layers)
    return caches


def decode_step(params, cfg: ModelConfig, caches, batch, pos, mesh=None):
    """One-token decode against pre-filled caches; returns (logits, caches)."""
    mesh = rtm.active_mesh(mesh)
    h = constrain(_embed_in(params, cfg, batch), mesh, (DP, _seq_ax(cfg), None))

    def body(carry, inp):
        p, c, g = inp
        h, new_c = _block_decode(p, cfg, carry, c, pos, g, mesh)
        return h, new_c

    new_caches = {}
    if cfg.family == "moe" and cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        h, new_caches["dense_layers"] = jax.lax.scan(
            body, h, (params["dense_layers"], caches["dense_layers"], _global_flags(cfg, nd))
        )
    n = params["layers"]["ln1"].shape[0]
    h, new_caches["layers"] = jax.lax.scan(
        body, h, (params["layers"], caches["layers"], _global_flags(cfg, n)),
        unroll=n if cfg.unroll else 1,
    )
    h = rms_norm(h, params["final_norm"], zero_centered=cfg.post_norms)
    if cfg.frontend == "audio":
        logits = jnp.einsum("bsd,kdv->bskv", h, params["lm_head"])
    else:
        logits = head_matmul(cfg, h, params["lm_head"])
    return softcap(logits, cfg.final_softcap), new_caches


def prefill(params, cfg: ModelConfig, batch, mesh=None):
    """Prefill: forward over the prompt, returning last-token logits and the
    filled KV caches (ready for decode at pos = seq_len)."""
    mesh = rtm.active_mesh(mesh)
    h = constrain(_embed_in(params, cfg, batch), mesh, (DP, _seq_ax(cfg), None))
    s = h.shape[1]
    positions = _positions(cfg, batch, s)

    def body(carry, inp):
        p, g = inp
        zc = cfg.post_norms
        with jax.named_scope("attention"):
            a = rms_norm(carry, p["ln1"], zero_centered=zc)
            if cfg.use_mla:
                c_kv, k_pe = mla_mod._latent_kv(p["attn"], mla_config(cfg), a, positions if positions.ndim == 1 else jnp.arange(s))
                a = mla_mod.mla_fwd(p["attn"], mla_config(cfg), a, positions if positions.ndim == 1 else jnp.arange(s), mesh=mesh)
                cache = mla_mod.MLACache(c_kv=c_kv, k_pe=k_pe)
            else:
                a, cache = attn.attention_fwd(
                    p["attn"], attn_config(cfg), a, positions, is_global=g, return_cache=True, mesh=mesh
                )
            a = constrain(a, mesh, (DP, _seq_ax(cfg), None))
            if cfg.post_norms:
                a = rms_norm(a, p["post_attn_norm"], zero_centered=True)
        hh = carry + a
        with jax.named_scope("mlp"):
            m = rms_norm(hh, p["ln2"], zero_centered=zc)
            if cfg.num_experts and "router" in p["mlp"]:
                m = moe_mod.moe_ffn(p["mlp"], moe_config(cfg), m, mesh=mesh)
            else:
                m = mlp_fwd(p["mlp"], cfg, m, mesh=mesh)
            m = constrain(m, mesh, (DP, _seq_ax(cfg), None))
            if cfg.post_norms:
                m = rms_norm(m, p["post_mlp_norm"], zero_centered=True)
        return constrain(hh + m, mesh, (DP, _seq_ax(cfg), None)), cache

    if cfg.remat:
        body = jax.checkpoint(body)

    def run_stack(h, stacked, n):
        if cfg.unroll:
            outs = []
            for i, g in enumerate(_static_flags(cfg, n)):
                p = jax.tree.map(lambda x: x[i], stacked)
                h, cache = body(h, (p, g))
                outs.append(cache)
            return h, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return jax.lax.scan(lambda c, i: body(c, i), h, (stacked, _global_flags(cfg, n)))

    caches = {}
    if cfg.family == "moe" and cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        h, caches["dense_layers"] = run_stack(h, params["dense_layers"], nd)
    n = params["layers"]["ln1"].shape[0]
    h, caches["layers"] = run_stack(h, params["layers"], n)
    h = rms_norm(h[:, -1:], params["final_norm"], zero_centered=cfg.post_norms)
    if cfg.frontend == "audio":
        logits = jnp.einsum("bsd,kdv->bskv", h, params["lm_head"])
    else:
        logits = head_matmul(cfg, h, params["lm_head"])
    return softcap(logits, cfg.final_softcap), caches
