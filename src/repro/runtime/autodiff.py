"""Sparsity-aware differentiation for the planned matmul.

TensorDash's training claim rests on exploiting sparsity in *all three*
per-layer products (paper Eq. 1-3, the roles named in
:mod:`repro.core.perf_model`):

* ``FWD`` (A*W)          — the planned forward ``out = a @ b``;
* ``BWD_INPUT`` (W*G)    — ``da = g @ b.T``, sparse stream = the output
  gradients ``g`` (ReLU'd forwards make these the sparsest tensors in
  training);
* ``BWD_WEIGHT`` (A*G)   — ``db = a.T @ g``, sparse stream = the transposed
  forward operand, whose plan is a pure metadata transpose of the forward
  plan (:func:`repro.kernels.tensordash_spmm.transpose_plan` — no second
  pass over ``a``).

:func:`planned_matmul` is the one differentiation rule every backend's
``matmul_planned`` wraps: the backward rule builds/reuses
:class:`~repro.runtime.plan.SparsityPlan`\\ s for both gradient products and
executes them through the :mod:`~repro.runtime.backends` registry, replacing
the dense-VJP escape hatch the Pallas backend used to carry.

Gradient semantics are those of the *math* function ``a @ b`` (as before):
the plan only elides all-zero blocks of the operand it was built from, so
the planned forward equals the dense product and the dense cotangents are
exact.  The backward merely *executes* them sparsely — eliding all-zero
blocks of ``g`` / ``a.T`` — which changes nothing but the work done.

Plan reuse: when a plan cache + key ride along (``Runtime.matmul`` threads
its own), concrete (eager) backward executions cache the transposed-operand
plan — for a weight-side product that is "plan W and W.T once, reuse across
microbatches".  Inside ``jit``/``grad``/``scan`` operands are tracers, plans
are part of the traced program (the cache's ``traced`` counter observes
them), and XLA hoists the loop-invariant weight plans instead.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tensordash_spmm import (
    _check_compact_grid,
    plan_from_mask_csr,
    transpose_plan_csr,
)
from repro.runtime.plan import PlanCache, SparsityPlan, _tile_block

__all__ = [
    "PlannedVJP",
    "FusedVJP",
    "planned_matmul",
    "planned_matmul_grads",
    "fused_planned_matmul",
]


@dataclasses.dataclass(frozen=True)
class PlannedVJP:
    """Static context for one planned matmul's differentiation rule.

    ``backend`` executes the primal, ``grad_backend`` the two backward
    products (same registry; defaults to the primal's).  ``cache``/``key``
    opt the backward's plans into a :class:`PlanCache` (hashed by identity —
    two contexts sharing a cache compare equal only on the same cache).
    ``compact_grid`` is the grid family (``"ragged"`` v3 / ``"v2"`` /
    ``"v1"``, normalized at construction) every product of this matmul
    executes under by default; all three are bit-identical, only issued
    steps differ.  ``db`` optionally carries a ``repro.tune`` TuningDB so
    each *backward* product resolves its own tuned lane width and grid
    family (:meth:`_bwd_policy`) — the transposed plan generally wants a
    different geometry than the forward.
    """

    backend: str
    bm: int
    bk: int
    bn: int
    out_dtype: Any = None
    grad_backend: str | None = None
    cache: PlanCache | None = None
    key: Any = None
    compact_grid: Any = "ragged"
    db: Any = None  # optional repro.tune.TuningDB (hashed by identity)

    def __post_init__(self):
        # one canonical literal per mode, so jit's static-arg caches never
        # see True/"v2" as two distinct contexts
        object.__setattr__(
            self, "compact_grid", _check_compact_grid(self.compact_grid)
        )

    @property
    def bwd_backend(self) -> str:
        return self.grad_backend or self.backend

    def _execute(self, name, nnz, idx, a, b, *, bm, bk, bn, out_dtype,
                 workqueue=None, compact_grid=None):
        from repro.runtime.backends import KernelRequest, get_backend  # local: import cycle

        return get_backend(name).execute_planned(KernelRequest(
            nnz=nnz, idx=idx, a=a, b=b, bm=bm, bk=bk, bn=bn,
            out_dtype=out_dtype,
            compact_grid=(self.compact_grid if compact_grid is None
                          else compact_grid),
            workqueue=workqueue,
        ))

    def _plan_workqueue(self, plan: SparsityPlan, mode=None):
        """The plan's CSR triple when the ragged grid will consume it (and
        the plan carries one), else ``None`` — the kernel derives it
        in-graph for traced plans.  ``mode`` overrides the context's grid
        family (a tuned backward product may run a different one)."""
        mode = self.compact_grid if mode is None else mode
        return plan.workqueue() if mode == "ragged" else None

    def _bwd_policy(self, op, m, k, n, dtype, *, bn):
        """Tuned ``(bn, compact_grid)`` for one backward product, resolved
        from the riding TuningDB under the product's *own* key (``op`` is
        ``"matmul_da"`` / ``"matmul_db"``) — the transposed plan generally
        wants a different lane width and grid family than the forward.
        Only those two knobs are free: ``bm/bk`` are pinned by the backward
        plan's geometry (a metadata transform of the forward plan), which
        keeps the tuned backward bit-identical to the default one.  The
        operands arrive padded, so a tuned lane width that would need more
        padding keeps the default.  Returns ``(bn, None)`` — the context
        defaults — when no DB rides along or the cell is unmeasured."""
        if self.db is None:
            return bn, None
        pol = self.db.resolve(op=op, m=m, k=k, n=n, dtype=dtype)
        if pol is None:
            return bn, None
        tuned = _tile_block(pol.bn, n)
        return (tuned if n % tuned == 0 else bn), pol.compact_grid


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _cot_plan(ctx: PlannedVJP, g) -> SparsityPlan:
    """Plan the output-gradient stream (Eq. 2's sparse operand) — dynamic,
    per call; routed through the cache for counter visibility (a fresh
    cotangent never hits by identity, and never should)."""
    from repro.runtime.plan import plan_operand

    if ctx.cache is not None:
        return ctx.cache.get_or_build(("vjp_cot", ctx.key), g, ctx.bm, ctx.bn)
    return plan_operand(g, ctx.bm, ctx.bn)


def _lhs_t_plan(ctx: PlannedVJP, nnz, idx, a) -> SparsityPlan:
    """Plan of ``a.T`` (Eq. 3's sparse operand), derived by metadata
    transpose of the forward plan.

    The derived plan depends only on the forward plan's metadata, so cache
    hits are identity-validated against ``idx`` (not ``a``): as long as the
    forward plan is being reused — a cached static-weight plan across
    microbatches — its transpose is reused too, planned exactly once.
    """
    key = ("vjp_lhs_t", ctx.key)
    cache, concrete = ctx.cache, not _is_traced(idx)
    if cache is not None:
        if concrete:
            hit = cache.lookup(key, idx, ctx.bk, ctx.bm)
            if hit is not None:
                return hit
        else:
            cache.traced += 1
    nnz_t, idx_t, row_starts, work_row, work_kblk = transpose_plan_csr(nnz, idx)
    plan = SparsityPlan(
        nnz=nnz_t, idx=idx_t, bm=ctx.bk, bk=ctx.bm,
        shape=(a.shape[1], a.shape[0]), dtype=a.dtype,
        row_starts=row_starts, work_row=work_row, work_kblk=work_kblk,
    )
    if cache is not None and concrete:
        cache.store(key, idx, plan)
    return plan


def planned_matmul_grads(ctx: PlannedVJP, nnz, idx, a, b, g):
    """Both training cotangents of the planned ``a @ b``, registry-executed.

    ``da = g @ b.T`` planned over ``g``'s zero blocks (BWD_INPUT) and
    ``db = a.T @ g`` planned over ``a.T``'s (BWD_WEIGHT); fp32 accumulation,
    operand dtypes restored.  This is the exact function the ``custom_vjp``
    backward rule runs — callable eagerly (manual backprop, benchmarks,
    cache-counter tests) with concrete arrays, where plan caching is live.
    """
    g32 = g.astype(jnp.float32)
    pg = _cot_plan(ctx, g32)
    bn_da, cg_da = ctx._bwd_policy(
        "matmul_da", g.shape[0], g.shape[1], b.shape[0], a.dtype, bn=ctx.bk
    )
    da = ctx._execute(
        ctx.bwd_backend, pg.nnz, pg.idx, g32, b.astype(jnp.float32).T,
        bm=ctx.bm, bk=ctx.bn, bn=bn_da, out_dtype=a.dtype,
        workqueue=ctx._plan_workqueue(pg, cg_da), compact_grid=cg_da,
    )
    pt = _lhs_t_plan(ctx, nnz, idx, a)
    bn_db, cg_db = ctx._bwd_policy(
        "matmul_db", a.shape[1], a.shape[0], g.shape[1], b.dtype, bn=ctx.bn
    )
    db = ctx._execute(
        ctx.bwd_backend, pt.nnz, pt.idx, a.astype(jnp.float32).T, g32,
        bm=ctx.bk, bk=ctx.bm, bn=bn_db, out_dtype=b.dtype,
        workqueue=ctx._plan_workqueue(pt, cg_db), compact_grid=cg_db,
    )
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def planned_matmul(ctx: PlannedVJP, nnz, idx, a, b):
    """Planned ``a @ b`` on ``ctx.backend`` with the sparsity-aware VJP."""
    return ctx._execute(
        ctx.backend, nnz, idx, a, b,
        bm=ctx.bm, bk=ctx.bk, bn=ctx.bn, out_dtype=ctx.out_dtype,
    )


def _planned_fwd(ctx, nnz, idx, a, b):
    return planned_matmul(ctx, nnz, idx, a, b), (nnz, idx, a, b)


def _planned_bwd(ctx, res, g):
    nnz, idx, a, b = res
    da, db = planned_matmul_grads(ctx, nnz, idx, a, b, g)
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int plan metadata
    return zero(nnz), zero(idx), da, db


planned_matmul.defvjp(_planned_fwd, _planned_bwd)


# ---------------------------------------------------------------------------
# Fused-epilogue matmul: act(a @ b + bias) + residual, with the emitted
# output mask feeding the backward G-stream plan (paper §3.7).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedVJP(PlannedVJP):
    """Static context for the fused planned matmul's differentiation rule.

    Adds the epilogue: ``activation`` is applied to ``a @ b + bias`` in the
    kernel's store step, then ``residual`` is added.  The backward rule's
    **emitted-mask fast path** plans the output-gradient stream (Eq. 2's
    sparse operand) from the mask the forward kernel emitted — a pure
    metadata transform — whenever the epilogue guarantees the gradient
    vanishes on masked-off blocks: ReLU-family activations with no residual
    (``act'`` is zero wherever the output block is all zero).  Otherwise it
    falls back to planning the cotangent by value, exactly like
    :func:`planned_matmul`.

    Differentiating a ReLU-family epilogue *with* a residual is refused
    (``NotImplementedError``): ``act'`` would have to be reconstructed from
    ``out - residual``, which rounding/cancellation can corrupt by whole
    gradients, not ulps.  Residual fusion stays fully supported for
    inference and for ``activation="none"`` (``act' = 1``, exact).

    Precision note: without a residual, ``act'`` is reconstructed from the
    *stored* output, so a low-precision ``out_dtype`` rounds it — exact for
    fp32, ~2^-9 relative for bf16 (the same order as bf16 training noise
    elsewhere).  Formats with a narrow exponent (fp16) additionally flush
    tiny activations' gradients and should not be used as ``out_dtype``
    when training through the fused path.
    """

    activation: str = "none"

    @property
    def mask_plans_cotangent(self) -> bool:
        return self.activation in ("relu", "squared_relu")

    def _act_grad(self, y32, g32):
        """``g * act'(pre)`` computed from the post-activation value ``y``
        (pre-residual, fp32): relu' = [y > 0]; (relu^2)' = 2*sqrt(y)."""
        if self.activation == "none":
            return g32
        if self.activation == "relu":
            return g32 * (y32 > 0)
        if self.activation == "squared_relu":
            return g32 * 2.0 * jnp.sqrt(y32)
        raise ValueError(self.activation)


def _mask_plan(ctx: FusedVJP, mask) -> SparsityPlan:
    """Plan the cotangent stream from the forward's emitted output mask —
    metadata only, no pass over gradient values (the v3 work queue rides
    along in the same fused dispatch).  The mask granularity ``(bm, bn)``
    is exactly the cotangent's blocking for Eq. 2."""
    nnz_g, idx_g, row_starts, work_row, work_kblk = plan_from_mask_csr(mask)
    mb, nb = mask.shape
    return SparsityPlan(
        nnz=nnz_g, idx=idx_g, bm=ctx.bm, bk=ctx.bn,
        shape=(mb * ctx.bm, nb * ctx.bn), dtype=jnp.float32,
        row_starts=row_starts, work_row=work_row, work_kblk=work_kblk,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_planned_matmul(ctx: FusedVJP, nnz, idx, a, b, bias, residual):
    """Planned ``act(a @ b + bias) + residual`` on ``ctx.backend``, returning
    ``(out, mask)`` where ``mask`` is the emitted int8 output block-nonzero
    map.  ``bias``/``residual`` may be ``None`` (empty pytrees — their
    cotangents are then ``None`` too)."""
    from repro.runtime.backends import KernelRequest, get_backend  # local: import cycle

    return get_backend(ctx.backend).execute_fused(KernelRequest(
        nnz=nnz, idx=idx, a=a, b=b, bias=bias, residual=residual,
        bm=ctx.bm, bk=ctx.bk, bn=ctx.bn,
        activation=ctx.activation, out_dtype=ctx.out_dtype,
        compact_grid=ctx.compact_grid,
    ))


def _fused_fwd(ctx, nnz, idx, a, b, bias, residual):
    out, mask = fused_planned_matmul(ctx, nnz, idx, a, b, bias, residual)
    return (out, mask), (nnz, idx, a, b, bias, residual, out, mask)


def _fused_bwd(ctx: FusedVJP, res, cots):
    nnz, idx, a, b, bias, residual, out, mask = res
    g, _ = cots  # the int8 mask output has a symbolic-zero cotangent
    g32 = g.astype(jnp.float32)
    # post-activation, pre-residual value (fp32): act' is a function of it
    y32 = out.astype(jnp.float32)
    if residual is not None and ctx.activation != "none":
        # act'(y) would have to be reconstructed as out - residual, which
        # loses the activation's sign/value to rounding and cancellation
        # (|act| < ulp(res) reads as zero: the relu gate then silently
        # drops whole gradients, not ulps).  Refuse rather than corrupt;
        # "none" is exact (act' = 1, no reconstruction needed).
        raise NotImplementedError(
            f"differentiating a fused {ctx.activation!r} epilogue with a "
            "residual is not supported: the backward cannot exactly recover "
            "the pre-residual activation from the stored output — apply the "
            "residual outside the kernel when training through it"
        )
    g_pre = ctx._act_grad(y32, g32)

    # Eq. 2 (W*G): da = g_pre @ b.T, sparse stream = the gradient through the
    # epilogue.  Fast path: a ReLU-family epilogue (no residual) zeroes the
    # gradient wherever the emitted mask is zero, so the plan comes from the
    # mask — metadata already on hand, no values pass over g_pre.
    if ctx.mask_plans_cotangent and residual is None:
        pg = _mask_plan(ctx, mask)
        if ctx.cache is not None:
            ctx.cache.traced += int(_is_traced(mask))
    else:
        pg = _cot_plan(ctx, g_pre)
    bn_da, cg_da = ctx._bwd_policy(
        "matmul_da", g.shape[0], g.shape[1], b.shape[0], a.dtype, bn=ctx.bk
    )
    da = ctx._execute(
        ctx.bwd_backend, pg.nnz, pg.idx, g_pre, b.astype(jnp.float32).T,
        bm=ctx.bm, bk=ctx.bn, bn=bn_da, out_dtype=a.dtype,
        workqueue=ctx._plan_workqueue(pg, cg_da), compact_grid=cg_da,
    )
    # Eq. 3 (A*G): db = a.T @ g_pre, planned by metadata transpose of the
    # forward plan (shared with the unfused rule).
    pt = _lhs_t_plan(ctx, nnz, idx, a)
    bn_db, cg_db = ctx._bwd_policy(
        "matmul_db", a.shape[1], a.shape[0], g.shape[1], b.dtype, bn=ctx.bn
    )
    db = ctx._execute(
        ctx.bwd_backend, pt.nnz, pt.idx, a.astype(jnp.float32).T, g_pre,
        bm=ctx.bk, bk=ctx.bm, bn=bn_db, out_dtype=b.dtype,
        workqueue=ctx._plan_workqueue(pt, cg_db), compact_grid=cg_db,
    )
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int plan metadata
    dbias = None if bias is None else jnp.sum(g_pre, axis=0).astype(bias.dtype)
    dres = None if residual is None else g.astype(residual.dtype)
    return zero(nnz), zero(idx), da, db, dbias, dres


fused_planned_matmul.defvjp(_fused_fwd, _fused_bwd)
