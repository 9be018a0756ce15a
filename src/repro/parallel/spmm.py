"""Distributed sparse execution: per-shard ragged work queues under shard_map.

The single-device planned/fused SpMM (v3, ``kernels/tensordash_spmm``) walks
a CSR work queue whose length is ``sum(max(nnz, 1))`` — kernel time tracks
effectual work.  This module lifts that property onto a device mesh: a
:class:`~repro.runtime.plan.SparsityPlan` is split along M (row-parallel
over the policy's data axes) or N (column-parallel over the model axis) and
every device builds a work queue from *its own shard's* ``plan_workqueue``,
so each device's grid is ``O(sum(nnz_shard))`` and load balance tracks local
effectual work, not the global ``max(nnz)`` (the naive split that leaves
devices idle behind one dense row — the Procrustes load-balance problem).

Distribution axes and their collectives:

* ``"M"`` — shard ``a``'s block rows.  Rows are dealt serpentine by
  descending work (:func:`repro.runtime.plan.balanced_row_order`, pure data
  movement), ``b`` is replicated, the output comes back row-sharded and is
  unpermuted.  No collective: every contraction is complete on-device, so
  results are **bit-identical** to single-device execution.
* ``"N"`` — shard ``b``'s columns.  The schedule is replicated (every shard
  walks the full queue against its own output columns).  No collective;
  bit-identical.
* ``"K"`` — shard the contraction.  Each device replans its K-block slice
  from the expanded block mask (metadata only) and the partials meet in a
  fp32 ``psum``.  The reassociated accumulation is allclose, *not* bitwise —
  and a fused nonlinear epilogue cannot distribute over the psum, so fused
  K-sharding is refused.

Differentiation: :class:`ShardedVJP` mirrors the single-device rule
(``runtime/autodiff``) with every product on per-shard queues — the
cotangent plan ``da = g @ b.T`` is always M-sharded over ``g``'s rows, and
the transposed weight-gradient plan ``db = a.T @ g`` shards along the
conjugate N axis with its metadata replicated.  Both backward contractions
stay device-local, so the gradients are bit-identical to single-device too.

Everything degrades gracefully: no mesh, a mesh without the policy's axes,
or shapes that don't divide the shard count fall back to the unsharded
executor — the same replicate-don't-split convention as
``parallel/sharding``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.tensordash_spmm import plan_from_mask_csr, plan_workqueue
from repro.parallel.sharding import ShardingPolicy
from repro.runtime.autodiff import (
    FusedVJP,
    PlannedVJP,
    _cot_plan,
    _lhs_t_plan,
    _mask_plan,
)
from repro.runtime.backends import KernelRequest, _all_concrete, get_backend
from repro.runtime.plan import SparsityPlan, balanced_row_order

__all__ = [
    "ShardedVJP",
    "ShardedFusedVJP",
    "sharded_execute_planned",
    "sharded_execute_fused",
    "sharded_matmul",
    "sharded_matmul_fused",
    "sharded_matmul_grads",
    "sharded_planned_matmul",
    "sharded_fused_matmul",
]


def _take_block_rows(x, order, bm: int):
    """Permute ``x``'s block rows (rows ``[i*bm, (i+1)*bm)`` move as one) —
    pure data movement, so execution on the permuted operand is bitwise."""
    m = x.shape[0]
    return jnp.take(x.reshape(m // bm, bm, x.shape[1]), order, axis=0).reshape(x.shape)


def _plan_block_mask(nnz, idx):
    """Expand compacted ``(nnz, idx)`` to the int8 ``[Rb, Kb]`` block mask
    in-graph (tail duplicates resolve via a scatter-max)."""
    nnz = jnp.asarray(nnz)
    idx = jnp.asarray(idx)
    rb, kb = idx.shape
    valid = (jnp.arange(kb, dtype=jnp.int32)[None, :] < nnz[:, None]).astype(jnp.int8)
    rows = jnp.broadcast_to(jnp.arange(rb, dtype=jnp.int32)[:, None], (rb, kb))
    return jnp.zeros((rb, kb), jnp.int8).at[rows, idx].max(valid)


def _divides(req: KernelRequest, axis: str, n_shards: int) -> bool:
    """Whether the sharded dim splits evenly into ``n_shards`` whole blocks."""
    if axis == "M":
        return (req.a.shape[0] // req.bm) % n_shards == 0
    if axis == "N":
        return (req.b.shape[1] // req.bn) % n_shards == 0
    return (req.a.shape[1] // req.bk) % n_shards == 0


def _spec_axis(names: tuple):
    return names if len(names) > 1 else names[0]


def _shard_m(be, req: KernelRequest, mesh, names, balance: bool, fused: bool):
    """Row-parallel execution: per-shard queues over dealt block rows."""
    ax = _spec_axis(names)
    ragged = req.compact_grid == "ragged"
    if balance:
        order = balanced_row_order(req.nnz, int(np.prod([mesh.shape[a] for a in names])))
        inv = jnp.argsort(order)  # argsort of a permutation = its inverse
        nnz = jnp.take(jnp.asarray(req.nnz), order, axis=0)
        idx = jnp.take(jnp.asarray(req.idx), order, axis=0)
        a = _take_block_rows(req.a, order, req.bm)
        residual = (
            _take_block_rows(req.residual, order, req.bm)
            if req.residual is not None else None
        )
    else:
        inv = None
        nnz, idx = jnp.asarray(req.nnz), jnp.asarray(req.idx)
        a, residual = req.a, req.residual
    ops = [nnz, idx, a, req.b]
    specs = [P(ax), P(ax, None), P(ax, None), P(None, None)]
    has_bias = fused and req.bias is not None
    has_res = fused and req.residual is not None
    if has_bias:
        ops.append(req.bias)
        specs.append(P(None))
    if has_res:
        ops.append(residual)
        specs.append(P(ax, None))
    out_specs = (P(ax, None), P(ax, None)) if fused else P(ax, None)

    def body(nnz_l, idx_l, a_l, b_l, *rest):
        # each shard's own queue: grid steps = sum(max(nnz_shard, 1))
        wq = plan_workqueue(nnz_l, idx_l) if ragged else None
        req_l = req.replace(
            nnz=nnz_l, idx=idx_l, a=a_l, b=b_l, workqueue=wq,
            bias=rest[0] if has_bias else None,
            residual=rest[-1] if has_res else None,
        )
        return be.execute_fused(req_l) if fused else be.execute_planned(req_l)

    out = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs), out_specs=out_specs,
        check_vma=False,
    )(*ops)
    if not fused:
        return _take_block_rows(out, inv, req.bm) if inv is not None else out
    y, mask = out
    if inv is not None:
        y = _take_block_rows(y, inv, req.bm)
        mask = jnp.take(mask, inv, axis=0)
    return y, mask


def _shard_n(be, req: KernelRequest, mesh, names, fused: bool):
    """Column-parallel execution: replicated schedule, sharded ``b`` cols."""
    ax = _spec_axis(names)
    ragged = req.compact_grid == "ragged"
    ops = [jnp.asarray(req.nnz), jnp.asarray(req.idx), req.a, req.b]
    specs = [P(None), P(None, None), P(None, None), P(None, ax)]
    has_bias = fused and req.bias is not None
    has_res = fused and req.residual is not None
    if has_bias:
        ops.append(req.bias)
        specs.append(P(ax))
    if has_res:
        ops.append(req.residual)
        specs.append(P(None, ax))
    has_wq = ragged and req.workqueue is not None
    if has_wq:  # the global queue is every shard's queue — replicate it
        ops.extend(jnp.asarray(w) for w in req.workqueue)
        specs.extend([P(None)] * 3)
    out_specs = (P(None, ax), P(None, ax)) if fused else P(None, ax)

    def body(nnz_l, idx_l, a_l, b_l, *rest):
        rest = list(rest)
        wq = tuple(rest[-3:]) if has_wq else None
        if wq is None and ragged:
            wq = plan_workqueue(nnz_l, idx_l)
        req_l = req.replace(
            nnz=nnz_l, idx=idx_l, a=a_l, b=b_l, workqueue=wq,
            bias=rest[0] if has_bias else None,
            residual=rest[1] if has_bias and has_res else (rest[0] if has_res else None),
        )
        return be.execute_fused(req_l) if fused else be.execute_planned(req_l)

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs), out_specs=out_specs,
        check_vma=False,
    )(*ops)


def _shard_k(be, req: KernelRequest, mesh, names):
    """Contraction-parallel execution: each shard replans its K slice
    (metadata only) and the fp32 partials meet in a psum.  Reassociated
    accumulation — allclose to single-device, not bitwise."""
    ax = _spec_axis(names)
    ragged = req.compact_grid == "ragged"
    mask = _plan_block_mask(req.nnz, req.idx)

    def body(mask_l, a_l, b_l):
        nnz_l, idx_l, rs, wr, wk = plan_from_mask_csr(mask_l)
        part = be.execute_planned(req.replace(
            nnz=nnz_l, idx=idx_l, a=a_l, b=b_l, out_dtype=jnp.float32,
            workqueue=(rs, wr, wk) if ragged else None,
        ))
        return jax.lax.psum(part, ax)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, ax), P(None, ax), P(ax, None)),
        out_specs=P(None, None), check_vma=False,
    )(mask, req.a, req.b)
    return out.astype(req.out_dtype or req.a.dtype)


def _injected_shard_fault(site: str) -> bool:
    """Consult the ambient :class:`repro.resilience.FaultPlan` (contextvar
    probe — nanoseconds when none is installed).  ``shard_stall`` sleeps
    host-side at dispatch (a slow shard, detected by the callers' step/TTL
    deadlines); ``shard_fail`` returns True, which the executors contain by
    degrading to the single-device path — correct output at reduced
    throughput — with a warning and a ``ResilienceLog`` event."""
    from repro.resilience import faults as _faults

    fp = _faults.active()
    if fp is None:
        return False
    t = fp.tick(site)
    _faults.stall(fp, "shard_stall", t)
    if fp.fires("shard_fail", t):
        import warnings

        from repro.resilience.log import record as _record

        warnings.warn(
            f"shard failure at {site} (injected): degrading to unsharded "
            f"execution", RuntimeWarning, stacklevel=3,
        )
        _record("shard", site, "fallback-unsharded", tick=t)
        return True
    return False


def sharded_execute_planned(backend: str, req: KernelRequest,
                            policy: ShardingPolicy, *, axis: str = "M",
                            balance: bool = True):
    """Primal planned ``a @ b`` distributed per ``policy`` (global layout in,
    global layout out).  Falls back to the unsharded executor when the mesh
    lacks the axis, the blocked shape doesn't divide the shard count, or a
    shard is (injected as) failed."""
    be = get_backend(backend)
    names, n_shards = policy.spmm_axes(axis)
    if (n_shards <= 1 or not _divides(req, axis, n_shards)
            or _injected_shard_fault("parallel.execute_planned")):
        return be.execute_planned(req)
    if axis == "M":
        return _shard_m(be, req, policy.mesh, names, balance, fused=False)
    if axis == "N":
        return _shard_n(be, req, policy.mesh, names, fused=False)
    return _shard_k(be, req, policy.mesh, names)


def sharded_execute_fused(backend: str, req: KernelRequest,
                          policy: ShardingPolicy, *, axis: str = "M",
                          balance: bool = True):
    """Primal fused ``act(a @ b + bias) + residual`` distributed per
    ``policy``; returns ``(out, mask)`` in the global layout.  ``"K"`` is
    refused: the nonlinear epilogue cannot distribute over the psum."""
    if axis == "K":
        raise NotImplementedError(
            "fused K-sharded execution is unsupported: the epilogue "
            "(bias/activation) must run after the psum — shard M or N, or "
            "apply the epilogue outside the kernel"
        )
    be = get_backend(backend)
    names, n_shards = policy.spmm_axes(axis)
    if (n_shards <= 1 or not _divides(req, axis, n_shards)
            or _injected_shard_fault("parallel.execute_fused")):
        return be.execute_fused(req)
    if axis == "M":
        return _shard_m(be, req, policy.mesh, names, balance, fused=True)
    return _shard_n(be, req, policy.mesh, names, fused=True)


# ---------------------------------------------------------------------------
# Differentiation: the sharded twins of runtime/autodiff's rules.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedVJP(PlannedVJP):
    """:class:`~repro.runtime.autodiff.PlannedVJP` whose every product runs
    under ``shard_map`` on per-shard queues.  The forward distributes on
    ``axis``; the backward's distribution is fixed by the products' shapes —
    ``da = g @ b.T`` M-sharded over the cotangent's rows (data axes, its
    plan dealt serpentine like any forward), ``db = a.T @ g`` N-sharded over
    its columns (the conjugate model axis) with the transposed plan's
    metadata replicated.  Contractions stay device-local, so both gradients
    are bit-identical to the single-device rule."""

    policy: ShardingPolicy = ShardingPolicy()
    axis: str = "M"
    balance: bool = True

    def _sharded_execute(self, name, nnz, idx, a, b, *, bm, bk, bn,
                         out_dtype, workqueue=None, axis="M",
                         compact_grid=None):
        req = KernelRequest(
            nnz=nnz, idx=idx, a=a, b=b, bm=bm, bk=bk, bn=bn,
            out_dtype=out_dtype,
            compact_grid=(self.compact_grid if compact_grid is None
                          else compact_grid),
            workqueue=workqueue,
        )
        return sharded_execute_planned(
            name, req, self.policy, axis=axis, balance=self.balance
        )


def sharded_matmul_grads(ctx: ShardedVJP, nnz, idx, a, b, g):
    """Both training cotangents on per-shard queues (see
    :class:`ShardedVJP`); callable eagerly like
    :func:`repro.runtime.autodiff.planned_matmul_grads`."""
    g32 = g.astype(jnp.float32)
    pg = _cot_plan(ctx, g32)
    # per-shard queues AND per-product tuned policy: each backward product
    # resolves its own lane width / grid family key (the transposed plan
    # generally wants a different geometry than the forward)
    bn_da, cg_da = ctx._bwd_policy(
        "matmul_da", g.shape[0], g.shape[1], b.shape[0], a.dtype, bn=ctx.bk
    )
    da = ctx._sharded_execute(
        ctx.bwd_backend, pg.nnz, pg.idx, g32, b.astype(jnp.float32).T,
        bm=ctx.bm, bk=ctx.bn, bn=bn_da, out_dtype=a.dtype,
        workqueue=ctx._plan_workqueue(pg, cg_da), axis="M",
        compact_grid=cg_da,
    )
    pt = _lhs_t_plan(ctx, nnz, idx, a)
    bn_db, cg_db = ctx._bwd_policy(
        "matmul_db", a.shape[1], a.shape[0], g.shape[1], b.dtype, bn=ctx.bn
    )
    db = ctx._sharded_execute(
        ctx.bwd_backend, pt.nnz, pt.idx, a.astype(jnp.float32).T, g32,
        bm=ctx.bk, bk=ctx.bm, bn=bn_db, out_dtype=b.dtype,
        workqueue=ctx._plan_workqueue(pt, cg_db), axis="N",
        compact_grid=cg_db,
    )
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def sharded_planned_matmul(ctx: ShardedVJP, nnz, idx, a, b):
    """Sharded planned ``a @ b`` with the sparsity-aware distributed VJP."""
    return ctx._sharded_execute(
        ctx.backend, nnz, idx, a, b,
        bm=ctx.bm, bk=ctx.bk, bn=ctx.bn, out_dtype=ctx.out_dtype,
        axis=ctx.axis,
    )


def _sharded_fwd(ctx, nnz, idx, a, b):
    return sharded_planned_matmul(ctx, nnz, idx, a, b), (nnz, idx, a, b)


def _sharded_bwd(ctx, res, g):
    nnz, idx, a, b = res
    da, db = sharded_matmul_grads(ctx, nnz, idx, a, b, g)
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int plan metadata
    return zero(nnz), zero(idx), da, db


sharded_planned_matmul.defvjp(_sharded_fwd, _sharded_bwd)


@dataclasses.dataclass(frozen=True)
class ShardedFusedVJP(ShardedVJP, FusedVJP):
    """Sharded twin of :class:`~repro.runtime.autodiff.FusedVJP`: the fused
    epilogue's differentiation rule (emitted-mask fast path included) with
    every product under ``shard_map``."""


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def sharded_fused_matmul(ctx: ShardedFusedVJP, nnz, idx, a, b, bias, residual):
    """Sharded planned ``act(a @ b + bias) + residual`` -> ``(out, mask)``
    with the sparsity-aware distributed VJP."""
    req = KernelRequest(
        nnz=nnz, idx=idx, a=a, b=b, bias=bias, residual=residual,
        bm=ctx.bm, bk=ctx.bk, bn=ctx.bn, activation=ctx.activation,
        out_dtype=ctx.out_dtype, compact_grid=ctx.compact_grid,
    )
    return sharded_execute_fused(
        ctx.backend, req, ctx.policy, axis=ctx.axis, balance=ctx.balance
    )


def _sfused_fwd(ctx, nnz, idx, a, b, bias, residual):
    out, mask = sharded_fused_matmul(ctx, nnz, idx, a, b, bias, residual)
    return (out, mask), (nnz, idx, a, b, bias, residual, out, mask)


def _sfused_bwd(ctx: ShardedFusedVJP, res, cots):
    nnz, idx, a, b, bias, residual, out, mask = res
    g, _ = cots  # the int8 mask output has a symbolic-zero cotangent
    g32 = g.astype(jnp.float32)
    y32 = out.astype(jnp.float32)
    if residual is not None and ctx.activation != "none":
        # same refusal as the single-device rule: act'(out - residual)
        # loses whole gradients to rounding, not ulps
        raise NotImplementedError(
            f"differentiating a fused {ctx.activation!r} epilogue with a "
            "residual is not supported: the backward cannot exactly recover "
            "the pre-residual activation from the stored output — apply the "
            "residual outside the kernel when training through it"
        )
    g_pre = ctx._act_grad(y32, g32)
    if ctx.mask_plans_cotangent and residual is None:
        pg = _mask_plan(ctx, mask)
        if ctx.cache is not None:
            ctx.cache.traced += int(isinstance(mask, jax.core.Tracer))
    else:
        pg = _cot_plan(ctx, g_pre)
    bn_da, cg_da = ctx._bwd_policy(
        "matmul_da", g.shape[0], g.shape[1], b.shape[0], a.dtype, bn=ctx.bk
    )
    da = ctx._sharded_execute(
        ctx.bwd_backend, pg.nnz, pg.idx, g_pre, b.astype(jnp.float32).T,
        bm=ctx.bm, bk=ctx.bn, bn=bn_da, out_dtype=a.dtype,
        workqueue=ctx._plan_workqueue(pg, cg_da), axis="M",
        compact_grid=cg_da,
    )
    pt = _lhs_t_plan(ctx, nnz, idx, a)
    bn_db, cg_db = ctx._bwd_policy(
        "matmul_db", a.shape[1], a.shape[0], g.shape[1], b.dtype, bn=ctx.bn
    )
    db = ctx._sharded_execute(
        ctx.bwd_backend, pt.nnz, pt.idx, a.astype(jnp.float32).T, g_pre,
        bm=ctx.bk, bk=ctx.bm, bn=bn_db, out_dtype=b.dtype,
        workqueue=ctx._plan_workqueue(pt, cg_db), axis="N",
        compact_grid=cg_db,
    )
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int plan metadata
    dbias = None if bias is None else jnp.sum(g_pre, axis=0).astype(bias.dtype)
    dres = None if residual is None else g.astype(residual.dtype)
    return zero(nnz), zero(idx), da, db, dbias, dres


sharded_fused_matmul.defvjp(_sfused_fwd, _sfused_bwd)


# ---------------------------------------------------------------------------
# Plan-level entry points (what Runtime.matmul_sharded dispatches).
# ---------------------------------------------------------------------------


def _validate_launch(plan: SparsityPlan, validate: str | None) -> None:
    """Gated static verification of a concrete plan at the distributed
    launch boundary (``Runtime(validate=...)``, ambient when unthreaded)."""
    if validate is None:
        from repro import runtime as rtm  # local: import cycle

        validate = rtm.resolve().validate
    if validate != "off":
        from repro.analysis.plan_check import check_plan  # local: keep import light

        check_plan(plan, level=validate)


def sharded_matmul(plan: SparsityPlan, a, b, *, bn: int, backend: str,
                   policy: ShardingPolicy, axis: str = "M",
                   balance: bool = True, out_dtype=None, plan_cache=None,
                   plan_key=None, grad_backend=None, compact_grid="ragged",
                   validate: str | None = None, db=None):
    """Sharded planned ``a @ b`` with the distributed sparsity-aware VJP —
    the ``shard_map`` twin of ``KernelBackend.matmul_planned`` (same
    concrete fast path skipping the custom_vjp machinery).  ``validate``
    (default: the ambient runtime's level) statically verifies a concrete
    plan before the distributed dispatch — the launch boundary where a
    corrupt queue would otherwise surface as a wrong answer on one shard."""
    if _all_concrete(plan.nnz, plan.idx, a, b):
        _validate_launch(plan, validate)
        req = KernelRequest(
            nnz=plan.nnz, idx=plan.idx, a=a, b=b,
            bm=plan.bm, bk=plan.bk, bn=bn,
            out_dtype=out_dtype, compact_grid=compact_grid,
            workqueue=plan.workqueue() if compact_grid == "ragged" else None,
        )
        return sharded_execute_planned(
            backend, req, policy, axis=axis, balance=balance
        )
    ctx = ShardedVJP(
        backend=backend, bm=plan.bm, bk=plan.bk, bn=bn, out_dtype=out_dtype,
        grad_backend=grad_backend, cache=plan_cache, key=plan_key,
        compact_grid=compact_grid, db=db,
        policy=policy, axis=axis, balance=balance,
    )
    return sharded_planned_matmul(ctx, plan.nnz, plan.idx, a, b)


def sharded_matmul_fused(plan: SparsityPlan, a, b, *, bias=None,
                         residual=None, activation: str = "none", bn: int,
                         backend: str, policy: ShardingPolicy,
                         axis: str = "M", balance: bool = True,
                         out_dtype=None, plan_cache=None, plan_key=None,
                         grad_backend=None, compact_grid="ragged",
                         validate: str | None = None, db=None):
    """Sharded fused matmul with the distributed VJP — the ``shard_map``
    twin of ``KernelBackend.matmul_fused``; returns ``(out, mask)``.
    ``validate`` as in :func:`sharded_matmul`."""
    if _all_concrete(plan.nnz, plan.idx, a, b, bias, residual):
        _validate_launch(plan, validate)
        req = KernelRequest(
            nnz=plan.nnz, idx=plan.idx, a=a, b=b,
            bias=bias, residual=residual, activation=activation,
            bm=plan.bm, bk=plan.bk, bn=bn,
            out_dtype=out_dtype, compact_grid=compact_grid,
            workqueue=plan.workqueue() if compact_grid == "ragged" else None,
        )
        return sharded_execute_fused(
            backend, req, policy, axis=axis, balance=balance
        )
    ctx = ShardedFusedVJP(
        backend=backend, bm=plan.bm, bk=plan.bk, bn=bn, out_dtype=out_dtype,
        grad_backend=grad_backend, cache=plan_cache, key=plan_key,
        activation=activation, compact_grid=compact_grid, db=db,
        policy=policy, axis=axis, balance=balance,
    )
    return sharded_fused_matmul(ctx, plan.nnz, plan.idx, a, b, bias, residual)
