"""TensorDash on TPU: work-compacted dynamic block-sparse matmul kernels.

This is the MXU-granularity adaptation of the paper's PE (DESIGN.md §2).
The element-level mechanism — *compact the effectual work stream at run time
with a restricted-movement interconnect* — becomes, at TPU block granularity:

1. ``plan_blocks`` (the "hardware scheduler"): from the sparse operand's
   runtime values, build per-M-block-row a *compacted* list of effectual
   K-block indices plus a count.  Compaction is an O(Kb) ``cumsum`` +
   scatter over the block-nonzero mask (the analogue of the Z-vector and
   priority encoders) — pure data movement of metadata, no sort.

2. The Pallas kernel (the "sparse interconnect"): the K grid dimension walks
   the compacted index list via scalar-prefetch index maps — the multiplexer
   that advances effectual blocks into the slots of ineffectual ones
   (lookahead across the whole K stream; unlike the 3-deep staging buffer the
   TPU's VMEM pipeline depth allows unbounded lookahead *within* a block row,
   but no lookaside across rows — block rows are independent, which is what
   keeps the interconnect "sparse" in the paper's sense).

3. **Grid compaction** (v2): the K grid dimension is bounded by the *dynamic*
   per-call ``max(nnz)`` (clamped to >= 1 so all-zero operands still zero
   the output) instead of the static ``Kb``.  Skipped blocks therefore cost
   **zero grid steps** — elided MACs buy wall-clock, the paper's "advance
   work in time" made real on TPU — and kernel time scales with block
   density.  Rows whose ``nnz`` is below the bound still ``pl.when``-gate
   their tail steps (their index maps re-reference the last effectual block,
   so the revisit elides the HBM->VMEM copy: the residual gating is
   power-gating, not time).  The v1 behaviour — full ``Kb`` grid, every
   skipped step gated but still issued — is kept behind
   ``compact_grid=False`` for A/B benchmarking (``spmm_compacted_micro``).

3b. **Ragged work-queue grid** (v3, the default): v2's bound is the per-call
   ``max(nnz)``, so one dense row drags every row back to dense cost —
   skewed sparsity (the common case for trained activations/gradients) pays
   ``Mb * max(nnz)`` steps for ``sum(nnz)`` work.  v3 flattens the plan into
   a CSR-style work queue (:func:`plan_workqueue`): ``row_starts =
   cumsum(max(nnz, 1))`` plus flat ``work_row[t]`` / ``work_kblk[t]`` lists,
   one entry per *effectual* block (all-zero rows keep one gated entry so
   their output still zero-fills).  The kernel then issues a
   ``(Nb, total_work)`` grid whose scalar-prefetch index maps derive
   ``(m_i, k_idx)`` per step; the accumulator zeroes at ``t ==
   row_starts[m]`` and stores at ``t == row_starts[m+1] - 1``.  Kernel steps
   equal effectual blocks *exactly*, independent of skew — wall-clock is
   ``O(sum(nnz))``, not ``O(Mb * max(nnz))`` — and per-row accumulation
   order is unchanged (ascending plan order), so v3 is bit-identical to v2
   and v1 (``spmm_ragged_micro`` gates the skew win in CI).

4. **Fused epilogues + emitted output plans** (§3.7 backside scheduler):
   :func:`tensordash_matmul_fused` applies bias + activation (+ optional
   residual add + out-dtype cast) inside the store step — no HBM round-trip
   between an FFN's two matmuls — and emits the block-nonzero mask of its
   *output* as a second, cheap ``int8 [Mb, Nb]`` result.  That mask is the
   backside scheduler's product: the op that *wrote* the operand hands its
   consumer the schedule, so the consumer's :func:`plan_from_mask` is a pure
   metadata transform (no pass over the values) — replanning the FFN
   intermediate, and the backward G-stream through a ReLU-family epilogue,
   becomes free.

Measured density→speedup (interpret-mode grid steps, 128x256x64 @ bm=16,
bk=32, bn=16, uniform per-row nnz): density 1.0 → 1.0x, 0.5 → 2.0x,
0.25 → 4.0x, 0.05 → 8.0x (wall-clock tracks step count; see
``spmm_compacted_micro``).  Raggedness costs: the grid bound is the *max*
row count, so rows below the max ride along gated — worst case (one dense
row) degrades to v1, never below it.

The kernels compute ``C[M, N] = A[M, K] @ B[K, N]`` where ``A`` is the
dynamically-sparse operand stream (activations / gradients in the paper's
three training convolutions).  Numerical fidelity is untouched: only
multiplications by all-zero blocks are elided.

VMEM budget (defaults, fp32): A block 128x512 (256 KB) + B block 512x128
(256 KB) + C block 128x128 (64 KB) + fp32 accumulator (64 KB) < 1 MB, well
inside the ~16 MB VMEM of a TPU core; all dims are multiples of the MXU's
128 and the fp32 sublane tile (8, 128).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "COMPACT_GRID_MODES",
    "CompactGrid",
    "plan_blocks",
    "plan_blocks_csr",
    "plan_to_mask",
    "plan_from_mask",
    "plan_from_mask_csr",
    "plan_workqueue",
    "dense_plan",
    "dense_plan_csr",
    "transpose_plan",
    "transpose_plan_csr",
    "planned_grid_steps",
    "tensordash_matmul_planned",
    "tensordash_matmul_fused",
    "tensordash_matmul",
]

#: epilogue activations the fused kernel understands (statically selected)
FUSED_ACTIVATIONS = ("none", "relu", "squared_relu")


#: valid ``compact_grid`` modes: v3 ragged work queue / v2 max(nnz) bound /
#: v1 full gated grid
COMPACT_GRID_MODES = ("ragged", "v2", "v1")

#: the normalized grid-family type every layer carries after
#: :func:`_check_compact_grid` (legacy ``True``/``False`` normalize to
#: ``"v2"``/``"v1"`` at entry, so jit static-arg caches see one canonical
#: value per mode)
CompactGrid = Literal["ragged", "v2", "v1"]


def _check_compact_grid(value) -> CompactGrid:
    """Normalize a grid-mode value to its canonical literal, rejecting
    anything unrecognized loudly: a stray truthy value (a typo'd string, a
    future mode name) dispatched by truthiness would silently select the v2
    branch — numerically correct, so the user would never notice they lost
    the skew-immune v3 behavior they asked for.  Legacy boolean spellings
    (``True`` = v2, ``False`` = v1) are accepted and normalized, so every
    downstream dispatch can compare against the literals alone."""
    if isinstance(value, str) and value in COMPACT_GRID_MODES:
        return value
    if value is True:
        return "v2"
    if value is False:
        return "v1"
    raise ValueError(
        f"compact_grid={value!r} not one of {COMPACT_GRID_MODES} "
        '("ragged" = v3 work queue, "v2"/True = max(nnz) grid, '
        '"v1"/False = full gated grid)'
    )


def _mask_to_plan_argsort(nonzero: jax.Array):
    """Legacy argsort-based compaction (v1) — kept as the equality oracle
    for :func:`_mask_to_plan` and the ``plan_cache_micro`` planning-time
    A/B; new code should call :func:`_mask_to_plan`."""
    kb = nonzero.shape[1]
    nnz = jnp.sum(nonzero, axis=1).astype(jnp.int32)  # [Mb]
    # stable sort: effectual block ids first, in ascending k order
    order = jnp.argsort(~nonzero, axis=1, stable=True).astype(jnp.int32)
    pos = jnp.arange(kb, dtype=jnp.int32)[None, :]
    last = jnp.maximum(nnz - 1, 0)[:, None]
    idx = jnp.where(pos < jnp.maximum(nnz, 1)[:, None], order, jnp.take_along_axis(order, last, axis=1))
    return nnz, idx


@jax.jit
def _mask_to_plan(nonzero: jax.Array):
    """Compact a block-nonzero mask ``[Mb, Kb]`` into ``(nnz, idx)``.

    O(Kb) per row: a ``cumsum`` assigns each effectual block its compacted
    slot, a scatter writes it (ineffectual blocks are dropped out of
    bounds), and the tail repeats the last effectual index so revisited
    grid steps hit a resident block.  Bit-identical to the legacy argsort
    path (ascending effectual order is what the cumsum produces naturally)
    at ~O(Kb log Kb) less work — the delta is visible in
    ``plan_cache_micro``'s derived string.  Jitted: plan compaction is one
    dispatch, which is what keeps the emitted-mask path's metadata
    replanning off the hot path's dispatch budget.
    """
    mb, kb = nonzero.shape
    nonzero = nonzero != 0  # accept bool or int8 masks
    nnz = jnp.sum(nonzero, axis=1).astype(jnp.int32)  # [Mb]
    slot = jnp.cumsum(nonzero, axis=1, dtype=jnp.int32) - 1  # target slot per k
    rows = jnp.arange(mb, dtype=jnp.int32)[:, None]
    ks = jnp.broadcast_to(jnp.arange(kb, dtype=jnp.int32)[None, :], (mb, kb))
    idx = jnp.zeros((mb, kb), jnp.int32).at[
        rows, jnp.where(nonzero, slot, kb)
    ].set(ks, mode="drop")
    pos = jnp.arange(kb, dtype=jnp.int32)[None, :]
    last = jnp.take_along_axis(idx, jnp.maximum(nnz - 1, 0)[:, None], axis=1)
    idx = jnp.where(pos < jnp.maximum(nnz, 1)[:, None], idx, last)
    return nnz, idx


def plan_blocks(a: jax.Array, bm: int, bk: int):
    """Runtime block scheduler: compacted effectual K-block lists.

    Returns ``(nnz [Mb] int32, idx [Mb, Kb] int32)`` where ``idx[m, :nnz[m]]``
    are the K-block indices (ascending) whose ``bm x bk`` block of ``a`` is
    not entirely zero; the tail repeats the last effectual index (or 0) so
    skipped grid steps revisit a resident block.
    """
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0, (a.shape, bm, bk)
    mb, kb = m // bm, k // bk
    blocks = a.reshape(mb, bm, kb, bk)
    nonzero = jnp.any(blocks != 0, axis=(1, 3))  # [Mb, Kb]
    return _mask_to_plan(nonzero)


@jax.jit
def plan_workqueue(nnz: jax.Array, idx: jax.Array):
    """Flatten a ``(nnz, idx)`` plan into the v3 CSR-style work queue.

    Returns ``(row_starts [Mb+1], work_row [Mb*Kb], work_kblk [Mb*Kb])``,
    all int32: work item ``t`` in ``[row_starts[m], row_starts[m+1])``
    belongs to block row ``m`` and contracts K block ``work_kblk[t] =
    idx[m, t - row_starts[m]]``.  Every row owns at least one item
    (``max(nnz, 1)``) so an all-zero row still gets a gated step that
    zero-fills its output; ``row_starts[-1]`` is the total work — the exact
    number of grid steps the ragged kernel issues per N block.  The flat
    arrays are statically ``Mb * Kb`` long (the dense worst case, the same
    footprint as ``idx``); the tail past ``row_starts[-1]`` is never
    visited.  Pure metadata — O(Mb*Kb) elementwise work, no pass over the
    operand values, one fused dispatch — so deriving the queue from an
    emitted mask or a transposed plan stays allocation-pattern-identical to
    v2 planning.

    The queue invariants this construction guarantees (every effectual MAC
    lands exactly once; see the list in
    :mod:`repro.analysis.plan_check`) are statically checkable:
    ``repro.analysis.verify_plan`` proves them for a concrete plan and
    ``repro.analysis.check_grid`` re-enacts this grid's predicates on a
    hand-built (or corrupted) queue.
    """
    mb, kb = idx.shape
    flat = mb * kb
    work = jnp.maximum(nnz, 1).astype(jnp.int32)  # [Mb] items per row
    row_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(work, dtype=jnp.int32)]
    )
    j = jnp.arange(kb, dtype=jnp.int32)[None, :]
    # scatter item (m, j) to flat slot row_starts[m] + j; surplus j >= work[m]
    # drops out of bounds
    pos = jnp.where(j < work[:, None], row_starts[:-1, None] + j, flat)
    rows = jnp.broadcast_to(jnp.arange(mb, dtype=jnp.int32)[:, None], (mb, kb))
    work_row = (
        jnp.zeros((flat,), jnp.int32).at[pos.reshape(-1)].set(rows.reshape(-1), mode="drop")
    )
    work_kblk = (
        jnp.zeros((flat,), jnp.int32).at[pos.reshape(-1)].set(idx.reshape(-1), mode="drop")
    )
    return row_starts, work_row, work_kblk


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def plan_blocks_csr(a: jax.Array, bm: int, bk: int):
    """:func:`plan_blocks` plus the v3 work queue, in one fused dispatch.

    Returns ``(nnz, idx, row_starts, work_row, work_kblk)`` — the full
    :class:`~repro.runtime.plan.SparsityPlan` payload.  One jitted program
    (mask reduction, compaction and queue flattening all inline into this
    trace) vs the two+ dispatches of ``plan_blocks`` followed by
    :func:`plan_workqueue`.
    """
    nnz, idx = plan_blocks(a, bm, bk)
    return (nnz, idx) + plan_workqueue(nnz, idx)


def plan_to_mask(nnz: jax.Array, idx: jax.Array) -> jax.Array:
    """Recover the block-nonzero mask ``[Mb, Kb]`` a plan was compacted from.

    The compaction is lossless: ``idx[r, :nnz[r]]`` lists exactly the
    effectual blocks, so the mask — and hence any re-blocked plan — can be
    reconstructed from metadata alone, without another pass over the data.
    """
    mb, kb = idx.shape
    valid = jnp.arange(kb, dtype=jnp.int32)[None, :] < nnz[:, None]
    mask = jnp.zeros((mb, kb), bool)
    return mask.at[jnp.arange(mb)[:, None], idx].max(valid)


@functools.partial(jax.jit, static_argnames=("coarsen",))
def plan_from_mask(mask: jax.Array, *, coarsen: int = 1):
    """Plan ``(nnz, idx)`` from an emitted block-nonzero mask — metadata only.

    ``mask`` is the ``[Mb, Nb]`` int8/bool second output of
    :func:`tensordash_matmul_fused` (the backside scheduler's product,
    §3.7).  ``coarsen`` groups that many adjacent mask columns into one
    consumer K block (the consumer may contract with ``bk`` a multiple of
    the producer's ``bn``); a coarse block is effectual iff any member is.
    No pass over the operand values is made.
    """
    mb, nb = mask.shape
    if nb % coarsen:
        raise ValueError(f"mask with {nb} columns cannot coarsen by {coarsen}")
    nonzero = mask != 0
    if coarsen > 1:
        nonzero = jnp.any(nonzero.reshape(mb, nb // coarsen, coarsen), axis=2)
    return _mask_to_plan(nonzero)


@functools.partial(jax.jit, static_argnames=("coarsen",))
def plan_from_mask_csr(mask: jax.Array, *, coarsen: int = 1):
    """:func:`plan_from_mask` plus the v3 work queue, one fused dispatch.

    The emitted-mask replanning path stays a single jitted program (and the
    same allocation pattern as v2 planning — the queue arrays are the
    ``idx``-sized metadata the plan already carries, flattened): the §3.7
    backside scheduler hands its consumer the *ragged* schedule for free.
    """
    nnz, idx = plan_from_mask(mask, coarsen=coarsen)
    return (nnz, idx) + plan_workqueue(nnz, idx)


@functools.lru_cache(maxsize=256)
def dense_plan(mb: int, kb: int):
    """The trivial all-effectual plan — pure metadata (no operand pass).

    For a known-dense stream (e.g. the FFN input feeding the fused first
    matmul) the full plan is just ``nnz = Kb`` and ``idx = arange``; the
    compacted grid then degenerates to the dense grid, as it must.
    Memoized per geometry: repeated decode/FFN calls at one shape pay zero
    dispatches for it.  Returns *numpy* arrays: they are valid operands for
    every executor, and caching them can never capture a tracer when the
    first call happens inside a ``jit``/``scan`` trace.
    """
    nnz = np.full((mb,), kb, np.int32)
    idx = np.ascontiguousarray(
        np.broadcast_to(np.arange(kb, dtype=np.int32), (mb, kb))
    )
    # shared by every caller at this geometry: freeze so an in-place edit
    # raises instead of silently corrupting the cached schedule
    nnz.flags.writeable = False
    idx.flags.writeable = False
    return nnz, idx


@functools.lru_cache(maxsize=256)
def dense_plan_csr(mb: int, kb: int):
    """:func:`dense_plan` plus its (closed-form) v3 work queue — numpy,
    memoized per geometry, zero dispatches: the dense queue is just every
    ``(m, k)`` pair in row-major order with ``row_starts = m * Kb``."""
    nnz, idx = dense_plan(mb, kb)
    row_starts = np.arange(mb + 1, dtype=np.int32) * kb
    work_row = np.repeat(np.arange(mb, dtype=np.int32), kb)
    work_kblk = np.ascontiguousarray(
        np.broadcast_to(np.arange(kb, dtype=np.int32), (mb, kb))
    ).reshape(-1)
    for arr in (row_starts, work_row, work_kblk):
        arr.flags.writeable = False
    return nnz, idx, row_starts, work_row, work_kblk


def transpose_plan(nnz: jax.Array, idx: jax.Array):
    """Plan of ``a.T`` (blocks ``bk x bm``) from the plan of ``a``.

    The backward pass needs the weight-gradient product ``a.T @ g`` (paper
    Eq. 3) planned over ``a.T``; its block-nonzero mask is just the transpose
    of ``a``'s, so the transposed plan is a pure metadata transform — the
    software analogue of the paper's backside scheduler emitting the
    transposed schedule alongside the forward one (§3.7).
    """
    return _mask_to_plan(plan_to_mask(nnz, idx).T)


@jax.jit
def transpose_plan_csr(nnz: jax.Array, idx: jax.Array):
    """:func:`transpose_plan` plus the transposed plan's v3 work queue —
    still a pure metadata transform (one fused dispatch), so the backward
    weight-gradient product (paper Eq. 3) rides the ragged grid without a
    second pass over ``a``."""
    nnz_t, idx_t = _mask_to_plan(plan_to_mask(nnz, idx).T)
    return (nnz_t, idx_t) + plan_workqueue(nnz_t, idx_t)


def planned_grid_steps(nnz, kb: int, mb: int, nb: int, *, compact_grid="ragged") -> int:
    """Grid steps the planned kernel will issue — the "time" the paper's
    scheduler buys.  v1 (``compact_grid="v1"``) always issues the full
    ``Mb * Nb * Kb``; v2 (``"v2"``) issues ``Mb * Nb * max(nnz, 1)``; v3
    (``"ragged"``) issues ``Nb * sum(max(nnz, 1))`` — effectual blocks
    exactly (plus one gated zero-fill step per all-zero row), independent
    of skew.

    Concrete plans only (this is a benchmark/report helper, not a kernel
    primitive): the counts are computed host-side from ``nnz`` in one
    device fetch.  Under ``jit``/``grad`` the plan is a tracer and the
    reduction would silently block on the device — raise a clear error
    instead; call this outside the traced region, or use
    ``SparsityPlan.grid_steps`` which serves cached host-side stats.
    """
    compact_grid = _check_compact_grid(compact_grid)
    if isinstance(nnz, jax.core.Tracer):
        raise TypeError(
            "planned_grid_steps needs a concrete plan: nnz is a tracer "
            "(inside jit/grad/scan), and counting grid steps would force a "
            "blocking device sync mid-trace — compute step counts outside "
            "the traced region (e.g. via SparsityPlan.grid_steps, which "
            "caches host-side plan stats)"
        )
    nnz_h = np.asarray(nnz)
    if compact_grid == "ragged":
        return nb * int(np.maximum(nnz_h, 1).sum())
    kdim = kb if compact_grid == "v1" else max(int(nnz_h.max(initial=0)), 1)
    return mb * nb * kdim


def _kernel(nnz_ref, idx_ref, a_ref, b_ref, o_ref, acc_ref):
    m_i = pl.program_id(0)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Effectual step: accumulate this block's contribution on the MXU.
    @pl.when(k_i < nnz_ref[m_i])
    def _mac():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    # num_programs(2) is the (possibly dynamic) compacted K bound.
    @pl.when(k_i == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _epilogue(acc, bias_blk, res_blk, activation: str):
    """Shared fp32 epilogue: bias -> activation -> residual.  The emitted
    mask is computed on this fp32 value (pre-cast), so a block the cast
    rounds to zero still reads as effectual — conservative, never wrong."""
    out = acc
    if bias_blk is not None:
        out = out + bias_blk
    if activation == "relu":
        out = jnp.maximum(out, 0.0)
    elif activation == "squared_relu":
        out = jnp.square(jnp.maximum(out, 0.0))
    elif activation != "none":
        raise ValueError(f"unknown fused activation {activation!r}")
    if res_blk is not None:
        # Parity note: for "none"/"relu" the residual add follows an add/max
        # and is bitwise identical across backends.  For "squared_relu" the
        # square's multiply feeds this add and XLA:CPU may contract the pair
        # into an FMA inside the staged kernel (optimization_barrier does
        # not survive Pallas staging), so that one combination is within
        # 1 ulp of the reference executor rather than bitwise.
        out = out + res_blk
    return out


#: lanes per emitted-mask block.  The chip only tiles blocks whose last two
#: dims are (8, 128)-aligned or span the array, so each ``(m, n)`` flag is
#: stored as one ``[1, 128]`` int32 row of a ``[Mb, 1, Nb * 128]`` buffer
#: and the wrapper reads every 128th lane back into the ``int8 [Mb, Nb]``
#: mask callers see.
_MASK_LANES = 128


def _store_mask(mask_ref, out):
    """Store the block-nonzero flag of the fp32 epilogue value ``out``."""
    flag = jnp.max(jnp.where(out != 0, 1, 0).astype(jnp.int32))
    mask_ref[...] = jnp.full(mask_ref.shape, flag, jnp.int32)


def _fused_kernel(nnz_ref, idx_ref, a_ref, b_ref, *rest,
                  activation: str, has_bias: bool, has_residual: bool):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    res_ref = rest.pop(0) if has_residual else None
    o_ref, mask_ref, acc_ref = rest
    m_i = pl.program_id(0)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_i < nnz_ref[m_i])
    def _mac():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k_i == pl.num_programs(2) - 1)
    def _store():
        out = _epilogue(
            acc_ref[...],
            bias_ref[...] if has_bias else None,
            res_ref[...].astype(jnp.float32) if has_residual else None,
            activation,
        )
        _store_mask(mask_ref, out)
        o_ref[...] = out.astype(o_ref.dtype)


def _ragged_kernel(nnz_ref, rs_ref, wr_ref, wk_ref, a_ref, b_ref, o_ref, acc_ref):
    """v3 work-queue kernel: grid ``(Nb, total_work)``; step ``t`` is one
    effectual block of row ``wr_ref[t]`` (or the single gated zero-fill item
    of an all-zero row).  Per-row accumulation order is ascending plan
    order, exactly as v1/v2 — bit-identical outputs."""
    t = pl.program_id(1)
    m_i = wr_ref[t]

    @pl.when(t == rs_ref[m_i])
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # All queue items of a row with nnz > 0 are effectual by construction;
    # the only gated item is an all-zero row's zero-fill placeholder.
    @pl.when(nnz_ref[m_i] > 0)
    def _mac():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(t == rs_ref[m_i + 1] - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ragged_fused_kernel(nnz_ref, rs_ref, wr_ref, wk_ref, a_ref, b_ref, *rest,
                         activation: str, has_bias: bool, has_residual: bool):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    res_ref = rest.pop(0) if has_residual else None
    o_ref, mask_ref, acc_ref = rest
    t = pl.program_id(1)
    m_i = wr_ref[t]

    @pl.when(t == rs_ref[m_i])
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(nnz_ref[m_i] > 0)
    def _mac():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(t == rs_ref[m_i + 1] - 1)
    def _store():
        out = _epilogue(
            acc_ref[...],
            bias_ref[...] if has_bias else None,
            res_ref[...].astype(jnp.float32) if has_residual else None,
            activation,
        )
        _store_mask(mask_ref, out)
        o_ref[...] = out.astype(o_ref.dtype)


def _ragged_grid_and_maps(nnz, idx, nb: int, workqueue):
    """v3 grid geometry: a flat ``(Nb, total_work)`` grid over the CSR work
    queue.  ``total_work = row_starts[-1] = sum(max(nnz, 1))`` is dynamic
    per call; the scalar-prefetch index maps dereference the queue to place
    each step at ``(work_row[t], work_kblk[t])``.  The queue is derived from
    ``(nnz, idx)`` in-graph when the caller has none cached (a pure metadata
    transform XLA hoists out of loops), or reused verbatim from the
    :class:`~repro.runtime.plan.SparsityPlan` that carries it.

    The index arithmetic here is mirrored host-side by
    :func:`repro.analysis.grid_check.check_grid` (``compact_grid="ragged"``),
    which proves in-bounds access, store-exactly-once, and
    zero-before-accumulate for a concrete queue — keep the two in sync."""
    if workqueue is None:
        workqueue = plan_workqueue(nnz, idx)
    row_starts, work_row, work_kblk = workqueue
    grid = (nb, row_starts[-1])

    def a_map(n_i, t, nnz_ref, rs_ref, wr_ref, wk_ref):
        del n_i, nnz_ref, rs_ref
        return (wr_ref[t], wk_ref[t])

    def b_map(n_i, t, nnz_ref, rs_ref, wr_ref, wk_ref):
        del nnz_ref, rs_ref, wr_ref
        return (wk_ref[t], n_i)

    def o_map(n_i, t, nnz_ref, rs_ref, wr_ref, wk_ref):
        del nnz_ref, rs_ref, wk_ref
        return (wr_ref[t], n_i)

    return (row_starts, work_row, work_kblk), grid, a_map, b_map, o_map


def _grid_and_maps(nnz, mb: int, nb: int, kb: int, compact_grid: CompactGrid):
    """Common v1/v2 grid geometry: the K dimension is the dynamic compacted
    bound ``max(nnz)`` (>= 1 so the zero accumulator still stores) or the
    static Kb.  ``compact_grid`` is the normalized literal (``"v2"``/``"v1"``
    — never a bool, and never dispatched by truthiness: ``"v1"`` is truthy)."""
    kdim = jnp.maximum(jnp.max(nnz), 1) if compact_grid == "v2" else kb
    grid = (mb, nb, kdim)

    def a_map(m_i, n_i, k_i, nnz_ref, idx_ref):
        del n_i, nnz_ref
        return (m_i, idx_ref[m_i, k_i])

    def b_map(m_i, n_i, k_i, nnz_ref, idx_ref):
        del nnz_ref
        return (idx_ref[m_i, k_i], n_i)

    def o_map(m_i, n_i, k_i, nnz_ref, idx_ref):
        del k_i, nnz_ref, idx_ref
        return (m_i, n_i)

    return grid, a_map, b_map, o_map


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype", "compact_grid"),
)
def tensordash_matmul_planned(
    nnz: jax.Array,
    idx: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=None,
    compact_grid="ragged",
    workqueue=None,
):
    """Block-sparse ``a @ b`` given a precomputed block plan (see
    :func:`plan_blocks`).  Splitting planning from execution lets the plan be
    produced by the *backside scheduler* (paper §3.7): e.g. the op that wrote
    ``a`` emits the plan alongside, so consumers skip the replanning pass.

    ``compact_grid`` selects the grid family — all three execute the same
    per-row schedule and are bit-identical:

    * ``"ragged"`` (default, v3): flat ``(Nb, total_work)`` work-queue grid;
      steps equal effectual blocks exactly (``O(sum(nnz))``), skew-immune.
      ``workqueue`` optionally supplies the precomputed
      ``(row_starts, work_row, work_kblk)`` triple (e.g. from a
      ``SparsityPlan`` that carries it); otherwise it is derived in-graph.
    * ``"v2"``: ``(Mb, Nb, max(nnz))`` grid — one dense row drags every
      row to dense cost.
    * ``"v1"``: full ``(Mb, Nb, Kb)`` gated grid, for A/B baselines.

    Legacy boolean spellings (``True`` = v2, ``False`` = v1) normalize at
    entry (:func:`_check_compact_grid`).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (a.shape, b.shape, bm, bk, bn)
    mb, kb, nb = m // bm, k // bk, n // bn
    out_dtype = out_dtype or a.dtype

    compact_grid = _check_compact_grid(compact_grid)
    if compact_grid == "ragged":
        wq, grid, a_map, b_map, o_map = _ragged_grid_and_maps(nnz, idx, nb, workqueue)
        operands = (nnz,) + wq + (a, b)
        kernel, num_prefetch = _ragged_kernel, 4
        semantics = ("parallel", "arbitrary")
    else:
        grid, a_map, b_map, o_map = _grid_and_maps(nnz, mb, nb, kb, compact_grid)
        operands = (nnz, idx, a, b)
        kernel, num_prefetch = _kernel, 2
        semantics = ("parallel", "parallel", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="tensordash_matmul_planned",
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "bm", "bk", "bn", "interpret", "out_dtype",
                     "compact_grid"),
)
def tensordash_matmul_fused(
    nnz: jax.Array,
    idx: jax.Array,
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    activation: str = "none",
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=None,
    compact_grid="ragged",
    workqueue=None,
):
    """Planned ``act(a @ b + bias) + residual`` with the epilogue fused into
    the store step, plus the emitted output plan.

    Returns ``(out [M, N], mask int8 [M/bm, N/bn])``.  The epilogue runs on
    the fp32 accumulator — one store to HBM instead of a matmul round-trip
    followed by elementwise passes — and the mask is the block-nonzero map
    of the fp32 epilogue value: the §3.7 backside scheduler emitting the
    *consumer's* schedule alongside the producer's data.  Feed it to
    :func:`plan_from_mask` to plan the next matmul without touching values.
    ``compact_grid``/``workqueue`` select the grid family exactly as in
    :func:`tensordash_matmul_planned` (default: the v3 ragged work queue).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (a.shape, b.shape, bm, bk, bn)
    if activation not in FUSED_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} not in {FUSED_ACTIVATIONS}")
    mb, kb, nb = m // bm, k // bk, n // bn
    out_dtype = out_dtype or a.dtype

    compact_grid = _check_compact_grid(compact_grid)
    if compact_grid == "ragged":
        wq, grid, a_map, b_map, o_map = _ragged_grid_and_maps(nnz, idx, nb, workqueue)
        operands = list((nnz,) + wq + (a, b))
        base_kernel, num_prefetch = _ragged_fused_kernel, 4
        semantics = ("parallel", "arbitrary")

        def bias_map(n_i, t, nnz_ref, rs_ref, wr_ref, wk_ref):
            del t, nnz_ref, rs_ref, wr_ref, wk_ref
            return (0, n_i)
    else:
        grid, a_map, b_map, o_map = _grid_and_maps(nnz, mb, nb, kb, compact_grid)
        operands = [nnz, idx, a, b]
        base_kernel, num_prefetch = _fused_kernel, 2
        semantics = ("parallel", "parallel", "arbitrary")

        def bias_map(m_i, n_i, k_i, nnz_ref, idx_ref):
            del m_i, k_i, nnz_ref, idx_ref
            return (0, n_i)

    in_specs = [
        pl.BlockSpec((bm, bk), a_map),
        pl.BlockSpec((bk, bn), b_map),
    ]
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), bias_map))
        operands.append(bias.astype(jnp.float32).reshape(1, n))
    if residual is not None:
        assert residual.shape == (m, n), (residual.shape, (m, n))
        in_specs.append(pl.BlockSpec((bm, bn), o_map))
        operands.append(residual)

    def mask_map(*args):  # the output block (m_i, n_i), lane-dense
        m_i, n_i = o_map(*args)
        return (m_i, 0, n_i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, bn), o_map),
            pl.BlockSpec((pl.squeezed, 1, _MASK_LANES), mask_map),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(
        base_kernel,
        activation=activation,
        has_bias=bias is not None,
        has_residual=residual is not None,
    )
    out, mask = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((mb, 1, nb * _MASK_LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="tensordash_matmul_fused",
    )(*operands)
    return out, mask[:, 0, ::_MASK_LANES].astype(jnp.int8)


def tensordash_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=None,
    compact_grid="ragged",
):
    """Dynamic block-sparse ``a @ b``: plan at run time, then execute."""
    nnz, idx = plan_blocks(a, bm, bk)
    return tensordash_matmul_planned(
        nnz, idx, a, b, bm=bm, bk=bk, bn=bn, interpret=interpret,
        out_dtype=out_dtype, compact_grid=compact_grid,
    )
