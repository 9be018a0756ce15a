"""First-class block-sparsity plans + a keyed plan cache.

A :class:`SparsityPlan` promotes the raw ``(nnz, idx)`` pair produced by
``repro.kernels.tensordash_spmm.plan_blocks`` to an object that carries its
own block geometry, the shape/dtype of the operand it was planned for, and
measured density statistics.  It is the software analogue of the paper's
hardware scheduler output (the compacted effectual-work stream, §3.1): the
schedule is *data*, separable from execution, so it can be produced once and
replayed many times.

:class:`PlanCache` is the amortization mechanism (paper §3.7, the backside
scheduler): a keyed cache so a plan computed once — e.g. at serving prefill
for a static sparse weight — is reused across every subsequent decode step
instead of being recomputed per token.  Cache hits are validated by operand
*identity* (``entry.source is operand``), so a hit is always numerically
exact: the plan can only be replayed against the very array it was computed
from.  Plans are never cached for traced values (inside ``jit``/``scan``
the plan is part of the traced program and caching it would leak tracers).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SparsityPlan",
    "PlanShards",
    "PlanCache",
    "plan_operand",
    "plan_from_emitted_mask",
    "dense_operand_plan",
    "balanced_row_order",
    "shard_plan",
    "unshard_plan",
]


#: the TPU's lane width: a kernel block dim must be a multiple of it (the
#: sublane dim of 8 divides it) or span the whole array dim
LANE = 128


def _fit_block(block: int, dim: int) -> int:
    """Largest divisor of ``dim`` that is <= ``block`` (always >= 1).

    The clamp for blockings of a fixed tensor that cannot be padded: the
    dynamic-sparsity weight masks, the tuner's candidate lattice and the
    replanning of a corrupt plan.  Execution geometry goes through
    :func:`_tile_block` instead.
    """
    b = max(1, min(block, dim))
    while dim % b:
        b -= 1
    return b


def _tile_block(block: int, dim: int) -> int:
    """Chip-legal tile for ``dim`` at the target ``block``.

    The whole dim when it fits the target; otherwise the largest multiple
    of ``align = min(block, LANE)`` that is <= ``block`` and divides ``dim``
    rounded up to ``align``.  A target of at least :data:`LANE` therefore
    always yields a multiple of 128 or the full dim, which the TPU compiler
    accepts in either position of a 2-D block.  Where the tile does not
    divide ``dim`` the executor zero-pads the operand to a tile multiple
    (:func:`_round_up`): the padding adds only all-zero blocks, which the
    plan skips, so results are those of the unpadded product.  Targets
    under :data:`LANE` are interpreter-only test geometries.
    """
    if dim <= block:
        return dim
    align = min(block, LANE)
    padded = _round_up(dim, align)
    b = block // align * align
    while padded % b:
        b -= align
    return b


def _round_up(dim: int, block: int) -> int:
    return -(-dim // block) * block


def _pad_to(x, shape):
    """``x`` zero-padded at the end of each dim up to ``shape`` (``x``
    itself when it already has that shape)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, shape)])


@dataclasses.dataclass(frozen=True)
class SparsityPlan:
    """Compacted effectual-block schedule for one 2-D operand.

    ``idx[r, :nnz[r]]`` lists (ascending) the effectual K-block indices of
    block-row ``r`` of the planned operand; the tail repeats the last
    effectual index so skipped grid steps revisit a resident block.

    ``row_starts`` / ``work_row`` / ``work_kblk`` are the CSR-style v3 work
    queue (``repro.kernels.tensordash_spmm.plan_workqueue``): the same
    schedule flattened to one entry per effectual block, which the ragged
    kernel walks as a ``(Nb, total_work)`` grid.  Plans built by the
    planning entry points carry the queue from birth (one fused dispatch);
    hand-rolled plans get it lazily via :meth:`workqueue`.

    ``side`` records which matmul operand the plan describes: ``"A"`` plans
    the left operand ``a [M, K]`` with ``(bm, bk)`` blocks; ``"B"`` plans
    the *transposed* right operand ``b.T [N, K]`` (weight sparsity), so the
    planned block rows run over N.
    """

    nnz: Any  # [Rb] int32
    idx: Any  # [Rb, Kb] int32
    bm: int  # block rows of the planned operand
    bk: int  # block size along the contraction dim
    shape: tuple[int, int]  # shape of the planned operand (post-transpose for B)
    dtype: Any
    side: str = "A"
    row_starts: Any = None  # [Rb+1] int32 CSR offsets (v3 work queue)
    work_row: Any = None  # [Rb*Kb] int32 block row per work item
    work_kblk: Any = None  # [Rb*Kb] int32 K block per work item
    #: host-side stat cache (max/sum of nnz etc.) — populated on first use,
    #: excluded from equality/repr; one device fetch amortized over every
    #: report/benchmark query on this plan
    _host: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False
    )

    @property
    def block_rows(self) -> int:
        return self.shape[0] // self.bm

    @property
    def k_blocks(self) -> int:
        return self.shape[1] // self.bk

    @property
    def total_blocks(self) -> int:
        return self.block_rows * self.k_blocks

    def workqueue(self):
        """The ``(row_starts, work_row, work_kblk)`` triple, deriving (and
        memoizing, for concrete plans) it when the plan was built without
        one.  A pure metadata transform either way — never a values pass."""
        if self.row_starts is None:
            from repro.kernels.tensordash_spmm import plan_workqueue  # local: keep import light

            rs, wr, wk = plan_workqueue(self.nnz, self.idx)
            if not isinstance(rs, jax.core.Tracer):
                # frozen dataclass: memoize via object.__setattr__ (plans
                # under trace are per-trace objects; don't pin tracers)
                object.__setattr__(self, "row_starts", rs)
                object.__setattr__(self, "work_row", wr)
                object.__setattr__(self, "work_kblk", wk)
            return rs, wr, wk
        return self.row_starts, self.work_row, self.work_kblk

    def host_nnz(self):
        """``nnz`` as a cached host-side numpy array (concrete plans only).

        Every stat below derives from this one fetch; under tracing the
        counts are symbolic and fetching would silently block mid-trace, so
        raise a clear error instead.
        """
        if "nnz" not in self._host:
            if isinstance(self.nnz, jax.core.Tracer):
                raise TypeError(
                    "plan stats need a concrete plan: nnz is a tracer "
                    "(inside jit/grad/scan) — query stats outside the "
                    "traced region"
                )
            self._host["nnz"] = np.asarray(self.nnz)
        return self._host["nnz"]

    def effectual_blocks(self) -> int:
        """Number of not-all-zero blocks (concrete plans only)."""
        return int(self.host_nnz().sum())

    def total_work(self) -> int:
        """v3 ragged-grid steps per N block: ``sum(max(nnz, 1))`` — the
        effectual blocks plus one gated zero-fill step per all-zero row."""
        return int(np.maximum(self.host_nnz(), 1).sum())

    def max_nnz(self) -> int:
        """The v2 grid's per-row K bound, ``max(nnz, 1)``."""
        return max(int(self.host_nnz().max(initial=0)), 1)

    def grid_steps(self, nb: int, *, compact_grid="ragged") -> int:
        """Grid steps the planned kernel issues against ``nb`` output-column
        blocks, from cached host-side stats (no device sync after the first
        query; concrete plans only — tracers raise via :meth:`host_nnz`)."""
        from repro.kernels.tensordash_spmm import _check_compact_grid  # local: keep import light

        compact_grid = _check_compact_grid(compact_grid)
        if compact_grid == "ragged":
            return nb * self.total_work()
        kdim = self.max_nnz() if compact_grid == "v2" else self.k_blocks
        return self.block_rows * nb * kdim

    def density(self) -> float:
        """Fraction of blocks that carry effectual work."""
        return self.effectual_blocks() / max(self.total_blocks, 1)

    def skipped_fraction(self) -> float:
        return 1.0 - self.density()

    def stats(self) -> dict:
        return {
            "shape": self.shape,
            "block": (self.bm, self.bk),
            "side": self.side,
            "blocks": self.total_blocks,
            "effectual": self.effectual_blocks(),
            "total_work": self.total_work(),
            "density": self.density(),
        }

    def shard(self, n_shards: int, *, axis: str = "M",
              balance: bool = True) -> "PlanShards":
        """This plan split into ``n_shards`` per-shard work queues
        (:func:`shard_plan`), memoized host-side per ``(n_shards, axis,
        balance)`` — one split amortized over every stats/report query.
        Concrete plans only (tracers raise via :meth:`host_nnz`)."""
        key = ("shards", n_shards, axis, balance)
        if key not in self._host:
            self._host[key] = shard_plan(
                self, n_shards, axis=axis, balance=balance
            )
        return self._host[key]


def plan_operand(a, bm: int, bk: int, *, side: str = "A") -> SparsityPlan:
    """Plan a 2-D operand (already transposed for ``side="B"``).

    One fused dispatch builds the whole payload — compacted ``(nnz, idx)``
    plus the v3 work queue — so ragged execution never pays a second
    planning pass.  Dims the blocks do not divide are zero-padded first
    (:func:`_tile_block`): the plan's ``shape`` is the padded shape, and
    executors pad the operand to it."""
    from repro.kernels.tensordash_spmm import plan_blocks_csr  # local: keep import light

    m, k = _round_up(a.shape[0], bm), _round_up(a.shape[1], bk)
    a = _pad_to(a, (m, k))
    nnz, idx, row_starts, work_row, work_kblk = plan_blocks_csr(a, bm, bk)
    return SparsityPlan(
        nnz=nnz, idx=idx, bm=bm, bk=bk, shape=(m, k), dtype=a.dtype, side=side,
        row_starts=row_starts, work_row=work_row, work_kblk=work_kblk,
    )


def plan_from_emitted_mask(mask, shape, dtype, *, bm: int, mask_bn: int,
                           bk: int | None = None) -> SparsityPlan:
    """Build the consumer's :class:`SparsityPlan` from a producer-emitted
    output mask — pure metadata, no pass over the operand values.

    ``mask`` is the ``int8 [M/bm, N/mask_bn]`` second output of the fused
    kernel for an operand of ``shape = (M, N)``.  When the consumer's
    contraction block ``bk`` is a multiple of the producer's ``mask_bn``,
    adjacent mask columns are coarsened (a coarse block is effectual iff any
    member is); otherwise the plan keeps the emitted ``mask_bn`` granularity
    — finer blocks, identical numerics.

    The v3 work queue rides along in the same fused dispatch, so emitted-mask
    replanning stays one program and the same allocation pattern as v2 —
    the producer hands its consumer the *ragged* schedule for free.
    """
    from repro.kernels.tensordash_spmm import plan_from_mask_csr  # local: keep import light

    coarsen = 1
    plan_bk = mask_bn
    if bk is not None and bk != mask_bn:
        if bk % mask_bn == 0 and shape[1] % bk == 0:
            coarsen, plan_bk = bk // mask_bn, bk
    nnz, idx, row_starts, work_row, work_kblk = plan_from_mask_csr(mask, coarsen=coarsen)
    return SparsityPlan(
        nnz=nnz, idx=idx, bm=bm, bk=plan_bk, shape=tuple(shape), dtype=dtype,
        row_starts=row_starts, work_row=work_row, work_kblk=work_kblk,
    )


def dense_operand_plan(shape, dtype, *, bm: int, bk: int, side: str = "A") -> SparsityPlan:
    """The trivial all-effectual plan for a known-dense operand — metadata
    only (``nnz = Kb``, ``idx = arange``, closed-form work queue), skipping
    the values pass a :func:`plan_operand` call would make.  Like that
    call, it covers ``shape`` rounded up to whole blocks."""
    from repro.kernels.tensordash_spmm import dense_plan_csr  # local: keep import light

    m, k = _round_up(shape[0], bm), _round_up(shape[1], bk)
    nnz, idx, row_starts, work_row, work_kblk = dense_plan_csr(m // bm, k // bk)
    return SparsityPlan(
        nnz=nnz, idx=idx, bm=bm, bk=bk, shape=(m, k), dtype=dtype, side=side,
        row_starts=row_starts, work_row=work_row, work_kblk=work_kblk,
    )


# ---------------------------------------------------------------------------
# Plan sharding: per-shard ragged work queues for shard_map execution.
# ---------------------------------------------------------------------------


def balanced_row_order(nnz, n_shards: int):
    """Serpentine-balanced block-row order for an M-sharded plan.

    Rows sorted by descending work (``max(nnz, 1)``) are dealt boustrophedon
    across ``n_shards`` — shard ``s`` takes position ``s`` on even rounds and
    ``n_shards-1-s`` on odd ones — so every shard gets exactly ``Rb /
    n_shards`` rows (uniform ``shard_map`` shapes) with near-equal total
    work: after round ``2t`` every shard holds the same number of rows and
    the pairwise work gap is bounded by one row of round ``2t-1``.  Returns
    the ``[Rb] int32`` order, *shard-major*: shard ``s`` owns
    ``order[s*r:(s+1)*r]``.  Pure ``jnp`` metadata ops, so the identical
    assignment is computable host-side (concrete plans) and in-graph
    (traced cotangent plans inside ``jit``/``grad``) — what keeps the
    sharded backward bit-identical to the host-side split the tests oracle
    against.  Reordering block rows is pure data movement: each row's
    schedule travels with it, so execution stays bitwise regardless of the
    assignment.
    """
    import jax.numpy as jnp  # local: keep module import light

    nnz = jnp.asarray(nnz)
    (rb,) = nnz.shape
    if rb % n_shards:
        raise ValueError(f"{rb} block rows not divisible by {n_shards} shards")
    work = jnp.maximum(nnz, 1)
    by_work = jnp.argsort(-work, stable=True).astype(jnp.int32)
    rounds = rb // n_shards
    s = jnp.arange(n_shards, dtype=jnp.int32)[:, None]
    r = jnp.arange(rounds, dtype=jnp.int32)[None, :]
    pos = r * n_shards + jnp.where(r % 2 == 0, s, n_shards - 1 - s)
    return by_work[pos.reshape(-1)]


@dataclasses.dataclass(frozen=True)
class PlanShards:
    """A :class:`SparsityPlan` split into per-shard ragged work queues.

    ``nnz``/``idx``/``row_starts``/``work_row``/``work_kblk`` carry a leading
    shard dim (numpy, host-side — every executor accepts numpy metadata, the
    ``dense_plan_csr`` precedent).  Per axis:

    * ``"M"`` (row-parallel): block rows are dealt to shards by ``order``
      (serpentine-balanced when ``balance``, else contiguous); shard ``s``
      owns rows ``order[s*r:(s+1)*r]`` with their global K indices intact.
    * ``"N"`` (column-parallel): the schedule is replicated — every shard
      walks the full queue against its own output-column slice.
    * ``"K"`` (contraction-parallel): each shard replans its K-block slice
      (local indices, rebased to the slice) from the expanded block mask.
    """

    plan: SparsityPlan
    axis: str
    n_shards: int
    order: Any  # [Rb] int32 block-row assignment (shard-major; M only)
    nnz: Any  # [S, rows]
    idx: Any  # [S, rows, Kb_local]
    row_starts: Any  # [S, rows+1]
    work_row: Any  # [S, rows*Kb_local]
    work_kblk: Any

    def shard_work(self) -> np.ndarray:
        """Per-shard ragged-grid steps per N block: ``sum(max(nnz, 1))``."""
        return np.maximum(np.asarray(self.nnz), 1).sum(axis=1)

    def imbalance(self) -> float:
        """Max-over-mean of :meth:`shard_work` — 1.0 is a perfect balance;
        the naive contiguous / global-max split's figure of demerit."""
        w = self.shard_work()
        return float(w.max() / w.mean())

    def stats(self) -> dict:
        w = self.shard_work()
        return {
            "axis": self.axis,
            "n_shards": self.n_shards,
            "shard_work": [int(x) for x in w],
            "imbalance": self.imbalance(),
            "total_work": int(w.sum()),
        }


def _plan_block_mask_np(nnz: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Expand compacted ``(nnz, idx)`` back to the bool ``[Rb, Kb]`` block
    mask (the tail's repeated indices are excluded by the ``nnz`` bound)."""
    rb, kb = idx.shape
    valid = np.arange(kb, dtype=np.int64)[None, :] < nnz[:, None]
    rows = np.broadcast_to(np.arange(rb, dtype=np.int64)[:, None], idx.shape)
    mask = np.zeros((rb, kb), bool)
    mask[rows[valid], idx[valid]] = True
    return mask


def shard_plan(plan: SparsityPlan, n_shards: int, *, axis: str = "M",
               balance: bool = True) -> PlanShards:
    """Split ``plan`` into ``n_shards`` per-shard work queues (host-side).

    Each shard's CSR queue is rebuilt from *its own* rows/columns —
    ``row_starts[s][-1]`` is exactly that shard's ragged-grid steps per N
    block, ``O(sum(nnz_shard))``, which is what makes per-device load track
    local effectual work instead of the global ``max(nnz)``.  ``balance``
    (M axis) deals rows serpentine by descending work
    (:func:`balanced_row_order`); ``False`` keeps the naive contiguous
    split, the imbalance baseline the benchmarks measure against.
    Concrete plans only — the in-graph twin lives in
    ``repro.parallel.spmm`` (same assignment, same numerics).
    """
    from repro.sparse_train.plan_edit import (  # local: import cycle
        _mask_to_plan_np, _workqueue_np,
    )

    if axis not in ("M", "N", "K"):
        raise ValueError(f"shard axis {axis!r} not in ('M', 'N', 'K')")
    nnz = plan.host_nnz().astype(np.int32)
    idx = np.asarray(plan.idx, dtype=np.int32)
    rb, kb = idx.shape
    order = np.arange(rb, dtype=np.int32)
    if axis == "M":
        if rb % n_shards:
            raise ValueError(
                f"{rb} block rows not divisible by {n_shards} shards"
            )
        if balance:
            order = np.asarray(balanced_row_order(nnz, n_shards))
        rows = rb // n_shards
        nnz_s = nnz[order].reshape(n_shards, rows)
        idx_s = idx[order].reshape(n_shards, rows, kb)
    elif axis == "N":
        # output columns shard; the schedule replicates to every shard
        nnz_s = np.broadcast_to(nnz, (n_shards, rb)).copy()
        idx_s = np.broadcast_to(idx, (n_shards, rb, kb)).copy()
    else:  # K: rebase each shard's plan to its K-block slice
        if kb % n_shards:
            raise ValueError(
                f"{kb} K blocks not divisible by {n_shards} shards"
            )
        kbl = kb // n_shards
        mask = _plan_block_mask_np(nnz, idx)
        parts = [
            _mask_to_plan_np(mask[:, s * kbl:(s + 1) * kbl])
            for s in range(n_shards)
        ]
        nnz_s = np.stack([p[0] for p in parts])
        idx_s = np.stack([p[1] for p in parts])
    queues = [_workqueue_np(nnz_s[s], idx_s[s]) for s in range(n_shards)]
    return PlanShards(
        plan=plan, axis=axis, n_shards=n_shards, order=order,
        nnz=nnz_s, idx=idx_s,
        row_starts=np.stack([q[0] for q in queues]),
        work_row=np.stack([q[1] for q in queues]),
        work_kblk=np.stack([q[2] for q in queues]),
    )


def unshard_plan(shards: PlanShards) -> SparsityPlan:
    """Reassemble the global plan from its shards — the exact inverse of
    :func:`shard_plan` (bit-identical metadata, pinned by the round-trip
    test).  Queues are rebuilt from the merged schedule."""
    from repro.sparse_train.plan_edit import (  # local: import cycle
        _mask_to_plan_np, _workqueue_np,
    )

    src = shards.plan
    if shards.axis == "N":
        nnz, idx = np.asarray(shards.nnz[0]), np.asarray(shards.idx[0])
    elif shards.axis == "M":
        rb = shards.order.shape[0]
        kb = shards.idx.shape[-1]
        nnz = np.empty((rb,), np.int32)
        idx = np.empty((rb, kb), np.int32)
        nnz[shards.order] = shards.nnz.reshape(rb)
        idx[shards.order] = shards.idx.reshape(rb, kb)
    else:  # K: splice per-shard local masks back into global columns
        s_, rb, kbl = shards.idx.shape
        mask = np.zeros((rb, s_ * kbl), bool)
        for s in range(s_):
            mask[:, s * kbl:(s + 1) * kbl] = _plan_block_mask_np(
                np.asarray(shards.nnz[s]), np.asarray(shards.idx[s])
            )
        nnz, idx = _mask_to_plan_np(mask)
    rs, wr, wk = _workqueue_np(nnz, idx)
    return SparsityPlan(
        nnz=nnz, idx=idx, bm=src.bm, bk=src.bk, shape=src.shape,
        dtype=src.dtype, side=src.side,
        row_starts=rs, work_row=wr, work_kblk=wk,
    )


class PlanCache:
    """Keyed SparsityPlan cache with identity-validated hits, LRU eviction.

    Entries are keyed by ``(key, side, shape, dtype, bm, bk)`` and store the
    source operand alongside the plan.  A lookup only hits when the stored
    source *is* the queried array (same buffer), which makes reuse exact by
    construction — a rebound key (new weights under the same name) is a miss
    and transparently replaces the stale entry.

    Eviction is LRU: a hit moves its entry to the back of the queue, so
    sustained serving with more live weights than ``capacity`` evicts the
    coldest plan, never a just-hit hot one (the FIFO predecessor thrashed
    exactly those).

    ``validate`` (normally propagated from ``Runtime(validate=...)``) gates
    the static verifier at every insertion: ``"boundary"`` runs the O(Rb)
    structural checks, ``"full"`` the O(entries) content checks
    (:func:`repro.analysis.plan_check.verify_plan`).  Hits are never
    re-verified — an entry that passed at ``store`` time is immutable.
    """

    def __init__(self, capacity: int | None = None, validate: str = "off"):
        self._entries: dict[tuple, tuple[Any, SparsityPlan]] = {}
        self.capacity = capacity
        self.validate = validate
        self.hits = 0
        self.misses = 0
        #: plans built for traced operands (inside jit/grad/scan): part of the
        #: traced program, never cached — counted so tests can observe that a
        #: compiled path (e.g. the sparsity-aware backward) did plan
        self.traced = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _key(self, key, a, bm: int, bk: int, side: str) -> tuple:
        return (key, side, tuple(a.shape), str(a.dtype), bm, bk)

    def lookup(self, key, a, bm: int, bk: int, side: str = "A") -> SparsityPlan | None:
        k = self._key(key, a, bm, bk, side)
        entry = self._entries.get(k)
        if entry is not None and entry[0] is a:
            self.hits += 1
            # LRU: move-to-end on hit (dicts iterate in insertion order, so
            # eviction pops the front = least recently used)
            self._entries[k] = self._entries.pop(k)
            return entry[1]
        return None

    def store(self, key, a, plan: SparsityPlan) -> SparsityPlan:
        self.misses += 1
        if self.validate != "off" and not isinstance(plan.nnz, jax.core.Tracer):
            from repro.analysis.plan_check import check_plan  # local: keep import light

            check_plan(plan, level=self.validate)
        k = self._key(key, a, plan.bm, plan.bk, plan.side)
        # rebinding an existing key replaces (and refreshes recency) — never
        # evicts a live unrelated entry
        if k in self._entries:
            self._entries.pop(k)
        elif self.capacity is not None and len(self._entries) >= self.capacity:
            self._entries.pop(next(iter(self._entries)))  # LRU eviction (front)
        self._entries[k] = (a, plan)
        return plan

    def get_or_build(self, key, a, bm: int, bk: int, *, side: str = "A") -> SparsityPlan:
        if isinstance(a, jax.core.Tracer):
            # Inside a trace the plan is part of the program; never cache.
            self.traced += 1
            operand = a.T if side == "B" else a
            return plan_operand(operand, bm, bk, side=side)
        plan = self.lookup(key, a, bm, bk, side)
        if plan is not None:
            return plan
        operand = a.T if side == "B" else a
        return self.store(key, a, plan_operand(operand, bm, bk, side=side))

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "traced": self.traced,
        }

    def plan_stats(self, shards: int | None = None) -> list[dict]:
        """Per-plan work summary for every live entry (LRU order, coldest
        first): the v3 ragged-grid ``total_work`` and the skipped fraction,
        so production traces can observe per-operand *skew*, not just hit
        rates.  Cached entries are always concrete, so the host-side stats
        never sync mid-trace.

        With ``shards`` (a device count), every plan whose block rows divide
        it additionally reports the M-sharded split: per-shard ``total_work``
        (``shard_work``, the exact per-device ragged-grid steps per N block),
        the per-shard skipped fractions, and the ``imbalance`` ratio
        (max/mean) under the serpentine-balanced deal — the number the
        distributed launchers surface per device.  Plans with indivisible
        row counts report global aggregates only, mirroring the executor's
        replicate-don't-split fallback."""
        out = []
        for (key, side, *_rest), (_, plan) in self._entries.items():
            # shape/block come from the plan itself: identity-anchored
            # backward entries (autodiff's transposed-plan cache) key on the
            # idx metadata array, whose shape is the block grid, not the
            # operand
            entry = {
                "key": key,
                "side": side,
                "shape": plan.shape,
                "block": (plan.bm, plan.bk),
                "blocks": plan.total_blocks,
                "total_work": plan.total_work(),
                "skipped_fraction": plan.skipped_fraction(),
            }
            if shards and shards > 1 and plan.block_rows % shards == 0:
                ps = plan.shard(shards)
                per_shard = ps.shard_work()
                blocks_per_shard = plan.total_blocks / shards
                entry["shard_work"] = [int(w) for w in per_shard]
                entry["shard_skipped"] = [
                    1.0 - float(n.sum()) / blocks_per_shard
                    for n in np.asarray(ps.nnz)
                ]
                entry["imbalance"] = ps.imbalance()
            out.append(entry)
        return out

    def scrub(self, *, level: str | None = None) -> list[tuple]:
        """Re-verify every live entry and evict the corrupt ones.

        The recovery half of cache poisoning: store-time validation proves
        an entry was good when it went in; ``scrub`` is for when something
        mutated it afterwards (a chaos injector here; bad in-place edits or
        memory corruption in the wild).  Returns ``[(key, error), ...]`` for
        the evicted entries — an evicted plan is rebuilt from its operand on
        the next ``get_or_build`` miss.  ``level`` defaults to ``"full"``:
        a scrub is an explicit offline sweep, so it pays for the O(entries)
        content checks that catch what the cheap boundary tier cannot
        (index bounds, queue-entry consistency).
        """
        from repro.analysis.plan_check import (  # local: keep import light
            PlanVerificationError, check_plan,
        )

        level = level or "full"
        bad = []
        for k, (_, plan) in list(self._entries.items()):
            if isinstance(plan.nnz, jax.core.Tracer):  # pragma: no cover
                continue  # never cached; defensive
            try:
                check_plan(plan, level=level)
            except PlanVerificationError as e:
                bad.append((k, str(e)))
                del self._entries[k]
        return bad

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0
        self.traced = 0
