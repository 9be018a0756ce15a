"""The single front door for execution policy.

A frozen :class:`Runtime` bundles everything that used to be ambient
string-and-kwarg state — the kernel backend name, block geometry
``bm/bk/bn``, the device mesh, a plan-cache handle and the dtype policy —
into one value that is either passed explicitly or installed as the ambient
runtime with ``with runtime.use(rt):``.

Resolution precedence (``resolve``):

1. an explicitly passed ``Runtime``;
2. the ambient runtime installed by :func:`use`;
3. the process-wide default (dense backend, no mesh).

The PR-1 era entry points (``mode=`` kwargs on ``repro.kernels.ops``,
``ModelConfig.ffn_kernel_mode``, hand-threaded ``mesh=`` on the train-step
factories) completed their one-release deprecation cycle and are gone; all
code constructs a ``Runtime``.

Block geometry is a *target*, not a contract: planned execution fits each
block dim to its operand dim as a chip-legal tile (:meth:`Runtime.fit`) —
the whole dim when it is smaller than the target, else a multiple of 128 —
and zero-pads an operand the tile does not divide, instead of silently
falling back to dense XLA.  Neither changes numerics (padding adds only
all-zero blocks, which the plan skips); both only change the block
granularity at which all-zero work is skipped.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime.backends import KernelBackend, get_backend

if False:  # import-time cycle (sharding -> models -> runtime); type-only
    from repro.parallel.sharding import ShardingPolicy
from repro.runtime.plan import (
    PlanCache,
    SparsityPlan,
    _fit_block,
    _pad_to,
    _round_up,
    _tile_block,
    dense_operand_plan,
    plan_from_emitted_mask,
    plan_operand,
)

__all__ = [
    "Runtime",
    "use",
    "current",
    "resolve",
    "active_mesh",
    "active_policy",
    "default_runtime",
    "cache_batch_axes",
]

GEOMETRIES = ("explicit", "auto")


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution policy: backend + block geometry + mesh + plan cache.

    ``bm/bk/bn`` are the block-sparse tile geometry (defaults sized for the
    TPU MXU; tests shrink them).  ``plan_cache`` is carried by handle so a
    serving engine's plans survive across steps; it is excluded from
    equality so two runtimes with the same policy compare equal.

    ``compact_grid`` picks the kernel grid family — bit-identical outputs,
    different issued work: ``"ragged"`` (default, v3) walks the plan's CSR
    work queue so steps equal effectual blocks exactly (``O(sum(nnz))``,
    skew-immune); ``"v2"`` bounds the K grid by the per-call ``max(nnz)``
    (one dense row drags all rows to dense cost); ``"v1"`` issues the full
    gated grid — kept for A/B measurement.  Legacy ``True``/``False`` are
    accepted and normalized to ``"v2"``/``"v1"`` at construction.

    ``geometry="auto"`` consults :attr:`tuning_db` (a
    :class:`repro.tune.TuningDB`; discovered from disk when not passed) at
    every execution method: the measured-best ``bm/bk/bn``/grid-family/fuse
    policy for the call's ``(op, shape-bucket, dtype, density-bucket,
    platform)`` key overlays the fields above, and unmeasured cells fall
    back to them.  Construct via :meth:`tuned`.  Resolution never changes
    numerics (the tuner only stores candidates verified bit-identical to
    the reference backend at their geometry); with a caller-provided plan
    only the lane width and grid family are tuned, since ``bm/bk`` are the
    plan's own blocking.

    ``sharding`` is the declarative
    :class:`~repro.parallel.sharding.ShardingPolicy` — mesh, axis roles and
    parameter spec tables in one value; ``None`` means single-device.
    :attr:`mesh` reads back ``sharding.mesh`` (the old untyped ``mesh=``
    constructor shim completed its one-release deprecation cycle and is
    gone).

    ``validate`` gates the static plan verifier
    (:mod:`repro.analysis.plan_check`): ``"off"`` (default) trusts the
    planners; ``"boundary"`` runs the O(Rb) structural checks at every
    ``PlanCache`` insertion and ``edit_plan``; ``"full"`` adds the
    O(entries) content checks.  Traced plans are always skipped (they are
    part of the compiled program, not host metadata).
    """

    backend: str = "dense"
    bm: int = 128
    bk: int = 512
    bn: int = 128
    compact_grid: Any = "ragged"
    sharding: ShardingPolicy | None = None
    plan_cache: PlanCache = dataclasses.field(
        default_factory=PlanCache, compare=False, repr=False
    )
    compute_dtype: Any = None  # None: keep operand dtype
    # kernel accumulator precision; every current backend accumulates in
    # fp32 (validated in matmul) — a bf16-accumulate Pallas variant per the
    # paper's §bfloat16 evaluation would register a backend honouring this
    accum_dtype: Any = jnp.float32
    # static plan verification level ("off" | "boundary" | "full")
    validate: str = "off"
    # geometry policy: "explicit" uses bm/bk/bn/compact_grid as given;
    # "auto" overlays the measured-best policy from ``tuning_db`` per
    # (op, shape-bucket, dtype, density-bucket, platform) — see repro.tune
    geometry: str = "explicit"
    tuning_db: Any = dataclasses.field(default=None, compare=False, repr=False)

    # -- construction ------------------------------------------------------
    def __post_init__(self):
        from repro.analysis.plan_check import LEVELS
        from repro.kernels.tensordash_spmm import _check_compact_grid

        # fail at construction, not at the first kernel call deep in a
        # model: a typo'd mode string would otherwise silently select v2.
        # Stored normalized ("ragged"/"v2"/"v1") so jit static-arg caches
        # and policy comparisons see one canonical value per mode.
        object.__setattr__(
            self, "compact_grid", _check_compact_grid(self.compact_grid)
        )
        if self.validate not in LEVELS:
            raise ValueError(
                f"validate={self.validate!r} not one of {LEVELS}"
            )
        if self.geometry not in GEOMETRIES:
            raise ValueError(
                f"geometry={self.geometry!r} not one of {GEOMETRIES}"
            )
        if self.geometry == "auto" and self.tuning_db is None:
            from repro.tune import default_db  # local: tune imports runtime

            object.__setattr__(self, "tuning_db", default_db())
        # the cache is carried by handle; keep its gate in step with the
        # policy that owns it (replace() re-runs this on the same handle)
        self.plan_cache.validate = self.validate

    @classmethod
    def tuned(cls, db=None, *, path=None, **kw) -> "Runtime":
        """A ``geometry="auto"`` runtime resolving from ``db`` (a
        ``repro.tune.TuningDB``), from the file at ``path``, or from the
        discovered default DB (``$REPRO_TUNING_DB`` > CWD > repo root).
        Unmeasured cells fall back to the hand-tuned defaults, so an empty
        or missing DB degrades to exactly ``Runtime(**kw)``."""
        if db is not None and path is not None:
            raise ValueError("Runtime.tuned: pass db= or path=, not both")
        if path is not None:
            from repro.tune import TuningDB  # local: tune imports runtime

            db = TuningDB.load(path)
        return cls(geometry="auto", tuning_db=db, **kw)

    def replace(self, **kw) -> "Runtime":
        return dataclasses.replace(self, **kw)

    @property
    def mesh(self):
        """Read-alias for ``sharding.mesh`` (construct with
        ``sharding=ShardingPolicy(mesh=...)``)."""
        return self.sharding.mesh if self.sharding is not None else None

    @property
    def kernel(self) -> KernelBackend:
        return get_backend(self.backend)

    @property
    def wants_sparse(self) -> bool:
        """Whether this runtime's backend exploits block sparsity."""
        return self.kernel.sparse

    # -- scoping -----------------------------------------------------------
    def use(self):
        """``with rt.use():`` — install as the ambient runtime."""
        return use(self)

    # -- planning ----------------------------------------------------------
    def plan(self, a, *, key=None, side: str = "A") -> SparsityPlan:
        """Plan operand ``a`` (``side="B"``: plan ``a.T`` — weight side).

        With a ``key`` the plan is served from :attr:`plan_cache`; hits are
        identity-validated, so reuse is exact (see ``repro.runtime.plan``).
        """
        bm = self.bm if side == "A" else self.bn
        if key is None:
            operand = a.T if side == "B" else a
            return plan_operand(operand, bm, self.bk, side=side)
        return self.plan_cache.get_or_build(key, a, bm, self.bk, side=side)

    def fit(self, a_shape, b_shape) -> "Runtime":
        """This runtime with block geometry fitted to ``a @ b``'s shapes.

        Each of ``bm/bk/bn`` becomes a chip-legal tile of the corresponding
        operand dim (:func:`~repro.runtime.plan._tile_block`): the whole dim
        when it is under the target (a 3-token microbatch under bm=128 plans
        with bm=3), else a multiple of 128 — where none divides the dim the
        execution methods zero-pad the operands to a tile multiple (a
        50280-row vocabulary runs as 50304 rows of 128).  The plan cache
        handle is shared — fitted geometry is part of every cache key, so
        fitted and unfitted plans never collide.
        """
        m, k = a_shape
        n = b_shape[1]
        bm, bk, bn = _tile_block(self.bm, m), _tile_block(self.bk, k), _tile_block(self.bn, n)
        if (bm, bk, bn) == (self.bm, self.bk, self.bn):
            return self
        return self.replace(bm=bm, bk=bk, bn=bn)

    @property
    def _db(self):
        """The TuningDB to thread into kernels/VJPs — only under
        ``geometry="auto"`` (an explicit-geometry runtime never lets a DB
        second-guess its hand-set policy, forward or backward)."""
        return self.tuning_db if self.geometry == "auto" else None

    def lane(self, dim: int) -> int:
        """Fitted output-lane width: the chip-legal tile of ``dim`` at the
        target :attr:`bn` (operands are padded to a multiple of it)."""
        return _tile_block(self.bn, dim)

    def _policy(self, op: str, a_shape, b_shape, dtype, *, density=None):
        """The tuned policy for one call site, or ``None`` (explicit
        geometry, no DB, or a cold cell).  Warm lookups are one memoized
        dict probe in the :class:`~repro.tune.TuningDB` — nothing the eager
        serving path can measure (gated in ``autotune_micro``)."""
        if self.geometry != "auto" or self.tuning_db is None:
            return None
        return self.tuning_db.resolve(
            op=op, m=a_shape[0], k=a_shape[1], n=b_shape[1], dtype=dtype,
            density=density,
        )

    def _overlaid(self, op: str, a_shape, b_shape, dtype, *,
                  plan: SparsityPlan | None = None, density=None) -> "Runtime":
        """This runtime with the tuned policy for ``op`` overlaid
        (``geometry="auto"`` only), before fitting to the shapes."""
        pol = self._policy(op, a_shape, b_shape, dtype, density=density)
        rt = self
        if pol is not None:
            if plan is None:
                new = (pol.bm, pol.bk, pol.bn, pol.compact_grid)
                if new != (rt.bm, rt.bk, rt.bn, rt.compact_grid):
                    rt = rt.replace(bm=pol.bm, bk=pol.bk, bn=pol.bn,
                                    compact_grid=pol.compact_grid)
            elif (pol.bn, pol.compact_grid) != (rt.bn, rt.compact_grid):
                rt = rt.replace(bn=pol.bn, compact_grid=pol.compact_grid)
        return rt

    def _resolved(self, op: str, a_shape, b_shape, dtype, *,
                  plan: SparsityPlan | None = None, density=None) -> "Runtime":
        """THE geometry-resolution path every execution method funnels
        through.  Resolve the tuned policy for ``op`` (``geometry="auto"``
        only), overlay it on this runtime's defaults (:meth:`_overlaid`),
        then fit to the operand shapes.  With a caller-provided ``plan``,
        the plan's own blocking governs ``bm/bk`` (changing them would
        reassociate the block accumulation); only the lane width and grid
        family stay free to tune — the same contract the backward products
        follow (``PlannedVJP._bwd_policy``)."""
        rt = self._overlaid(op, a_shape, b_shape, dtype, plan=plan,
                            density=density)
        return rt if plan is not None else rt.fit(a_shape, b_shape)

    def supports_matmul(self, a_shape, b_shape, *, side: str = "A") -> bool:
        """Can the backend run ``a @ b`` block-sparse here?  Geometry always
        fits (it auto-clamps, see :meth:`fit`); only the platform can say no."""
        del a_shape, b_shape, side
        try:
            self.kernel.check_platform()
            return True
        except Exception:
            return False

    # -- execution ---------------------------------------------------------
    def _recovered_plan(self, plan: SparsityPlan, operand) -> SparsityPlan:
        """Boundary *recovery* for caller-provided plans (``validate`` !=
        ``"off"``, concrete plans only): verify the metadata, and on
        corruption degrade loudly — warn, record a ``ResilienceLog`` event,
        and replan from the operand's values — instead of executing a
        schedule that would drop or double-count blocks.  The contained
        output is numerically correct; the caller's broken plan is the
        thing that gets discarded.  ``operand`` is already post-transpose
        for ``side="B"`` (i.e. ``b.T``)."""
        if self.validate == "off" or isinstance(plan.nnz, jax.core.Tracer):
            return plan
        from repro.analysis.plan_check import PlanVerificationError, check_plan

        try:
            check_plan(plan, level=self.validate)
            return plan
        except PlanVerificationError as e:
            import warnings

            from repro.resilience.log import record as _record

            warnings.warn(
                f"corrupt SparsityPlan at Runtime.matmul boundary "
                f"(side={plan.side!r}, shape={plan.shape}): {e}; replanning "
                f"from operand values",
                RuntimeWarning, stacklevel=3,
            )
            _record("plan-corrupt", "runtime.matmul", "replan",
                    side=plan.side, shape=plan.shape, error=str(e))
            # keep the plan's own geometry when it still divides the operand
            # (corruption usually hits the schedule, not the blocking); a
            # geometry-level corruption falls back to the fitted defaults
            bm = (plan.bm if plan.bm > 0 and operand.shape[0] % plan.bm == 0
                  else _fit_block(self.bm, operand.shape[0]))
            bk = (plan.bk if plan.bk > 0 and operand.shape[1] % plan.bk == 0
                  else _fit_block(self.bk, operand.shape[1]))
            return plan_operand(operand, bm, bk, side=plan.side)

    def _dtype_prologue(self, a, b):
        """Shared matmul/matmul_fused entry checks: enforce the fp32
        accumulator policy and apply the compute-dtype cast."""
        if jnp.dtype(self.accum_dtype) != jnp.dtype(jnp.float32):
            raise NotImplementedError(
                f"accum_dtype={self.accum_dtype}: all registered backends "
                "accumulate in float32"
            )
        if self.compute_dtype is not None:
            a = a.astype(self.compute_dtype)
            b = b.astype(self.compute_dtype)
        return a, b

    def matmul(self, a, b, *, plan: SparsityPlan | None = None, plan_key=None,
               side: str = "A", op: str = "matmul", density=None):
        """``a @ b`` on this runtime's backend.

        ``side="A"`` (default) exploits dynamic sparsity of ``a``;
        ``side="B"`` exploits (static, typically weight) sparsity of ``b``,
        executed through the same kernel as ``(b.T @ a.T).T``.  ``plan_key``
        routes planning through the keyed cache — the serving decode loop's
        amortization path.  Block geometry fits the operand shapes
        (:meth:`fit`), padding where a tile does not divide: there is no
        silent dense fallback for small or odd operands.

        ``op`` names this call site's tuning key (``geometry="auto"``): a
        distinct op — ``"moe_expert"``, a custom pipeline stage — resolves
        its own measured policy even at shapes another op shares.
        ``density`` optionally refines the key to the operand's
        density-bucket; ``None`` resolves the ``"any"`` bucket.

        Differentiable: ``jax.grad`` through a planned matmul executes both
        gradient products (paper Eq. 2-3) through the backend registry with
        their own ``SparsityPlan``s (see ``repro.runtime.autodiff``); the
        plan cache — and the TuningDB, so each backward product resolves its
        own key — ride along, and eager backward passes reuse the static
        transposed-weight plan across microbatches.
        """
        a, b = self._dtype_prologue(a, b)
        kernel = self.kernel
        if not kernel.sparse and plan is None and plan_key is None:
            return kernel.matmul(a, b, bm=self.bm, bk=self.bk, bn=self.bn)
        # one resolution path: tuned-policy overlay + shape fit; with an
        # explicit plan its geometry governs and only the lane dim is fitted
        pol_rt = self._overlaid(op, a.shape, b.shape, a.dtype, plan=plan,
                                density=density)
        rt = pol_rt if plan is not None else pol_rt.fit(a.shape, b.shape)
        m, n = a.shape[0], b.shape[1]
        if side == "B":
            if plan is None:
                plan = rt.plan(b, key=plan_key, side="B")
            else:
                plan = self._recovered_plan(plan, b.T)
            # the tokens are this product's lanes: their tile comes from the
            # lane target, not from the vocabulary's row block
            bn = pol_rt.lane(m)
            out_t = kernel.matmul_planned(
                plan, _pad_to(b.T, plan.shape),
                _pad_to(a.T, (plan.shape[1], _round_up(m, bn))),
                bn=bn, out_dtype=a.dtype,
                plan_cache=self.plan_cache, plan_key=("B", plan_key),
                compact_grid=rt.compact_grid, db=self._db,
            )
            return _crop(out_t, (n, m)).T
        if plan is None:
            if plan_key is None:
                # keyless dynamic operand: plan inline (never cached), but
                # still thread the cache handle so backward planning stays
                # observable (``plan_cache.traced``) under jit/grad
                kernel.check_platform()
                plan = rt.plan(a)
            else:
                plan = rt.plan(a, key=plan_key)
        else:
            plan = self._recovered_plan(plan, a)
        bn = rt.lane(n)
        out = kernel.matmul_planned(
            plan, _pad_to(a, plan.shape),
            _pad_to(b, (plan.shape[1], _round_up(n, bn))),
            bn=bn, out_dtype=a.dtype,
            plan_cache=self.plan_cache, plan_key=("A", plan_key),
            compact_grid=rt.compact_grid, db=self._db,
        )
        return _crop(out, (m, n))

    def matmul_fused(self, a, b, *, bias=None, residual=None,
                     activation: str = "none", plan: SparsityPlan | None = None,
                     plan_key=None, assume_dense: bool = False,
                     op: str = "matmul_fused", density=None):
        """Fused ``act(a @ b + bias) + residual`` on this runtime's backend,
        returning ``(out, mask)``.

        The epilogue runs inside the kernel's store step (no HBM round-trip
        between matmul and activation) and ``mask`` is the emitted ``int8``
        output block-nonzero map — feed it to
        :func:`repro.runtime.plan.plan_from_emitted_mask` to plan the
        consumer matmul from metadata (paper §3.7's backside scheduler).
        ``assume_dense=True`` uses the trivial all-effectual plan for ``a``
        (metadata only — for streams known dense, e.g. an FFN input) instead
        of planning its values.  Differentiable: both backward products take
        metadata-only plans (emitted mask / forward-plan transpose) for
        ReLU-family activations.  The mask covers the tile grid of the
        operands as padded to tile multiples (:meth:`fit`).
        """
        a, b = self._dtype_prologue(a, b)
        kernel = self.kernel
        rt = self._resolved(op, a.shape, b.shape, a.dtype, plan=plan,
                            density=density)
        m, n = a.shape[0], b.shape[1]
        bn = rt.lane(n)
        if not kernel.sparse and plan is None and plan_key is None:
            # dense shortcut (mirrors matmul's, including the plan_key
            # condition: a keyed call routes through the planned path so the
            # plan cache stays populated/observable even on a dense dry-run):
            # one XLA dot + the shared fp32 epilogue; the mask is a blockwise
            # any at the geometry the planned path would emit
            from repro.kernels.ref import _epilogue_ref  # local: keep import light

            out32 = _epilogue_ref(
                jnp.dot(a, b, preferred_element_type=jnp.float32),
                bias, residual, activation,
            )
            mp, np_ = _round_up(m, rt.bm), _round_up(n, bn)
            mask = jnp.any(
                _pad_to(out32, (mp, np_)).reshape(mp // rt.bm, rt.bm, np_ // bn, bn)
                != 0, axis=(1, 3)
            ).astype(jnp.int8)
            return out32.astype(a.dtype), mask
        kernel.check_platform()
        if plan is None:
            if assume_dense:
                plan = dense_operand_plan(a.shape, a.dtype, bm=rt.bm, bk=rt.bk)
            else:
                plan = rt.plan(a, key=plan_key)
        else:
            plan = self._recovered_plan(plan, a)
        mp, np_ = plan.shape[0], _round_up(n, bn)
        out, mask = kernel.matmul_fused(
            plan, _pad_to(a, plan.shape), _pad_to(b, (plan.shape[1], np_)),
            bias=None if bias is None else _pad_to(bias, (np_,)),
            residual=None if residual is None else _pad_to(residual, (mp, np_)),
            activation=activation, bn=bn, out_dtype=a.dtype,
            plan_cache=self.plan_cache, plan_key=("A", plan_key),
            compact_grid=rt.compact_grid, db=self._db,
        )
        return _crop(out, (m, n)), mask

    def plan_for_fused_output(self, mask, h, w, *, k: int) -> SparsityPlan:
        """Consumer plan for a fused matmul's output ``h`` (about to be the
        sparse stream of ``h @ w``), built from the emitted ``mask`` alone.

        Recovers the producer's block geometry and coarsens to this
        runtime's fitted contraction block when divisible — the single
        place that geometry recovery lives, shared by every emitted-mask
        consumer (``sparse_ffn``, the transformer FFN).  ``k`` is the
        producer's contraction dim: the producer's tiles are re-resolved
        exactly as :meth:`matmul_fused` resolved them, which also recovers
        a grid the producer padded.
        """
        (m, n), (mb, nb) = h.shape, mask.shape
        prod = self._resolved("matmul_fused", (m, k), (k, n), h.dtype)
        bm, mask_bn = prod.bm, prod.lane(n)
        return plan_from_emitted_mask(
            mask, (mb * bm, nb * mask_bn), h.dtype, bm=bm, mask_bn=mask_bn,
            bk=self.fit(h.shape, w.shape).bk,
        )

    def matmul_grads(self, a, b, g, *, plan: SparsityPlan | None = None, plan_key=None):
        """Eager sparsity-aware cotangents ``(da, db)`` of ``a @ b``.

        Runs exactly the two registry-routed backward products the
        ``custom_vjp`` rule runs — ``da = g @ b.T`` planned over ``g``,
        ``db = a.T @ g`` planned over ``a.T`` (a metadata transpose of the
        forward plan).  Called with concrete arrays (manual backprop,
        microbenchmarks), plan reuse is live in :attr:`plan_cache` and
        observable via its hit/miss counters.
        """
        from repro.runtime.autodiff import PlannedVJP, planned_matmul_grads

        if plan is None:
            plan = self._resolved(
                "matmul", a.shape, b.shape, a.dtype
            ).plan(a, key=plan_key)
        bn = self.lane(g.shape[1])
        ctx = PlannedVJP(
            backend=self.backend, bm=plan.bm, bk=plan.bk, bn=bn,
            cache=self.plan_cache, key=("A", plan_key),
            compact_grid=self.compact_grid, db=self._db,
        )
        np_ = _round_up(g.shape[1], bn)
        da, db = planned_matmul_grads(
            ctx, plan.nnz, plan.idx, _pad_to(a, plan.shape),
            _pad_to(b, (plan.shape[1], np_)), _pad_to(g, (plan.shape[0], np_)),
        )
        return _crop(da, a.shape), _crop(db, b.shape)

    def matmul_sharded(self, a, b, *, axis: str = "M",
                       plan: SparsityPlan | None = None, plan_key=None,
                       balance: bool = True):
        """Distributed planned ``a @ b`` over :attr:`sharding`'s mesh.

        The plan is split into *per-shard* ragged work queues under
        ``shard_map`` (``repro.parallel.spmm``), so each device's grid is
        ``O(sum(nnz_shard))``.  ``axis`` picks the distribution: ``"M"``
        (row-parallel over the policy's data axes — ``a``'s block rows are
        dealt serpentine by work when ``balance``), ``"N"`` (column-parallel
        over the model axis; schedule replicated) or ``"K"``
        (contraction-parallel with a psum).  M/N keep every contraction
        device-local and are bit-identical to :meth:`matmul`; K
        reassociates the accumulation (allclose, not bitwise).
        Differentiable on M/N: both backward products ride per-shard queues
        — the cotangent plan M-sharded over its rows, the transposed
        weight-gradient plan along the conjugate N axis.  Degrades to
        :meth:`matmul` without a mesh-backed policy or when shapes don't
        divide the shard count.
        """
        from repro.parallel import spmm  # local: avoid import cycle

        policy = self.sharding
        if policy is None or policy.mesh is None:
            return self.matmul(a, b, plan=plan, plan_key=plan_key)
        a, b = self._dtype_prologue(a, b)
        rt = self._resolved("matmul", a.shape, b.shape, a.dtype, plan=plan)
        if plan is None:
            rt.kernel.check_platform()
            plan = rt.plan(a, key=plan_key)
        (m, _), n = a.shape, b.shape[1]
        bn = rt.lane(n)
        out = spmm.sharded_matmul(
            plan, _pad_to(a, plan.shape),
            _pad_to(b, (plan.shape[1], _round_up(n, bn))), bn=bn,
            backend=self.backend, policy=policy, axis=axis, balance=balance,
            out_dtype=a.dtype, plan_cache=self.plan_cache,
            plan_key=("A", plan_key), compact_grid=rt.compact_grid,
            validate=self.validate, db=self._db,
        )
        return _crop(out, (m, n))

    def matmul_fused_sharded(self, a, b, *, bias=None, residual=None,
                             activation: str = "none", axis: str = "M",
                             plan: SparsityPlan | None = None, plan_key=None,
                             assume_dense: bool = False, balance: bool = True):
        """Distributed :meth:`matmul_fused` — ``act(a @ b + bias) +
        residual`` under ``shard_map``, returning ``(out, mask)`` with the
        emitted mask in the global layout.  ``axis`` as in
        :meth:`matmul_sharded` (``"K"`` is refused for fused epilogues: the
        nonlinearity cannot distribute over the psum).  Degrades to
        :meth:`matmul_fused` without a mesh-backed policy."""
        from repro.parallel import spmm  # local: avoid import cycle

        policy = self.sharding
        if policy is None or policy.mesh is None:
            return self.matmul_fused(
                a, b, bias=bias, residual=residual, activation=activation,
                plan=plan, plan_key=plan_key, assume_dense=assume_dense,
            )
        a, b = self._dtype_prologue(a, b)
        rt = self._resolved("matmul_fused", a.shape, b.shape, a.dtype, plan=plan)
        rt.kernel.check_platform()
        m, n = a.shape[0], b.shape[1]
        if plan is None:
            if assume_dense:
                plan = dense_operand_plan(a.shape, a.dtype, bm=rt.bm, bk=rt.bk)
            else:
                plan = rt.plan(a, key=plan_key)
        bn = rt.lane(n)
        mp, np_ = plan.shape[0], _round_up(n, bn)
        out, mask = spmm.sharded_matmul_fused(
            plan, _pad_to(a, plan.shape), _pad_to(b, (plan.shape[1], np_)),
            bias=None if bias is None else _pad_to(bias, (np_,)),
            residual=None if residual is None else _pad_to(residual, (mp, np_)),
            activation=activation, bn=bn, backend=self.backend,
            policy=policy, axis=axis, balance=balance, out_dtype=a.dtype,
            plan_cache=self.plan_cache, plan_key=("A", plan_key),
            compact_grid=rt.compact_grid, validate=self.validate, db=self._db,
        )
        return _crop(out, (m, n)), mask

    def sparse_ffn(self, x, w1, w2, *, activation: str = "relu"):
        """FFN whose second matmul exploits the activation sparsity the
        first one produced (the framework's main kernel consumer).

        Sparse backends default to the fused + emitted-plan path: the first
        matmul applies the activation inside its store step (no HBM
        round-trip) and emits the intermediate's block-nonzero mask, from
        which the second matmul's :class:`SparsityPlan` is built as a pure
        metadata transform — the per-call replanning pass over the
        intermediate's values (the old ``argsort`` bottleneck in
        ``plan_cache_micro``) is gone.  Under ``geometry="auto"`` the
        fuse-or-not choice itself is measured: the ``"ffn"`` op's tuned
        policy can select the unfused chain (plan the intermediate by
        value) where that A/B won — the fuse decision is the one tuned
        knob that is allclose-not-bitwise, since fusion moves where the
        activation's rounding happens.  Dense backends keep the plain
        two-dot formulation.
        """
        if activation not in ("relu", "squared_relu"):
            raise ValueError(activation)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if not self.wants_sparse:
            h = jnp.dot(x2, w1, preferred_element_type=jnp.float32)
            h = jnp.maximum(h, 0.0)
            if activation == "squared_relu":
                h = jnp.square(h)
            h = h.astype(x.dtype)
            out = self.matmul(h, w2)
            return out.reshape(*lead, w2.shape[-1])
        pol = self._policy("ffn", x2.shape, w1.shape, x.dtype)
        if pol is not None and not pol.fuse:
            h = self.matmul(x2, w1).astype(jnp.float32)
            h = jnp.maximum(h, 0.0)
            if activation == "squared_relu":
                h = jnp.square(h)
            h = h.astype(x.dtype)
            out = self.matmul(h, w2, op="ffn")
            return out.reshape(*lead, w2.shape[-1])
        h, mask = self.matmul_fused(
            x2, w1, activation=activation, assume_dense=True
        )
        plan = self.plan_for_fused_output(mask, h, w2, k=x2.shape[1])
        out = self.matmul(h, w2, plan=plan, op="ffn")
        return out.reshape(*lead, w2.shape[-1])

    # -- serving cache layout ---------------------------------------------
    def grow_caches(self, cfg, caches, batch: int, max_len: int):
        """Grow prefill-time decode caches to ``max_len`` by layout, not by
        shape-guessing: allocate the model's canonical ``max_len`` cache and
        write the prefill values in at the origin of every leaf.  Replaces
        the brittle ``x.shape[2] == seq_len`` heuristic, which misfired when
        batch/sequence/feature dims collided."""
        from repro.models import model as M  # local: avoid import cycle

        target = M.init_cache(cfg, batch, max_len)

        def place(full, part):
            if full.shape == part.shape:
                return part.astype(full.dtype)
            if len(full.shape) != len(part.shape):
                raise ValueError(f"cache rank mismatch: {part.shape} -> {full.shape}")
            return jax.lax.dynamic_update_slice(
                full, part.astype(full.dtype), (0,) * len(full.shape)
            )

        return jax.tree.map(place, target, caches)

    def slot_caches(self, cfg, slots: int, max_len: int):
        """Packed decode caches for a continuous-batching engine: the model's
        canonical cache layout with ``slots`` as the batch dimension.  One
        allocation serves every request the engine will ever run; requests
        are written in and out of batch slots (:meth:`write_slot`) instead of
        reallocating per wave."""
        from repro.models import model as M  # local: avoid import cycle

        return M.init_cache(cfg, slots, max_len)

    def replicated(self, tree):
        """``tree`` placed whole on every device of the mesh (unchanged
        without one) — so per-slot engine state carries the same sharding
        before the first decode call as after it, and the decode program
        traces once."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec  # local: light

        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    def write_slot(self, cfg, caches, slot: int, part):
        """Write one request's caches (batch=1, already grown to the packed
        ``max_len`` via :meth:`grow_caches`) into batch slot ``slot``.

        The batch axis of every leaf is found by layout probing
        (:func:`cache_batch_axes`) — never by guessing which axis looks like
        a batch — so slot packing works across KV / MLA-latent / SSM-state
        cache trees uniformly."""
        axes = cache_batch_axes(cfg)

        def place(full, p, ax):
            if p.shape[ax] != 1:
                raise ValueError(
                    f"slot write expects a batch-1 cache part, got {p.shape} "
                    f"with batch axis {ax}"
                )
            start = [0] * full.ndim
            start[ax] = slot
            return jax.lax.dynamic_update_slice(full, p.astype(full.dtype), tuple(start))

        return jax.tree.map(place, caches, part, axes)


def _crop(x, shape):
    """The leading ``shape`` corner of a 2-D result computed on padded
    operands (``x`` itself when nothing was padded)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return x[: shape[0], : shape[1]]


@functools.lru_cache(maxsize=None)
def cache_batch_axes(cfg):
    """Per-leaf batch-axis index of ``cfg``'s decode-cache tree.

    Found by differencing abstract cache layouts at two batch sizes: the one
    axis whose extent changes with the batch is the batch axis.  No
    allocation (``jax.eval_shape``), no shape heuristics."""
    from repro.models import model as M  # local: avoid import cycle

    probe_len = 4
    t2 = jax.eval_shape(lambda: M.init_cache(cfg, 2, probe_len))
    t3 = jax.eval_shape(lambda: M.init_cache(cfg, 3, probe_len))

    def ax(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        if len(diffs) != 1:
            raise ValueError(f"ambiguous batch axis: {a.shape} vs {b.shape}")
        return diffs[0]

    return jax.tree.map(ax, t2, t3)


_DEFAULT = Runtime()
_ACTIVE: contextvars.ContextVar[Runtime | None] = contextvars.ContextVar(
    "repro_runtime", default=None
)


@contextlib.contextmanager
def use(rt: Runtime):
    """Install ``rt`` as the ambient runtime for the enclosed block."""
    token = _ACTIVE.set(rt)
    try:
        yield rt
    finally:
        _ACTIVE.reset(token)


def current() -> Runtime | None:
    """The ambient runtime installed by :func:`use`, or ``None``."""
    return _ACTIVE.get()


def default_runtime() -> Runtime:
    return _DEFAULT


def resolve(rt: Runtime | None = None) -> Runtime:
    """Resolve the effective runtime: explicit > ambient > default."""
    if rt is not None:
        return rt
    ambient = _ACTIVE.get()
    return ambient if ambient is not None else _DEFAULT


def active_mesh(mesh=None):
    """Explicit mesh if given, else the ambient runtime's mesh (if any)."""
    if mesh is not None:
        return mesh
    ambient = _ACTIVE.get()
    return ambient.mesh if ambient is not None else None


def active_policy(policy: ShardingPolicy | None = None) -> ShardingPolicy:
    """Explicit policy if given, else the ambient runtime's; a default
    (mesh-less) :class:`~repro.parallel.sharding.ShardingPolicy` when
    neither exists, so callers can thread one unconditionally."""
    if policy is not None:
        return policy
    ambient = _ACTIVE.get()
    if ambient is not None and ambient.sharding is not None:
        return ambient.sharding
    from repro.parallel.sharding import ShardingPolicy  # local: import cycle

    return ShardingPolicy()
