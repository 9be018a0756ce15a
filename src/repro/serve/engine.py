"""Continuous-batching serve engine: scheduler + jitted ``lax.scan`` decode.

The paper's amortized backside scheduler (§3.7) pays off when one
``SparsityPlan`` is replayed across many decode steps and many concurrent
requests.  The engine is built so that amortization actually meets traffic:

* :class:`Scheduler` — host-side bookkeeping only: a FIFO of pending
  requests and a slot table.  It admits requests into free batch slots and
  evicts finished ones; it never touches device state.

* :class:`ServeEngine` — device state as packed per-slot arrays (last
  token, position, active mask, remaining budget, per-request RNG key) plus
  ONE packed decode-cache allocation (``Runtime.slot_caches``); a request's
  prefill caches are written into its batch slot by layout
  (``Runtime.write_slot``), so admission is a slot write, not a
  reallocation.

* admission prefills each same-length group through one jitted program
  (:func:`_prefill_group`), cached per ``(group size, prompt length)``:
  after the first admission of a signature, a prefill is one dispatch
  (``ServeEngine.stats()["prefill_traces"]``).

* the decode loop is a single **jitted, ``lax.scan``-based program**
  (:func:`_decode_chunk`): ``chunk`` decode steps over all slots per call,
  cache buffers donated so XLA updates them in place.  Its shape signature
  is ``(slots, chunk, max_len)`` — admitting, finishing (EOS or budget) and
  backfilling slots changes *data*, never shapes, so the program traces
  once and is replayed for the engine's whole lifetime
  (``ServeEngine.stats()["decode_traces"]``).

Per-slot sequence positions ride as an int32 ``[slots]`` vector through
``model.decode_step`` — each slot attends and writes its KV at its own
position, which is what lets one scan serve requests of different lengths
simultaneously.

Under a sparse runtime the LM-head weight is planned once, eagerly, at the
first admission (a ``plan_cache`` miss) and replayed from
``rt.plan_cache`` at every later one (identity-validated hits): that cached
plan's ``total_work`` prices the work budget and feeds the per-plan skew
report.  The jitted prefill and decode programs carry the plan as part of
the traced program (observable via ``rt.plan_cache.stats()["traced"]``);
in the decode scan XLA hoists the scan-invariant weight plan out of the
loop, so it is computed once per chunk call, not per token.  Execution goes
through the v3 ragged work-queue kernel (the runtime default): each decode
step's LM-head matmul issues exactly ``sum(nnz)`` contraction grid steps —
one per effectual block — instead of the full ``Kb`` per row, so a
block-pruned head's elided columns buy wall-clock on every token of every
slot even when the pruning is skewed across rows (under the v2
``compact_grid=True`` bound a single dense vocabulary row would drag every
row back to dense cost).  The engine's plan cache is LRU — sustained
serving with more live weights than capacity keeps the hottest plans
resident — and ``launch/serve.py`` prints each cached plan's
``total_work`` / skipped fraction so that skew is visible in traces.

RNG: every request's sampling stream is ``fold_in(PRNGKey(seed), rid)``,
split before first use and advanced per emitted token — so sampled output
is deterministic per (seed, rid) and independent of which slot the request
lands in or what else shares the batch.

Resilience (``repro.resilience``): admission is priority-with-aging over a
*bounded* pending queue (``QueueFull`` is typed so callers can retry with
backoff, distinct from shed-by-policy), every request can carry a TTL
deadline (expired requests are evicted from queue and slots), admission can
shed load against a work budget priced by the cached plans'
``total_work``, and the decode scan carries an in-graph ``isfinite``
watchdog that retires a NaN/Inf-poisoned slot with an error status without
perturbing healthy batch-mates (their sampling is per-row, their KV rows
are per-slot — bit-identity is asserted by the chaos suite) and without
changing the scan's shape signature.  Every degradation lands in the
engine's :class:`repro.resilience.ResilienceLog`.

Tracing: ``step`` records host spans (:mod:`repro.trace`, names
``serve.*``) around the work it already does — admission of each group,
its prefill, cache growth, each request's slot write and slot state, the
first-token fetch, the decode chunk through its token fetch, and retiring.
They land in a ``jax.profiler`` trace on the device's clock when one is
active, and cost about a microsecond each when none is; no span adds a
device sync.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime as rtm
from repro import trace as tr
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.resilience import faults as rfaults
from repro.resilience import log as rlog

__all__ = [
    "Request", "Scheduler", "ServeEngine", "QueueFull",
    "prefill_step", "decode_one", "generate",
]


class QueueFull(RuntimeError):
    """The bounded pending queue is at capacity.

    Typed (and distinct from shed-by-policy, which *admits* the submit and
    later finishes the victim with ``finish_reason="shed"``) so callers can
    catch it and retry with backoff instead of silently growing an
    unbounded queue.
    """


def prefill_step(params, cfg: ModelConfig, batch, mesh=None):
    """Prompt -> (last-position logits, filled caches)."""
    return M.prefill(params, cfg, batch, mesh=rtm.active_mesh(mesh))


def decode_one(params, cfg: ModelConfig, caches, step_batch, pos, mesh=None):
    """One token for every sequence in the batch (``pos`` scalar or [B])."""
    return M.decode_step(params, cfg, caches, step_batch, pos, mesh=rtm.active_mesh(mesh))


def _sample_rows(logits, keys, temperature: float):
    """Per-row sampling: logits [B, V] fp32, keys [B, 2] — one RNG stream
    per request, so batch composition never perturbs a request's tokens."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sample = lambda l, k: jax.random.categorical(k, l / temperature)
    return jax.vmap(sample)(logits, keys).astype(jnp.int32)


#: number of times the decode-chunk program has been traced (not executed) —
#: the compile-count probe: continuous batching must keep this at one per
#: (slots, chunk, cache-shape) signature for the life of the process.
DECODE_TRACES = 0

#: number of times the admission prefill program has been traced — one per
#: (group size, prompt length) signature, never one per admission.
PREFILL_TRACES = 0


@functools.partial(jax.jit, static_argnames=("cfg", "rt"))
def _prefill_group(params, prompts, *, cfg, rt):
    """Prefill a same-length group of prompts [g, s] as one program.

    Returns the last position's logits as float32 and the prefill caches.
    The weights are an argument (never closed over, so never baked in as
    constants) and nothing is donated: a failed admission keeps every
    buffer it was handed.  Under a sparse runtime the head's weight-side
    plan is part of the traced program, as in :func:`_decode_chunk`.
    """
    global PREFILL_TRACES
    PREFILL_TRACES += 1
    with rtm.use(rt):
        logits, caches = M.prefill(params, cfg, {"tokens": prompts})
    return logits[:, -1].astype(jnp.float32), caches


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rt", "steps", "temperature", "eos_id", "pad_id",
                     "watchdog"),
    donate_argnums=(1, 2, 3, 4, 5, 6),
)
def _decode_chunk(params, caches, tok, pos, active, remaining, keys, poison, *,
                  cfg, rt, steps, temperature, eos_id, pad_id, watchdog):
    """``steps`` decode steps over the packed slot batch, as one program.

    Carry: (tok [B], caches, pos [B], active [B] bool, remaining [B], keys
    [B,2], faulted [B] bool).  Inactive slots still flow through the model
    (static shapes) but their position is frozen, their emission masked to
    ``pad_id`` and their RNG stream untouched; any KV rows they scribble at
    the frozen position are overwritten by a later occupant's own
    write-before-read at that position, and masked out of attention until
    then.

    ``poison`` is the fault-injection hook: int32 [B] codes (0 clean, 1 NaN,
    2 Inf) overwriting a slot's last-position logits — the same trust
    boundary a numerically-diverged model or corrupted activation would
    poison in production.  With ``watchdog`` (static) the program checks
    ``isfinite`` on every slot's logits row each step and *retires* a
    non-finite slot in-graph: its emission is masked to ``pad_id``, its RNG
    and position freeze, and it leaves ``active``; the per-row sampling and
    per-slot KV layout mean healthy slots' tokens are bit-identical to a
    fault-free run.  The shape signature is unchanged by faults — the
    program still traces once.

    Emits ``(tokens [steps, B], emitted [steps, B])`` plus ``faulted [B]``
    (which slots the watchdog retired); donated buffers make the cache
    update in place.
    """
    global DECODE_TRACES
    DECODE_TRACES += 1

    def step(carry, _):
        tok, caches, pos, active, remaining, keys, faulted = carry
        with rtm.use(rt):
            logits, caches = M.decode_step(
                params, cfg, caches, {"tokens": tok[:, None]}, pos
            )
        row = logits[:, -1].astype(jnp.float32)
        row = jnp.where((poison == 1)[:, None], jnp.float32(jnp.nan), row)
        row = jnp.where((poison == 2)[:, None], jnp.float32(jnp.inf), row)
        if watchdog:
            finite = jnp.all(jnp.isfinite(row), axis=-1)
            faulted = faulted | (active & ~finite)
            good = active & finite
            # a non-finite row would make categorical/argmax emit garbage
            # into *this* row only — but sanitize before sampling anyway so
            # the sampler never sees NaN (some backends are strict)
            row = jnp.where(good[:, None], row, jnp.zeros_like(row))
        else:
            good = active
        splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        nxt_keys, subs = splits[:, 0], splits[:, 1]
        nxt = _sample_rows(row, subs, temperature)
        nxt = jnp.where(good, nxt, jnp.int32(pad_id))
        live = good.astype(jnp.int32)
        pos = pos + live
        remaining = remaining - live
        done = remaining <= 0
        if eos_id is not None:
            done = done | (nxt == jnp.int32(eos_id))
        emitted = good
        keys = jnp.where(good[:, None], nxt_keys, keys)
        active = good & ~done
        return (nxt, caches, pos, active, remaining, keys, faulted), (nxt, emitted)

    faulted0 = jnp.zeros(active.shape, bool)
    carry = (tok, caches, pos, active, remaining, keys, faulted0)
    (tok, caches, pos, active, remaining, keys, faulted), (toks, emitted) = (
        jax.lax.scan(step, carry, None, length=steps)
    )
    return caches, tok, pos, active, remaining, keys, toks, emitted, faulted


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record."""

    rid: int
    prompt: Any  # int32 [s]
    max_new: int
    arrival: float = 0.0  # traffic-replay timestamp (seconds, engine clock)
    priority: int = 0  # higher admits first (aged so low never starves)
    deadline: float | None = None  # absolute engine-clock TTL expiry
    # engine-filled:
    tokens: list = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: str | None = None  # "eos"|"length"|"error"|"expired"|"shed"
    error: str | None = None  # detail for finish_reason == "error"
    slot: int | None = None
    retries: int = 0  # admission retries after transient (alloc) failures
    t_submit: float = 0.0
    t_admit: float = 0.0  # its group's admission started (before prefill)
    t_first: float = 0.0  # first token (produced at admission, from prefill)
    t_finish: float = 0.0

    @property
    def ok(self) -> bool:
        """Finished by producing its output (EOS or budget), not degraded."""
        return self.finished and self.finish_reason in ("eos", "length")


class Scheduler:
    """Slot table + bounded priority admission.  Pure host-side bookkeeping.

    ``admit(now)`` packs pending requests into free batch slots by
    *effective* priority ``priority + age_boost * (now - t_submit)`` — a
    strictly-higher-priority request jumps the queue, but an aging
    lower-priority one eventually outranks fresh high-priority traffic, so
    nothing starves; equal effective priorities break ties in submission
    order, which with the default ``priority=0`` everywhere degenerates to
    exact FIFO.  The pending queue is bounded (``max_pending``):
    ``submit`` raises :class:`QueueFull` at capacity so backpressure is a
    typed signal, not an unbounded deque.
    """

    def __init__(self, slots: int, *, max_pending: int | None = None,
                 age_boost: float = 0.1):
        self.num_slots = slots
        self.max_pending = max_pending
        self.age_boost = float(age_boost)
        self.pending: collections.deque[Request] = collections.deque()
        self.table: list[Request | None] = [None] * slots

    def submit(self, req: Request) -> None:
        if self.max_pending is not None and len(self.pending) >= self.max_pending:
            raise QueueFull(
                f"pending queue at capacity ({self.max_pending}); retry with "
                f"backoff"
            )
        self.pending.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.pending) or any(r is not None for r in self.table)

    def occupied(self) -> list[tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.table) if r is not None]

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.table) if r is None]

    def effective_priority(self, req: Request, now: float) -> float:
        return req.priority + self.age_boost * max(now - req.t_submit, 0.0)

    def expire_pending(self, now: float) -> list[Request]:
        """Drop (and return) pending requests whose deadline has passed."""
        expired = [r for r in self.pending
                   if r.deadline is not None and r.deadline <= now]
        if expired:
            dead = set(id(r) for r in expired)
            self.pending = collections.deque(
                r for r in self.pending if id(r) not in dead
            )
        return expired

    def admit(self, now: float = 0.0) -> list[tuple[int, Request]]:
        """Place pending requests into free slots by effective priority
        (aged); returns the placements."""
        placed = []
        for slot in self.free_slots():
            if not self.pending:
                break
            best = max(
                range(len(self.pending)),
                key=lambda i: (self.effective_priority(self.pending[i], now), -i),
            )
            req = self.pending[best]
            del self.pending[best]
            req.slot = slot
            self.table[slot] = req
            placed.append((slot, req))
        return placed

    def evict(self, slot: int) -> Request:
        req = self.table[slot]
        assert req is not None, f"evicting empty slot {slot}"
        self.table[slot] = None
        req.slot = None
        return req


class ServeEngine:
    """Continuous-batching generation over a fixed-capacity slot array.

    One engine owns one packed cache allocation, one jitted decode program
    per ``(slots, chunk)`` signature, and one plan cache (the runtime's).
    Submit any number of requests; ``run()`` drains them with slots
    backfilled as requests finish.

    ``chunk`` is the number of decode steps fused into one jitted
    ``lax.scan`` call — larger chunks amortize dispatch further but delay
    admission of newly arrived requests by up to ``chunk`` steps.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 8,
                 max_len: int = 256, rt: "rtm.Runtime | None" = None,
                 temperature: float = 0.0, eos_id: int | None = None,
                 pad_id: int = 0, seed: int = 0, chunk: int = 8,
                 max_pending: int | None = None, age_boost: float = 0.1,
                 work_budget: int | None = None, watchdog: bool = True,
                 fault_plan: "rfaults.FaultPlan | None" = None,
                 log: "rlog.ResilienceLog | None" = None):
        self.params = params
        self.cfg = cfg
        self.rt = rtm.resolve(rt)
        self.watchdog = bool(watchdog)
        self.work_budget = work_budget
        self.fault_plan = fault_plan
        self.log = log if log is not None else (rlog.ambient_log()
                                                or rlog.ResilienceLog())
        if self.rt.geometry == "auto" and self.rt.tuning_db is not None:
            # prewarm the TuningDB memo for the decode hot-path cells (FFN
            # up/down projections at slot-batch width) so the first jitted
            # decode trace resolves against a warm probe instead of paying
            # the cold bucket-and-lookup inside tracing
            d_ff = cfg.d_ff or cfg.d_model * 4
            for op, kdim, ndim in (("matmul", cfg.d_model, d_ff),
                                   ("ffn", d_ff, cfg.d_model)):
                self.rt._policy(op, (slots, kdim), (kdim, ndim), jnp.float32)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.chunk = max(int(chunk), 1)
        self.sched = Scheduler(slots, max_pending=max_pending,
                               age_boost=age_boost)
        self._rids = itertools.count()
        self._base_key = jax.random.PRNGKey(seed)
        self._requests: dict[int, Request] = {}
        self._t0 = time.monotonic()
        # packed per-slot device state; a failed cache allocation degrades
        # to half the slot count (contained capacity loss, not a crash)
        self.caches, slots = self._alloc_slot_caches(cfg, slots)
        self.sched.num_slots = slots
        self.sched.table = self.sched.table[:slots]
        (self.tok, self.pos, self.active, self.remaining, self.keys,
         self._zero_poison) = self.rt.replicated((
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), bool),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, 2), jnp.uint32),
            jnp.zeros((slots,), jnp.int32),
        ))
        # counters
        self.tokens_out = 0
        self.chunks_run = 0
        self.steps_run = 0
        self.admitted = 0  # requests admitted into a slot
        self.prefill_groups = 0  # prefill calls (one per admitted group)
        self.prefill_tokens = 0  # prompt tokens prefilled

    def _alloc_slot_caches(self, cfg, slots: int):
        """Allocate the packed decode caches, halving ``slots`` (down to 1)
        on allocation failure — serving degrades to reduced concurrency
        instead of dying at construction."""
        while True:
            try:
                rfaults.maybe_alloc_failure(
                    self.fault_plan or rfaults.active(), "slot_caches"
                )
                return self.rt.slot_caches(cfg, slots, self.max_len), slots
            except (rfaults.SimulatedAllocFailure, MemoryError) as e:
                if slots <= 1:
                    raise
                self.log.record("alloc", "serve.slot_caches", "halve-slots",
                                slots=slots, error=str(e))
                slots = slots // 2

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new: int = 32, arrival: float = 0.0, *,
               priority: int = 0, ttl: float | None = None) -> int:
        """Queue one request; returns its rid.  ``prompt`` is int32 [s] with
        ``s + max_new <= max_len``.

        ``priority`` orders admission (higher first, aged — see
        :meth:`Scheduler.admit`); ``ttl`` seconds bounds the request's whole
        lifetime: a request still queued or still decoding at
        ``now + ttl`` is evicted with ``finish_reason="expired"``.  Raises
        :class:`QueueFull` when the bounded pending queue is at capacity
        (retry with backoff); under a work budget the engine may instead
        admit the submit and *shed* the cheapest-to-drop request
        (``finish_reason="shed"``).
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be rank-1, got {prompt.shape}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if prompt.shape[0] + max_new > self.max_len:
            raise ValueError(
                f"prompt ({prompt.shape[0]}) + max_new ({max_new}) exceeds "
                f"engine max_len ({self.max_len})"
            )
        now = self._now()
        req = Request(rid=next(self._rids), prompt=prompt, max_new=int(max_new),
                      arrival=float(arrival), priority=int(priority),
                      deadline=None if ttl is None else now + float(ttl),
                      t_submit=now)
        try:
            self.sched.submit(req)
        except QueueFull:
            self.log.record("queue", "serve.submit", "reject",
                            rid=req.rid, pending=len(self.sched.pending))
            raise
        self._requests[req.rid] = req
        self._shed_to_budget(now)
        return req.rid

    # -- plan-aware load shedding ------------------------------------------
    def _plan_cost(self) -> float:
        """Per-token admission cost from the cached plans' ``total_work``
        (the exact v3 ragged-grid steps a decode step replays) — the
        ROADMAP's plan-aware cost model.  Falls back to 1.0 (token units)
        when no plan is cached (dense runtime / before the first
        admission's :meth:`_plan_head`)."""
        total = sum(ps["total_work"] for ps in self.rt.plan_cache.plan_stats())
        return float(total) if total > 0 else 1.0

    def _plan_head(self, g: int) -> None:
        """Plan the LM-head weight eagerly into ``rt.plan_cache``, at the
        geometry a ``g``-row head matmul resolves: a miss at the first
        admission, an identity-validated hit at every later one.  The
        jitted programs plan in-trace and never cache, so this is the plan
        :meth:`_plan_cost` and ``plan_stats()`` read."""
        w = self.params.get("lm_head")
        if not self.rt.wants_sparse or w is None or w.ndim != 2:
            return
        rt = self.rt._resolved("matmul", (g, w.shape[0]), w.shape, w.dtype)
        rt.plan(w, key=("lm_head", id(w)), side="B")

    def _outstanding_work(self) -> float:
        cost = self._plan_cost()
        work = 0.0
        for r in self.sched.pending:
            work += cost * r.max_new
        for _, r in self.sched.occupied():
            work += cost * max(r.max_new - len(r.tokens), 0)
        return work

    def _shed_to_budget(self, now: float) -> list[Request]:
        """Shed pending requests (lowest effective priority first) until the
        outstanding work estimate fits the budget.  Shedding is a policy
        decision recorded on the victim (``finish_reason="shed"``) — NOT a
        :class:`QueueFull`, which signals capacity, not cost."""
        if self.work_budget is None:
            return []
        shed: list[Request] = []
        while self.sched.pending and self._outstanding_work() > self.work_budget:
            victim = min(
                self.sched.pending,
                key=lambda r: (self.sched.effective_priority(r, now), -r.rid),
            )
            self.sched.pending.remove(victim)
            victim.finished = True
            victim.finish_reason = "shed"
            victim.t_finish = now
            self.log.record(
                "queue", "serve.admission", "shed", rid=victim.rid,
                priority=victim.priority, cost=self._plan_cost() * victim.max_new,
                budget=self.work_budget,
            )
            shed.append(victim)
        return shed

    # -- deadlines ---------------------------------------------------------
    def _expire(self, now: float) -> list[Request]:
        """TTL expiry: drop pending requests and evict *running* slots whose
        deadline passed (the slot's device lane is deactivated; its cache
        rows are overwritten by the next occupant's slot write)."""
        out = []
        for req in self.sched.expire_pending(now):
            req.finished = True
            req.finish_reason = "expired"
            req.t_finish = now
            self.log.record("deadline", "serve.pending", "expire",
                            rid=req.rid, waited=now - req.t_submit)
            out.append(req)
        for slot, req in self.sched.occupied():
            if req.deadline is not None and req.deadline <= now:
                self.sched.evict(slot)
                self.active = self.active.at[slot].set(False)
                req.finished = True
                req.finish_reason = "expired"
                req.t_finish = now
                self.log.record("deadline", "serve.slot", "expire",
                                rid=req.rid, slot=slot,
                                emitted=len(req.tokens))
                out.append(req)
        return out

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def now(self) -> float:
        """Seconds on the engine clock (origin = engine construction).
        Traffic replays should schedule arrivals on this clock so request
        timestamps (``t_submit``/``t_first``/``t_finish``) are comparable."""
        return self._now()

    # -- admission: prefill into slots -------------------------------------
    def _admit_group(self, placements: list[tuple[int, Request]]) -> None:
        """Prefill one same-prompt-length group as a single batch and write
        each request's caches into its slot (per-slot cache views)."""
        g = len(placements)
        s = placements[0][1].prompt.shape[0]
        with tr.span(tr.SERVE_ADMIT, n=g, s=s, rid=placements[0][1].rid):
            now = self._now()
            for _, req in placements:
                req.t_admit = now
            prompts = jnp.stack([r.prompt for _, r in placements])
            with rtm.use(self.rt):
                with tr.span(tr.SERVE_PREFILL, n=g, s=s):
                    self._plan_head(g)
                    last, caches = _prefill_group(self.params, prompts,
                                                  cfg=self.cfg, rt=self.rt)
                rfaults.maybe_alloc_failure(
                    self.fault_plan or rfaults.active(), "grow_caches"
                )
                with tr.span(tr.SERVE_GROW, n=g):
                    part = self.rt.grow_caches(self.cfg, caches, g, self.max_len)
                axes = rtm.cache_batch_axes(self.cfg)
                for j, (slot, req) in enumerate(placements):
                    with tr.span(tr.SERVE_SLOT_WRITE, rid=req.rid, slot=slot):
                        row = jax.tree.map(
                            lambda x, ax: jax.lax.slice_in_dim(x, j, j + 1, axis=ax),
                            part, axes,
                        )
                        self.caches = self.rt.write_slot(self.cfg, self.caches,
                                                         slot, row)
            with tr.span(tr.SERVE_FIRST_TOKEN, n=g):
                # per-request RNG: fold the rid in, split BEFORE the first
                # sample — the first token and every later token draw from
                # distinct subkeys, and the stream depends only on (seed, rid),
                # never on the batch
                keys = jnp.stack(
                    [jax.random.fold_in(self._base_key, r.rid) for _, r in placements]
                )
                splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                carried, subs = splits[:, 0], splits[:, 1]
                firsts = np.asarray(_sample_rows(last, subs, self.temperature))
            now = self._now()
            for j, (slot, req) in enumerate(placements):
                first = int(firsts[j])
                req.t_first = now
                req.tokens.append(first)
                self.tokens_out += 1
                is_eos = self.eos_id is not None and first == self.eos_id
                done = req.max_new <= 1 or is_eos
                with tr.span(tr.SERVE_SLOT_STATE, rid=req.rid, slot=slot):
                    self.tok = self.tok.at[slot].set(first)
                    self.pos = self.pos.at[slot].set(s)
                    self.remaining = self.remaining.at[slot].set(req.max_new - 1)
                    self.keys = self.keys.at[slot].set(carried[j])
                    self.active = self.active.at[slot].set(not done)
                if done:
                    req.finish_reason = "eos" if is_eos else "length"
        self.admitted += g
        self.prefill_groups += 1
        self.prefill_tokens += g * s

    #: admission retries before a transient-alloc-failed request is failed
    MAX_ADMIT_RETRIES = 3

    def _admit_all(self) -> None:
        """Admit pending requests into free slots, batching same-length
        prompts into one prefill each (one compiled prefill program per
        group size and prompt length).

        A transient allocation failure during a group's prefill/slot-write
        is contained: the group's requests go back to the pending queue
        (bounded retries, then ``finish_reason="error"``) — one bad
        admission never kills the engine loop or the healthy slots."""
        placements = self.sched.admit(self._now())
        by_len: dict[int, list[tuple[int, Request]]] = {}
        for slot, req in placements:
            by_len.setdefault(req.prompt.shape[0], []).append((slot, req))
        for group in by_len.values():
            try:
                self._admit_group(group)
            except (rfaults.SimulatedAllocFailure, MemoryError) as e:
                now = self._now()
                for slot, req in group:
                    self.sched.evict(slot)
                    req.retries += 1
                    if req.retries > self.MAX_ADMIT_RETRIES:
                        req.finished = True
                        req.finish_reason = "error"
                        req.error = f"admission failed: {e}"
                        req.t_finish = now
                        self.log.record("alloc", "serve.admit", "fail-request",
                                        rid=req.rid, retries=req.retries)
                    else:
                        self.sched.pending.appendleft(req)
                        self.log.record("alloc", "serve.admit", "requeue",
                                        rid=req.rid, retries=req.retries)

    def _retire_finished(self) -> list[Request]:
        """Evict every occupied slot whose device state went inactive."""
        with tr.span(tr.SERVE_RETIRE):
            active = np.asarray(self.active)
            out = []
            for slot, req in self.sched.occupied():
                if not active[slot]:
                    req.finished = True
                    req.t_finish = self._now()
                    if req.finish_reason is None:
                        last = req.tokens[-1] if req.tokens else None
                        req.finish_reason = (
                            "eos" if self.eos_id is not None and last == self.eos_id
                            else "length"
                        )
                    out.append(self.sched.evict(slot))
        return out

    # -- the serving loop --------------------------------------------------
    def step(self) -> list[Request]:
        """Expire, admit, run one decode chunk, retire finished.

        Returns the requests that finished during this call (including
        expired/shed/errored ones).  No fault class escapes this loop: the
        watchdog retires poisoned slots in-graph, admission failures requeue
        or fail the one request, deadlines evict, shedding drops — healthy
        slots keep decoding bit-identically throughout."""
        with tr.span(tr.SERVE_STEP):
            now = self._now()
            if self.fault_plan is not None:
                rfaults.stall(self.fault_plan, "step_stall",
                              self.fault_plan.tick("serve.step"))
            finished = self._expire(now)
            finished += self._shed_to_budget(now)
            self._admit_all()
            finished += self._retire_finished()  # requests done at admission
            # backfill slots freed by admission-time finishes before decoding
            self._admit_all()
            finished += self._retire_finished()
            if not bool(np.any(np.asarray(self.active))):
                return finished
            with tr.span(tr.SERVE_DECODE, steps=self.chunk):
                out = _decode_chunk(*self._decode_args(self._chunk_poison()),
                                    **self._decode_statics())
                (self.caches, self.tok, self.pos, self.active, self.remaining,
                 self.keys, toks, emitted, faulted) = out
                self.chunks_run += 1
                self.steps_run += self.chunk
                toks = np.asarray(toks)          # [steps, slots]
                emitted = np.asarray(emitted)    # [steps, slots] bool
                faulted = np.asarray(faulted)    # [slots] bool
            for slot, req in self.sched.occupied():
                new = toks[emitted[:, slot], slot].tolist()
                req.tokens.extend(new)
                self.tokens_out += len(new)
                if faulted[slot]:
                    # watchdog retired this slot in-graph; record the error
                    # status before _retire_finished assigns a reason
                    req.finish_reason = "error"
                    req.error = "non-finite logits (watchdog)"
                    self.log.record("nonfinite", "serve.decode.watchdog",
                                    "retire-slot", rid=req.rid, slot=slot,
                                    chunk=self.chunks_run - 1,
                                    emitted=len(req.tokens))
            finished += self._retire_finished()
            return finished

    def _decode_args(self, poison):
        return (self.params, self.caches, self.tok, self.pos, self.active,
                self.remaining, self.keys, poison)

    def _decode_statics(self) -> dict:
        return dict(
            cfg=self.cfg, rt=self.rt, steps=self.chunk,
            temperature=self.temperature, eos_id=self.eos_id,
            pad_id=self.pad_id, watchdog=self.watchdog,
        )

    def lower_decode(self):
        """The decode-chunk program :meth:`step` runs, lowered at the
        engine's current state (``.compile().as_text()`` shows which kernels
        it holds) without running it."""
        return _decode_chunk.lower(*self._decode_args(self._zero_poison),
                                   **self._decode_statics())

    def _chunk_poison(self):
        """The [slots] poison-code vector for this chunk (all zeros — one
        cached buffer, no per-chunk upload — unless a fault plan fires)."""
        if self.fault_plan is None:
            return self._zero_poison
        p = rfaults.poison_slots(
            self.fault_plan, self.fault_plan.tick("serve.decode_chunk"),
            self.sched.num_slots,
        )
        return self._zero_poison if not p.any() else jnp.asarray(p)

    def run(self) -> dict[int, list[int]]:
        """Drain every submitted request; returns {rid: emitted tokens}."""
        while self.sched.has_work:
            self.step()
        return {rid: r.tokens for rid, r in self._requests.items()}

    def stats(self) -> dict:
        """Engine + plan-cache counters.

        ``decode_traces`` and ``prefill_traces`` (process-wide
        :data:`DECODE_TRACES`, :data:`PREFILL_TRACES`) are the
        compile-count probes: at least ``prefill_groups - prefill_traces``
        of this engine's prefills replayed a compiled program.  The plan
        cache's ``traced`` counter only moves when *this* runtime's cache
        was threaded through a trace: two engines with equal-policy
        runtimes share one compiled decode and prefill program (jit statics
        hash the policy, not the cache handle), so the second engine's
        ``traced`` legitimately stays 0."""
        return {
            "tokens_out": self.tokens_out,
            "chunks_run": self.chunks_run,
            "steps_run": self.steps_run,
            "admitted": self.admitted,
            "prefill_groups": self.prefill_groups,
            "prefill_tokens": self.prefill_tokens,
            "slots": self.sched.num_slots,
            "decode_traces": DECODE_TRACES,
            "prefill_traces": PREFILL_TRACES,
            "plan_cache": self.rt.plan_cache.stats(),
            "resilience_events": len(self.log),
        }


def generate(
    params,
    cfg: ModelConfig,
    prompt_tokens,
    *,
    max_new: int = 32,
    max_len: int | None = None,
    temperature: float = 0.0,
    seed: int = 0,
    mesh=None,
    rt: "rtm.Runtime | None" = None,
):
    """End-to-end batched generation (LM archs).  prompt [B, S] int32.

    A thin convenience wrapper over :class:`ServeEngine`: every row becomes
    a request, slots equal the batch, one jitted chunk covers the whole
    decode.  ``rt`` selects the execution policy (backend, geometry, mesh,
    plan cache); when omitted it resolves ambient -> dense.
    """
    rt = rtm.resolve(rt)
    if mesh is not None:
        from repro.parallel.sharding import ShardingPolicy  # local: import cycle

        policy = rt.sharding or ShardingPolicy()
        rt = rt.replace(sharding=policy.replace(mesh=mesh))
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    b, s = prompt_tokens.shape
    max_len = max_len or (s + max_new)
    eng = ServeEngine(
        params, cfg, slots=b, max_len=max_len, rt=rt,
        temperature=temperature, seed=seed, chunk=max(max_new - 1, 1),
    )
    rids = [eng.submit(prompt_tokens[i], max_new=max_new) for i in range(b)]
    out = eng.run()
    return jnp.asarray(np.stack([out[r] for r in rids]), jnp.int32)  # [B, max_new]
