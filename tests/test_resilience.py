"""Fault injection + graceful degradation: the resilience layer.

Every injector class in ``repro.resilience.faults.KINDS`` must be detected
at its trust boundary and *contained*:

* NaN/Inf decode logits -> the in-graph watchdog retires exactly the
  poisoned slot (error status); healthy batch-mates stay bit-identical to a
  clean run and the decode program does not retrace;
* NaN loss/grads -> the guarded train step skips the update (params and
  opt state bitwise untouched);
* corrupt ``SparsityPlan`` metadata -> ``Runtime(validate=)`` *recovers* by
  replanning from operand values (bit-identical result), ``PlanCache.scrub``
  evicts, the dynamic-sparsity controller degrades to a from-scratch replan;
* corrupt TuningDB file -> load degrades to empty with a warning;
* failed/slow shard -> the sharded executors fall back to single-device;
* allocation failure -> the serve engine halves slots / requeues admission;
* deadlines, bounded queues and plan-aware shedding keep overload typed
  (``QueueFull``) or policy-shaped (``finish_reason="shed"``), never
  unbounded.

Everything replays from one seeded :class:`FaultPlan`, and every
degradation lands in the :class:`ResilienceLog`.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rtm
from repro.analysis.plan_check import PlanVerificationError, check_plan
from repro.configs import get_config, reduce_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.common import init_params
from repro.resilience import (
    DB_CORRUPTIONS,
    PLAN_CORRUPTIONS,
    FaultPlan,
    FaultSpec,
    ResilienceLog,
    SimulatedAllocFailure,
    capture_warnings,
    corrupt_cache_entry,
    corrupt_db_file,
    corrupt_file,
    corrupt_plan,
    inject,
    poison_slots,
    train_poison,
)
from repro.resilience import faults as rfaults
from repro.resilience import log as rlog
from repro.runtime import Runtime, plan_operand
from repro.serve import engine as serve_engine
from repro.serve.engine import QueueFull, Request, Scheduler, ServeEngine


def _small_setup(arch="deepseek-7b", seed=0):
    cfg = reduce_config(get_config(arch))
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(seed))
    return cfg, params


def _sparse_operand(rng, m=64, k=64, bm=8, bk=8, density=0.4):
    a = rng.normal(size=(m, k)).astype(np.float32)
    keep = rng.random((m // bm, k // bk)) < density
    for i in range(m // bm):
        for j in range(k // bk):
            if not keep[i, j]:
                a[i * bm:(i + 1) * bm, j * bk:(j + 1) * bk] = 0.0
    return jnp.asarray(a)


# ---------------------------------------------------------------------------
# FaultPlan: grammar, replay determinism
# ---------------------------------------------------------------------------


def test_fault_plan_parse_grammar():
    fp = FaultPlan.parse(
        "nan_logits@2:slot=1,count=3; alloc_fail@0:where=grow_caches;"
        "step_stall@4:secs=0.25", seed=7,
    )
    assert len(fp.specs) == 3 and fp.seed == 7 and bool(fp)
    s0 = fp.specs[0]
    assert (s0.kind, s0.at, s0.slot, s0.count) == ("nan_logits", 2, 1, 3)
    assert s0.fires_at(2) and s0.fires_at(4) and not s0.fires_at(5)
    assert fp.specs[1].where == "grow_caches"
    assert fp.specs[2].secs == 0.25
    assert not FaultPlan.parse("")  # empty plan is falsy
    assert not FaultPlan.parse(None)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("frobnicate@0")
    with pytest.raises(ValueError, match="unknown fault field"):
        FaultPlan.parse("nan_loss@0:wibble=3")


def test_fault_plan_ticks_and_reset():
    fp = FaultPlan.parse("shard_fail@1")
    assert [fp.tick("s") for _ in range(3)] == [0, 1, 2]
    assert fp.tick("other") == 0  # per-site counters
    assert not fp.fires("shard_fail", 0) and fp.fires("shard_fail", 1)
    fp.reset()
    assert fp.tick("s") == 0


def test_fault_plan_where_filter():
    fp = FaultPlan.parse("alloc_fail@0:where=slot_caches")
    assert fp.fires("alloc_fail", 0, where="slot_caches")
    assert not fp.fires("alloc_fail", 0, where="grow_caches")
    with pytest.raises(SimulatedAllocFailure):
        rfaults.maybe_alloc_failure(fp, "slot_caches")
    rfaults.maybe_alloc_failure(fp, "grow_caches")  # filtered: no raise


def test_seeded_corruption_replays_bit_identical():
    rng = np.random.default_rng(3)
    plan = plan_operand(_sparse_operand(rng), 8, 8)
    a = corrupt_plan(plan, rng=np.random.default_rng(11))
    b = corrupt_plan(plan, rng=np.random.default_rng(11))
    np.testing.assert_array_equal(np.asarray(a.nnz), np.asarray(b.nnz))
    np.testing.assert_array_equal(np.asarray(a.idx), np.asarray(b.idx))


def test_poison_codes():
    fp = FaultPlan.parse("nan_logits@1:slot=2;inf_logits@3")
    assert poison_slots(fp, 0, 4).tolist() == [0, 0, 0, 0]
    assert poison_slots(fp, 1, 4).tolist() == [0, 0, 1, 0]
    assert poison_slots(fp, 3, 4).tolist() == [2, 2, 2, 2]  # slot=-1: all
    assert poison_slots(None, 1, 4).tolist() == [0, 0, 0, 0]
    tp = FaultPlan.parse("nan_loss@1;nan_grad@2")
    assert [train_poison(tp, i) for i in range(3)] == [0, 1, 2]
    assert train_poison(None, 1) == 0


# ---------------------------------------------------------------------------
# injectors stay honest: every corruption mode actually violates an invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", PLAN_CORRUPTIONS)
def test_corrupt_plan_modes_fail_verification(mode):
    rng = np.random.default_rng(0)
    plan = plan_operand(_sparse_operand(rng), 8, 8)
    check_plan(plan, level="full")  # clean plan passes
    bad = corrupt_plan(plan, mode=mode)
    with pytest.raises(PlanVerificationError):
        check_plan(bad, level="full")
    if mode in ("nnz-range", "row-starts"):  # O(Rb) structure faults:
        with pytest.raises(PlanVerificationError):  # the cheap tier sees them
            check_plan(bad, level="boundary")
    # the input plan is untouched
    check_plan(plan, level="full")


# ---------------------------------------------------------------------------
# ResilienceLog
# ---------------------------------------------------------------------------


def test_resilience_log_counts_and_summary():
    log = ResilienceLog()
    assert len(log) == 0 and log.summary() != ""
    log.record("nonfinite", "serve.decode.watchdog", "retire-slot", rid=3)
    log.record("nonfinite", "serve.decode.watchdog", "retire-slot", rid=4)
    log.record("deadline", "serve.pending", "expire", rid=5)
    assert len(log) == 3
    assert log.counts()[("nonfinite", "retire-slot")] == 2
    assert len(log.by_kind("deadline")) == 1
    assert "retire-slot x2" in log.summary()
    assert '"rid": 3' in log.to_json()


def test_ambient_log_and_module_record():
    assert rlog.record("x", "y", "z") is None  # no-op without a log
    log = ResilienceLog()
    with rlog.use_log(log):
        assert rlog.ambient_log() is log
        rlog.record("shard", "site", "fallback")
    assert rlog.ambient_log() is None
    assert len(log) == 1 and log.events[0].kind == "shard"


def test_capture_warnings_mirrors_into_log():
    log = ResilienceLog()
    with pytest.warns(RuntimeWarning, match="hello"):  # still emitted
        with capture_warnings(log):
            warnings.warn("hello degradation", RuntimeWarning)
    assert len(log) == 1
    ev = log.events[0]
    assert ev.kind == "warning" and "hello degradation" in str(ev.detail)


# ---------------------------------------------------------------------------
# serve: watchdog containment — the tentpole invariant
# ---------------------------------------------------------------------------


def _run_engine(params, cfg, prompts, budgets, *, fault_plan=None,
                watchdog=True, temperature=0.8):
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=2, max_len=32, chunk=3, seed=0,
                      temperature=temperature, fault_plan=fault_plan, log=log,
                      watchdog=watchdog)
    for p, n in zip(prompts, budgets):
        eng.submit(p, max_new=n)
    out = eng.run()
    return eng, out, log


@pytest.mark.parametrize("kind,code", [("nan_logits", 1), ("inf_logits", 2)])
def test_watchdog_retires_poisoned_slot_healthy_bitident(kind, code):
    """Poison one slot's logits mid-decode: that request errors, every
    healthy batch-mate's tokens are bit-identical to a clean run, and the
    decode program does not retrace (shape signature unchanged)."""
    cfg, params = _small_setup()
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, (s,)), jnp.int32)
               for s in (5, 8, 5)]
    budgets = (6, 7, 5)
    _, clean, _ = _run_engine(params, cfg, prompts, budgets)
    traces_before = serve_engine.DECODE_TRACES
    fp = FaultPlan.parse(f"{kind}@0:slot=1")
    eng, out, log = _run_engine(params, cfg, prompts, budgets, fault_plan=fp)
    assert serve_engine.DECODE_TRACES == traces_before, "watchdog retraced"
    victim = eng._requests[1]
    assert victim.finish_reason == "error" and not victim.ok
    assert "watchdog" in victim.error
    # healthy batch-mates: bit-identical token streams
    for rid in (0, 2):
        assert out[rid] == clean[rid], f"rid {rid} perturbed by slot 1 fault"
        assert eng._requests[rid].ok
    ev = log.by_kind("nonfinite")
    assert len(ev) == 1 and ev[0].action == "retire-slot"
    assert ev[0].detail["rid"] == 1
    assert eng.stats()["resilience_events"] == len(log)


def test_watchdog_off_propagates_poison():
    """Sanity check on the detector itself: without the watchdog a poisoned
    slot keeps emitting (garbage) tokens instead of erroring — the fault
    class is real, the watchdog is what contains it."""
    cfg, params = _small_setup()
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)]
    fp = FaultPlan.parse("nan_logits@0:slot=0")
    eng, out, log = _run_engine(params, cfg, prompts, (6,), fault_plan=fp,
                                watchdog=False, temperature=0.0)
    req = eng._requests[0]
    assert req.finish_reason == "length" and req.error is None
    assert len(out[0]) == 6  # garbage tokens kept flowing
    assert not log.by_kind("nonfinite")


# ---------------------------------------------------------------------------
# serve: deadlines, bounded queue, priority, shedding
# ---------------------------------------------------------------------------


def test_ttl_expires_pending_and_running():
    cfg, params = _small_setup()
    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=1, max_len=32, chunk=2, log=log)
    r_run = eng.submit(p, max_new=20, ttl=1000.0)
    r_wait = eng.submit(p, max_new=4, ttl=1000.0)
    eng.step()  # admits r_run into the only slot; r_wait pending
    assert eng._requests[r_run].slot == 0
    # force both deadlines into the past (deterministic expiry)
    eng._requests[r_run].deadline = eng.now() - 1.0
    eng._requests[r_wait].deadline = eng.now() - 1.0
    finished = eng.step()
    reasons = {r.rid: r.finish_reason for r in finished}
    assert reasons == {r_run: "expired", r_wait: "expired"}
    assert not bool(np.asarray(eng.active)[0])  # slot lane deactivated
    sites = {e.site for e in log.by_kind("deadline")}
    assert sites == {"serve.slot", "serve.pending"}
    assert not eng.sched.has_work


def test_queue_full_is_typed_and_drains():
    cfg, params = _small_setup()
    rng = np.random.default_rng(2)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=1, max_len=32, chunk=2,
                      max_pending=2, log=log)
    eng.submit(p, max_new=2)
    eng.submit(p, max_new=2)
    with pytest.raises(QueueFull, match="retry with backoff"):
        eng.submit(p, max_new=2)
    assert len(eng._requests) == 2  # the rejected one was never registered
    assert log.by_kind("queue")[0].action == "reject"
    eng.step()  # drains one pending into the slot
    rid = eng.submit(p, max_new=2)  # capacity available again
    eng.run()
    assert eng._requests[rid].ok


def test_priority_admission_with_aging():
    sched = Scheduler(1, age_boost=0.1)
    lo = Request(rid=0, prompt=None, max_new=1, priority=0, t_submit=0.0)
    hi = Request(rid=1, prompt=None, max_new=1, priority=3, t_submit=10.0)
    sched.submit(lo), sched.submit(hi)
    # eff(lo) = 0.1*10 = 1 < eff(hi) = 3: priority wins while fresh
    ((slot, first),) = sched.admit(now=10.0)
    assert first.rid == 1
    sched.evict(slot)
    ((_, second),) = sched.admit(now=10.0)
    assert second.rid == 0
    # aged: the old low-priority request outranks fresh high-priority
    sched2 = Scheduler(1, age_boost=0.5)
    old_lo = Request(rid=0, prompt=None, max_new=1, priority=0, t_submit=0.0)
    fresh_hi = Request(rid=1, prompt=None, max_new=1, priority=3, t_submit=20.0)
    sched2.submit(old_lo), sched2.submit(fresh_hi)
    ((_, winner),) = sched2.admit(now=20.0)  # eff: 0 + 0.5*20 = 10 > 3
    assert winner.rid == 0
    # default priorities degenerate to exact FIFO
    sched3 = Scheduler(2)
    for i in range(3):
        sched3.submit(Request(rid=i, prompt=None, max_new=1))
    assert [r.rid for _, r in sched3.admit(now=5.0)] == [0, 1]


def test_plan_aware_shedding_is_not_queue_full():
    cfg, params = _small_setup()
    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=1, max_len=32, chunk=2,
                      work_budget=10.0, log=log)
    # dense runtime: plan cost falls back to 1.0/token
    assert eng._plan_cost() == 1.0
    keep = eng.submit(p, max_new=8, priority=5)
    victim = eng.submit(p, max_new=8, priority=0)  # 16 > 10: shed cheapest
    assert eng._requests[victim].finish_reason == "shed"
    assert not eng._requests[keep].finished
    ev = log.by_kind("queue")
    assert ev and ev[-1].action == "shed" and ev[-1].detail["rid"] == victim
    eng.run()
    assert eng._requests[keep].ok


# ---------------------------------------------------------------------------
# serve: allocation failure containment
# ---------------------------------------------------------------------------


def test_alloc_failure_halves_slots():
    cfg, params = _small_setup()
    fp = FaultPlan.parse("alloc_fail@0:where=slot_caches")
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=4, max_len=32, chunk=2,
                      fault_plan=fp, log=log)
    assert eng.sched.num_slots == 2  # degraded capacity, not a crash
    assert log.by_kind("alloc")[0].action == "halve-slots"
    rng = np.random.default_rng(4)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
    rid = eng.submit(p, max_new=3)
    eng.run()
    assert eng._requests[rid].ok  # still serves


def test_alloc_failure_at_admission_requeues_and_recovers():
    cfg, params = _small_setup()
    rng = np.random.default_rng(5)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
               for _ in range(2)]
    _, clean, _ = _run_engine(params, cfg, prompts, (4, 4))
    fp = FaultPlan.parse("alloc_fail@0:where=grow_caches")
    eng, out, log = _run_engine(params, cfg, prompts, (4, 4), fault_plan=fp)
    acts = [e.action for e in log.by_kind("alloc")]
    assert "requeue" in acts
    for rid in (0, 1):  # the transient failure cost a retry, not the result
        assert eng._requests[rid].ok
        assert out[rid] == clean[rid]


def test_alloc_failure_exhausts_retries_fails_one_request():
    cfg, params = _small_setup()
    rng = np.random.default_rng(6)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (5,)), jnp.int32)
    fp = FaultPlan.parse("alloc_fail@0:count=99,where=grow_caches")
    log = ResilienceLog()
    eng = ServeEngine(params, cfg, slots=1, max_len=32, chunk=2,
                      fault_plan=fp, log=log)
    rid = eng.submit(p, max_new=3)
    for _ in range(2 * eng.MAX_ADMIT_RETRIES + 4):
        if eng._requests[rid].finished:
            break
        eng.step()
    req = eng._requests[rid]
    assert req.finished and req.finish_reason == "error"
    assert "admission failed" in req.error
    assert req.retries > eng.MAX_ADMIT_RETRIES
    assert log.by_kind("alloc")[-1].action == "fail-request"
    assert not eng.sched.has_work  # the engine loop survived


# ---------------------------------------------------------------------------
# runtime boundary: corrupt plan metadata -> recovery, cache scrub
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", PLAN_CORRUPTIONS)
def test_runtime_recovers_corrupt_plan_bit_identical(mode):
    """A corrupt explicit plan at the ``Runtime.matmul`` boundary is
    detected by the validator and *recovered* — replanned from the operand —
    so the output is bit-identical to the clean-plan call.  Structure
    faults are exercised against the cheap boundary tier; content faults
    need ``validate="full"``."""
    rng = np.random.default_rng(7)
    a = _sparse_operand(rng)
    b = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    level = "boundary" if mode in ("nnz-range", "row-starts") else "full"
    rt = Runtime(backend="reference", bm=8, bk=8, validate=level)
    plan = plan_operand(a, 8, 8)
    want = rt.matmul(a, b, plan=plan)
    log = ResilienceLog()
    with rlog.use_log(log):
        with pytest.warns(RuntimeWarning, match="corrupt SparsityPlan"):
            got = rt.matmul(a, b, plan=corrupt_plan(plan, mode=mode))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ev = log.by_kind("plan-corrupt")
    assert len(ev) == 1 and ev[0].action == "replan"


def test_runtime_validate_off_skips_recovery():
    """validate="off" is the documented no-checking contract: the boundary
    does not pay for verification (and a corrupt plan is the caller's
    problem) — recovery is a ``validate`` feature, not a tax."""
    rng = np.random.default_rng(8)
    a = _sparse_operand(rng)
    rt = Runtime(backend="reference", bm=8, bk=8, validate="off")
    plan = plan_operand(a, 8, 8)
    assert rt._recovered_plan(plan, a) is plan
    bad = corrupt_plan(plan, mode="nnz-range")
    assert rt._recovered_plan(bad, a) is bad


def test_plan_cache_scrub_evicts_corrupt_entries():
    rng = np.random.default_rng(9)
    rt = Runtime(backend="reference", bm=8, bk=8, validate="boundary")
    for seed in (1, 2):
        a = _sparse_operand(np.random.default_rng(seed))
        plan = plan_operand(a, 8, 8)
        rt.plan_cache.store(("w", seed), plan.idx, plan)
    assert len(rt.plan_cache) == 2
    assert rt.plan_cache.scrub() == []  # clean cache: nothing evicted
    key = corrupt_cache_entry(rt.plan_cache, rng=rng)
    bad = rt.plan_cache.scrub()
    assert len(bad) == 1 and bad[0][0] == key
    assert len(rt.plan_cache) == 1
    assert rt.plan_cache.scrub() == []  # idempotent


# ---------------------------------------------------------------------------
# TuningDB file corruption -> degrade to empty, loudly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", DB_CORRUPTIONS)
def test_tuning_db_corruption_degrades_to_empty(mode, tmp_path):
    from repro.tune.db import TunedPolicy, TuningDB

    path = tmp_path / "db.json"
    db = TuningDB(platform="cpu")
    db.store(db.key(op="matmul", m=64, k=256, n=64, dtype=jnp.float32,
                    density=0.5),
             TunedPolicy(bm=8, bk=16, bn=16))
    db.save(path)
    assert len(TuningDB.load(path, platform="cpu")) == 1  # round-trips clean
    assert corrupt_db_file(path, mode=mode) == mode
    with pytest.warns(UserWarning, match="TuningDB"):
        db2 = TuningDB.load(path, platform="cpu")
    assert len(db2) == 0  # never serves corrupt policies


# ---------------------------------------------------------------------------
# sharded executors: failed/slow shard -> contained fallback
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 host devices (tests/conftest.py)")
@pytest.mark.parametrize("fused", [False, True])
def test_shard_failure_falls_back_to_unsharded(fused):
    from repro.parallel import spmm
    from repro.parallel.sharding import ShardingPolicy
    from repro.runtime.backends import KernelRequest, get_backend

    rng = np.random.default_rng(10)
    a = _sparse_operand(rng, m=128, k=64)
    b = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    plan = plan_operand(a, 8, 8)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b,
                        bm=8, bk=8, bn=8, workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=make_mesh((4, 2), ("data", "model")))
    be = get_backend("reference")
    log = ResilienceLog()
    fp = FaultPlan.parse("shard_fail@0:count=99")
    if fused:
        want, want_mask = be.execute_fused(req)
        with rlog.use_log(log), inject(fp):
            with pytest.warns(RuntimeWarning, match="degrading to unsharded"):
                got, got_mask = spmm.sharded_execute_fused(
                    "reference", req, policy, axis="M")
        np.testing.assert_array_equal(np.asarray(got_mask),
                                      np.asarray(want_mask))
    else:
        want = be.execute_planned(req)
        with rlog.use_log(log), inject(fp):
            with pytest.warns(RuntimeWarning, match="degrading to unsharded"):
                got = spmm.sharded_execute_planned(
                    "reference", req, policy, axis="M")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ev = log.by_kind("shard")
    assert ev and ev[0].action == "fallback-unsharded"


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 host devices (tests/conftest.py)")
def test_no_fault_plan_no_shard_overhead_path():
    """Without an ambient plan the executors take the sharded path (the
    contextvar probe must not change routing)."""
    from repro.parallel import spmm
    from repro.parallel.sharding import ShardingPolicy
    from repro.runtime.backends import KernelRequest, get_backend

    rng = np.random.default_rng(11)
    a = _sparse_operand(rng, m=128, k=64)
    b = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    plan = plan_operand(a, 8, 8)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b,
                        bm=8, bk=8, bn=8, workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=make_mesh((4, 2), ("data", "model")))
    want = get_backend("reference").execute_planned(req)
    got = spmm.sharded_execute_planned("reference", req, policy, axis="M")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# train: non-finite guard — skip-step leaves state bitwise untouched
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def train_setup():
    from repro.data.pipeline import SyntheticLM
    from repro.optim.adamw import OptConfig, init_opt_state

    cfg = reduce_config(get_config("qwen3-4b"))
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    data = SyntheticLM(cfg.vocab_size, 16, 4, seed=1)
    return cfg, OptConfig(lr=1e-3), params, opt, data.batch_at(0)


@pytest.mark.parametrize("code,what", [(1, "loss"), (2, "grads")])
def test_guarded_step_skips_poisoned_update(train_setup, code, what):
    from repro.train.step import make_train_step

    cfg, ocfg, params, opt, batch = train_setup
    step = jax.jit(make_train_step(cfg, ocfg,
                                   guard_nonfinite=True))
    p2, o2, m = step(params, opt, batch, poison=jnp.int32(code))
    assert int(m["nonfinite"]) == 1, f"NaN {what} undetected"
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_guard_is_free_on_clean_steps(train_setup):
    """The guard's where(ok, new, old) select must not perturb a clean
    update: guarded(poison=0) == unguarded, bitwise."""
    from repro.train.step import make_train_step

    cfg, ocfg, params, opt, batch = train_setup
    bare = jax.jit(make_train_step(cfg, ocfg))
    guarded = jax.jit(make_train_step(cfg, ocfg,
                                      guard_nonfinite=True))
    p1, o1, m1 = bare(params, opt, batch)
    p2, o2, m2 = guarded(params, opt, batch, poison=jnp.int32(0))
    assert int(m2["nonfinite"]) == 0
    np.testing.assert_array_equal(np.asarray(m1["loss"]),
                                  np.asarray(m2["loss"]))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# checkpoint: corrupt-on-disk -> restore_latest walks back
# ---------------------------------------------------------------------------


def test_restore_latest_skips_corrupt_checkpoint(tmp_path):
    import os

    from repro.checkpoint.manager import restore_latest, save

    tree = {"w": jnp.arange(6, dtype=jnp.float32)}
    save(tmp_path, 1, tree)
    save(tmp_path, 2, jax.tree.map(lambda x: x + 1, tree))
    corrupt_file(os.path.join(tmp_path, "step_000000000002", "arrays.npz"))
    log = ResilienceLog()
    with rlog.use_log(log):
        with pytest.warns(RuntimeWarning, match="unreadable"):
            step, got = restore_latest(tmp_path, tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(6))
    ev = log.by_kind("checkpoint")
    assert ev and ev[0].action == "skip-corrupt" and ev[0].detail["step"] == 2


def test_restore_latest_empty_and_all_corrupt(tmp_path):
    import os

    from repro.checkpoint.manager import restore_latest, save

    tree = {"w": jnp.zeros((3,))}
    assert restore_latest(tmp_path / "nope", tree) == (None, None)
    save(tmp_path, 1, tree)
    corrupt_file(os.path.join(tmp_path, "step_000000000001", "arrays.npz"))
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert restore_latest(tmp_path, tree) == (None, None)


# ---------------------------------------------------------------------------
# dynamic sparse training: corrupt live plan -> loud from-scratch replan
# ---------------------------------------------------------------------------


def _make_controller(validate="boundary"):
    from repro.sparse_train import DynamicSparsityConfig, DynamicSparsityController

    rng = np.random.default_rng(12)
    rt = Runtime(backend="dense", bm=8, bk=16, bn=16, validate=validate)
    params = {"w": jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32))}
    cfg = DynamicSparsityConfig(target=0.75, begin=0, end=6, update_every=1,
                                min_size=256)
    return DynamicSparsityController(cfg, params, rt=rt), params, rng


def test_controller_degrades_to_from_scratch_replan(monkeypatch):
    import repro.sparse_train.controller as ctrl_mod
    from repro.sparse_train import (
        apply_block_masks, block_scores, plan_from_block_mask,
    )

    clean_ctrl, params, rng = _make_controller()
    bad_ctrl, _, _ = _make_controller()
    (path,) = clean_ctrl.units
    spec = clean_ctrl.spec()
    scores = block_scores(apply_block_masks(params, clean_ctrl.masks(), spec),
                          spec)
    gs = {path: jnp.asarray(rng.random((4, 3)).astype(np.float32))}
    u = bad_ctrl.units[path]
    # inject a splice failure (what a corrupt live plan surfaces as: the
    # edit's structural validator rejecting its result)
    def broken_edit(plan, delta, **kw):
        raise ValueError("injected: spliced queue failed verification")

    log = ResilienceLog()
    with rlog.use_log(log), monkeypatch.context() as mp:
        mp.setattr(ctrl_mod, "edit_plan", broken_edit)
        # step 1: the cubic ramp actually prunes (step 0 is all-dense)
        with pytest.warns(RuntimeWarning, match="from-scratch replan"):
            rep_bad = bad_ctrl.update(1, scores, gs)
    rep_clean = clean_ctrl.update(1, scores, gs)
    assert rep_bad["pruned"] == rep_clean["pruned"] > 0
    ev = log.by_kind("plan-corrupt")
    assert ev and ev[0].action == "replan"
    # masks converge identically, and the replanned pair IS the post-delta
    # mask's from-scratch plan (bit-identical metadata)
    cu = clean_ctrl.units[path]
    np.testing.assert_array_equal(u.mask, cu.mask)
    bk, bn = u.block
    want = plan_from_block_mask(u.mask[0], bm=bk, bk=bn,
                                shape=(u.kb * bk, u.nb * bn),
                                dtype=u.bwd[0].dtype)
    np.testing.assert_array_equal(np.asarray(u.bwd[0].nnz),
                                  np.asarray(want.nnz))
    np.testing.assert_array_equal(np.asarray(u.bwd[0].idx),
                                  np.asarray(want.idx))
    # the recovered controller keeps ramping cleanly
    scores2 = block_scores(apply_block_masks(params, bad_ctrl.masks(), spec),
                           spec)
    bad_ctrl.update(2, scores2, gs)


def test_controller_drift_is_a_bug_not_a_degradation():
    """_delta_consistent separates plan-side corruption (recoverable) from
    controller drift (prune of inactive / regrow of active = bug)."""
    from repro.sparse_train import PlanDelta
    from repro.sparse_train.controller import DynamicSparsityController

    mask = np.ones((4, 3), bool)
    mask[0, 0] = False
    ok = DynamicSparsityController._delta_consistent
    assert ok(mask, PlanDelta.make([[1, 1]], [[0, 0]]))
    assert not ok(mask, PlanDelta.make([[0, 0]], []))  # prune inactive
    assert not ok(mask, PlanDelta.make([], [[1, 1]]))  # regrow active
