"""repro.runtime: backend registry parity, SparsityPlan cache semantics,
geometry auto-clamping, layout-driven cache growth, decode plan reuse."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rtm
from repro.configs import get_config, reduce_config
from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import model as M
from repro.models.common import init_params
from repro.runtime import (
    BackendCapabilityError,
    PlanCache,
    Runtime,
    available_backends,
    get_backend,
    register_backend,
)
from repro.serve.engine import ServeEngine, generate


def _sparse_operand(rng, m, k, bm, bk, density=0.5):
    a = rng.standard_normal((m, k)).astype(np.float32)
    mask = rng.random((m // bm, k // bk)) < density
    return jnp.asarray(
        (a.reshape(m // bm, bm, k // bk, bk) * mask[:, None, :, None]).reshape(m, k)
    )


# ---------------------------------------------------------------------------
# backend registry + parity
# ---------------------------------------------------------------------------


def test_registry_has_builtin_backends():
    assert {"dense", "reference", "pallas", "interpret"} <= set(available_backends())
    with pytest.raises(ValueError, match="unknown kernel backend"):
        get_backend("no-such-backend")


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (32, 64, 32, 16, 32, 16),
    (64, 128, 48, 16, 32, 16),
    (128, 256, 64, 32, 64, 32),
])
@pytest.mark.parametrize("density", [0.0, 0.4, 1.0])
def test_backend_parity_dense_vs_interpret_bit_exact(m, k, n, bm, bk, bn, density):
    """Registry parity sweep: executing the same SparsityPlan on the dense
    (pure-jnp schedule executor) and interpret (Pallas) backends is
    bit-exact — identical tile decomposition, identical fp32 accumulation
    order, only all-zero blocks elided."""
    rng = np.random.default_rng(m * 7 + n)
    a = _sparse_operand(rng, m, k, bm, bk, density)
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    rt = Runtime(backend="interpret", bm=bm, bk=bk, bn=bn)
    plan = rt.plan(a)
    out_dense = np.asarray(get_backend("dense").matmul_planned(plan, a, b, bn=bn))
    out_interp = np.asarray(get_backend("interpret").matmul_planned(plan, a, b, bn=bn))
    out_ref = np.asarray(get_backend("reference").matmul_planned(plan, a, b, bn=bn))
    np.testing.assert_array_equal(out_dense, out_interp)
    np.testing.assert_array_equal(out_ref, out_interp)
    # and everything matches plain XLA up to fp32 reduction-order noise
    np.testing.assert_allclose(out_interp, np.asarray(a @ b), rtol=2e-4, atol=2e-4)


def test_runtime_matmul_across_backends():
    rng = np.random.default_rng(0)
    a = _sparse_operand(rng, 64, 128, 16, 32)
    b = jnp.asarray(rng.standard_normal((128, 48)).astype(np.float32))
    outs = {
        name: np.asarray(Runtime(backend=name, bm=16, bk=32, bn=16).matmul(a, b))
        for name in ("dense", "reference", "interpret")
    }
    np.testing.assert_array_equal(outs["reference"], outs["interpret"])
    np.testing.assert_allclose(outs["dense"], outs["interpret"], rtol=2e-4, atol=2e-4)


def test_capability_checks():
    pallas = get_backend("pallas")
    if jax.default_backend() != "tpu":
        with pytest.raises(BackendCapabilityError, match="requires a TPU"):
            pallas.check_platform()
        assert not pallas.supports(32, 64, 32, bm=16, bk=32, bn=16)
        assert not Runtime(backend="pallas").supports_matmul((32, 64), (64, 32))
    interp = get_backend("interpret")
    # the raw backend API still rejects indivisible geometry ...
    with pytest.raises(BackendCapabilityError, match="not divisible"):
        interp.check_geometry(33, 64, 32, bm=16, bk=32, bn=16)
    # ... but the Runtime fits tiles and pads, so it supports any shape
    # on-platform: 33 rows keep the 16-row tile and run padded to 48
    rt = Runtime(backend="interpret", bm=16, bk=32, bn=16)
    assert rt.supports_matmul((33, 64), (64, 32))
    fitted = rt.fit((33, 64), (64, 32))
    assert (fitted.bm, fitted.bk, fitted.bn) == (16, 32, 16)
    # chip-legal at the default targets: a multiple of 128 or the whole dim
    wide = Runtime(backend="interpret").fit((200, 2560), (2560, 50280))
    assert (wide.bm, wide.bk, wide.bn) == (128, 512, 128)
    assert Runtime(backend="interpret").fit((4, 2560), (2560, 4)).bm == 4


def test_register_custom_backend():
    class Doubler(rtm.KernelBackend):
        name = "test-doubler"
        sparse = False

        def matmul(self, a, b, *, bm, bk, bn, out_dtype=None):
            return 2.0 * (a @ b)

    register_backend(Doubler())
    assert "test-doubler" in available_backends()
    a = jnp.ones((4, 4), jnp.float32)
    out = Runtime(backend="test-doubler").matmul(a, a)
    np.testing.assert_allclose(np.asarray(out), 8.0 * np.ones((4, 4)))


# ---------------------------------------------------------------------------
# SparsityPlan + PlanCache semantics
# ---------------------------------------------------------------------------


def test_plan_stats():
    rng = np.random.default_rng(3)
    a = _sparse_operand(rng, 64, 128, 16, 32, density=0.5)
    plan = Runtime(backend="interpret", bm=16, bk=32, bn=16).plan(a)
    s = plan.stats()
    assert s["blocks"] == 16 and 0.0 <= s["density"] <= 1.0
    assert s["effectual"] == int(np.asarray(plan.nnz).sum())


def test_plan_cache_hit_miss_semantics():
    rng = np.random.default_rng(1)
    rt = Runtime(backend="interpret", bm=16, bk=32, bn=16)
    a1 = _sparse_operand(rng, 32, 64, 16, 32)
    p1 = rt.plan(a1, key="w")
    assert rt.plan_cache.stats() == {"entries": 1, "hits": 0, "misses": 1, "traced": 0}
    assert rt.plan(a1, key="w") is p1  # identity-validated hit
    assert rt.plan_cache.hits == 1
    # same key, different array -> miss, entry replaced (never stale reuse)
    a2 = _sparse_operand(rng, 32, 64, 16, 32)
    p2 = rt.plan(a2, key="w")
    assert p2 is not p1 and rt.plan_cache.misses == 2
    assert rt.plan(a2, key="w") is p2
    # keyless planning never touches the cache
    before = rt.plan_cache.stats()
    rt.plan(a1)
    assert rt.plan_cache.stats() == before


def test_plan_cache_never_caches_tracers():
    rt = Runtime(backend="dense", bm=16, bk=32, bn=16)

    @jax.jit
    def f(a):
        return rt.plan(a, key="traced").nnz.sum()

    rng = np.random.default_rng(2)
    f(_sparse_operand(rng, 32, 64, 16, 32))
    assert len(rt.plan_cache) == 0 and rt.plan_cache.misses == 0


def test_plan_cache_lru_capacity():
    cache = PlanCache(capacity=2)
    rt = Runtime(backend="dense", bm=16, bk=32, bn=16, plan_cache=cache)
    rng = np.random.default_rng(4)
    arrays = [_sparse_operand(rng, 32, 64, 16, 32) for _ in range(3)]
    for i, a in enumerate(arrays):
        rt.plan(a, key=f"w{i}")
    assert len(cache) == 2  # oldest (least recently used) evicted
    # rebinding an existing key at capacity replaces in place: the other
    # live entry must survive
    rebound = rt.plan(_sparse_operand(rng, 32, 64, 16, 32), key="w2")
    assert len(cache) == 2
    assert rt.plan(arrays[1], key="w1") is not None and cache.hits >= 1


def test_plan_cache_lru_hit_survives_eviction():
    """Eviction is LRU, not FIFO: a just-hit entry must outlive an older
    *insertion* when a new entry forces eviction — serving with more live
    weights than capacity keeps the hottest plans resident."""
    cache = PlanCache(capacity=2)
    rt = Runtime(backend="dense", bm=16, bk=32, bn=16, plan_cache=cache)
    rng = np.random.default_rng(7)
    a0 = _sparse_operand(rng, 32, 64, 16, 32)
    a1 = _sparse_operand(rng, 32, 64, 16, 32)
    a2 = _sparse_operand(rng, 32, 64, 16, 32)
    p0 = rt.plan(a0, key="w0")
    rt.plan(a1, key="w1")
    assert rt.plan(a0, key="w0") is p0  # hit: w0 becomes most recent
    rt.plan(a2, key="w2")  # at capacity: must evict w1 (LRU), NOT w0
    misses = cache.misses
    assert rt.plan(a0, key="w0") is p0  # survived eviction (no new miss)
    assert cache.misses == misses
    assert rt.plan(a1, key="w1").nnz is not None  # w1 was the one evicted
    assert cache.misses == misses + 1


def test_sparse_backend_is_differentiable():
    """Training through the planned Pallas matmul: the sparsity-aware VJP
    yields the dense-math cotangents (only all-zero blocks are elided in
    the registry-routed backward products — see tests/test_backward_planned.py)."""
    rng = np.random.default_rng(8)
    a = _sparse_operand(rng, 32, 64, 16, 32)
    b = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    rt = Runtime(backend="interpret", bm=16, bk=32, bn=16)

    def loss(a, b, f):
        return jnp.sum(f(a, b) ** 2)

    da, db = jax.grad(lambda aa, bb: loss(aa, bb, rt.matmul), argnums=(0, 1))(a, b)
    da_ref, db_ref = jax.grad(
        lambda aa, bb: loss(aa, bb, lambda x, y: x @ y), argnums=(0, 1)
    )(a, b)
    np.testing.assert_allclose(np.asarray(da), np.asarray(da_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref), rtol=2e-4, atol=2e-4)


def test_accum_dtype_policy_is_enforced():
    rt = Runtime(backend="dense", accum_dtype=jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="accumulate in float32"):
        rt.matmul(jnp.ones((4, 4)), jnp.ones((4, 4)))


def test_geometry_autoclamps_no_dense_fallback():
    """A sparse backend whose blocks don't divide the shapes auto-clamps its
    geometry (bm 16 -> 3 for a 3-token microbatch) and stays on the planned
    path — no RuntimeWarning, no silent dense XLA numbers."""
    cfg = _relu_cfg()
    rng = np.random.default_rng(9)
    params = {
        "w_gate": jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32)) * 0.05,
        "w_up": jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32)) * 0.05,
        "w_down": jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32)) * 0.05,
    }
    x = jnp.asarray(rng.standard_normal((1, 3, 32)).astype(np.float32))  # 3 rows: indivisible
    from repro.models.transformer import mlp_fwd as _mlp

    with rtm.use(Runtime(backend="interpret", bm=16, bk=16, bn=16)):
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            out = _mlp(params, cfg, x)
    with rtm.use(Runtime(backend="dense")):
        ref = _mlp(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_clamped_geometry_matches_dense_and_is_bit_exact_across_backends():
    rng = np.random.default_rng(12)
    a = jnp.asarray(rng.standard_normal((6, 40)).astype(np.float32))  # 6x40: odd
    b = jnp.asarray(rng.standard_normal((40, 24)).astype(np.float32))
    outs = {
        name: np.asarray(Runtime(backend=name, bm=16, bk=32, bn=16).matmul(a, b))
        for name in ("reference", "interpret")
    }
    np.testing.assert_array_equal(outs["reference"], outs["interpret"])
    np.testing.assert_allclose(outs["interpret"], np.asarray(a @ b), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# runtime resolution (the PR-1 deprecation shims are gone)
# ---------------------------------------------------------------------------


def test_explicit_runtime_beats_ambient_beats_default():
    explicit = Runtime(backend="reference")
    ambient = Runtime(backend="interpret")
    assert rtm.resolve().backend == "dense"
    with rtm.use(ambient):
        assert rtm.resolve().backend == "interpret"
        assert rtm.resolve(explicit).backend == "reference"
    assert rtm.resolve().backend == "dense"


def test_legacy_shims_are_gone():
    """PR 2 scheduled the three one-release shims for removal here: the
    ``mode=`` kernel kwarg, ``ModelConfig.ffn_kernel_mode``, and explicit
    ``mesh=`` on the train-step factories must no longer exist."""
    import dataclasses as dc

    from repro.optim.adamw import OptConfig
    from repro.train.step import make_loss_fn, make_train_step

    rng = np.random.default_rng(5)
    a = _sparse_operand(rng, 32, 64, 16, 32)
    b = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    with pytest.raises(TypeError):
        kops.matmul(a, b, mode="interpret")
    # runtime= replaces it, bit-identical to the Runtime method
    legacy_free = kops.matmul(
        a, b, runtime=Runtime(backend="interpret"), bm=16, bk=32, bn=16
    )
    new = Runtime(backend="interpret", bm=16, bk=32, bn=16).matmul(a, b)
    np.testing.assert_array_equal(np.asarray(legacy_free), np.asarray(new))

    cfg = reduce_config(get_config("deepseek-7b"))
    assert "ffn_kernel_mode" not in {f.name for f in dc.fields(cfg)}
    with pytest.raises(TypeError):
        dc.replace(cfg, ffn_kernel_mode="interpret")
    with pytest.raises(TypeError):
        make_train_step(cfg, OptConfig(), object())  # positional mesh
    with pytest.raises(TypeError):
        make_loss_fn(cfg, object())


def test_ambient_mesh_resolution():
    from repro.parallel.sharding import ShardingPolicy

    assert rtm.active_mesh(None) is None
    sentinel = object()
    with rtm.use(Runtime(sharding=ShardingPolicy(mesh=sentinel))):
        assert rtm.active_mesh(None) is sentinel
        assert rtm.active_mesh("explicit") == "explicit"


def test_ambient_policy_resolution():
    from repro.parallel.sharding import ShardingPolicy

    # no ambient runtime: a fresh single-device policy
    assert rtm.active_policy().mesh is None
    pol = ShardingPolicy(mesh=object())
    assert rtm.active_policy(pol) is pol  # explicit wins
    with rtm.use(Runtime(sharding=pol)):
        assert rtm.active_policy() is pol
        other = ShardingPolicy()
        assert rtm.active_policy(other) is other


def test_mesh_kwarg_shim_is_gone():
    """PR 7 scheduled the one-release ``Runtime(mesh=...)`` constructor shim
    for removal here: the keyword must no longer exist, while the readable
    ``rt.mesh`` property (the ``sharding.mesh`` alias) keeps working."""
    from repro.parallel.sharding import ShardingPolicy

    sentinel = object()
    with pytest.raises(TypeError):
        Runtime(mesh=sentinel)
    # the replacement path is the only path, and reads back via .mesh
    rt = Runtime(sharding=ShardingPolicy(mesh=sentinel))
    assert rt.mesh is sentinel
    with rtm.use(rt):
        assert rtm.active_mesh(None) is sentinel
    assert Runtime().mesh is None
    assert rt.replace(bn=32).mesh is sentinel


# ---------------------------------------------------------------------------
# layout-driven cache growth (replaces the shape-guessing heuristic)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-2b", "mamba2-780m"])
def test_grow_caches_matches_canonical_layout(arch):
    cfg = reduce_config(get_config(arch))
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    b, s, max_len = 2, 8, 20
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    _, caches = M.prefill(params, cfg, {"tokens": toks})
    rt = Runtime()
    grown = rt.grow_caches(cfg, caches, b, max_len)
    target = M.init_cache(cfg, b, max_len)
    assert jax.tree.map(lambda x: x.shape, grown) == jax.tree.map(lambda x: x.shape, target)
    # prefill contents preserved at the origin of every leaf
    for g, c in zip(jax.tree.leaves(grown), jax.tree.leaves(caches)):
        sl = tuple(slice(0, d) for d in c.shape)
        np.testing.assert_array_equal(
            np.asarray(g[sl], np.float32), np.asarray(c, np.float32)
        )


def test_grow_caches_noop_when_max_len_equals_prompt():
    """The old heuristic's `max_len == s` edge: growth must be a no-op."""
    cfg = reduce_config(get_config("deepseek-7b"))
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    _, caches = M.prefill(params, cfg, {"tokens": toks})
    grown = Runtime().grow_caches(cfg, caches, b, s)
    for g, c in zip(jax.tree.leaves(grown), jax.tree.leaves(caches)):
        assert g.shape == c.shape


# ---------------------------------------------------------------------------
# serving: decode loop reuses the prefill-time SparsityPlan
# ---------------------------------------------------------------------------


def _relu_cfg():
    return ModelConfig(
        name="rt-test", family="dense", num_layers=2, d_model=32, vocab_size=64,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, activation="relu",
        q_chunk=16, remat=False,
    )


def test_generate_decode_reuses_prefill_plan():
    """The LM-head plan is computed once, eagerly, at the first admission;
    the jitted prefill and decode programs carry it as part of the traced
    program — ``traced`` counts
    the single trace, not one plan per token — and a second generation with
    the same runtime cache-hits the prefill plan and retraces nothing."""
    cfg = _relu_cfg()
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    max_new = 5
    # bm=2 tiles the decode batch rows; head runs weight-side (side="B")
    rt = Runtime(backend="interpret", bm=2, bk=16, bn=16)
    out_sparse = generate(params, cfg, prompt, max_new=max_new, rt=rt)
    stats = rt.plan_cache.stats()
    assert stats["entries"] == 1, stats  # one lm_head plan, planned at prefill
    assert stats["misses"] == 1, stats
    traced_after_first = stats["traced"]
    assert traced_after_first >= 1, stats  # the decode scan planned in-trace
    # second generation: prefill plan replayed (identity-validated hit), and
    # the decode program is replayed from the jit cache — no new trace
    generate(params, cfg, prompt, max_new=max_new, rt=rt)
    stats2 = rt.plan_cache.stats()
    assert stats2["hits"] >= 1, stats2
    assert stats2["misses"] == 1, stats2
    assert stats2["traced"] == traced_after_first, stats2
    out_dense = generate(params, cfg, prompt, max_new=max_new, rt=Runtime())
    np.testing.assert_array_equal(np.asarray(out_sparse), np.asarray(out_dense))


def test_generate_matches_dense_under_ambient_sparse_runtime():
    cfg = _relu_cfg()
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size)
    rt = Runtime(backend="reference", bm=2, bk=16, bn=16)
    with rtm.use(rt):
        out = generate(params, cfg, prompt, max_new=3)
        generate(params, cfg, prompt, max_new=3)
    out_dense = generate(params, cfg, prompt, max_new=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_dense))
    # the second ambient generation replays the first one's prefill plan
    assert rt.plan_cache.misses == 1 and rt.plan_cache.hits >= 1


def test_engine_plan_cost_prices_budget_in_head_plan_work():
    """Under a sparse runtime the work budget is priced in the cached
    LM-head plan's ``total_work`` (ragged-grid steps) from the first
    admission on, although the engine's prefill and decode are jitted."""
    cfg = _relu_cfg()
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(3), (6,), 0, cfg.vocab_size)
    rt = Runtime(backend="reference", bm=2, bk=16, bn=16)
    eng = ServeEngine(params, cfg, slots=2, max_len=16, chunk=2, rt=rt)
    assert eng._plan_cost() == 1.0  # nothing planned before an admission
    eng.submit(prompt, max_new=2)
    eng.run()
    [ps] = rt.plan_cache.plan_stats()
    assert ps["key"][0] == "lm_head" and ps["side"] == "B"
    work = float(ps["total_work"])
    assert eng._plan_cost() == work > 1.0
    # a budget of 1.5 requests' grid steps admits one request and sheds the
    # next; priced in tokens (cost 1.0) neither would be shed
    eng.work_budget = 1.5 * work * 4
    first = eng.submit(prompt, max_new=4)
    second = eng.submit(prompt, max_new=4)
    reqs = eng._requests
    assert not reqs[first].finished
    assert reqs[second].finish_reason == "shed"
