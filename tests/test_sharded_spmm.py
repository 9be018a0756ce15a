"""Distributed sparse execution under a forced 8-device host platform.

``tests/conftest.py`` sets ``--xla_force_host_platform_device_count=8``
before jax initialises, so these tests run a real ``shard_map`` over 8
devices.  The contract under test (``repro.parallel.spmm``): M- and
N-sharded planned/fused execution and both VJP products are **bit-identical**
to single-device, per-device grids are per-shard ragged work queues (steps =
``sum(max(nnz_shard, 1))``), and everything degrades gracefully when shapes
don't divide the mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rtm
from repro.kernels.ref import plan_workqueue_ref
from repro.launch.mesh import make_mesh
from repro.parallel import spmm
from repro.parallel.sharding import ShardingPolicy
from repro.runtime import (
    Runtime,
    balanced_row_order,
    plan_operand,
    shard_plan,
    unshard_plan,
)
from repro.runtime.backends import KernelRequest, get_backend

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (tests/conftest.py sets XLA_FLAGS)",
)

BM = BK = BN = 8


def _mixed_mesh():
    return make_mesh((4, 2), ("data", "model"))


def _powerlaw_operand(rng, m=512, k=128, *, mean_density=0.5):
    """[m, k] fp32 with power-law block-row density around ``mean_density``:
    a few dense rows, a long tail of nearly-empty ones — the skew v3's
    per-shard queues absorb and a contiguous global-max split cannot."""
    a = rng.normal(size=(m, k)).astype(np.float32)
    rb, kb = m // BM, k // BK
    # pareto tail, clipped to [1/kb, 1]; scaled to the requested mean
    dens = np.clip(rng.pareto(1.2, size=rb) / 3, 1.0 / kb, 1.0)
    dens *= mean_density / dens.mean()
    # densest rows first: clustered heavy rows are the worst case for a
    # contiguous split (and change nothing for the serpentine deal)
    dens = np.sort(np.clip(dens, 1.0 / kb, 1.0))[::-1]
    for i in range(rb):
        drop = rng.random(kb) > dens[i]
        for j in np.nonzero(drop)[0]:
            a[i * BM:(i + 1) * BM, j * BK:(j + 1) * BK] = 0.0
    return jnp.asarray(a)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(5)
    a = _powerlaw_operand(rng)
    b = jnp.asarray(rng.normal(size=(a.shape[1], 64)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    return a, b, bias


# ---------------------------------------------------------------------------
# plan layer: shard/unshard round-trip, per-shard queues vs the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("axis", ["M", "N", "K"])
@pytest.mark.parametrize("balance", [True, False])
def test_shard_unshard_round_trip(operands, axis, balance):
    a, _, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    shards = shard_plan(plan, 8, axis=axis, balance=balance)
    back = unshard_plan(shards)
    for name in ("nnz", "idx", "row_starts", "work_row", "work_kblk"):
        np.testing.assert_array_equal(
            np.asarray(getattr(back, name)), np.asarray(getattr(plan, name)),
            err_msg=f"{axis} round-trip broke {name}",
        )
    assert back.shape == plan.shape and (back.bm, back.bk) == (plan.bm, plan.bk)


@pytest.mark.parametrize("axis", ["M", "N", "K"])
def test_per_shard_workqueue_matches_oracle(operands, axis):
    """Every shard's (row_starts, work_row, work_kblk) is exactly the
    reference CSR queue of that shard's own (nnz, idx) — each device's grid
    is ``sum(max(nnz_shard, 1))`` steps, nothing global."""
    a, _, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    shards = plan.shard(8, axis=axis)
    for s in range(8):
        rs, wr, wk = plan_workqueue_ref(
            np.asarray(shards.nnz[s]), np.asarray(shards.idx[s])
        )
        np.testing.assert_array_equal(np.asarray(shards.row_starts[s]), rs)
        np.testing.assert_array_equal(np.asarray(shards.work_row[s]), wr)
        np.testing.assert_array_equal(np.asarray(shards.work_kblk[s]), wk)
    if axis == "M":  # the deal partitions the global queue exactly
        total = int(shards.shard_work().sum())
        assert total == int(np.maximum(np.asarray(plan.nnz), 1).sum())


def test_balanced_deal_within_10pct_where_naive_exceeds_2x(operands):
    """The acceptance skew bound: serpentine-balanced per-device grid steps
    stay within 10% of the mean on power-law rows where the naive contiguous
    split is more than 2x imbalanced."""
    a, _, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    work = np.maximum(np.asarray(plan.nnz), 1)
    naive = work.reshape(8, -1).sum(axis=1)  # contiguous block-row split
    naive_imb = naive.max() / naive.mean()
    assert naive_imb > 2.0, f"fixture not skewed enough: {naive_imb:.2f}x"
    balanced = plan.shard(8, axis="M", balance=True)
    per_dev = balanced.shard_work()
    assert per_dev.max() / per_dev.mean() <= 1.10, per_dev
    assert balanced.imbalance() <= 1.10
    # the in-graph deal is the host-side deal
    np.testing.assert_array_equal(
        np.asarray(jax.jit(balanced_row_order, static_argnums=1)(plan.nnz, 8)),
        np.asarray(balanced.order),
    )


def test_plan_stats_reports_per_shard_split(operands):
    a, b, _ = operands
    rt = Runtime(backend="reference", bm=BM, bk=BK, bn=BN)
    rt.matmul(a, b, plan_key="w0")
    stats = rt.plan_cache.plan_stats(shards=8)
    entry = next(s for s in stats if s["key"] == "w0")
    assert len(entry["shard_work"]) == 8
    assert len(entry["shard_skipped"]) == 8
    assert entry["imbalance"] >= 1.0
    assert sum(entry["shard_work"]) == entry["total_work"]


# ---------------------------------------------------------------------------
# executors: sharded vs single-device, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("axis", ["M", "N"])
def test_sharded_planned_forward_bitwise(operands, backend, axis):
    a, b, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b,
                        bm=BM, bk=BK, bn=BN, workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=_mixed_mesh())
    ref = get_backend(backend).execute_planned(req)
    out = spmm.sharded_execute_planned(backend, req, policy, axis=axis)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_k_psum_allclose(operands):
    """K-sharding reassociates the accumulation through a psum: allclose,
    documented as not bitwise."""
    a, b, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b,
                        bm=BM, bk=BK, bn=BN, workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=_mixed_mesh())
    ref = get_backend("reference").execute_planned(req)
    out = spmm.sharded_execute_planned("reference", req, policy, axis="K")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("axis", ["M", "N"])
def test_sharded_fused_forward_bitwise(operands, backend, axis):
    a, b, bias = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b, bias=bias,
                        activation="relu", bm=BM, bk=BK, bn=BN,
                        workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=_mixed_mesh())
    ref_out, ref_mask = get_backend(backend).execute_fused(req)
    out, mask = spmm.sharded_execute_fused(backend, req, policy, axis=axis)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref_mask))


def test_fused_k_sharding_refused(operands):
    a, b, bias = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a, b=b, bias=bias,
                        activation="relu", bm=BM, bk=BK, bn=BN)
    policy = ShardingPolicy(mesh=_mixed_mesh())
    with pytest.raises(NotImplementedError, match="psum"):
        spmm.sharded_execute_fused("reference", req, policy, axis="K")


def test_indivisible_shapes_fall_back_unsharded(operands):
    """3 block rows over 4 data shards: the executor degrades to the plain
    single-device path (replicate-don't-split), still bitwise of course."""
    a, b, _ = operands
    a3 = a[: 3 * BM]
    plan = plan_operand(a3, bm=BM, bk=BK)
    req = KernelRequest(nnz=plan.nnz, idx=plan.idx, a=a3, b=b,
                        bm=BM, bk=BK, bn=BN, workqueue=plan.workqueue())
    policy = ShardingPolicy(mesh=_mixed_mesh())
    out = spmm.sharded_execute_planned("reference", req, policy, axis="M")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(get_backend("reference").execute_planned(req))
    )


# ---------------------------------------------------------------------------
# differentiation: both VJP products, bitwise vs the single-device rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("axis", ["M", "N"])
def test_sharded_grads_bitwise(operands, axis):
    a, b, _ = operands
    rt = Runtime(backend="interpret", bm=BM, bk=BK, bn=BN)
    rts = rt.replace(sharding=ShardingPolicy(mesh=_mixed_mesh()))

    g_ref = jax.grad(lambda x, y: jnp.sum(rt.matmul(x, y) ** 2), argnums=(0, 1))(a, b)
    g_sh = jax.grad(
        lambda x, y: jnp.sum(rts.matmul_sharded(x, y, axis=axis) ** 2),
        argnums=(0, 1),
    )(a, b)
    np.testing.assert_array_equal(np.asarray(g_sh[0]), np.asarray(g_ref[0]))
    np.testing.assert_array_equal(np.asarray(g_sh[1]), np.asarray(g_ref[1]))


@pytest.mark.parametrize("axis", ["M", "N"])
def test_sharded_fused_grads_bitwise(operands, axis):
    a, b, bias = operands
    rt = Runtime(backend="interpret", bm=BM, bk=BK, bn=BN)
    rts = rt.replace(sharding=ShardingPolicy(mesh=_mixed_mesh()))

    def loss(runtime, sharded):
        def f(x, y, z):
            if sharded:
                out, _ = runtime.matmul_fused_sharded(
                    x, y, bias=z, activation="relu", axis=axis
                )
            else:
                out, _ = runtime.matmul_fused(x, y, bias=z, activation="relu")
            return jnp.sum(out ** 2)

        return f

    g_ref = jax.grad(loss(rt, False), argnums=(0, 1, 2))(a, b, bias)
    g_sh = jax.grad(loss(rts, True), argnums=(0, 1, 2))(a, b, bias)
    for got, want in zip(g_sh, g_ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_matmul_jit_and_no_mesh_degrade(operands):
    a, b, _ = operands
    rt = Runtime(backend="interpret", bm=BM, bk=BK, bn=BN)
    rts = rt.replace(sharding=ShardingPolicy(mesh=_mixed_mesh()))
    ref = rt.matmul(a, b)
    out = jax.jit(lambda x, y: rts.matmul_sharded(x, y, axis="M"))(a, b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # a policy-less runtime degrades matmul_sharded to plain matmul
    np.testing.assert_array_equal(
        np.asarray(rt.matmul_sharded(a, b)), np.asarray(ref)
    )


# ---------------------------------------------------------------------------
# dynamic sparsity: incremental edits flow into fresh per-shard queues
# ---------------------------------------------------------------------------


def test_dynamic_refresh_edits_apply_to_sharded_plans(operands):
    from repro.sparse_train.plan_edit import PlanDelta, edit_plan

    a, b, _ = operands
    plan = plan_operand(a, bm=BM, bk=BK)
    shards0 = plan.shard(8, axis="M")
    assert plan.shard(8, axis="M") is shards0  # memoized on the plan

    nnz = np.asarray(plan.nnz)
    idx = np.asarray(plan.idx)
    # prune one live block from the densest row, regrow one dead block in
    # the emptiest — the RigL refresh shape
    dense_r = int(nnz.argmax())
    sparse_r = int(nnz.argmin())
    live = (dense_r, int(idx[dense_r, 0]))
    dead_cols = sorted(set(range(idx.shape[1])) - set(idx[sparse_r, : nnz[sparse_r]]))
    delta = PlanDelta.make([live], [(sparse_r, dead_cols[0])])
    edited = edit_plan(plan, delta)

    # the edited plan's shards match a from-scratch shard of the edited
    # metadata, per-shard queues included (oracle check)
    es = edited.shard(8, axis="M")
    assert es is not shards0
    for s in range(8):
        rs, wr, wk = plan_workqueue_ref(
            np.asarray(es.nnz[s]), np.asarray(es.idx[s])
        )
        np.testing.assert_array_equal(np.asarray(es.row_starts[s]), rs)
        np.testing.assert_array_equal(np.asarray(es.work_row[s]), wr)
        np.testing.assert_array_equal(np.asarray(es.work_kblk[s]), wk)

    # and sharded execution of the edited plan is bitwise vs single-device
    a_masked = np.asarray(a).copy()
    r, c = live
    a_masked[r * BM:(r + 1) * BM, c * BK:(c + 1) * BK] = 0.0
    a_masked = jnp.asarray(a_masked)
    req = KernelRequest(nnz=edited.nnz, idx=edited.idx, a=a_masked, b=b,
                        bm=BM, bk=BK, bn=BN, workqueue=edited.workqueue())
    policy = ShardingPolicy(mesh=_mixed_mesh())
    out = spmm.sharded_execute_planned("reference", req, policy, axis="M")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(get_backend("reference").execute_planned(req))
    )


# ---------------------------------------------------------------------------
# acceptance: production configs build through ShardingPolicy (shape-level)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "qwen3-moe-235b-a22b"])
def test_configs_build_sharded_train_and_serve(arch):
    """Reduced 236b-class configs build a sharded train step and run the
    serve engine end-to-end through a mesh-backed ShardingPolicy — no
    hand-threaded ``mesh=`` anywhere."""
    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.models.common import init_params
    from repro.optim.adamw import OptConfig, init_opt_state
    from repro.serve.engine import generate
    from repro.train.step import make_train_step

    cfg = reduce_config(get_config(arch))
    mesh = _mixed_mesh()
    policy = ShardingPolicy(mesh=mesh)
    rt = Runtime(backend="dense", sharding=policy)
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    # batch divides the 4-wide data axis: the MoE dispatch shard_map splits
    # tokens over it
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    with mesh, rtm.use(rt):
        step = make_train_step(cfg, OptConfig())
        shapes = jax.eval_shape(
            step, params, opt, {"tokens": toks, "labels": toks}
        )
        p_shapes, _, metrics = shapes
        assert jax.tree.map(lambda x: x.shape, p_shapes) == jax.tree.map(
            lambda x: x.shape, params
        )
        assert "loss" in metrics
        out = generate(params, cfg, toks[:, :8], max_new=2, rt=rt)
    assert out.shape == (4, 2)
