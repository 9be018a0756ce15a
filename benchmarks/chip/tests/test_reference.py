"""The plain reference against the program's own model at a reduced size
on the CPU, both in float32: the same weights from the same seed, the same
logits to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np

import tiny
from harness import reference
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.launch.mesh import init_sharded_params, make_local_mesh
from repro.parallel.sharding import ShardingPolicy

SEED = 2**31 + 3


def _program(cell):
    cfg = ModelConfig(**cell.config["program"])
    policy = ShardingPolicy(mesh=make_local_mesh(devices=jax.devices()[:1]))
    params = init_sharded_params(cfg, policy, SEED)
    return cfg, params


def test_qwen3_weights_and_logits():
    cell = tiny.serve_cell()
    ref = reference.load_module("qwen3")
    cfg, params = _program(cell)
    W = ref.make_weights(ref.param_tree(cell.config), SEED)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), params, W)
    assert all(jax.tree.leaves(same))
    ids = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want = M.forward(p32, cfg, {"tokens": jnp.asarray(ids)}).reshape(80, -1)
    h = ref.final_hidden(cell.config, W, ids, np.arange(80), fp8=False)
    got = jnp.matmul(h, W["lm_head"].astype(jnp.float32), precision=ref.HI)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_qwen3_served_gap_of_greedy_tokens_is_small():
    cell = tiny.serve_cell()
    ref = reference.load_module("qwen3")
    W = ref.make_weights(ref.param_tree(cell.config), SEED)
    prompt = np.arange(1, 17, dtype=np.int32)
    ids, toks = prompt, []
    for _ in range(6):  # greedy continuation by the reference itself
        h = ref.final_hidden(cell.config, W, ids[None], np.asarray([len(ids) - 1]), False)
        nxt = int(jnp.argmax(h @ W["lm_head"].astype(jnp.float32)))
        toks.append(nxt)
        ids = np.append(ids, nxt).astype(np.int32)
    got = ref.served_gaps(cell.config, SEED, [(prompt, np.asarray(toks, np.int32))])
    assert got["tokens"] == 6 and got["widest_gap"] < 1e-4


def _mamba2(seq=192):
    cell = tiny.train_cell(seq=seq)
    ref = reference.load_module("mamba2")
    cfg, params = _program(cell)
    return cell, ref, cfg, params


def test_mamba2_weights_loss_and_gradients():
    # 192 positions: three blocks of the reference's decay sums, twelve of
    # the program's chunks
    from harness import traffic as gen

    cell, ref, cfg, params = _mamba2()
    W = ref.make_weights(ref.param_tree(cell.config), SEED)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), params, W)
    assert all(jax.tree.leaves(same))
    batch = gen.TrainStream(cell.traffic, SEED).batch(0)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(lambda p: M.loss_fn(
            p, cfg, {k: jnp.asarray(v) for k, v in batch.items()}))(p32)
    got, gg = ref.Trainer(cell.config, cell.traffic["optimizer"]).grads(W, batch)
    # float32 both sides, summed in other orders (chunked against quadratic
    # SSD): the loss to a few float32 ulps, every gradient leaf to 1e-4 of
    # its norm (measured: 2.6e-5 at worst, on a_log)
    assert abs(got - float(want)) <= 1e-6 * abs(float(want))
    for name, a, b in zip(ref.leaf_names(gg), jax.tree.leaves(gw), jax.tree.leaves(gg)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), name


def test_mamba2_adamw_matches_the_program():
    # the reference's update against the program's optimizer on the same
    # float32 gradients, over two steps: the same float32 arithmetic
    from repro.optim.adamw import OptConfig, apply_updates, init_opt_state

    cell = tiny.train_cell()
    opt = cell.traffic["optimizer"]
    ref = reference.load_module("mamba2")
    rng = np.random.default_rng(0)
    p = {"a": jnp.asarray(rng.normal(size=(64, 32)) * 0.05, jnp.bfloat16),
         "b": jnp.ones((32,), jnp.bfloat16)}
    state = init_opt_state(p)
    q, m, v = dict(p), {n: state.m[n] for n in p}, {n: state.v[n] for n in p}
    for t in (1, 2):
        g = {n: jnp.asarray(rng.normal(size=x.shape) * 3, jnp.float32) for n, x in p.items()}
        p, state, _ = apply_updates(p, g, state, OptConfig(**opt))
        gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values())))
        scale = jnp.float32(min(1.0, opt["clip_norm"] / (gnorm + 1e-9)))
        for n in q:
            q[n], m[n], v[n] = ref._adamw(q[n], g[n], m[n], v[n], scale, jnp.int32(t), opt)
        for n in q:
            np.testing.assert_allclose(np.asarray(m[n]), np.asarray(state.m[n]), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(v[n]), np.asarray(state.v[n]), rtol=1e-6)
            # bfloat16 storage: equal, or one ulp apart where the float32
            # clip scale's last bit sends a value across a rounding edge
            d = np.abs(np.asarray(q[n], np.float32) - np.asarray(p[n], np.float32))
            assert np.all(d <= np.abs(np.asarray(p[n], np.float32)) * 2 ** -7 + 1e-30), n
