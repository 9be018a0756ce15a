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
