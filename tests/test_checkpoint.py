"""Checkpointing: atomic roundtrip, keep-k pruning, resume, elastic reload."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import all_steps, latest_step, restore, save
from repro.launch.mesh import make_mesh


def _tree(key, scale=1.0):
    return {
        "w": jax.random.normal(key, (4, 8), jnp.float32) * scale,
        "nested": {"b": jnp.arange(5, dtype=jnp.int32)},
    }


def test_roundtrip(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save(str(tmp_path), 3, t)
    like = jax.tree.map(jnp.zeros_like, t)
    r = restore(str(tmp_path), 3, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_k_and_latest(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, t, keep=2)
    assert all_steps(str(tmp_path)) == [4, 5]
    assert latest_step(str(tmp_path)) == 5


def test_elastic_restore_with_shardings(tmp_path):
    """Restore onto explicit (single-device) shardings: the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((1, 1), ("data", "model"))
    t = _tree(jax.random.PRNGKey(1))
    save(str(tmp_path), 7, t)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    r = restore(str(tmp_path), 7, t, shardings=sh)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_no_partial_on_existing(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save(str(tmp_path), 1, t)
    # second save of same step replaces atomically
    t2 = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.int32 else x, t)
    save(str(tmp_path), 1, t2)
    r = restore(str(tmp_path), 1, t)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t2["w"]))
