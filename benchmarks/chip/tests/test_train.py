"""A training run with the timed path broken underneath comes out not
correct, once for each fault a training cell on one chip can have, and a
sound run comes out correct.  The harness's look for a chip is skipped: a
tiny mamba2 training cell runs on the CPU through the same driver,
reference and limits as the benchmark's cell (``harness/train.py``)."""
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from harness import train

SEED = 2**31 + 31


def _train(hook=None):
    cell = tiny.train_cell(seq=128)
    return train.run(cell, SEED, 1.0, False, time.monotonic(), jax.devices()[:1],
                     program_hook=hook)


def test_sound_train_run_is_correct():
    result, checks = _train()
    assert result["correct"], checks
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(checks) == set(train.NUMBERS)


def _unchanged(prog):
    """Every step returns the state it was given."""
    raw = prog.raw_step
    prog.step_fn = jax.jit(lambda p, o, b, poison=None: (p, o, raw(p, o, b, poison=poison)[2]))


def _half_batch(prog):
    """Every step is fed the first half of its rows: the loss is the mean
    over the rest."""
    whole = prog.feed
    prog.feed = lambda batch: whole({k: v[: len(v) // 2] for k, v in batch.items()})


def _leaf_scaled(prog):
    """One parameter leaf, the first (the embedding), scaled by 1.01 before
    its change is read."""
    read = prog.change_norms

    def scaled():
        w = prog.params["embed"]
        prog.params["embed"] = (1.01 * w.astype(jnp.float32)).astype(w.dtype)
        return read()

    prog.change_norms = scaled


@pytest.mark.parametrize("fault, fails", [
    (_unchanged, ("first_grad_gap", "change_gap", "grad_dir_median")),
    (_half_batch, ("change_gap",)),
    (_leaf_scaled, ("change_gap",)),
], ids=["state_unchanged", "half_batch", "leaf_scaled"])
def test_broken_step_is_not_correct(fault, fails):
    result, checks = _train(fault)
    assert not result["correct"], checks
    for name in fails:
        assert checks[name]["value"] > checks[name]["limit"], (name, checks)
