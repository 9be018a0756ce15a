"""The control -- the plain reference computed in float8, the precision
below the configurations' bfloat16, put in the program's place -- read
through the same comparison a run makes, at a size a test run can hold, as
on the chip at the cell's own size (``calibrate.py --control``,
``calibrate_train.py --control``, readings in PERF.md).  For serving, the
gap of the token the control puts first at each position of the same
prompts and tokens comes out not correct.  For training, the control's own
steps (``precision="chip"``: bfloat16 products and residual stream, float8
activations) on the same batches from the same weights come out not
correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import reference

SEEDS = [2**31 + 41, 2**31 + 42, 2**31 + 43]


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_the_limit(seed):
    # full depth at a small width (float8's error grows with depth), and
    # some hundreds of positions, as a run compares
    cell = tiny.serve_cell(layers=36, d=128, vocab=8192)
    rng = np.random.default_rng(seed)
    seqs = [(rng.integers(0, 8192, 32).astype(np.int32),
             rng.integers(0, 8192, 64).astype(np.int32), 64) for _ in range(8)]
    checks = reference.check_served(cell, seed, seqs, control=True)
    assert not reference.correct(checks), checks
    assert not checks["widest_logit_gap"]["ok"]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_the_limits(seed):
    # the control's own train steps from the seed's weights on the cell's
    # batches, read as a run reads the program's, at full depth (48 layers;
    # float8's error grows with depth) and a small width: its first
    # gradient points elsewhere than the reference's
    from harness import train

    cell = tiny.train_cell(seq=128, layers=48)
    ref = reference.load_module("mamba2")
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(8, 256)) * 30, jnp.float32)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / ref.F8_MAX
    assert jnp.array_equal(ref.fp8(x), (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s)
    want = train.reference_readings(cell, seed)
    got = train.readings(train.reference_readings(cell, seed, precision="chip"), want)
    checks = train.checks_of(cell, got)
    assert not reference.correct(checks), checks
    assert not checks["grad_dir_median"]["ok"]
