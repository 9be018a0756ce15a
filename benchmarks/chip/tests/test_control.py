"""The control -- the plain reference computed in float8, the precision
below the configurations' bfloat16, put in the program's place -- comes out
not correct through the same comparison a run makes, at a size a test run
can hold, as it does on the chip at the cell's own size
(``calibrate.py --control``, readings in PERF.md).  For serving, the gap of
the token the control puts first at each position of the same prompts and
tokens."""
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
