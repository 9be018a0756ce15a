"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have, and a sound run comes out correct.  The
harness's look for a chip is skipped: a tiny serving cell runs on the CPU
through the same driver, reference and limits as the benchmark's cell.  A
serving cell on one chip can have one of the faults: a token altered where
it is produced."""
import time

import jax

import tiny
from harness import serve

SEED = 2**31 + 21


def _serve(hook=None):
    cell = tiny.serve_cell()
    return serve.run(cell, SEED, 2.0, False, time.monotonic(), jax.devices()[:1],
                     engine_hook=hook)


def test_sound_serve_run_is_correct():
    result, checks = _serve()
    assert result["correct"], checks


def test_served_token_altered_is_not_correct():
    def hook(eng):
        step, seen = eng.step, set()

        def altered():  # every request's first token, as it is produced
            out = step()
            for r in eng._requests.values():
                if r.tokens and r.rid not in seen:
                    seen.add(r.rid)
                    r.tokens[0] = (r.tokens[0] + 1) % eng.cfg.vocab_size
            return out

        eng.step = altered

    result, checks = _serve(hook)
    assert not result["correct"]
    assert checks["widest_logit_gap"]["value"] > checks["widest_logit_gap"]["limit"]
