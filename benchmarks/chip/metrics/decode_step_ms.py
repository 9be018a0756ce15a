"""Median wall time of one decode step: ``eng.step()`` calls that admitted
no request (so ran one decode chunk and nothing else), over the chunk's
steps (host clock, around a call that ends in the tokens' transfer)."""
import numpy as np


def read(run):
    if run.kind != "serve" or not run.quiet_step_walls:
        return None
    return float(np.median(np.asarray(run.quiet_step_walls)) / run.chunk * 1e3)
