"""95th percentile, over every finished request due in the window, of
``(finish - first token) / (tokens - 1)`` (host clock).  The engine hands
tokens to the host a chunk at a time, so the gaps between single tokens
cannot be seen; this is the pace of the whole answer."""
import numpy as np


def read(run):
    if run.kind != "serve" or not run.tpot_s:
        return None
    return float(np.percentile(np.asarray(run.tpot_s), 95) * 1e3)
