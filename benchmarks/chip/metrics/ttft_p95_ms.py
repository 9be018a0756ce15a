"""95th percentile, over every request due in the window, of its first
token's time less the time it was due (host clock).  Past the knee it is
mostly the queue's wait, which grows through the window, so it swings with
the smallest change of throughput and is a per-layer reading, not a bound."""
import numpy as np


def read(run):
    if run.kind != "serve" or not run.ttft_s:
        return None
    return float(np.percentile(np.asarray(run.ttft_s), 95) * 1e3)
