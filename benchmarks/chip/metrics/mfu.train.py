"""Model operations of every train step finished in the window
(``harness/flops.py``: three times the forward's products, recomputation
not counted) over the window's seconds times the chip's peak bf16 rate."""


def read(run):
    if run.kind != "train" or run.ops <= 0:
        return None
    return 100.0 * run.ops / (run.window_s * run.peak["bf16_flops_per_s"])
