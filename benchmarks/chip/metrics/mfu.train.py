"""Model operations of every train step finished in the window
(``harness/flops.py``: three times the forward's products, recomputation
not counted) over the window's seconds times the peak bf16 rate of all the
chips the step runs on."""


def read(run):
    if run.kind != "train" or run.ops <= 0:
        return None
    return 100.0 * run.ops / (run.window_s * run.chips * run.peak["bf16_flops_per_s"])
