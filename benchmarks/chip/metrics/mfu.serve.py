"""Model operations of every prefill and decode token produced in the
window (``harness/flops.py``, no padding, attention over the keys really
attended) over the window's seconds times the chip's peak bf16 rate."""


def read(run):
    if run.kind != "serve" or run.ops <= 0:
        return None
    return 100.0 * run.ops / (run.window_s * run.peak["bf16_flops_per_s"])
