"""Roofline share of the LM head's ragged Pallas kernel while serving: the
least time of every call in the traced window (memory-bound at these token
counts: the head's 0.78 GB of weights are read once per call) over the
calls' device time.  The kernel computes the head transposed, so a call's
token count is the last dim of its ``[vocab, tokens]`` result: every slot
in decode, the group's prompts in a prefill."""
from harness import kernels


def read(run):
    if run.kind != "serve":
        return None
    return kernels.head_roofline(run, lambda e: kernels.result_shape(e)[1])
