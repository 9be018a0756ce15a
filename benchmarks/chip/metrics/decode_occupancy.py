"""Share of decode-slot steps that emitted a token in the window: tokens
from decode steps over (decode steps run x slots), from the engine's own
counters."""


def read(run):
    if run.kind != "serve" or run.steps == 0:
        return None
    return 100.0 * (run.tokens - run.prefill_tokens) / (run.steps * run.slots)
