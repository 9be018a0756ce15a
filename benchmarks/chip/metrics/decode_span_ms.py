"""Median over the engine's ``serve.decode`` spans in the traced window
(one decode chunk, dispatch through the fetch of its tokens) of the span's
time over its ``steps`` stat: one decode step, over every chunk, whether or
not its ``step()`` also admitted (``harness/spans.py``)."""
from harness import spans


def read(run):
    r = spans.for_run(run)
    return None if r is None else spans.median_ms_per(r.spans, spans.DECODE, "steps")
