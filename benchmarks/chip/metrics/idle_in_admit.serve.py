"""Share of the traced window in which the device is idle while the host is
inside the engine's ``serve.admit`` span (admission of one same-length
group: prefill, cache growth, slot writes, first tokens): the intersection
of each device's idle intervals with the union of those spans, averaged
over devices (``harness/spans.py``)."""
from harness import spans


def read(run):
    r = spans.for_run(run)
    if r is None:
        return None
    return spans.idle_in(r.idle, r.spans, spans.ADMIT, r.w0, r.w1)
