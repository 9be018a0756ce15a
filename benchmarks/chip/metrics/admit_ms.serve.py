"""Host time inside the engine's ``serve.admit`` spans in the traced window
over the requests they admitted (the sum of their ``n`` stats): what one
admission costs the serving loop (``harness/spans.py``)."""
from harness import spans


def read(run):
    r = spans.for_run(run)
    return None if r is None else spans.ms_per(r.spans, spans.ADMIT, "n")
