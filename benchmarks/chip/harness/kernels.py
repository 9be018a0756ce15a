"""Roofline share of the LM head's kernel from the device trace.

Each kernel event found in the traced window is one call of the ragged
planned kernel on the head.  Its least time is the larger of its operations over the
chip's peak and its bytes over the chip's bandwidth (``flops.kernel_call``,
with the head's nonzero blocks measured from the data and the call's token
count ``m``); the share is the sum of least times over the sum of the
events' device time.
"""
from __future__ import annotations

import re

from harness import flops

_RESULT = re.compile(r" = \w+\[(\d+),(\d+)\]")


def result_shape(event) -> tuple[int, int]:
    """The 2-D result shape in the HLO text the trace names an op by."""
    m = _RESULT.search(event.name)
    if m is None:
        raise ValueError(f"no 2-D result shape in {event.name[:120]!r}")
    return int(m.group(1)), int(m.group(2))


def head_roofline(run, m_of_event) -> float | None:
    """``m_of_event(event)`` gives the call's token count."""
    if run.trace is None:
        return None
    events = run.trace.kernel_events(run.kernel_pattern)
    if not events:
        return None
    least = 0.0
    for e in events:
        ops, byts = flops.kernel_call(m_of_event(e), run.head["k"], run.head["n"],
                                      run.head["nonzero_elems"])
        least += flops.least_time(ops, byts, run.peak)[0]
    busy = sum(e.dur for e in events)
    return 100.0 * least / busy if busy > 0 else None
