"""Host spans on the profiler's clock.

:func:`span` is ``jax.profiler.TraceAnnotation``: while a profiler trace is
active (``jax.profiler.start_trace``) each span becomes a host event in the
same ``.xplane.pb`` as the device's operations, on the same clock, written
out at ``stop_trace``; with no trace active a span costs about a
microsecond and records nothing.  There is no buffer or exporter of our
own: the profiler is the buffer.

A span's stats come back as the event's ``stats`` in
``jax.profiler.ProfileData``, parsed as a number where they can be, so each
stat is one integer; a request whose id matters gets a span of its own.
"""
from __future__ import annotations

import jax

#: one ``ServeEngine.step`` call
SERVE_STEP = "serve.step"
#: admission of one same-prompt-length group (stats ``n``, ``s``, ``rid``)
SERVE_ADMIT = "serve.admit"
#: the group's prefill (``n``, ``s``)
SERVE_PREFILL = "serve.prefill"
#: growing the group's prefill caches to ``max_len`` (``n``)
SERVE_GROW = "serve.grow"
#: one request's caches written into its slot (``rid``, ``slot``)
SERVE_SLOT_WRITE = "serve.slot_write"
#: sampling the group's first tokens and fetching them (``n``)
SERVE_FIRST_TOKEN = "serve.first_token"
#: one request's per-slot decode state set (``rid``, ``slot``)
SERVE_SLOT_STATE = "serve.slot_state"
#: one decode chunk, dispatch through the fetch of its tokens (``steps``)
SERVE_DECODE = "serve.decode"
#: evicting the slots whose device state went inactive
SERVE_RETIRE = "serve.retire"


def span(name: str, **stats: int) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with integer ``stats``, for a ``with``."""
    return jax.profiler.TraceAnnotation(name, **stats)
