"""The serving engine's own spans in a traced run, against the device's
idle time.

The program records host spans named ``serve.*`` (``repro.trace``) on the
profiler's clock, the clock of the device's operations.  :func:`load` reads
a trace's ``.xplane.pb`` once per path: those spans clipped to the
benchmark's ``bench.window`` span, and each device's idle intervals inside
it (the window less the union of its ``XLA Ops`` events).  The arithmetic
is pure functions over plain lists, so it can be checked without a trace:

* :func:`idle_in` — the share of the window in which a device is idle while
  the host is inside a span of a given name: the intersection of the idle
  intervals with the union of those spans, averaged over devices;
* :func:`ms_per` — host time inside spans of a name over the sum of one of
  their stats (a span cut by the window counts its stat by the share left);
* :func:`median_ms_per` — the median of one span's time over its stat;
* :func:`idle_by_innermost` — idle seconds by the innermost span the host
  was in, ``outside serve.*`` for the rest; they add up to the idle time.

A trace with no ``serve.*`` spans (a program that records none) reads as
nothing, and the metrics built on it return ``None``.

    python3 benchmarks/chip/harness/spans.py [trace.xplane.pb]

prints the idle table of a trace (the newest under ``.bench_trace`` by
default) as one JSON line.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import numpy as np

if __package__ in (None, ""):  # run as a script: benchmarks/chip on the path
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import bench, trace  # noqa: E402

PREFIX = "serve."
ADMIT = "serve.admit"
DECODE = "serve.decode"
OUTSIDE = "outside serve.*"


@dataclasses.dataclass
class Span:
    name: str
    start: float  # seconds on the trace clock
    end: float
    stats: dict
    share: float = 1.0  # of the span's time that lies inside the window


@dataclasses.dataclass
class Reading:
    w0: float  # the window, seconds on the trace clock
    w1: float
    spans: list  # Span, clipped to the window, by start
    idle: list  # per device: sorted disjoint (start, end) idle intervals


def merge(intervals) -> list:
    """Sorted disjoint ``(start, end)`` covering the same points."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    return [tuple(r) for r in trace._union(iv).tolist()]


def complement(busy, w0: float, w1: float) -> list:
    """The parts of ``[w0, w1]`` outside the sorted disjoint ``busy``."""
    out, t = [], w0
    for s, e in busy:
        s, e = max(s, w0), min(e, w1)
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def clip(spans, w0: float, w1: float) -> list:
    """The spans' parts inside ``[w0, w1]``, by start."""
    out = []
    for sp in spans:
        s, e = max(sp.start, w0), min(sp.end, w1)
        if e > s:
            share = (e - s) / (sp.end - sp.start)
            out.append(Span(sp.name, s, e, sp.stats, sp.share * share))
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


def _measure_before(intervals):
    """``F(t)``: the length of the sorted disjoint ``intervals`` before
    ``t``, for arrays of ``t``."""
    iv = [(s, e) for s, e in intervals if e > s]
    if not iv:
        return lambda t: np.zeros_like(np.asarray(t, np.float64))
    xs = np.asarray(iv, np.float64).ravel()
    lens = np.diff(xs)[::2]
    fs = np.repeat(np.r_[0.0, np.cumsum(lens)], 2)[1:-1]
    return lambda t: np.interp(t, xs, fs)


def overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    if not a or not b:
        return 0.0
    f = _measure_before(a)
    bb = np.asarray(b, np.float64)
    return float(np.sum(f(bb[:, 1]) - f(bb[:, 0])))


def idle_in(idle, spans, name: str, w0: float, w1: float) -> float:
    """Percent of ``[w0, w1]`` in which a device is idle while the host is
    inside a span named ``name``, averaged over devices."""
    inside = merge([(sp.start, sp.end) for sp in spans if sp.name == name])
    if not idle or w1 <= w0:
        return 0.0
    return 100.0 * float(np.mean([overlap(d, inside) for d in idle])) / (w1 - w0)


def ms_per(spans, name: str, stat: str) -> float | None:
    """Milliseconds inside spans named ``name`` over the sum of their
    ``stat``, each counted by the share of its span left in the window."""
    named = [sp for sp in spans if sp.name == name]
    count = sum(sp.stats[stat] * sp.share for sp in named)
    if count <= 0:
        return None
    return 1e3 * sum(sp.end - sp.start for sp in named) / count


def median_ms_per(spans, name: str, stat: str) -> float | None:
    """Median over whole spans named ``name`` of milliseconds per ``stat``."""
    per = [(sp.end - sp.start) / sp.stats[stat] for sp in spans
           if sp.name == name and sp.share == 1.0 and sp.stats[stat] > 0]
    return 1e3 * float(np.median(per)) if per else None


def innermost(spans, w0: float, w1: float) -> list:
    """``(name, start, end)`` pieces covering ``[w0, w1]``, each named by
    the innermost of the nested ``spans`` over it (``OUTSIDE`` where none
    is); a span that overruns its parent is cut at the parent's end."""
    out, stack, t = [], [], w0

    def emit(name, end):
        nonlocal t
        if end > t:
            out.append((name, t, end))
            t = end

    for sp in clip(spans, w0, w1):
        while stack and stack[-1][1] <= sp.start:
            emit(*stack.pop())
        emit(stack[-1][0] if stack else OUTSIDE, sp.start)
        end = min(sp.end, stack[-1][1]) if stack else sp.end
        stack.append((sp.name, end))
    while stack:
        emit(*stack.pop())
    emit(OUTSIDE, w1)
    return out


def idle_by_innermost(idle, spans, w0: float, w1: float) -> dict:
    """Idle seconds, averaged over devices, by the innermost span the host
    was in; the values add up to the mean idle time."""
    pieces = innermost(spans, w0, w1)
    if not idle:
        return {}
    bounds = np.asarray([(a, b) for _, a, b in pieces], np.float64)
    per = np.zeros(len(pieces))
    for d in idle:
        f = _measure_before(d)
        per += f(bounds[:, 1]) - f(bounds[:, 0])
    out: dict[str, float] = {}
    for (name, _, _), v in zip(pieces, per / len(idle)):
        out[name] = out.get(name, 0.0) + float(v)
    return out


_READINGS: dict = {}


def load(path) -> Reading:
    """The ``serve.*`` spans and the devices' idle intervals inside the
    window of the trace at ``path`` (read once per path)."""
    path = str(path)
    if path not in _READINGS:
        _READINGS[path] = _read(path)
    return _READINGS[path]


def _read(path: str) -> Reading:
    from jax.profiler import ProfileData

    spans, window, devices = [], None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                          dict(e.stats)))
                    elif e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
        elif plane.name.startswith("/device:"):
            ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == trace.OP_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
    if window is None or not devices:
        raise ValueError(f"trace {path}: no {trace.WINDOW_SPAN} span or no "
                         f"device operations")
    w0, w1 = window
    return Reading(w0, w1, clip(spans, w0, w1),
                   [complement(merge(d), w0, w1) for d in devices])


def for_run(run) -> Reading | None:
    """The reading of a traced serving run, or ``None`` where the run was
    not traced or its program recorded no ``serve.*`` span."""
    if run.kind != "serve" or run.trace is None:
        return None
    reading = load(trace.latest_xplane(bench.TRACE_DIR))
    return reading if reading.spans else None


def table(reading: Reading) -> dict:
    """The idle table of a reading, seconds and percent of the window."""
    window = reading.w1 - reading.w0
    by = idle_by_innermost(reading.idle, reading.spans, reading.w0, reading.w1)
    busy = window - sum(by.values())
    return {
        "window_s": window,
        "device_idle_pct": 100.0 * (1.0 - busy / window),
        "idle_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
        "idle_pct": {k: 100.0 * v / window
                     for k, v in sorted(by.items(), key=lambda kv: -kv[1])},
        "idle_in_admit_pct": idle_in(reading.idle, reading.spans, ADMIT,
                                     reading.w0, reading.w1),
        "admit_ms_per_request": ms_per(reading.spans, ADMIT, "n"),
        "decode_ms_per_step": median_ms_per(reading.spans, DECODE, "steps"),
        "spans": {n: sum(1 for sp in reading.spans if sp.name == n)
                  for n in sorted({sp.name for sp in reading.spans})},
    }


if __name__ == "__main__":
    import json

    path = sys.argv[1] if len(sys.argv) > 1 else trace.latest_xplane(bench.TRACE_DIR)
    print(json.dumps(table(load(path))))
