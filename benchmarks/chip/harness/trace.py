"""Reduction of a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation that ran, on the same
clock as the host's spans (plane ``/host:CPU``), which the benchmark writes
with ``jax.profiler.TraceAnnotation`` from its own files.

* The window is the host span named ``bench.window``.
* Busy time is the union of the device operations' intervals inside the
  window, averaged over the chips; idle is the rest of the window.
* Each stretch of idle device time is named by the innermost benchmark span
  (``bench.*``) that the host was in at its midpoint, or ``host.other``.
* Kernel events are found by a pattern on the name the trace gives them.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceEvent:
    name: str
    start: float  # seconds on the trace clock
    dur: float
    stats: dict


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over chips
    chips: int
    ops: dict  # op name -> device seconds inside the window, over chips
    events: list  # DeviceEvent inside the window, every chip
    spans: list  # (name, start, end) of the benchmark's host spans
    idle_by_span: dict  # host span name -> idle device seconds, over chips

    def kernel_events(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [e for e in self.events if rx.search(e.name)]

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations that took most device time, by their short
        HLO name; loops (``while``), which contain other operations, are
        left out."""
        short = {}
        for k, v in self.ops.items():
            name = k.split(" = ", 1)[0]
            if not name.lstrip("%").startswith(("while", "conditional", "call")):
                short[name] = short.get(name, 0.0) + v
        return sorted(([k, v] for k, v in short.items()), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


def latest_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged ``[start, end]`` rows of possibly overlapping intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _stat_dict(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def reduce(path: pathlib.Path, keep_stats: str = r"$^") -> Reduction:
    """Reduce the trace at ``path``; events whose name matches ``keep_stats``
    keep their stats (reading every event's would be slow)."""
    from jax.profiler import ProfileData

    keep = re.compile(keep_stats)
    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == OP_LINE]
            evs = []
            for line in lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    evs.append(DeviceEvent(
                        e.name, s, e.duration_ns * 1e-9,
                        _stat_dict(e) if keep.search(e.name) else {}))
            if lines:
                devices.append(evs)
    windows = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError(f"trace {path}: window span {len(windows)}, "
                         f"device op lines {len(devices)}")
    _, w0, w1 = windows[-1]
    inner = sorted((sp for sp in spans if sp[0] != WINDOW_SPAN
                    and sp[2] > w0 and sp[1] < w1), key=lambda sp: sp[1])
    starts = np.asarray([sp[1] for sp in inner])

    def host_span_at(t: float) -> str:
        """The latest-starting benchmark span that covers ``t``: the
        innermost, since spans nest or follow one another."""
        i = int(np.searchsorted(starts, t, side="right")) - 1
        for j in range(i, max(i - 16, -1), -1):
            if inner[j][1] <= t < inner[j][2]:
                return inner[j][0]
        return "host.other"

    busy, ops, kept, idle = 0.0, {}, [], {}
    for evs in devices:
        inside = [e for e in evs if e.start < w1 and e.start + e.dur > w0]
        kept += inside
        iv = np.asarray([[max(e.start, w0), min(e.start + e.dur, w1)]
                         for e in inside], np.float64)
        merged = _union(iv)
        busy += float(np.sum(merged[:, 1] - merged[:, 0])) if len(merged) else 0.0
        for e in inside:
            ops[e.name] = ops.get(e.name, 0.0) + e.dur
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        for a, b in edges:
            if b <= a:
                continue
            name = host_span_at(0.5 * (a + b))
            idle[name] = idle.get(name, 0.0) + (b - a)
    n = len(devices)
    return Reduction(
        window_s=w1 - w0, busy_s=busy / n, chips=n,
        ops={k: v / n for k, v in ops.items()}, events=kept, spans=inner,
        idle_by_span={k: v / n for k, v in idle.items()},
    )
