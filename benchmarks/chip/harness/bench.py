"""Plumbing every cell shares.

* :func:`load_cell` finds a workload of ``BENCHMARK.json`` and the files it
  names by name: ``configs/<config>.json``, ``traffic/<traffic>.json`` and,
  for each per-layer metric, ``metrics/<metric>.py``.
* :func:`prepare_jax` turns on the program's persistent compilation cache
  (``repro.launch.mesh.enable_compile_cache``) and :func:`require_chips`
  refuses anything but a TPU with enough chips, so no device metric is ever
  printed from a CPU.
* :class:`CompileCounter` counts programs built (compiled, or loaded from
  the persistent cache) while it is armed, through ``jax.monitoring``.
* :func:`span` puts a host span on the profiler's clock.
* :func:`emit` prints the result line and the compared numbers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = HERE.parents[1]  # the checkout
TRACE_DIR = ROOT / ".bench_trace"  # emptied before each traced run

#: JAX's event for every executable it builds: a backend compile or a load
#: from the persistent cache (``jax/_src/dispatch.py``)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    end_to_end: list  # the end-to-end metric entries this cell reports
    per_layer: list  # the per-layer metric entries this cell reports
    limits: dict = dataclasses.field(default_factory=dict)  # limits/<cell>.json


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    here = root / HERE.relative_to(ROOT)
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    limits = here / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        limits=json.loads(limits.read_text())["limits"] if limits.exists() else {},
    )


def load_reader(metric: str, here: pathlib.Path = HERE):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = here / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "harness" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][kind]


def prepare_jax() -> None:
    """Before any program runs: the program's own persistent compilation
    cache (``$JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache``), with every program kept in it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """The TPU devices a cell of ``n`` chips runs on; :class:`NoChip`
    otherwise."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        raise NoChip(f"JAX found no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chip(s), JAX found {len(devices)}")
    return devices[:n]


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


class CompileCounter:
    """Counts executables JAX builds while :attr:`armed`."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self.names: list[str] = []  # what was built, for the run's stderr
        self.seconds: list[float] = []  # how long each build took

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds.append(float(duration))

    def report(self) -> None:
        """Name what was built inside the window, on stderr."""
        if self.names:
            seen: dict[str, int] = {}
            for n in self.names:
                seen[n] = seen.get(n, 0) + 1
            print(f"built inside the window: {seen}, the slowest in "
                  f"{max(self.seconds):.3f} s", file=sys.stderr, flush=True)

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def span(name: str, on: bool):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_per_layer(cell: Cell, run) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers on stderr and the result line (with the
    same numbers under ``checks``, its last key) as stdout's last line."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c['rule']})", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
