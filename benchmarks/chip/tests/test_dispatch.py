"""``run.py`` sends each cell to the driver of its traffic's kind, with
the chip look and the compile cache stubbed out: a serving cell to
``harness/serve.py`` and a training cell to ``harness/train.py``, each
printing the result line with the same keys; a kind it does not know ends
the run before any driver starts."""
import importlib.util
import json

import pytest

from harness import bench, serve, train

RESULT = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
          "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                     "memory_peak_bytes": 1}}


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run", bench.HERE / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def calls(monkeypatch):
    got = []
    monkeypatch.setattr(bench, "prepare_jax", lambda: None)
    monkeypatch.setattr(bench, "require_chips", lambda n: ["chip"] * n)
    for mod in (serve, train):
        monkeypatch.setattr(mod, "run", lambda cell, *a, _m=mod.__name__: (
            got.append((_m, cell.name)) or (RESULT, {"x": {"value": 0, "limit": 1,
                                                           "rule": "at most"}})))
    return got


@pytest.mark.parametrize("workload, driver", [
    ("qwen3-4b.serve.saturated", "harness.serve"),
    ("mamba2-780m.train.2x2048", "harness.train"),
])
def test_each_kind_goes_to_its_driver(workload, driver, calls, capsys):
    args = ["--workload", workload, "--seed", "1", "--seconds", "1", "--trace", "0"]
    assert _run_module().main(args) == 0
    assert calls == [(driver, workload)]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_unknown_kind_stops_before_any_driver(calls, monkeypatch):
    load = bench.load_cell

    def bogus(name, root=bench.ROOT):
        cell = load(name, root)
        cell.traffic = dict(cell.traffic, kind="bogus")
        return cell

    monkeypatch.setattr(bench, "load_cell", bogus)
    with pytest.raises(SystemExit, match="unknown traffic kind"):
        _run_module().main(["--workload", "mamba2-780m.train.2x2048", "--seed", "1",
                            "--seconds", "1", "--trace", "0"])
    assert calls == []
