"""``BENCHMARK.json`` keeps to the shape the harness and its checks rely on:
names, units, layers, every file it names, a reader for every per-layer
metric and a limits file for every cell."""
import json
import re

import pytest

from harness import bench

SPEC = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and (bench.ROOT / entry["file"]).is_file()
    assert all(NAME.match(k) for k in entry["reduced"]) and len(entry["why"]) <= 200


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=lambda e: e["name"])
def test_workload(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    assert entry["chips"] in (1, 4)
    cell = bench.load_cell(entry["name"])
    assert cell.limits, "every cell has limits/<cell>.json"
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        e2e = {m["name"]: m for m in SPEC["end_to_end"]}
        assert metric["moves"] in e2e and len(metric["layer"]) <= 200
        moved = set(e2e[metric["moves"]].get("workloads", cells))
        assert set(metric["workloads"]) <= moved
        assert callable(bench.load_reader(metric["name"]))
