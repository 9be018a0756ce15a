"""The harness is driven by data: a traffic mix and a per-layer metric added
as files of their own, with a workload entry in ``BENCHMARK.json``, are found
by name in a scratch copy with no other file edited."""
import json
import shutil

from harness import bench


def test_new_traffic_and_metric_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(bench.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    here = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}

    mix = json.loads((here / "traffic" / "serve.saturated.json").read_text())
    mix.update(rate_per_s=6.0, why="a second, busier chat mix")
    (here / "traffic" / "serve.busy.json").write_text(json.dumps(mix))
    (here / "metrics" / "answer_tokens.py").write_text(
        "def read(run):\n    return float(run.tokens - run.prefill_tokens)\n")
    spec["workloads"].append({"name": "qwen3-4b.serve.busy", "config": "qwen3-4b",
                              "traffic": "serve.busy", "chips": 1, "why": "busier"})
    spec["per_layer"].append({"name": "answer_tokens", "unit": "tokens",
                              "better": "higher", "source": "program_counter",
                              "layer": "serving engine", "moves": "serve_tokens_per_s",
                              "workloads": ["qwen3-4b.serve.busy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bench.load_cell("qwen3-4b.serve.busy", root=root)
    assert cell.traffic["rate_per_s"] == 6.0
    assert cell.config["program"]["name"] == "qwen3-4b"
    assert [m["name"] for m in cell.per_layer] == ["answer_tokens"]
    reader = bench.load_reader("answer_tokens", here=here)

    class Run:
        tokens, prefill_tokens = 10, 3

    assert reader(Run()) == 7.0
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed
