"""A tiny serving cell of the qwen3 family, for the CPU: the same
harness, program path and reference as the benchmark's cells, at widths a
test can hold, with the Pallas kernel in interpret mode."""
from __future__ import annotations

import copy
import json

from harness import bench


def _load(path):
    return json.loads((bench.HERE / path).read_text())


def serve_cell(limits_of: str = "qwen3-4b.serve.saturated", *, layers: int = 2,
               d: int = 64, vocab: int = 512) -> bench.Cell:
    cfg = _load("configs/qwen3-4b.json")
    cfg.update(hidden_size=d, num_hidden_layers=layers, num_attention_heads=4,
               num_key_value_heads=2, head_dim=d // 4, intermediate_size=2 * d,
               vocab_size=vocab)
    cfg["program"] = dict(cfg["program"], name="tiny-dense", num_layers=layers,
                          d_model=d, num_heads=4, num_kv_heads=2, head_dim=d // 4,
                          d_ff=2 * d, vocab_size=vocab)
    tr = _load("traffic/serve.saturated.json")
    tr.update(rate_per_s=6.0, arrival_block=4, prompt_lengths=[16, 32], prompt_weights=[0.5, 0.5],
              output_min=4, output_max=12, slots=4, max_len=64, chunk=4,
              backend="interpret", warm_groups=2, drain_seconds=120,
              check={"sample_requests": 3, "min_tokens": 10})
    return bench.Cell(name="tiny.serve", chips=1, config=cfg, traffic=tr,
                      end_to_end=[], per_layer=[],
                      limits=copy.deepcopy(bench.load_cell(limits_of).limits))
