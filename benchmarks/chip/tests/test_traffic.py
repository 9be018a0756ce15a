"""The generator: the same seed gives the same requests; every block of
every seed holds the same multiset of lengths, budgets and gaps, in another
order."""
import collections
import json

import numpy as np
import pytest

from harness import bench
from harness import traffic as gen

MIX = bench.load_cell("qwen3-4b.serve.saturated").traffic


def _key(reqs):
    return [(r.arrival, r.prompt.tobytes(), r.max_new) for r in reqs]


def test_same_seed_same_requests():
    a = gen.serve_requests(MIX, 2**31 + 5, 45, 151936)
    b = gen.serve_requests(MIX, 2**31 + 5, 45, 151936)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_every_seed_same_work(seed):
    base = gen.serve_requests(MIX, 1, 45, 151936)
    reqs = gen.serve_requests(MIX, seed, 45, 151936)
    assert len(reqs) == len(base) == gen.request_count(MIX, 45)
    assert sorted(len(r.prompt) for r in reqs) == sorted(len(r.prompt) for r in base)
    assert sorted(r.max_new for r in reqs) == sorted(r.max_new for r in base)
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.arrival for r in rs]))
    np.testing.assert_allclose(gaps(reqs), gaps(base), rtol=1e-9)


def test_lengths_follow_the_ladder():
    reqs = gen.serve_requests(MIX, 3, 45, 151936)
    n = len(reqs)
    counts = collections.Counter(len(r.prompt) for r in reqs)
    for length, w in zip(MIX["prompt_lengths"], MIX["prompt_weights"]):
        assert abs(counts[length] - n * w) <= 1
    budgets = [r.max_new for r in reqs]
    assert min(budgets) >= MIX["output_min"] and max(budgets) <= MIX["output_max"]
    arrivals = [r.arrival for r in reqs]
    assert arrivals == sorted(arrivals) and 0 < arrivals[-1] < 45
    assert all(len(r.prompt) + r.max_new <= MIX["max_len"] for r in reqs)


def test_rate_matches_the_mix():
    reqs = gen.serve_requests(MIX, 4, 45, 151936)
    assert len(reqs) % MIX["arrival_block"] == 0
    assert abs(len(reqs) - MIX["rate_per_s"] * 45) <= MIX["arrival_block"] / 2


@pytest.mark.parametrize("seed", [5, 2**31 + 13])
def test_every_block_same_work(seed):
    reqs = gen.serve_requests(MIX, seed, 51, 151936)
    b = MIX["arrival_block"]
    blocks = [reqs[i:i + b] for i in range(0, len(reqs), b)]
    assert len(blocks) > 1
    gaps = np.diff([0.0] + [r.arrival for r in reqs])
    for i, blk in enumerate(blocks):
        assert sorted(len(r.prompt) for r in blk) == sorted(len(r.prompt) for r in blocks[0])
        assert sorted(r.max_new for r in blk) == sorted(r.max_new for r in blocks[0])
        np.testing.assert_allclose(np.sort(gaps[i * b:(i + 1) * b]),
                                   np.sort(gaps[:b]), rtol=1e-9)
