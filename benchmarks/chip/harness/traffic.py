"""The one generator every traffic file is read by.

A serving mix (``"kind": "serve"``) is an open loop: about ``rate_per_s``
times the window's seconds requests, each due at its arrival whether or not
the server has finished earlier ones.  The requests come in blocks of
``arrival_block``, and every block of every seed holds the same multiset of
prompt lengths, output budgets and gaps between arrivals, in another order:
so the seed changes which request comes when and what its tokens are, never
the amount of work, and no seed can crowd the long prompts into one stretch
of the window.

* prompt lengths: the ``prompt_lengths`` ladder, each rung held by its share
  of ``prompt_weights`` (largest remainder);
* output budgets: ``output_min`` to ``output_max``, evenly spread;
* gaps: the quantiles of an exponential distribution of mean
  ``1 / rate_per_s`` (Poisson arrivals), scaled so the last request is due
  inside the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    """One generated request: due ``arrival`` seconds into the window."""

    index: int
    arrival: float
    prompt: np.ndarray  # int32 [s]
    max_new: int


def _shares(n: int, weights) -> list[int]:
    """``n`` split by ``weights`` with the largest-remainder rule."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def request_count(traffic: dict, seconds: float) -> int:
    """Whole blocks of ``arrival_block`` requests, as near ``rate_per_s`` x
    ``seconds`` as they come."""
    b = traffic["arrival_block"]
    return b * max(1, int(round(traffic["rate_per_s"] * seconds / b)))


def serve_requests(traffic: dict, seed: int, seconds: float,
                   vocab_size: int) -> list[Request]:
    """The requests due in a window of ``seconds``, in arrival order."""
    rng = np.random.default_rng(seed)
    n, b = request_count(traffic, seconds), traffic["arrival_block"]
    lengths = np.repeat(traffic["prompt_lengths"],
                        _shares(b, traffic["prompt_weights"]))
    lo, hi = traffic["output_min"], traffic["output_max"]
    budgets = np.round(lo + (hi - lo) * (np.arange(b) + 0.5) / b).astype(int)
    q = (np.arange(b) + 0.5) / b
    gaps = -np.log1p(-q) / traffic["rate_per_s"]
    lengths, budgets, gaps = (
        np.concatenate([rng.permutation(x) for _ in range(n // b)])
        for x in (lengths, budgets, gaps))
    arrivals = np.cumsum(gaps)
    arrivals *= seconds * (n - 0.5) / n / arrivals[-1]
    return [
        Request(i, float(arrivals[i]),
                rng.integers(0, vocab_size, size=int(lengths[i])).astype(np.int32),
                int(budgets[i]))
        for i in range(n)
    ]
