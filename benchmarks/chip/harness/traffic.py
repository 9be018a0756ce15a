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

A training mix (``"kind": "train"``) is a stream of batches of ``batch``
sequences of ``seq`` tokens, one batch a step, with next-token labels.  The
ids follow a Zipf law of exponent ``tokens.zipf_exponent`` over the
``tokens.vocab`` real ids: rank ``r`` is drawn with weight ``1 / r**a``, and
which id holds which rank is a permutation drawn from the seed.  Every step
draws new rows, so a seed changes which tokens come, never the amount of
work.
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


class TrainStream:
    """The batches of a training mix for one seed: :meth:`batch` of step
    ``i`` is the same whenever it is asked for."""

    def __init__(self, traffic: dict, seed: int):
        tok = traffic["tokens"]
        ranks = np.arange(1, tok["vocab"] + 1, dtype=np.float64)
        w = ranks ** -float(tok["zipf_exponent"])
        self.cdf = np.cumsum(w / w.sum())
        self.ids = np.random.default_rng([seed, 0]).permutation(tok["vocab"]).astype(np.int32)
        self.shape = (traffic["batch"], traffic["seq"] + 1)
        self.seed = seed

    def batch(self, step: int) -> dict:
        """``tokens`` and ``labels`` (the tokens shifted by one), int32
        ``[batch, seq]``."""
        u = np.random.default_rng([self.seed, 1, step]).random(self.shape)
        rank = np.minimum(np.searchsorted(self.cdf, u), len(self.ids) - 1)
        toks = self.ids[rank]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
