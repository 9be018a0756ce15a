"""The comparison that decides ``correct``.

The plain reference of a configuration is ``reference/<module>.py``, named by
the configuration file's ``reference.module``; it imports nothing of the
program and makes its own weights from the seed.  The limits of a cell are
in ``limits/<cell>.json``, each with the readings it was set from.

Served cells: once the window has closed, a sample of the finished requests
drawn from the seed, the longest always among them, is run through the
reference over its prompt and its served tokens.  The number compared is the
widest gap by which a served (greedy) token's reference logit lies below the
reference's best logit at that position.
"""
from __future__ import annotations

import importlib.util

import numpy as np

from harness import bench


def load_module(name: str):
    path = bench.HERE / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample_requests(finished: list, seed: int, k: int) -> list:
    """``k`` finished requests drawn from ``seed``: the longest (prompt plus
    output) and ``k - 1`` others."""
    if not finished:
        return []
    size = [int(r.prompt.shape[0]) + len(r.tokens) for r in finished]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [finished[longest]] + [finished[rest[i]] for i in sorted(pick)]


def _check(value, limit, rule: str) -> dict:
    ok = limit is not None and (value <= limit if rule == "at most" else value >= limit)
    return {"value": value, "limit": limit, "rule": rule, "ok": bool(ok)}


def served_checks(cell, got: dict, seqs: list, gap: str = "widest_gap") -> dict:
    """The served cell's numbers beside their limits, from the reference's
    readings ``got``: ``gap`` names the widest gap compared (the program's,
    or the control's put in its place)."""
    short = sum(len(t) != budget for _, t, budget in seqs)
    return {
        "checked_tokens": _check(got["tokens"], cell.traffic["check"]["min_tokens"],
                                 "at least"),
        "short_answers": _check(short, 0, "at most"),
        "widest_logit_gap": _check(got[gap], cell.limits.get("widest_logit_gap"),
                                   "at most"),
    }


def check_served(cell, seed: int, seqs: list, control: bool = False) -> dict:
    """``seqs``: ``(prompt, served tokens, budget)`` of each sampled request.
    With ``control``, the float8 control is compared in the program's place."""
    if not seqs:
        return {"checked_tokens": _check(0, 1, "at least")}
    ref = load_module(cell.config["reference"]["module"])
    got = ref.served_gaps(cell.config, seed, [(p, t) for p, t, _ in seqs],
                          control=control)
    return served_checks(cell, got, seqs,
                         "control_widest_gap" if control else "widest_gap")


def correct(checks: dict) -> bool:
    return all(c["ok"] for c in checks.values())
