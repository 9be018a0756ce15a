"""The serving engine's spans (``repro.trace``) in a profiler trace.

A tiny engine runs under ``jax.profiler.start_trace``; its ``.xplane.pb``
is read back with ``jax.profiler.ProfileData``, and the ``serve.*`` host
spans must describe what the engine did: one prefill and one slot write per
request inside each admission, admission counts that match the engine's
counters, and request timestamps in order.  Without a profiler the same run
gives the same tokens and counters.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace as tr
from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.models.common import init_params
from repro.serve.engine import ServeEngine

#: prompt lengths and budgets: the first two share a length, so the first
#: admission is one group of two; the rest backfill the two slots
LENS, BUDGETS = (5, 5, 8, 3, 6, 8), (4, 6, 2, 5, 3, 4)


def _run():
    cfg = reduce_config(get_config("deepseek-7b"))
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ServeEngine(params, cfg, slots=2, max_len=32, chunk=3)
    for s, n in zip(LENS, BUDGETS):
        eng.submit(jnp.asarray(rng.integers(0, cfg.vocab_size, (s,)), jnp.int32),
                   max_new=n)
    return eng, eng.run()


def _serve_spans(path):
    """``(name, start_ns, end_ns, stats)`` of every ``serve.*`` host span."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve_trace")
    jax.profiler.start_trace(str(d))
    try:
        eng, out = _run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(d.rglob("*.xplane.pb"))[-1]
    return eng, out, _serve_spans(path)


def _inside(sp, outer):
    return outer[1] <= sp[1] and sp[2] <= outer[2]


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def test_every_admit_holds_one_prefill_and_n_slot_writes(traced):
    _, _, spans = traced
    admits = _named(spans, tr.SERVE_ADMIT)
    assert admits and admits[0][3]["n"] == 2  # the two length-5 prompts
    for a in admits:
        inner = [sp for sp in spans if sp is not a and _inside(sp, a)]
        prefills = _named(inner, tr.SERVE_PREFILL)
        assert len(prefills) == 1
        assert prefills[0][3] == {"n": a[3]["n"], "s": a[3]["s"]}
        assert len(_named(inner, tr.SERVE_SLOT_WRITE)) == a[3]["n"]
        assert len(_named(inner, tr.SERVE_SLOT_STATE)) == a[3]["n"]
        assert len(_named(inner, tr.SERVE_GROW)) == 1
        assert len(_named(inner, tr.SERVE_FIRST_TOKEN)) == 1


def test_admit_spans_count_what_the_counters_count(traced):
    eng, _, spans = traced
    admits = _named(spans, tr.SERVE_ADMIT)
    st = eng.stats()
    assert sum(a[3]["n"] for a in admits) == st["admitted"] == len(LENS)
    assert len(admits) == st["prefill_groups"]
    assert sum(a[3]["n"] * a[3]["s"] for a in admits) == st["prefill_tokens"]
    assert st["prefill_tokens"] == sum(LENS)
    decodes = _named(spans, tr.SERVE_DECODE)
    assert len(decodes) == st["chunks_run"]
    assert all(d[3]["steps"] == eng.chunk for d in decodes)


def test_slot_write_carries_a_rid_of_its_group(traced):
    eng, _, spans = traced
    seen = []
    for a in _named(spans, tr.SERVE_ADMIT):
        writes = _named([sp for sp in spans if _inside(sp, a)], tr.SERVE_SLOT_WRITE)
        rids = [w[3]["rid"] for w in writes]
        assert rids[0] == a[3]["rid"]
        for w in writes:
            req = eng._requests[w[3]["rid"]]
            assert int(req.prompt.shape[0]) == a[3]["s"]
            assert 0 <= w[3]["slot"] < eng.sched.num_slots
        seen += rids
    assert sorted(seen) == sorted(eng._requests)


def test_every_span_nests_in_a_step(traced):
    _, _, spans = traced
    steps = _named(spans, tr.SERVE_STEP)
    assert steps
    for sp in spans:
        if sp[0] != tr.SERVE_STEP:
            assert any(_inside(sp, s) for s in steps), sp[0]


def test_request_timestamps_are_ordered(traced):
    eng, _, _ = traced
    for req in eng._requests.values():
        assert 0.0 < req.t_submit <= req.t_admit <= req.t_first <= req.t_finish


def test_untraced_run_gives_the_same_tokens_and_counters(traced):
    eng, out, _ = traced
    eng2, out2 = _run()
    assert out2 == out
    keys = ("tokens_out", "chunks_run", "steps_run", "admitted",
            "prefill_groups", "prefill_tokens")
    assert {k: eng2.stats()[k] for k in keys} == {k: eng.stats()[k] for k in keys}
