"""The reading of the engine's ``serve.*`` spans against device idle time
(``harness/spans.py``): the arithmetic on hand-made spans and idle
intervals with known answers, then the three readers on a small trace
recorded on one v5e chip (``data/serve_spans.xplane.pb``: a tiny qwen3
engine of ``reduce_config``, 4 slots, chunk 4, warmed, then two 8-token
prompts submitted and three ``step()`` calls inside one ``bench.window``;
the planes and lines no reader uses, the HLO metadata, host threads
without spans and device lines other than ``XLA Ops``, were dropped from
the file with the event metadata only they used), and on a trace whose
program records no ``serve.*`` span (``data/small_trace.xplane.pb``),
where they return ``None``."""
import pathlib
import shutil
import types

import pytest

from harness import bench, spans

DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ("idle_in_admit.serve", "admit_ms.serve", "decode_span_ms")


def S(name, start, end, **stats):
    return spans.Span(name, float(start), float(end), stats)


def test_merge_and_complement():
    assert spans.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert spans.merge([]) == []
    assert spans.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert spans.complement([(-1, 1), (4, 9)], 0, 5) == [(1, 4)]


def test_overlap_counts_idle_that_crosses_a_span_edge():
    idle = [(0.0, 2.0), (5.0, 7.0)]
    assert spans.overlap(idle, [(1.0, 6.0)]) == pytest.approx(2.0)
    assert spans.overlap(idle, [(2.0, 5.0)]) == 0.0
    assert spans.overlap(idle, [(-1.0, 10.0)]) == pytest.approx(4.0)
    assert spans.overlap([], [(0.0, 1.0)]) == 0.0


def test_idle_in_admit_intersects_not_midpoints():
    # window [0, 10]; idle [1, 4] and [6, 10]; admits [3, 7] and [9, 12]
    idle = [[(1.0, 4.0), (6.0, 10.0)]]
    got = spans.clip([S("serve.admit", 3, 7, n=1), S("serve.admit", 9, 12, n=2),
                      S("serve.decode", 0, 3, steps=8)], 0, 10)
    # [3,4] + [6,7] + [9,10] = 3 s of 10
    assert spans.idle_in(idle, got, "serve.admit", 0, 10) == pytest.approx(30.0)
    # nested spans of one name count once: the union, not the sum
    nested = [S("serve.admit", 3, 7, n=1), S("serve.admit", 3.5, 6.5, n=1)]
    assert spans.idle_in(idle, nested, "serve.admit", 0, 10) == pytest.approx(20.0)
    # averaged over devices: the second device never idles
    two = idle + [[]]
    assert spans.idle_in(two, got, "serve.admit", 0, 10) == pytest.approx(15.0)


def test_a_span_clipped_by_the_window_counts_its_share():
    got = spans.clip([S("serve.admit", -2, 2, n=4), S("serve.admit", 5, 6, n=1),
                      S("serve.admit", 11, 12, n=9)], 0, 10)
    assert [(sp.start, sp.end, sp.share) for sp in got] == [(0, 2, 0.5), (5, 6, 1.0)]
    # (2 + 1) s over (4 x 0.5 + 1) requests
    assert spans.ms_per(got, "serve.admit", "n") == pytest.approx(1e3)
    assert spans.ms_per(got, "serve.decode", "steps") is None


def test_median_decode_step_uses_whole_spans_only():
    got = spans.clip([S("serve.decode", -1, 0.8, steps=8),  # cut: left out
                      S("serve.decode", 1, 1.4, steps=8),
                      S("serve.decode", 2, 2.32, steps=8),
                      S("serve.decode", 3, 3.8, steps=4)], 0, 10)
    assert spans.median_ms_per(got, "serve.decode", "steps") == pytest.approx(50.0)
    assert spans.median_ms_per([], "serve.decode", "steps") is None


def test_idle_by_innermost_span_adds_up():
    # step [0, 8] holds admit [1, 5] (holding prefill [2, 3]) and decode [6, 8]
    got = [S("serve.step", 0, 8), S("serve.admit", 1, 5, n=1, s=8, rid=0),
           S("serve.prefill", 2, 3, n=1, s=8), S("serve.decode", 6, 8, steps=4)]
    pieces = spans.innermost(got, 0, 10)
    assert [p[0] for p in pieces] == ["serve.step", "serve.admit", "serve.prefill",
                                      "serve.admit", "serve.step", "serve.decode",
                                      spans.OUTSIDE]
    assert pieces[0][1] == 0 and pieces[-1][2] == 10
    idle = [[(0.5, 2.5), (4.0, 9.0)], [(0.0, 10.0)]]
    by = spans.idle_by_innermost(idle, got, 0, 10)
    # device 0: step 0.5+1, admit 1+1, prefill 0.5, decode 2, outside 1
    # device 1: step 1+1, admit 1+2, prefill 1, decode 2, outside 2
    assert by == pytest.approx({"serve.step": 1.75, "serve.admit": 2.5,
                                "serve.prefill": 0.75, "serve.decode": 2.0,
                                spans.OUTSIDE: 1.5})
    assert sum(by.values()) == pytest.approx((7.0 + 10.0) / 2)


def test_a_child_that_overruns_its_parent_is_cut():
    got = [S("serve.step", 0, 4), S("serve.decode", 3, 6, steps=8)]
    assert spans.innermost(got, 0, 6) == [("serve.step", 0, 3), ("serve.decode", 3, 4),
                                          (spans.OUTSIDE, 4, 6)]


def _run_on(trace_file, tmp_path, monkeypatch):
    """A traced serving run whose trace directory holds ``trace_file``."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(trace_file, d / "host.xplane.pb")
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path)
    return types.SimpleNamespace(kind="serve", trace=object())


def test_readers_on_a_chip_trace(tmp_path, monkeypatch):
    run = _run_on(DATA / "serve_spans.xplane.pb", tmp_path, monkeypatch)
    got = {m: bench.load_reader(m)(run) for m in READERS}
    assert all(isinstance(v, float) for v in got.values()), got
    assert 0.0 <= got["idle_in_admit.serve"] <= 100.0
    assert got["admit_ms.serve"] > 0 and got["decode_span_ms"] > 0
    r = spans.load(spans.trace.latest_xplane(tmp_path))
    t = spans.table(r)
    assert sum(t["idle_pct"].values()) == pytest.approx(t["device_idle_pct"])
    assert t["idle_in_admit_pct"] <= t["device_idle_pct"]
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode"} <= set(t["spans"])


def test_readers_without_serve_spans_or_trace(tmp_path, monkeypatch):
    run = _run_on(DATA / "small_trace.xplane.pb", tmp_path, monkeypatch)
    assert {m: bench.load_reader(m)(run) for m in READERS} == dict.fromkeys(READERS)
    untraced = types.SimpleNamespace(kind="serve", trace=None)
    assert {m: bench.load_reader(m)(untraced) for m in READERS} == dict.fromkeys(READERS)
