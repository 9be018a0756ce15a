"""The trace reduction on a small trace recorded on one v5e chip
(``data/small_trace.xplane.pb``): five ``bench.step`` spans, each one
jitted 4096x4096 bf16 matmul run to completion, each followed by a
``bench.wait`` span of a 20 ms host sleep, inside one ``bench.window``."""
import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(DATA, keep_stats=r"fusion|dot|convolution")


def test_window_and_spans(red):
    names = [s[0] for s in red.spans]
    assert names.count("bench.step") == 5 and names.count("bench.wait") == 5
    assert red.chips == 1
    assert 5 * 0.02 < red.window_s < 2.0


def test_busy_and_idle_add_up(red):
    idle = sum(red.idle_by_span.values())
    assert red.busy_s > 0
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)


def test_idle_is_named_by_the_host_span(red):
    # the device has nothing to do while the host sleeps
    assert red.idle_by_span["bench.wait"] >= 5 * 0.02 * 0.95
    assert red.top_idle(1)[0][0] == "bench.wait"


def test_ops_and_kernel_pattern(red):
    assert sum(red.ops.values()) >= red.busy_s * 0.999
    top = red.top_ops(10)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert red.kernel_events(r"^no such op$") == []
    everything = red.kernel_events(r".")
    assert len(everything) == len(red.events) > 0
