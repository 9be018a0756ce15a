"""The training cell's per-layer readers on hand-made runs with known
answers: the head kernel's three products priced each at its own shape,
the whole step's share of the peak, idle time and compiles."""
import types

import pytest

from harness import bench, trace
from harness.train import TrainRun

PEAK = bench.load_peaks("TPU v5 lite")
HEAD = {"k": 1536, "n": 50288, "m": 4096, "density": 1.0}
PATTERN = bench.load_cell("mamba2-780m.train.2x2048").config["kernels"]["head"]


def _event(shape, dur):
    name = f"%tensordash_matmul_planned.7 = bf16[{shape[0]},{shape[1]}]{{1,0}} custom-call(...)"
    return trace.DeviceEvent(name, 0.0, dur, {})


def _run(events, **kw):
    red = trace.Reduction(window_s=2.0, busy_s=1.5, chips=1, ops={}, events=events,
                          spans=[], idle_by_span={})
    args = dict(peak=PEAK, window_s=10.0, ops=5.0e14,
                compiles=0, head=HEAD, trace=red, kernel_pattern=PATTERN)
    args.update(kw)
    return TrainRun(**args)


def test_head_roofline_prices_each_product_at_its_shape():
    # forward [V, T] (V padded to 50304), input gradient [T, d], weight
    # gradient [d, V]: each 2 x 4096 x 1536 x 50288 operations, compute-bound
    ops = 2.0 * 4096 * 1536 * 50288
    least = ops / PEAK["bf16_flops_per_s"]
    events = [_event((50304, 4096), 4 * least), _event((4096, 1536), 5 * least),
              _event((1536, 50288), 5 * least)]
    got = bench.load_reader("head_roofline.train")(_run(events))
    assert got == pytest.approx(100.0 * 3 / 14)


def test_head_roofline_refuses_a_shape_it_cannot_place():
    with pytest.raises(ValueError, match="none of"):
        bench.load_reader("head_roofline.train")(_run([_event((4096, 999), 1.0)]))


def test_readers_of_a_train_run():
    run = _run([])
    assert bench.load_reader("head_roofline.train")(run) is None  # no kernel event
    assert bench.load_reader("mfu.train")(run) == pytest.approx(
        100.0 * 5.0e14 / (10.0 * 197e12))
    assert bench.load_reader("device_idle.train")(run) == pytest.approx(25.0)
    assert bench.load_reader("compiles.train")(run) == 0.0
    untraced = _run([], trace=None)
    assert bench.load_reader("device_idle.train")(untraced) is None
    assert bench.load_reader("head_roofline.train")(untraced) is None
    serving = types.SimpleNamespace(kind="serve", trace=None, ops=1.0, compiles=3)
    for name in ("mfu.train", "head_roofline.train", "device_idle.train", "compiles.train"):
        assert bench.load_reader(name)(serving) is None
