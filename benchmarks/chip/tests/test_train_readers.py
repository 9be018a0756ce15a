"""The training cells' per-layer readers on hand-made runs with known
answers: the head kernel's three products priced each at its own shape
(at one chip's share of the rows on four), the whole step's share of the
peak of every chip it runs on, idle time and compiles."""
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


def _run(events, chips=1, **kw):
    red = trace.Reduction(window_s=2.0, busy_s=1.5, chips=chips, ops={}, events=events,
                          spans=[], idle_by_span={})
    args = dict(peak=PEAK, window_s=10.0, ops=5.0e14, chips=chips,
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


def test_one_chip_readings_of_a_recorded_run():
    # mamba2-780m.train.2x2048's traced run in the ledger (PR 15): 15 steps
    # in 6.016 s, the head's three products with their device seconds from
    # its breakdown; the readers give what they gave before they counted
    # chips, the ledger's 17.378 and 26.04 among them
    events = [_event((4096, 1536), 0.346969083 / 15), _event((50304, 4096), 0.262130423 / 15),
              _event((50304, 1536), 0.222659041 / 15)] * 15
    step_ops = 3.0 * 4096 * 1_674_516_480
    run = _run(events, window_s=6.016, ops=15 * step_ops)
    assert run.chips == 1
    least = 45 * 2.0 * 4096 * 1536 * 50288 / 197e12
    roof = bench.load_reader("head_roofline.train")(run)
    assert roof == pytest.approx(100.0 * least / 0.831758547, rel=1e-12)
    assert roof == pytest.approx(17.378, abs=0.01)
    mfu = bench.load_reader("mfu.train")(run)
    assert mfu == pytest.approx(100.0 * 15 * step_ops / (6.016 * 197e12), rel=1e-12)
    assert mfu == pytest.approx(26.04, abs=0.05)


def test_four_chips_price_each_chip_at_its_own_rows():
    # qwen3-4b at 4 x 2048 tokens on four chips: each chip runs the head's
    # products on its own 2048 rows against the whole [2560, 151936] head
    head = {"k": 2560, "n": 151936, "m": 8192, "density": 1.0}
    least = 2.0 * 2048 * 2560 * 151936 / PEAK["bf16_flops_per_s"]
    events = [_event((151936, 2048), 2 * least), _event((2048, 2560), 2 * least),
              _event((2560, 151936), 2 * least)] * 4
    run = _run(events, chips=4, head=head, ops=2.0e14, window_s=1.0)
    assert bench.load_reader("head_roofline.train")(run) == pytest.approx(50.0)
    assert bench.load_reader("mfu.train")(run) == pytest.approx(
        100.0 * 2.0e14 / (4 * 197e12))
    # at the step's whole token count no result shape would place
    with pytest.raises(ValueError, match="none of"):
        bench.load_reader("head_roofline.train")(_run(events, chips=1, head=head))

