"""``harness/flops.py`` against counts worked out by hand."""
import pytest

from harness import bench, flops

PEAK = bench.load_peaks("TPU v5 lite")
QWEN = bench.load_cell("qwen3-4b.serve.saturated").config["program"]


def test_head_decode_call_qwen3():
    # 16 slots through the [2560, 151936] head, every block nonzero
    ops, byts = flops.kernel_call(16, 2560, 151936, 2560 * 151936)
    assert ops == 12_446_597_120
    assert byts == 782_856_192  # 2 B x (388956160 + 16x2560 + 16x151936)
    t, bound = flops.least_time(ops, byts, PEAK)
    assert bound == "memory" and t == pytest.approx(782_856_192 / 819e9)


def test_head_call_compute_bound():
    # 4096 tokens through a [1536, 50280] head
    ops, byts = flops.kernel_call(4096, 1536, 50280, 1536 * 50280)
    assert ops == 632_668_815_360
    assert byts == 578_936_832
    t, bound = flops.least_time(ops, byts, PEAK)
    assert bound == "compute" and t == pytest.approx(632_668_815_360 / 197e12)


def test_padding_does_not_count():
    import jax.numpy as jnp

    w = jnp.ones((130, 200)).at[:, 128:].set(0)  # one nonzero block column
    assert flops.nonzero_block_elems(w) == 130 * 128


def test_qwen3_decode_token():
    # token 1 of a 128-token prompt: position 128, 129 keys attended
    layer = 52_428_800 + 149_422_080 + 16_384 * 129
    want = 36 * layer + 777_912_320
    assert want == 8_120_631_296
    assert flops.dense_request_ops(QWEN, 128, 1, 2) == want


def test_qwen3_prefill():
    # 128 prompt positions through 36 layers (keys 1..128), the head once
    want = 36 * (128 * 201_850_880 + 16_384 * (128 * 129 // 2)) + 777_912_320
    assert want == 935_776_354_304
    assert flops.dense_request_ops(QWEN, 128, 0, 1) == want
