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


MAMBA = bench.load_cell("mamba2-780m.train.2x2048").config["program"]


def test_mamba2_layer_token():
    # d 1536, inner 3072, state 128, 48 heads of 64, conv 4, chunks of 256
    proj = 2 * 1536 * (2 * 3072 + 2 * 128 + 48) + 2 * 3072 * 1536  # 29245440
    conv = 2 * 4 * (3072 + 2 * 128)  # 26624
    ssd = 2 * 128 * 128.5 + 2 * 3072 * 128.5 + 4 * 3072 * 128  # 32896 + 789504 + 1572864
    assert proj + conv + ssd == 31_667_328
    assert flops.ssm_layer_ops(MAMBA, 2048) == 31_667_328


def test_mamba2_train_step():
    # 3 x (48 layers + the [1536, 50288] head) x 4096 tokens
    per_token = 48 * 31_667_328 + 2 * 1536 * 50288
    assert per_token == 1_674_516_480
    assert flops.ssm_train_step_ops(MAMBA, 2, 2048) == 3 * 4096 * per_token
    # a sequence shorter than a chunk is one chunk of its own length
    assert flops.ssm_layer_ops(MAMBA, 64) == flops.ssm_layer_ops(dict(MAMBA, ssm_chunk=64), 64)


def test_train_step_by_family():
    # qwen3-4b, 2 sequences of 128: 36 layers of 128 positions (keys 1..128),
    # the head at every position, three times over
    layers = 36 * (128 * 201_850_880 + 16_384 * (128 * 129 // 2))
    head = 128 * 777_912_320
    assert (layers, head) == (934_998_441_984, 99_572_776_960)
    assert flops.train_step_ops(QWEN, 2, 128) == 6 * (layers + head) == 6_207_427_313_664
    assert flops.train_step_ops(MAMBA, 2, 2048) == flops.ssm_train_step_ops(MAMBA, 2, 2048)
    with pytest.raises(ValueError, match="moe"):
        flops.train_step_ops(dict(QWEN, family="moe"), 2, 128)


def test_a_reference_module_brings_its_own_count():
    import types

    from harness import reference

    own = types.SimpleNamespace(
        train_step_ops=lambda p, batch, seq: 7.0 * batch * seq,
        request_ops=lambda p, prompt, first, last: 11.0 * (last - first))
    hybrid = dict(QWEN, family="hybrid")
    assert flops.train_step_ops(hybrid, 2, 128, own) == 7.0 * 256
    assert flops.request_ops(hybrid, 128, 0, 3, own) == 33.0
    with pytest.raises(ValueError, match="hybrid"):
        flops.request_ops(hybrid, 128, 0, 3)
    # the benchmark's references bring none: the family's counts, as before
    for name, p in (("qwen3", QWEN), ("mamba2", MAMBA)):
        ref = reference.load_module(name)
        assert flops.train_step_ops(p, 2, 2048, ref) == flops.train_step_ops(p, 2, 2048)
    ref = reference.load_module("qwen3")
    assert flops.request_ops(QWEN, 128, 0, 1, ref) == 935_776_354_304
    assert flops.request_ops(QWEN, 128, 1, 2, ref) == 8_120_631_296
    assert flops.train_step_ops(QWEN, 2, 128, ref) == 6_207_427_313_664
