"""Ahead-of-time compiles of the TensorDash kernels for a described TPU v5e
chip, at the widths the chip runs them.

Nothing here needs a chip: the TPU compiler compiles for a ``v5e:2x2``
topology described in a fixture, and refuses what the chip would refuse —
blocks whose last two dims are neither (8, 128)-aligned nor the whole array
dim, kernels that overflow VMEM, programs that do not fit HBM.  Each case
goes through ``Runtime`` (planning, tile fitting and padding included) with
the ``pallas`` backend, and checks that the compiled program holds the
kernel (``tpu_custom_call``) and fits one chip's 16 GB.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process may load the TPU library at a time,
and every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.runtime import Runtime
from repro.runtime.backends import PallasBackend

#: one v5e chip's HBM
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def pallas(monkeypatch):
    """A ``pallas`` runtime; ``check_platform`` sees this host's CPU, so it
    is steered to the described chip the programs are compiled for."""
    monkeypatch.setattr(PallasBackend, "check_platform", lambda self: None)
    return Runtime(backend="pallas")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lm_head(rt, tokens, d_model, vocab):
    """``h @ lm_head`` exploiting the head's block sparsity (the models'
    ``head_matmul``): the ragged planned kernel over ``lm_head.T``."""
    def f(h, w):
        return rt.matmul(h, w, side="B")
    return f, [(tokens, d_model), (d_model, vocab)]


def _fused_ffn(rt, m, k, n):
    """``relu(x @ w + bias)`` through the fused kernel, plus its mask."""
    def f(x, w, bias):
        return rt.matmul_fused(x, w, bias=bias, activation="relu")
    return f, [(m, k), (k, n), (n,)]


def _lm_head_grad(rt, tokens, d_model, vocab):
    """Both backward products of the planned head (paper Eq. 2 and 3)."""
    def loss(h, w):
        return jnp.sum(rt.matmul(h, w, side="B").astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1)), [(tokens, d_model), (d_model, vocab)]


CASES = {
    # qwen3-4b LM head, decoding 4 slots
    "qwen3_4b_head_decode": (_lm_head, (4, 2560, 151936), 1),
    # qwen3-4b FFN up-projection at 2048 tokens
    "fused_ffn_2048x2560x9728": (_fused_ffn, (2048, 2560, 9728), 1),
    # mamba2-780m LM head, training at 2 x 2048 tokens: both backward
    # products (the forward's value is dead under grad of a sum)
    "mamba2_780m_head_train_grad": (_lm_head_grad, (4096, 1536, 50280), 2),
    # a 200-token prefill: the token lanes are padded to 256
    "qwen3_4b_head_prefill_200": (_lm_head, (200, 2560, 151936), 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, pallas):
    make, dims, min_kernels = CASES[case]
    fn, shapes = make(pallas, *dims)
    dtypes = [jnp.float32 if len(s) == 1 else jnp.bfloat16 for s in shapes]
    args = [_sds(s, d, one_chip) for s, d in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= min_kernels
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{case}: {used / 1e9:.2f} GB"
