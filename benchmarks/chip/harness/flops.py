"""Operations and bytes, from a configuration's shapes alone.

Model operations count each multiply-add as two operations, once: nothing
that remat recomputes, nothing that padding adds.  Attention counts the keys
a query really attends (causal: a token at position ``t`` sees ``t + 1``),
not the cache slots a kernel reads.  The LM head of a prefill counts one
position per request, since only its last logits are used.

A kernel call's operations count only the nonzero 128x128 blocks of its
sparse operand (``nonzero_elems``, measured from the data), so a roofline
share reads the same work whatever implements the product; its bytes are
the nonzero blocks read once, the dense operand read once and the output
written once.
"""
from __future__ import annotations

BF16 = 2  # bytes


def dense_layer_ops(p: dict, ctx: int) -> float:
    """One token through one dense GQA layer that attends ``ctx`` keys."""
    d, h, kh, hd, ff = (p["d_model"], p["num_heads"], p["num_kv_heads"],
                        p["head_dim"], p["d_ff"])
    proj = 2 * d * h * hd + 2 * 2 * d * kh * hd + 2 * h * hd * d
    core = 2 * 2 * h * hd * ctx
    mlp = (3 if p.get("mlp_gated", True) else 2) * 2 * d * ff
    return float(proj + core + mlp)


def head_ops(p: dict) -> float:
    return float(2 * p["d_model"] * p["vocab_size"])


def dense_request_ops(p: dict, prompt: int, first: int, last: int) -> float:
    """Operations that produce output tokens ``first..last-1`` (0-based) of a
    request with a ``prompt``-token prompt.  Token 0 comes from the prefill
    (every prompt position through every layer, the head once); token
    ``k > 0`` from one decode step at position ``prompt + k - 1``."""
    layers = p["num_layers"]
    ops = 0.0
    if first == 0 and last > 0:
        keys = prompt * (prompt + 1) / 2  # sum of t + 1 over the prompt
        ops += layers * (prompt * dense_layer_ops(p, 0)
                         + 2 * 2 * p["num_heads"] * p["head_dim"] * keys)
        ops += head_ops(p)
        first = 1
    for k in range(first, last):
        ops += layers * dense_layer_ops(p, prompt + k) + head_ops(p)
    return ops


def kernel_call(m: int, k: int, n: int, nonzero_elems: float) -> tuple[float, float]:
    """``(ops, bytes)`` of one bf16 product of an ``[m, k]`` dense operand
    with a ``[k, n]`` sparse one holding ``nonzero_elems`` elements in its
    nonzero blocks."""
    ops = 2.0 * m * nonzero_elems
    byts = BF16 * (nonzero_elems + m * k + m * n)
    return ops, float(byts)


def least_time(ops: float, byts: float, peak: dict) -> tuple[float, str]:
    """The larger of ops over peak FLOP/s and bytes over bandwidth, and
    which of the two bounds it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = byts / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def nonzero_block_elems(w, block: int = 128) -> float:
    """Elements of ``w`` (2-D, device array) inside its nonzero
    ``block x block`` tiles, padding not counted."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(x):
        r, c = x.shape
        rp, cp = -(-r // block) * block, -(-c // block) * block
        nz = jnp.pad(x != 0, ((0, rp - r), (0, cp - c)))
        ones = jnp.pad(jnp.ones((r, c), jnp.float32), ((0, rp - r), (0, cp - c)))
        tiles = nz.reshape(rp // block, block, cp // block, block).any(axis=(1, 3))
        sizes = ones.reshape(rp // block, block, cp // block, block).sum(axis=(1, 3))
        return jnp.sum(jnp.where(tiles, sizes, 0.0))

    return float(count(w))
