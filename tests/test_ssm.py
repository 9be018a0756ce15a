"""Mamba2 SSD vs the naive recurrence oracle; decode continuity."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ssm import (
    SSMConfig, init_ssm_cache, ssd_chunked, ssm_decode, ssm_fwd, ssm_specs,
)
from repro.models.common import init_params


def _naive_ssd(x, dt, a_log, b, c):
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    a = -np.exp(np.asarray(a_log, np.float64))
    state = np.zeros((bsz, h, p, n))
    ys = np.zeros((bsz, s, h, p))
    x64, dt64, b64, c64 = (np.asarray(t, np.float64) for t in (x, dt, b, c))
    for t in range(s):
        da = np.exp(dt64[:, t] * a)  # [B,H]
        state = state * da[..., None, None] + np.einsum(
            "bhp,bn,bh->bhpn", x64[:, t], b64[:, t], dt64[:, t]
        )
        ys[:, t] = np.einsum("bhpn,bn->bhp", state, c64[:, t])
    return ys, state


def test_ssd_chunked_matches_naive():
    rng = np.random.default_rng(0)
    bsz, s, h, p, n = 2, 32, 3, 4, 8
    x = rng.standard_normal((bsz, s, h, p)).astype(np.float32)
    dt = (0.1 + rng.random((bsz, s, h))).astype(np.float32)
    a_log = rng.standard_normal(h).astype(np.float32) * 0.3
    b = rng.standard_normal((bsz, s, n)).astype(np.float32)
    c = rng.standard_normal((bsz, s, n)).astype(np.float32)
    y, state = ssd_chunked(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a_log),
                           jnp.asarray(b), jnp.asarray(c), chunk=8)
    y_ref, state_ref = _naive_ssd(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(y, np.float64), y_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state, np.float64), state_ref, rtol=2e-3, atol=2e-3)


def test_prefill_decode_continuity():
    """ssm_fwd over S tokens == ssm_fwd over S-1 then ssm_decode of the last."""
    cfg = SSMConfig(d_model=32, d_state=8, expand=2, head_dim=8, chunk=8)
    key = jax.random.PRNGKey(0)
    params = init_params(ssm_specs(cfg), key, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32) * 0.3
    full = ssm_fwd(params, cfg, x)
    prefix, cache = ssm_fwd(params, cfg, x[:, :-1], return_cache=True)
    last, _ = ssm_decode(params, cfg, x[:, -1:], cache)
    np.testing.assert_allclose(
        np.asarray(last[:, 0]), np.asarray(full[:, -1]), rtol=2e-2, atol=2e-2
    )


def test_ssd_chunked_grads_finite_when_decay_overflows_exp():
    """A full 128-step chunk at dt = 1 puts cum_i - cum_j near 348 above the
    diagonal, past float32 exp's range (~88): the masked-out half of the
    intra-chunk decay must not turn into 0 * inf = NaN in the backward."""
    bsz, s, h, p, n = 1, 256, 2, 4, 8
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (bsz, s, h, p), jnp.float32)
    b = jax.random.normal(k[1], (bsz, s, n), jnp.float32)
    c = jax.random.normal(k[2], (bsz, s, n), jnp.float32)
    dt = jnp.ones((bsz, s, h), jnp.float32)

    def loss(x, dt):
        y, _ = ssd_chunked(x, dt, jnp.ones((h,)), b, c, chunk=128)
        return jnp.sum(y)

    gx, gdt = jax.grad(loss, argnums=(0, 1))(x, dt)
    assert bool(jnp.all(jnp.isfinite(gx))) and bool(jnp.all(jnp.isfinite(gdt)))
