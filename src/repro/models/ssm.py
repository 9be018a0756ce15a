"""Mamba2 (state-space duality, arXiv:2405.21060) in pure JAX.

Chunked SSD: the sequence is split into chunks; within a chunk the
semiseparable matrix is materialised (attention-like, MXU-friendly), across
chunks a small ``[H, P, N]`` state is carried by a scan — the TPU-native
formulation (large dense matmuls inside, tiny sequential state outside).

TP layout: heads (d_inner) sharded over ``model``; the B/C projections
(ngroups=1) are replicated — the same layout real Mamba TP uses.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import Spec, rms_norm, silu
from repro.parallel.sharding import DP, constrain


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def num_heads(self) -> int:
        return self.d_inner // self.head_dim


def ssm_specs(cfg: SSMConfig) -> dict:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.num_heads
    gn = cfg.n_groups * cfg.d_state
    w = cfg.conv_width
    return {
        "in_z": Spec((d, di), ("embed", "heads")),
        "in_x": Spec((d, di), ("embed", "heads")),
        "in_b": Spec((d, gn), ("embed", None)),
        "in_c": Spec((d, gn), ("embed", None)),
        "in_dt": Spec((d, h), ("embed", "heads")),
        "conv_x_w": Spec((w, di), (None, "heads")),
        "conv_x_b": Spec((di,), ("heads",), init="zeros"),
        "conv_b_w": Spec((w, gn), (None, None)),
        "conv_b_b": Spec((gn,), (None,), init="zeros"),
        "conv_c_w": Spec((w, gn), (None, None)),
        "conv_c_b": Spec((gn,), (None,), init="zeros"),
        "dt_bias": Spec((h,), ("heads",), init="zeros"),
        "a_log": Spec((h,), ("heads",), init="ones"),
        "d_skip": Spec((h,), ("heads",), init="ones"),
        "norm_w": Spec((di,), ("heads",), init="ones"),
        "out_proj": Spec((di, d), ("heads", "embed")),
    }


class SSMCache(NamedTuple):
    conv_x: jax.Array  # [B, W-1, d_inner]
    conv_b: jax.Array  # [B, W-1, G*N]
    conv_c: jax.Array  # [B, W-1, G*N]
    state: jax.Array  # [B, H, P, N] f32


def init_ssm_cache(cfg: SSMConfig, batch: int, dtype=jnp.bfloat16) -> SSMCache:
    w = cfg.conv_width - 1
    gn = cfg.n_groups * cfg.d_state
    return SSMCache(
        conv_x=jnp.zeros((batch, w, cfg.d_inner), dtype),
        conv_b=jnp.zeros((batch, w, gn), dtype),
        conv_c=jnp.zeros((batch, w, gn), dtype),
        state=jnp.zeros((batch, cfg.num_heads, cfg.head_dim, cfg.d_state), jnp.float32),
    )


def _causal_conv(x, w, b):
    """Depthwise causal conv: x [B,S,C], w [W,C] -> [B,S,C] (W static)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(xp[:, i : i + s, :] * w[i] for i in range(width))
    return y + b


def _conv_step(x_new, conv_state, w, b):
    """One-token conv update: x_new [B,C], conv_state [B,W-1,C]."""
    window = jnp.concatenate([conv_state, x_new[:, None]], axis=1)  # [B,W,C]
    y = jnp.einsum("bwc,wc->bc", window, w) + b
    return y, window[:, 1:]


def ssd_chunked(x, dt, a_log, b_in, c_in, *, chunk: int, init_state=None):
    """Chunked SSD.  x [B,S,H,P], dt [B,S,H] (post-softplus), a_log [H],
    b_in/c_in [B,S,N] (ngroups=1, broadcast over heads).
    Returns (y [B,S,H,P], final_state [B,H,P,N] f32)."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    q = chunk if s >= chunk and s % chunk == 0 else s
    nc = s // q
    a = -jnp.exp(a_log.astype(jnp.float32))  # [H], negative
    dt = dt.astype(jnp.float32)
    dta = dt * a  # [B,S,H] log-decay increments
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(jnp.float32)

    # chunked views
    def ch(t, extra=()):
        return t.reshape((bsz, nc, q) + t.shape[2:])

    dta_c = ch(dta)  # [B,nc,Q,H]
    x_c = ch(xdt)  # [B,nc,Q,H,P]
    b_c = ch(b_in.astype(jnp.float32))  # [B,nc,Q,N]
    c_c = ch(c_in.astype(jnp.float32))  # [B,nc,Q,N]
    cum = jnp.cumsum(dta_c, axis=2)  # [B,nc,Q,H]

    # intra-chunk (diagonal blocks): L[i,j] = exp(cum_i - cum_j), i >= j.
    # Masked before the exp: above the diagonal cum_i - cum_j > 0 grows with
    # the chunk and overflows, and the backward's 0 * inf would be NaN.
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((q, q), bool))
    l_mat = jnp.exp(jnp.where(tri[None, None, :, :, None], li, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", c_c, b_c)  # [B,nc,Q,Q]
    y_diag = jnp.einsum("bcij,bcijh,bcjhp->bcihp", cb, l_mat, x_c)

    # per-chunk input states
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,nc,Q,H]
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", b_c, decay_states, x_c)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [B,nc,H]
    s0 = (
        jnp.zeros((bsz, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def scan_body(carry, inp):
        st, dec = inp  # [B,H,P,N], [B,H]
        new = carry * dec[..., None, None] + st
        return new, carry  # emit the state *entering* the chunk

    final_state, prev_states = jax.lax.scan(
        scan_body,
        s0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # [B,nc,H,P,N]

    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", c_c, prev_states, jnp.exp(cum))
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final_state


def ssm_fwd(params, cfg: SSMConfig, x, *, init_state=None, return_cache=False, mesh=None):
    """Full-sequence Mamba2 block.  x [B,S,D] -> [B,S,D].

    With ``return_cache`` also returns the :class:`SSMCache` (conv tails +
    final SSD state) that lets decode continue exactly after this prefix.
    """
    bsz, s, _ = x.shape
    h, p = cfg.num_heads, cfg.head_dim
    z = constrain(x @ params["in_z"], mesh, (DP, None, "model"))
    xin = constrain(x @ params["in_x"], mesh, (DP, None, "model"))
    bin_ = x @ params["in_b"]
    cin = x @ params["in_c"]
    xs = _causal_conv(xin, params["conv_x_w"], params["conv_x_b"])
    bs = _causal_conv(bin_, params["conv_b_w"], params["conv_b_b"])
    cs = _causal_conv(cin, params["conv_c_w"], params["conv_c_b"])
    xs, bs, cs = silu(xs), silu(bs), silu(cs)
    dt = jax.nn.softplus((x @ params["in_dt"]).astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    y, state = ssd_chunked(
        xs.reshape(bsz, s, h, p), dt, params["a_log"], bs, cs,
        chunk=cfg.chunk, init_state=init_state,
    )
    y = y + params["d_skip"].astype(y.dtype)[:, None] * xs.reshape(bsz, s, h, p)
    y = y.reshape(bsz, s, -1)
    y = rms_norm(y * silu(z), params["norm_w"])
    out = y @ params["out_proj"]
    if return_cache:
        w = cfg.conv_width - 1
        cache = SSMCache(
            conv_x=xin[:, -w:], conv_b=bin_[:, -w:], conv_c=cin[:, -w:], state=state
        )
        return out, cache
    return out


def ssm_decode(params, cfg: SSMConfig, x, cache: SSMCache, mesh=None):
    """One-token recurrent update.  x [B,1,D] -> (y [B,1,D], new cache)."""
    bsz = x.shape[0]
    h, p = cfg.num_heads, cfg.head_dim
    x1 = x[:, 0]
    z = x1 @ params["in_z"]
    xs, conv_x = _conv_step(x1 @ params["in_x"], cache.conv_x, params["conv_x_w"], params["conv_x_b"])
    bs, conv_b = _conv_step(x1 @ params["in_b"], cache.conv_b, params["conv_b_w"], params["conv_b_b"])
    cs, conv_c = _conv_step(x1 @ params["in_c"], cache.conv_c, params["conv_c_w"], params["conv_c_b"])
    xs, bs, cs = silu(xs), silu(bs), silu(cs)
    dt = jax.nn.softplus((x1 @ params["in_dt"]).astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))  # [B,H]
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    da = jnp.exp(dt * a)  # [B,H]
    xh = xs.reshape(bsz, h, p).astype(jnp.float32)
    state = cache.state * da[..., None, None] + jnp.einsum(
        "bhp,bn,bh->bhpn", xh, bs.astype(jnp.float32), dt
    )
    y = jnp.einsum("bhpn,bn->bhp", state, cs.astype(jnp.float32))
    y = y + params["d_skip"].astype(jnp.float32) [None, :, None] * xh
    y = y.reshape(bsz, -1).astype(x.dtype)
    y = rms_norm(y * silu(z), params["norm_w"])
    out = (y @ params["out_proj"])[:, None]
    return out, SSMCache(conv_x=conv_x, conv_b=conv_b, conv_c=conv_c, state=state)
