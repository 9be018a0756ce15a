"""Plain reference of the Qwen3 dense decoder (Qwen/Qwen3-4B ``config.json``).

Per layer: RMSNorm, GQA attention with per-head RMSNorm on queries and keys
(qk-norm) before rotary embeddings (half-split rotation, base
``rope_theta``), a causal softmax, the output projection and a residual;
then RMSNorm, a SwiGLU MLP (``silu(x Wg) * (x Wu) Wd``) and a residual.  A
final RMSNorm and the LM head give the logits.  Departure from the published
model, as the program runs it: the LM head is its own matrix, not the
embedding's transpose.

Everything is computed in float32 with ``Precision.HIGHEST``, one layer at a
time, with no cache, batching tricks or kernels; it imports nothing of the
program.  The control (``precision="fp8"``) is the same computation with
every matrix product's inputs rounded to float8 e4m3, scaled per row of the
activations and per weight matrix.

Weights: the program's parameter stream for this configuration, made here
from the seed: one key per parameter, split from ``PRNGKey(seed)`` in the
order of the sorted parameter paths; matrices ``N(0, 1) / sqrt(fan_in)``,
the embedding ``N(0, 1)``, norm weights one; drawn in float32 and stored in
bfloat16, the type the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest float8 e4m3 value


def dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], L=c["num_hidden_layers"],
                h=c["num_attention_heads"], kh=c["num_key_value_heads"],
                hd=c["head_dim"], ff=c["intermediate_size"],
                V=c["vocab_size"], theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]))


def param_tree(c: dict) -> dict:
    """``path -> (shape, init, std)`` as nested dicts."""
    k = dims(c)
    d, L, h, kh, hd, ff, V = (k[x] for x in ("d", "L", "h", "kh", "hd", "ff", "V"))
    mat = lambda *s: (s, "normal", 1.0 / math.sqrt(s[-2]))
    one = lambda *s: (s, "ones", None)
    return {
        "embed": ((V, d), "normal", 1.0),
        "final_norm": one(d),
        "layers": {
            "attn": {"k_norm": one(L, hd), "q_norm": one(L, hd),
                     "wk": mat(L, d, kh * hd), "wo": mat(L, h * hd, d),
                     "wq": mat(L, d, h * hd), "wv": mat(L, d, kh * hd)},
            "ln1": one(L, d), "ln2": one(L, d),
            "mlp": {"w_down": mat(L, ff, d), "w_gate": mat(L, d, ff),
                    "w_up": mat(L, d, ff)},
        },
        "lm_head": mat(d, V),
    }


def make_weights(tree: dict, seed: int, dtype=jnp.bfloat16):
    """The parameter stream of ``tree`` from ``seed`` (one jitted call)."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, init, std), kk in zip(leaves, keys):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append((std * jax.random.normal(kk, shape, jnp.float32)).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _q8(x, axis):
    """``(values, scale)``: ``x / scale`` rounded to float8 e4m3 (held in
    float32, exactly), scale per ``axis`` (``None``: whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def fp8_round(x, axis=-1):
    """``x`` rounded to float8 e4m3 at a scale per ``axis``."""
    q, s = _q8(x, axis)
    return q * s


def matmul(a, b, fp8: bool):
    """``a @ b`` in float32 (``HIGHEST``), or with float8 inputs."""
    if not fp8:
        return jnp.matmul(a, b, precision=HI)
    qa, sa = _q8(a, -1)
    qb, sb = _q8(b, None)
    # float8 values are exact in bfloat16: one pass, float32 accumulation
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * sa * sb


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Half-split rotary embedding: x [B, S, H, D], pos [S]."""
    dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(h, layers, i, k: dict, fp8: bool):
    """Layer ``i`` of the stacked bf16 ``layers`` on float32 ``h`` [B, S, d]."""
    w = jax.tree.map(lambda x: x[i].astype(jnp.float32), layers)
    b, s, _ = h.shape
    H, KH, HD, eps = k["h"], k["kh"], k["hd"], k["eps"]
    pos = jnp.arange(s)
    a = rms(h, w["ln1"], eps)
    q = matmul(a, w["attn"]["wq"], fp8).reshape(b, s, H, HD)
    kk = matmul(a, w["attn"]["wk"], fp8).reshape(b, s, KH, HD)
    v = matmul(a, w["attn"]["wv"], fp8).reshape(b, s, KH, HD)
    q = rope(rms(q, w["attn"]["q_norm"], eps), pos, k["theta"])
    kk = rope(rms(kk, w["attn"]["k_norm"], eps), pos, k["theta"])
    g = H // KH
    qg = q.reshape(b, s, KH, g, HD)
    if fp8:
        qg, kk, v = fp8_round(qg), fp8_round(kk), fp8_round(v)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, kk, precision=HI) / math.sqrt(HD)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    if fp8:
        p = fp8_round(p)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI).reshape(b, s, H * HD)
    h = h + matmul(o, w["attn"]["wo"], fp8)
    m = rms(h, w["ln2"], eps)
    up = jax.nn.silu(matmul(m, w["mlp"]["w_gate"], fp8)) * matmul(m, w["mlp"]["w_up"], fp8)
    return h + matmul(up, w["mlp"]["w_down"], fp8)


def final_hidden(c: dict, W, ids: np.ndarray, rows: np.ndarray, fp8: bool):
    """Final-normed hidden states ``[len(rows), d]`` at flat positions
    ``rows`` of the ``[B, S]`` token batch ``ids``."""
    k = dims(c)
    step = jax.jit(lambda h, layers, i: layer(h, layers, i, k, fp8))
    h = W["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(k["L"]):
        h = step(h, W["layers"], i)
    h = h.reshape(-1, k["d"])[jnp.asarray(rows)]
    return rms(h, W["final_norm"].astype(jnp.float32), k["eps"])


def _batch(seqs, pad_to: int = 256):
    """Token batch of prompt + served tokens (the last served token is never
    input), padded at the end (causal: padding changes no earlier position),
    with the flat row and the token of every served position."""
    need = [len(p) + len(t) - 1 for p, t in seqs]
    s = -(-max(need) // pad_to) * pad_to
    ids = np.zeros((len(seqs), s), np.int32)
    rows, toks = [], []
    for b, (p, t) in enumerate(seqs):
        full = np.concatenate([p, t])[: need[b]]
        ids[b, : need[b]] = full
        rows += [b * s + len(p) - 1 + j for j in range(len(t))]
        toks += list(t)
    return ids, np.asarray(rows), np.asarray(toks, np.int32)


def served_gaps(c: dict, seed: int, seqs: list, control: bool = False) -> dict:
    """Widest gap, over every served token of ``seqs`` (``(prompt, tokens)``
    pairs), between the reference's best logit and the served token's.  With
    ``control``, also the widest gap of the token that the float8 control
    puts first at the same positions."""
    W = make_weights(param_tree(c), seed)
    ids, rows, toks = _batch(seqs)
    h32 = final_hidden(c, W, ids, rows, fp8=False)
    h8 = final_hidden(c, W, ids, rows, fp8=True) if control else None

    @jax.jit
    def gaps(h, h8, tok, head):
        logits = jnp.matmul(h, head.astype(jnp.float32), precision=HI)
        best = jnp.max(logits, axis=-1)
        served = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        out = [best - served]
        if h8 is not None:
            pick = jnp.argmax(matmul(h8, head.astype(jnp.float32), True), axis=-1)
            out.append(best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0])
        return out

    chunk = 512
    served, ctl = [], []
    for i in range(0, len(rows), chunk):
        sl = slice(i, i + chunk)
        pad = chunk - len(rows[sl])
        tok = jnp.asarray(np.pad(toks[sl], (0, pad)))
        hh = jnp.pad(h32[sl], ((0, pad), (0, 0)))
        hc = None if h8 is None else jnp.pad(h8[sl], ((0, pad), (0, 0)))
        g = gaps(hh, hc, tok, W["lm_head"])
        served.append(np.asarray(g[0])[: chunk - pad])
        if control:
            ctl.append(np.asarray(g[1])[: chunk - pad])
    out = {"widest_gap": float(np.max(np.concatenate(served))), "tokens": int(len(rows))}
    if control:
        out["control_widest_gap"] = float(np.max(np.concatenate(ctl)))
    return out
