"""Plain reference of the Mamba-2 language model (state-spaces/mamba2-780m
``config.json``, arXiv:2405.21060), for training.

Per layer: RMSNorm; projections of the normed input to ``z``, ``x``, ``B``,
``C`` (one group) and ``dt``; a causal depthwise convolution of width
``d_conv`` with bias over ``x``, ``B`` and ``C``, then SiLU;
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` per head; the SSD
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = C_t h_t``,
written as its quadratic form over the whole sequence, not in chunks::

    y_i = sum_{j <= i} (C_i . B_j) exp(A sum_{k=j+1..i} dt_k) dt_j x_j

then ``y + D x``, the gated RMSNorm ``rms(y * silu(z)) * w``, the
out-projection and the residual.  A final RMSNorm and the LM head give the
logits; the loss is the mean next-token cross-entropy over the vocabulary's
padded rows as well.  The exponent's sum over ``k`` is formed from partial
sums inside blocks of ``BLOCK`` positions and the totals of whole blocks
added from block ``j`` upward, never as the difference of two prefix sums
over the sequence: those reach thousands, where a float32 ulp would round
every decay factor by about 1e-4.

Departures from the published model, as the program runs it (the
configuration file's ``departures``, with the values the program runs under
``reference.as_run``): the LM head is its own matrix; the residual stream is
not held in float32 by the program (here, at ``precision="float32"``,
everything is); RMSNorm's epsilon is the program's; the five projections
are five matrices (the published ``in_proj`` is one matrix cut into the same
five parts).

Training: gradients by ``jax.vjp`` one layer at a time (the residual stream
at each layer's input is kept, each layer recomputed in its backward), then
AdamW in float32 as the program's ``OptConfig`` states it: clipping to a
global norm, the warmup-cosine rate, bias-corrected moments and weight
decay on every parameter.  Parameters are stored in bfloat16 after each
update, as the configuration states (bfloat16 weights, float32 moments).
Each step passes over the layers' gradients twice, first for the global
norm that clipping needs and then to update each layer as its gradient is
made, so no whole float32 gradient is held beside the moments: it would not
fit one chip.

``precision="float32"``: every operation in float32, products at
``Precision.HIGHEST``.  ``precision="chip"``, the control: the same
computation with every product's inputs in bfloat16 at the default
precision (float32 accumulation), the residual stream stored in bfloat16
after every add (as the program carries it), every activation between
operations, the inputs of every RMSNorm among them, rounded to float8 e4m3
at a scale per row (last axis) and its cotangent likewise, and each
gradient rounded to bfloat16, the type of its parameter.  Where the program
keeps bfloat16 or float32, the control keeps float8 or bfloat16: it rounds
at least what the program rounds.  ``precision="bf16"``, a witness and no
control: the same with every activation rounded to bfloat16 instead, the
program's own type, to read how far rounding alone at the program's
precision moves the numbers compared.

Weights: the program's parameter stream for this configuration, made here
from the seed: one key per parameter, split from ``PRNGKey(seed)`` in the
order of the sorted parameter paths; matrices ``N(0, 1) / sqrt(fan_in)``
(a convolution's fan-in is its width), the embedding ``N(0, 1)``; RMSNorm
weights, ``A_log`` and ``D`` one; ``dt_bias`` and convolution biases zero;
drawn in float32 and stored in bfloat16.  Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest float8 e4m3 value
BLOCK = 64  # positions per block of the decay exponent's partial sums
HEAD_GROUP = 8  # heads whose [S, S] decay matrices are made at once
LOSS_ROWS = 1024  # token rows of the LM head and loss made at once


def dims(c: dict) -> dict:
    """The sizes of configuration ``c``, with what its ``reference.as_run``
    says the program runs in place of the published keys."""
    c = dict(c, **c.get("reference", {}).get("as_run", {}))
    if c["tie_embeddings"]:
        raise NotImplementedError("a tied LM head: this reference keeps its own")
    s = c["ssm_cfg"]
    mult = c["pad_vocab_size_multiple"]
    d, di = c["d_model"], s["expand"] * c["d_model"]
    return dict(d=d, L=c["n_layer"], V=-(-c["vocab_size"] // mult) * mult,
                N=s["ngroups"] * s["d_state"], W=s["d_conv"], P=s["headdim"],
                di=di, H=di // s["headdim"], eps=float(c["norm_epsilon"]))


def param_tree(c: dict) -> dict:
    """``path -> (shape, init, std)`` as nested dicts."""
    k = dims(c)
    d, L, V, N, W, di, H = (k[x] for x in ("d", "L", "V", "N", "W", "di", "H"))
    mat = lambda *s: (s, "normal", 1.0 / math.sqrt(s[-2]))
    one = lambda *s: (s, "ones", None)
    zero = lambda *s: (s, "zeros", None)
    return {
        "embed": ((V, d), "normal", 1.0),
        "final_norm": one(d),
        "layers": {
            "ln": one(L, d),
            "ssm": {
                "a_log": one(L, H), "conv_b_b": zero(L, N), "conv_b_w": mat(L, W, N),
                "conv_c_b": zero(L, N), "conv_c_w": mat(L, W, N),
                "conv_x_b": zero(L, di), "conv_x_w": mat(L, W, di),
                "d_skip": one(L, H), "dt_bias": zero(L, H),
                "in_b": mat(L, d, N), "in_c": mat(L, d, N), "in_dt": mat(L, d, H),
                "in_x": mat(L, d, di), "in_z": mat(L, d, di),
                "norm_w": one(L, di), "out_proj": mat(L, di, d),
            },
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


def leaf_names(tree) -> list[str]:
    """``"layers/ssm/in_c"``-style names of ``tree``'s leaves, in order."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in paths]


# -- precision ---------------------------------------------------------------

def _round8(x):
    """``x`` rounded to the nearest float8 e4m3 value (ties to even) at a
    scale per row of the last axis.  Done in float32 arithmetic: the
    power-of-two step of each value is read from its exponent bits, so no
    conversion to a float8 type, whose overflow is NaN, takes part."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    y = jnp.clip(x / s, -F8_MAX, F8_MAX)
    e = (jax.lax.bitcast_convert_type(jnp.abs(y), jnp.int32) >> 23) - 127
    e = jnp.maximum(e, -6) - 3  # 3 mantissa bits; below 2**-6 the subnormal step
    step = jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)
    return jnp.round(y / step) * step * s


def _round16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rounding(fn):
    """An activation rounded by ``fn`` going forward, its cotangent
    likewise going back."""
    f = jax.custom_vjp(fn)
    f.defvjp(lambda x: (fn(x), None), lambda _, g: (fn(g),))
    return f


fp8 = _rounding(_round8)
bf16 = _rounding(_round16)


class Ops:
    """The products and roundings of one precision: ``"float32"``, the
    control ``"chip"`` (float8 activations), or the witness ``"bf16"``
    (the control with bfloat16 activations, the program's own type)."""

    def __init__(self, precision: str):
        self.chip = precision != "float32"
        self.round = {"float32": None, "chip": fp8, "bf16": bf16}[precision]

    def act(self, x):
        return self.round(x) if self.chip else x

    def store(self, x):
        """The residual stream as it is kept between layers."""
        return x.astype(jnp.bfloat16).astype(jnp.float32) if self.chip else x

    def mm(self, a, b):
        return self.ein("...i,ij->...j", a, b)

    def ein(self, spec, *xs):
        if self.chip:
            xs = [x.astype(jnp.bfloat16) for x in xs]
            return jnp.einsum(spec, *xs, preferred_element_type=jnp.float32)
        return jnp.einsum(spec, *xs, precision=HI)


# -- the model ---------------------------------------------------------------

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w, b):
    """Depthwise causal convolution: ``y_t = b + sum_i w[i] x_{t-W+1+i}``;
    x [B, S, C], w [W, C]."""
    width, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(width)) + b


def decay_exponents(dta, block: int = BLOCK):
    """``e[b, h, i, j] = sum_{k=j+1..i} dta[b, k, h]`` for ``j <= i``, from
    partial sums inside blocks and totals of whole blocks; dta [B, S, g]."""
    b, s, g = dta.shape
    q = block if s % block == 0 else s
    nb = s // q
    part = jnp.cumsum(dta.reshape(b, nb, q, g), axis=2)  # inclusive, in block
    tot = part[:, :, -1]  # [B, nb, g]
    # whole[bi, bj] = sum of the totals of blocks bj .. bi-1 (0 for bi <= bj)
    upper = jnp.arange(nb)[:, None] >= jnp.arange(nb)[None, :]  # [b, bj]: b >= bj
    terms = jnp.where(upper[None, :, :, None], tot[:, :, None, :], 0.0)
    whole = jnp.cumsum(terms, axis=1)
    whole = jnp.concatenate([jnp.zeros_like(whole[:, :1]), whole[:, :-1]], axis=1)
    whole = jnp.repeat(jnp.repeat(whole, q, axis=1), q, axis=2)  # [B, S, S, g]
    part = part.reshape(b, s, g)
    e = part[:, :, None, :] - part[:, None, :, :] + whole
    return jnp.moveaxis(e, 3, 1)  # [B, g, S, S]


def ssd(x, dt, a, bb, cc, ops: Ops):
    """The SSD's quadratic form.  x [B, S, H, P], dt [B, S, H], a [H] (< 0),
    bb/cc [B, S, N] -> y [B, S, H, P]."""
    b, s, h, p = x.shape
    g = math.gcd(h, HEAD_GROUP)
    xdt = ops.act(x * dt[..., None])
    cb = ops.act(ops.ein("bin,bjn->bij", cc, bb))  # [B, S, S]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        dta_g, xdt_g = args  # [B, S, g], [B, S, g, P]
        e = decay_exponents(dta_g)
        # masked before the exp and after: above the diagonal the exponent
        # is positive and would overflow, and an inf there times a zero
        # cotangent is NaN
        decay = ops.act(jnp.where(causal, jnp.exp(jnp.where(causal, e, 0.0)), 0.0))
        m = ops.act(cb[:, None] * decay)  # [B, g, S, S]
        return ops.ein("bhij,bjhp->bihp", m, xdt_g)

    dta = (dt * a).reshape(b, s, h // g, g)
    xg = xdt.reshape(b, s, h // g, g, p)
    y = jax.lax.map(group, (jnp.moveaxis(dta, 2, 0), jnp.moveaxis(xg, 2, 0)))
    return jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)


def layer(w, h, k: dict, ops: Ops):
    """One Mamba-2 block with its residual; ``w`` one layer's float32
    parameters, h float32 [B, S, d]."""
    b, s, _ = h.shape
    H, P, eps, act = k["H"], k["P"], k["eps"], ops.act
    m = w["ssm"]
    a = act(rms(act(h), w["ln"], eps))
    z = act(ops.mm(a, m["in_z"]))
    x = act(silu(causal_conv(act(ops.mm(a, m["in_x"])), m["conv_x_w"], m["conv_x_b"])))
    bb = act(silu(causal_conv(act(ops.mm(a, m["in_b"])), m["conv_b_w"], m["conv_b_b"])))
    cc = act(silu(causal_conv(act(ops.mm(a, m["in_c"])), m["conv_c_w"], m["conv_c_b"])))
    dt = act(jax.nn.softplus(ops.mm(a, m["in_dt"]) + m["dt_bias"]))
    xh = x.reshape(b, s, H, P)
    y = ssd(xh, dt, -jnp.exp(m["a_log"]), bb, cc, ops)
    y = act(y + m["d_skip"][:, None] * xh).reshape(b, s, H * P)
    y = act(rms(act(y * silu(z)), m["norm_w"], eps))
    return ops.store(h + act(ops.mm(y, m["out_proj"])))


def head_loss(fn, head, h, labels, k: dict, ops: Ops):
    """Sum over tokens of the next-token cross-entropy; h [B, S, d] f32."""
    d = h.shape[-1]
    rows_at_once = math.gcd(labels.size, LOSS_ROWS)
    x = ops.act(rms(ops.act(h), fn, k["eps"])).reshape(-1, rows_at_once, d)
    lab = labels.reshape(-1, rows_at_once)

    @jax.checkpoint
    def rows(args):
        xr, lr = args
        logits = ops.act(ops.mm(xr, head))
        best = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(best - jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(rows, (x, lab)))


# -- training ----------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _sq(tree):
    return jax.tree.map(lambda x: jnp.sum(jnp.square(x)), tree)


def _adamw(p, g, m, v, scale, t, o: dict):
    """One AdamW update of one array, as ``OptConfig`` states it."""
    t = t.astype(jnp.float32)
    warm = jnp.minimum(t / max(o["warmup_steps"], 1), 1.0)
    frac = jnp.clip((t - o["warmup_steps"]) / max(o["total_steps"] - o["warmup_steps"], 1),
                    0.0, 1.0)
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    lr = o["lr"] * warm * cos
    b1, b2 = o["beta1"], o["beta2"]
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    p32 = p.astype(jnp.float32)
    delta = mhat / (jnp.sqrt(vhat) + o["eps"]) + o["weight_decay"] * p32
    return (p32 - lr * delta).astype(p.dtype), m, v


class Trainer:
    """The reference's train step over bfloat16 parameters ``W`` with float32
    moments, one layer at a time."""

    def __init__(self, c: dict, opt: dict, precision: str = "float32"):
        self.k, self.o = dims(c), dict(opt)
        self.ops = Ops(precision)
        k, ops = self.k, self.ops
        at = lambda stack, l: jax.tree.map(lambda x: x[l], stack)
        put = lambda stack, l, new: jax.tree.map(lambda x, n: x.at[l].set(n), stack, new)

        def cast(dw):  # the control's gradients in their parameters' type
            if not ops.chip:
                return dw
            return jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), dw)

        def layer_fwd(stack, l, h):
            return layer(_f32(at(stack, l)), h, k, ops)

        def layer_vjp(stack, l, h, g):
            _, vjp = jax.vjp(lambda w, x: layer(w, x, k, ops), _f32(at(stack, l)), h)
            dw, dh = vjp(g)
            return dh, cast(dw)

        def layer_norms(stack, l, h, g):
            dh, dw = layer_vjp(stack, l, h, g)
            return dh, _sq(dw)

        def layer_update(stack, m, v, l, h, g, scale, t):
            dh, dw = layer_vjp(stack, l, h, g)
            out = jax.tree.map(lambda p, gg, mm, vv: _adamw(p, gg, mm, vv, scale, t, self.o),
                               at(stack, l), dw, at(m, l), at(v, l))
            pick = lambda i: jax.tree.map(lambda o_: o_[i], out,
                                          is_leaf=lambda x: isinstance(x, tuple))
            return dh, put(stack, l, pick(0)), put(m, l, pick(1)), put(v, l, pick(2))

        def head(fn, lm, h, labels):
            n = labels.size
            loss, vjp = jax.vjp(lambda f, w, x: head_loss(f, w, x, labels, k, ops) / n,
                              fn.astype(jnp.float32), lm.astype(jnp.float32), h)
            dfn, dlm, dh = vjp(jnp.ones((), jnp.float32))
            return loss, dh, cast({"final_norm": dfn, "lm_head": dlm})

        def embed_grad(tokens, g, vocab):
            e = jnp.zeros((vocab, g.shape[-1]), jnp.float32)
            return cast({"embed": e.at[tokens.reshape(-1)].add(g.reshape(-1, g.shape[-1]))})

        def embed_in(embed, tokens):
            return ops.store(embed[tokens].astype(jnp.float32))

        self.layer_fwd = jax.jit(layer_fwd)
        self.layer_grads = jax.jit(layer_vjp)
        self.layer_norms = jax.jit(layer_norms)
        self.layer_update = jax.jit(layer_update, donate_argnums=(0, 1, 2))
        self.head = jax.jit(head)
        self.head_loss = jax.jit(lambda fn, lm, h, labels: head_loss(
            fn.astype(jnp.float32), lm.astype(jnp.float32), h, labels, k, ops) / labels.size)
        self.embed_grad = jax.jit(embed_grad, static_argnums=2)
        self.embed_in = jax.jit(embed_in)
        self.update = jax.jit(
            lambda p, g, m, v, scale, t: jax.tree.map(
                lambda a, b, c_, d_: _adamw(a, b, c_, d_, scale, t, self.o), p, g, m, v),
            donate_argnums=(0, 2, 3))
        self.sq = jax.jit(_sq)

    def forward(self, W, tokens):
        """The residual stream at every layer's input and after the last."""
        hs = [self.embed_in(W["embed"], tokens)]
        for l in range(self.k["L"]):
            hs.append(self.layer_fwd(W["layers"], l, hs[-1]))
        return hs

    def loss(self, W, batch) -> float:
        hs = self.forward(W, jnp.asarray(batch["tokens"]))
        return float(self.head_loss(W["final_norm"], W["lm_head"], hs[-1],
                                    jnp.asarray(batch["labels"])))

    def grads(self, W, batch):
        """The loss and every float32 gradient, through the same passes as
        :meth:`step`; for sizes at which a whole gradient fits."""
        tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
        hs = self.forward(W, tokens)
        loss, g, top = self.head(W["final_norm"], W["lm_head"], hs[-1], labels)
        per_layer = []
        for l in reversed(range(self.k["L"])):
            g, dw = self.layer_grads(W["layers"], l, hs[l], g)
            per_layer.insert(0, dw)
        layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
        return float(loss), dict(top, layers=layers,
                                 **self.embed_grad(tokens, g, self.k["V"]))

    def step(self, W, m, v, t: int, batch, keep: bool = False):
        """One train step: ``(loss, raw per-leaf gradient norms, global norm,
        W, m, v, grads)``; ``t`` counts from 1.  With ``keep``, ``grads``
        holds every raw float32 gradient on the host, by leaf name (one
        layer fetched at a time); otherwise it is None."""
        tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
        hs = self.forward(W, tokens)
        L = self.k["L"]
        # pass 1: squared norms of every gradient, for the clip
        loss, g, dtop = self.head(W["final_norm"], W["lm_head"], hs[-1], labels)
        layer_sq, kept = None, []
        for l in reversed(range(L)):
            if keep:
                g, dw = self.layer_grads(W["layers"], l, hs[l], g)
                s = self.sq(dw)
                kept.insert(0, jax.device_get(dw))
            else:
                g, s = self.layer_norms(W["layers"], l, hs[l], g)
            layer_sq = s if layer_sq is None else jax.tree.map(jnp.add, layer_sq, s)
        de = self.embed_grad(tokens, g, self.k["V"])
        sq = dict(self.sq(dtop), layers=layer_sq, **self.sq(de))
        names = leaf_names(sq)
        norms = {n: float(np.sqrt(x)) for n, x in zip(names, jax.tree.leaves(sq))}
        grads = None
        if keep:
            layers = jax.tree.map(lambda *xs: np.stack(xs), *kept)
            del kept
            full = dict(jax.device_get(dtop), layers=layers, **jax.device_get(de))
            grads = dict(zip(names, jax.tree.leaves(full)))
        del de
        gnorm = float(np.sqrt(sum(x * x for x in norms.values())))
        scale = jnp.float32(min(1.0, self.o["clip_norm"] / (gnorm + 1e-9)))
        tt = jnp.int32(t)
        # pass 2: the same gradients again, each layer updated as it is made
        _, g, dtop = self.head(W["final_norm"], W["lm_head"], hs[-1], labels)
        top = {n: W[n] for n in dtop}
        top, mt, vt = self._update(top, dtop, {n: m[n] for n in dtop},
                                   {n: v[n] for n in dtop}, scale, tt)
        stack, ml, vl = W["layers"], m["layers"], v["layers"]
        for l in reversed(range(L)):
            g, stack, ml, vl = self.layer_update(stack, ml, vl, l, hs[l], g, scale, tt)
        del hs
        de = self.embed_grad(tokens, g, self.k["V"])
        emb, me, ve = self._update({"embed": W["embed"]}, de, {"embed": m["embed"]},
                                   {"embed": v["embed"]}, scale, tt)
        W = dict(top, layers=stack, **emb)
        m = dict(mt, layers=ml, **me)
        v = dict(vt, layers=vl, **ve)
        return float(loss), norms, gnorm, W, m, v, grads

    def _update(self, p, g, m, v, scale, t):
        out = self.update(p, g, m, v, scale, t)
        return tuple({n: out[n][i] for n in out} for i in range(3))


def train_readings(c: dict, opt: dict, seed: int, batches: list,
                   precision: str = "float32") -> dict:
    """From the seed's weights, one train step on each of ``batches`` but
    the last and the loss on every one: ``losses``, the raw gradient of
    every leaf at the first step on the host (``grads``), its norm
    (``grad_norms``) and their global norm (``grad_norm``), and the norm
    of every leaf's change after the steps (``change_norms``)."""
    tr = Trainer(c, opt, precision)
    tree = param_tree(c)
    W = make_weights(tree, seed)
    zeros = lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), W)
    m, v = zeros(), zeros()
    losses, first = [], None
    for t, batch in enumerate(batches[:-1], start=1):
        loss, norms, gnorm, W, m, v, grads = tr.step(W, m, v, t, batch, keep=first is None)
        losses.append(loss)
        if first is None:
            first = (norms, gnorm, grads)
    del m, v
    losses.append(tr.loss(W, batches[-1]))
    W0 = make_weights(tree, seed)
    change = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))),
        a, b))(W, W0)
    return {"losses": losses, "grad_norms": first[0], "grad_norm": first[1],
            "grads": first[2],
            "change_norms": dict(zip(leaf_names(change), map(float, jax.tree.leaves(change))))}
