#!/usr/bin/env python3
"""Smoke check of the TensorDash runtime on TPU chips, at full model width.

    python chip_smoke.py              # one chip: kernels, serving, training
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One process runs every phase through the normal entry points (``Runtime``,
``ServeEngine``, ``make_train_step`` via the ``launch.train`` loop,
``ShardingPolicy``) with the ``pallas`` backend and random weights made from
``--seed``.  Each phase prints its findings on lines of its own and fails the
run if a check fails; the last line is one JSON object naming the device.
Wall times and rates printed here are smoke readings (compilation
included), not measurements.

Phases on one chip:

1. device   — JAX must see a TPU; anything else exits non-zero.
2. kernels  — the planned ragged kernel and the fused relu+bias kernel at a
   qwen3-4b FFN shape and at the qwen3-4b LM-head decode shape, at block
   densities 0, 0.5 and 1, against a float32 ``HIGHEST`` dot of the
   block-masked operands; the fused kernel's emitted mask against the mask
   of its own output.
3. serving  — qwen3-4b: forward logits on ``pallas`` against ``dense``, then
   requests through ``ServeEngine`` and the ``launch.serve`` replay loop.
4. training — mamba2-780m train steps through the ``launch.train`` loop.

With ``--chips 4``: qwen3-4b train steps on a four-chip ``data`` mesh, and
``Runtime.matmul_sharded`` along M and N compared bitwise with the same plan
on one chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import re
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

#: bf16 unit roundoff (8 significant bits) and fp32 unit roundoff
U_BF16 = 2.0 ** -8
U_F32 = 2.0 ** -24

#: (name, tokens, d_model, width, sparse side) of the kernel phase: the
#: qwen3-4b FFN up-projection at 2048 tokens, and its LM head decoding 4
#: slots, whose sparse operand is the head (side "B", the models'
#: ``head_matmul``)
KERNEL_SHAPES = [("ffn", 2048, 2560, 9728, "A"),
                 ("lm_head_decode", 4, 2560, 151936, "B")]
#: the sharded SpMM's ``a @ b`` (the qwen3-4b FFN up-projection)
SPMM_SHAPE = (2048, 2560, 9728)


def fail(msg: str) -> None:
    print(f"FAIL {msg}", flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def kernel_count(lowered) -> int:
    """``tpu_custom_call`` instructions in the compiled program."""
    return lowered.compile().as_text().count("tpu_custom_call")


def kernel_result_shapes(compiled_text: str) -> list[tuple[int, ...]]:
    """Result shapes of the compiled program's ``tpu_custom_call``s."""
    pat = re.compile(r"= \w+\[([0-9,]*)\]\S* custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"')
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in pat.finditer(compiled_text)]


# -- phase 2: kernels ------------------------------------------------------

def _block_masked(key, shape, block, density, dtype):
    """Normal values with whole ``block``s zeroed: each kept with
    probability ``density`` (0 and 1 exact)."""
    import jax
    import jax.numpy as jnp

    kv, km = jax.random.split(key)
    x = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    grid = (-(-shape[0] // block[0]), -(-shape[1] // block[1]))
    keep = jax.random.uniform(km, grid) < density
    keep = jnp.repeat(jnp.repeat(keep, block[0], 0), block[1], 1)
    return jnp.where(keep[: shape[0], : shape[1]], x, 0).astype(dtype)


def _tolerance(a32, b32, ref, k, bias=None):
    """Elementwise bound on |kernel - ref| for bf16 operands.

    The MXU forms each bf16 x bf16 product exactly in fp32 and accumulates
    in fp32, so the accumulation error is at most ``k * U_F32 * sum|a||b|``
    (the same bound holds for the fp32 ``HIGHEST`` reference, hence twice
    it); the single rounding of the fp32 result to the bf16 output adds at
    most ``U_BF16 * |x|``.  The bias is added in fp32 and relu is
    1-Lipschitz, so neither widens the bound beyond ``|bias|`` in the sum.
    """
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    mag = jnp.dot(jnp.abs(a32), jnp.abs(b32), precision=hi)
    if bias is not None:
        mag = mag + jnp.abs(bias)
    return U_BF16 * jnp.abs(ref) * (1 + U_BF16) + 2 * k * U_F32 * mag


def _worst(err, tol) -> float:
    """Largest ``err / tol``; where the bound is 0 (an all-zero block of
    either operand) the error must be exactly 0."""
    import jax.numpy as jnp

    safe = jnp.where(tol > 0, tol, 1.0)
    return float(jnp.max(jnp.where(tol > 0, err / safe,
                                   jnp.where(err > 0, jnp.inf, 0.0))))


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime import Runtime

    rt = Runtime(backend="pallas")
    hi = jax.lax.Precision.HIGHEST
    key = jax.random.PRNGKey(seed)
    for name, m, k, n, side in KERNEL_SHAPES:
        for density in (0.0, 0.5, 1.0):
            key, ka, kb, kc = jax.random.split(key, 4)
            fit = rt.fit((m, k), (k, n))
            if side == "A":
                a = _block_masked(ka, (m, k), (fit.bm, fit.bk), density, jnp.bfloat16)
                b = jax.random.normal(kb, (k, n), jnp.float32).astype(jnp.bfloat16)
            else:
                a = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
                # the head's plan blocks lm_head.T into (vocab tile, bk)
                b = _block_masked(kb, (n, k), (fit.bn, fit.bk), density,
                                  jnp.bfloat16).T
            a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
            ref = jnp.dot(a32, b32, precision=hi)
            out = rt.matmul(a, b, side=side).astype(jnp.float32)
            tol = _tolerance(a32, b32, ref, k)
            err = jnp.abs(out - ref)
            worst = _worst(err, tol)
            print(f"kernels planned {name} {m}x{k}x{n} side={side} "
                  f"density={density} max_abs_err={float(jnp.max(err)):.6g} "
                  f"err/tol={worst:.4g}", flush=True)
            check(out.shape == (m, n) and worst <= 1.0,
                  f"planned {name} density={density}: err/tol {worst}")

            # the fused kernel: relu(a @ b + bias), sparse stream a
            if side == "B":
                a = _block_masked(ka, (m, k), (fit.bm, fit.bk), density, jnp.bfloat16)
                b = jax.random.normal(kb, (k, n), jnp.float32).astype(jnp.bfloat16)
                a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
            bias = jax.random.normal(kc, (n,), jnp.float32)
            pre = jnp.dot(a32, b32, precision=hi) + bias
            ref = jnp.maximum(pre, 0.0)
            y, mask = rt.matmul_fused(a, b, bias=bias, activation="relu")
            y = y.astype(jnp.float32)
            tol = _tolerance(a32, b32, pre, k, bias)
            err = jnp.abs(y - ref)
            worst = _worst(err, tol)
            mb, nb = mask.shape
            check(m % mb == 0 and n % nb == 0, f"fused {name}: mask {mask.shape}")
            seen = jnp.any(y.reshape(mb, m // mb, nb, n // nb) != 0, axis=(1, 3))
            same = bool(jnp.array_equal(seen, mask.astype(bool)))
            print(f"kernels fused-relu-bias {name} {m}x{k}x{n} density={density} "
                  f"max_abs_err={float(jnp.max(err)):.6g} err/tol={worst:.4g} "
                  f"mask={mb}x{nb} nonzero={int(np.asarray(mask).sum())} "
                  f"mask_matches_output={same}", flush=True)
            check(worst <= 1.0, f"fused {name} density={density}: err/tol {worst}")
            check(same, f"fused {name} density={density}: emitted mask differs")


# -- phase 3: serving ------------------------------------------------------

def phase_serving(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import runtime as rtm
    from repro.configs import get_config
    from repro.launch import serve as launch_serve
    from repro.launch.mesh import init_sharded_params, make_local_mesh
    from repro.models import model as M
    from repro.parallel.sharding import ShardingPolicy
    from repro.serve import engine as serve_engine
    from repro.serve.engine import ServeEngine

    cfg = get_config("qwen3-4b")
    policy = ShardingPolicy(mesh=make_local_mesh())
    rt = rtm.Runtime(backend="pallas", sharding=policy)
    dense = rtm.Runtime(backend="dense", sharding=policy)
    t0 = time.monotonic()
    params = init_sharded_params(cfg, policy, seed)
    jax.block_until_ready(params)
    print(f"serving qwen3-4b params={cfg.param_count() / 1e9:.2f}B "
          f"init={time.monotonic() - t0:.1f}s", flush=True)

    # forward logits at 200 tokens: pallas against dense.  Only the LM head
    # differs (the silu FFN never reaches the kernel).  Each side rounds its
    # fp32-accumulated head product to bf16 once, so the two can be up to
    # 2 * U_BF16 * |x| apart.  Besides, XLA may fuse the final norm into
    # each program's head differently, so single elements of the bf16 hidden
    # state can round to adjacent values, which moves a logit by about
    # U_BF16 times one product term.  Both effects are small and unbiased, so
    # the bound is on the whole: the Frobenius norm of the difference within
    # 2 * U_BF16 of the logits', and no element further apart than two
    # roundings of the largest logit.
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, 200), 0,
                                cfg.vocab_size)

    def forward(runtime):
        def f(p, t):
            with rtm.use(runtime):
                return M.forward(p, cfg, {"tokens": t})
        return jax.jit(f)

    fwd = forward(rt)
    got = fwd(params, tokens).astype(jnp.float32)
    want = forward(dense)(params, tokens).astype(jnp.float32)
    diff = jnp.abs(got - want)
    rel = float(jnp.linalg.norm(diff) / jnp.linalg.norm(want))
    max_diff, top = float(jnp.max(diff)), float(jnp.max(jnp.abs(want)))
    n_fwd = kernel_count(fwd.lower(params, tokens))
    print(f"serving forward 1x200 logits{tuple(got.shape)} pallas-vs-dense "
          f"rel_frobenius={rel:.6g} (bound {2 * U_BF16:.6g}) "
          f"max_abs_diff={max_diff:.6g} (bound {2 * U_BF16 * top:.6g}) "
          f"finite={bool(jnp.all(jnp.isfinite(got)))} tpu_custom_call={n_fwd}",
          flush=True)
    check(bool(jnp.all(jnp.isfinite(got))), "serving forward: non-finite logits")
    check(rel <= 2 * U_BF16 and max_diff <= 2 * U_BF16 * top,
          f"serving forward: rel {rel}, max diff {max_diff}")
    check(n_fwd > 0, "serving forward: no kernel in the program")
    del got, want, diff

    slots, new, requests, prompt_len = 4, 16, 8, 200
    prompts, budgets, arrivals = launch_serve.make_traffic(
        np.random.default_rng(seed), cfg.vocab_size, requests=requests,
        prompt_len=prompt_len, new=new, rate=0.0,
    )
    eng = ServeEngine(params, cfg, slots=slots, max_len=prompt_len + new,
                      rt=rt, seed=seed)
    traces0 = serve_engine.DECODE_TRACES
    dt = launch_serve.replay(eng, prompts, budgets, arrivals)
    st = eng.stats()
    traces = st["decode_traces"] - traces0
    reasons = launch_serve.finish_reasons(eng)
    n_dec = kernel_count(eng.lower_decode())
    print(f"serving engine requests={requests} slots={slots} "
          f"prompt_lens={sorted(len(p) for p in prompts)} "
          f"new={[int(x) for x in budgets]} finish={reasons} "
          f"decode_traces={traces} chunks={st['chunks_run']} "
          f"tokens_out={st['tokens_out']} tpu_custom_call={n_dec}", flush=True)
    print(f"serving smoke-rate {st['tokens_out'] / dt:.1f} tok/s over {dt:.1f}s "
          "(compilation included; a smoke reading, not a measurement)",
          flush=True)
    check(reasons == {"length": requests}, f"serving: finish reasons {reasons}")
    check(traces == 1, f"serving: decode program traced {traces}x")
    check(n_dec > 0, "serving decode: no kernel in the program")


# -- phase 4: training -----------------------------------------------------

def _train(arch: str, batch: int, seq: int, steps: int) -> None:
    """Train steps through the ``launch.train`` loop (weights from its
    fixed seed 0)."""
    import jax
    import numpy as np

    from repro.launch import train as launch_train

    argv = ["--arch", arch, "--backend", "pallas", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq)]
    t0 = time.monotonic()
    run = launch_train.main(argv)
    dt = time.monotonic() - t0
    n = kernel_count(run.lower())
    for h in run.history:
        print(f"training {arch} step={h['step']} loss={h['loss']:.6g} "
              f"grad_norm={h['grad_norm']:.6g} nonfinite={int(h['nonfinite'])} "
              f"seconds={h['seconds']:.3f}", flush=True)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()]
    print(f"training {arch} batch={batch}x{seq} devices={len(jax.devices())} "
          f"steps={len(run.history)} wall={dt:.1f}s tpu_custom_call={n} "
          f"peak_bytes_in_use={max(peak)} (per device {peak})", flush=True)
    check(len(run.history) == steps, f"training {arch}: {len(run.history)} steps")
    for h in run.history:
        check(bool(np.isfinite(h["loss"]) and np.isfinite(h["grad_norm"]))
              and not h["nonfinite"],
              f"training {arch}: non-finite at step {h['step']}")
    check(n > 0, f"training {arch}: no kernel in the step program")


# -- --chips 4: the sharded SpMM -------------------------------------------

def phase_sharded_spmm(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import ShardingPolicy
    from repro.runtime import Runtime, shard_plan

    devices = jax.devices()
    one = Runtime(backend="pallas")
    m, k, n = SPMM_SHAPE
    key_a, key_b = jax.random.split(jax.random.PRNGKey(seed))
    fit = one.fit((m, k), (k, n))
    a = _block_masked(key_a, (m, k), (fit.bm, fit.bk), 0.5, jnp.bfloat16)
    b = jax.random.normal(key_b, (k, n), jnp.float32).astype(jnp.bfloat16)
    a0, b0 = jax.device_put(a, devices[0]), jax.device_put(b, devices[0])
    plan = one.plan(a0)
    want = np.asarray(one.matmul(a0, b0, plan=plan))
    for axis, data in (("M", True), ("N", False)):
        rt = Runtime(backend="pallas",
                     sharding=ShardingPolicy(mesh=make_local_mesh(data=data)))
        names, shards = rt.sharding.spmm_axes(axis)
        f = jax.jit(lambda x, y: rt.matmul_sharded(x, y, axis=axis, plan=plan))
        got = np.asarray(f(a, b))
        shapes = kernel_result_shapes(f.lower(a, b).compile().as_text())
        local = (m // shards, n) if axis == "M" else (m, n // shards)
        work = shard_plan(plan, shards, axis=axis).shard_work()
        total = plan.total_work()
        work_ok = (int(work.sum()) == total if axis == "M"
                   else all(int(w) == total for w in work))
        same = bool(np.array_equal(got, want))
        print(f"sharded-spmm axis={axis} mesh={names}x{shards} "
              f"bitwise_equal_one_chip={same} kernel_result_shapes={shapes} "
              f"shard_work={[int(w) for w in work]} plan_total_work={total}",
              flush=True)
        check(same, f"sharded-spmm {axis}: differs from one chip")
        check(shapes and all(s == local for s in shapes),
              f"sharded-spmm {axis}: kernel ran on {shapes}, not per-shard {local}")
        check(work_ok, f"sharded-spmm {axis}: shard work {work} vs {total}")


# -- entry -----------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.mesh import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    devices = jax.devices()
    platform = devices[0].platform
    print(f"device platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)}", flush=True)
    check(platform == "tpu", f"device: found platform {platform!r}, not a TPU")
    check(len(devices) >= args.chips,
          f"device: {len(devices)} chip(s), --chips {args.chips} needs more")

    t0 = time.monotonic()
    if args.chips == 4:
        phases = [
            ("training-4chip", lambda: _train("qwen3-4b", 4, 1024, 3)),
            ("sharded-spmm", lambda: phase_sharded_spmm(args.seed)),
        ]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(args.seed)),
            ("serving", lambda: phase_serving(args.seed)),
            ("training", lambda: _train("mamba2-780m", 2, 2048, 4)),
        ]
    for name, phase in phases:
        t = time.monotonic()
        phase()
        # the phase's arrays go before the next one allocates, including
        # those held through jit caches (a runtime's plan cache, as a static
        # argument, holds the weights it planned)
        jax.clear_caches()
        gc.collect()
        print(f"phase {name} ok in {time.monotonic() - t:.1f}s", flush=True)
    print(f"all phases ok in {time.monotonic() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
