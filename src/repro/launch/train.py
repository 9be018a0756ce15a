"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
        --steps 20 --ckpt-dir /tmp/ckpt

Without ``--smoke`` this runs the config at its published widths on a
``data`` mesh over the devices present (batch parallel plus FSDP); ``--smoke``
runs the reduced config (CPU) through the identical code path: mesh,
sharded params, checkpointing, preemption guard, straggler deadline,
TensorDash sparsity projection.  The step donates ``params`` and the
optimizer state, so each step updates them in place.

Resilience: the step is non-finite-guarded (``make_train_step(
guard_nonfinite=True)``) — a NaN/Inf loss or gradient skips the update,
backs off exponentially, and after ``--max-faults`` *consecutive* faulted
steps checkpoints-before-abort (exit code 3); a step past
``--step-deadline`` checkpoints and exits with code 4.  ``--inject-faults`` replays
a seeded :class:`repro.resilience.FaultPlan` (``nan_loss@3;step_stall@5:
secs=1`` ...) through the exact production loop, and every degradation —
skip-step, straggler abort, preemption save, corrupt-checkpoint skip — is
surfaced in the :class:`repro.resilience.ResilienceLog` summary.
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime as rtm
from repro.checkpoint.manager import PreemptionGuard, restore_latest, save
from repro.resilience import FaultPlan, ResilienceLog, capture_warnings
from repro.resilience import faults as rfaults
from repro.resilience import log as rlog
from repro.configs import get_config, reduce_config
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import (
    enable_compile_cache, init_sharded_params, make_local_mesh,
)
from repro.optim.adamw import OptConfig, init_opt_state
from repro.parallel.sharding import ShardingPolicy
from repro.train.step import make_train_step

_DST_INT_KEYS = {"update_every", "begin", "end", "t_end", "min_size"}
_DST_FLOAT_KEYS = {"target", "alpha"}


def parse_dynamic_sparsity(spec: str) -> dict:
    """``target=0.9,update_every=100`` -> DynamicSparsityConfig kwargs."""
    kw: dict = {}
    for item in filter(None, (s.strip() for s in spec.split(","))):
        key, sep, val = item.partition("=")
        key = key.strip().replace("-", "_")
        if not sep:
            raise argparse.ArgumentTypeError(
                f"--dynamic-sparsity item {item!r} is not key=value"
            )
        if key in _DST_INT_KEYS:
            kw[key] = int(val)
        elif key in _DST_FLOAT_KEYS:
            kw[key] = float(val)
        elif key == "exclude":
            kw[key] = tuple(filter(None, val.split("+")))
        else:
            raise argparse.ArgumentTypeError(
                f"--dynamic-sparsity key {key!r} unknown (ints: "
                f"{sorted(_DST_INT_KEYS)}, floats: {sorted(_DST_FLOAT_KEYS)}, "
                "exclude=tok+tok)"
            )
    return kw


@dataclasses.dataclass
class TrainRun:
    """What :func:`main` ran: the host copy of every executed step's
    metrics (plus its wall ``seconds``), and ``lower()``, which lowers the
    step program again as its last step ran (same arguments, mesh and
    runtime) without running it."""

    history: list
    lower: Callable[[], Any]


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--step-deadline", type=float, default=300.0,
                    help="straggler mitigation: abort+checkpoint if a step "
                         "exceeds this (the first executed step is exempt: "
                         "it pays trace+compile)")
    ap.add_argument("--backend", default="dense", choices=rtm.available_backends(),
                    help="kernel backend for the TensorDash sparse paths")
    ap.add_argument("--sparsity-taps", action="store_true",
                    help="record per-layer A/G densities + modeled TensorDash "
                         "speedup every step (paper Fig. 14 live view)")
    ap.add_argument("--dynamic-sparsity", type=parse_dynamic_sparsity,
                    default=None, metavar="KVS",
                    help="RigL dynamic sparse training, e.g. "
                         "'target=0.9,update_every=100' (keys = "
                         "repro.sparse_train.DynamicSparsityConfig fields; "
                         "ramp end defaults to --steps)")
    ap.add_argument("--bm", type=int, default=None, help="block rows (sparse kernels)")
    ap.add_argument("--bk", type=int, default=None, help="contraction block size")
    ap.add_argument("--bn", type=int, default=None, help="output block size")
    ap.add_argument("--geometry", default="explicit", choices=rtm.GEOMETRIES,
                    help="'auto' resolves tile geometry / grid family per "
                         "call site from the TuningDB (python -m repro.tune)")
    ap.add_argument("--inject-faults", default="", metavar="SPEC",
                    help="seeded fault replay, e.g. 'nan_loss@3;step_stall@5:"
                         "secs=1' (repro.resilience.FaultPlan grammar)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--max-faults", type=int, default=3,
                    help="consecutive non-finite steps before checkpoint+abort")
    ap.add_argument("--fault-backoff", type=float, default=0.5,
                    help="base seconds for exponential backoff after a "
                         "skipped (non-finite) step")
    ap.add_argument("--no-nonfinite-guard", action="store_true",
                    help="disable the in-graph skip-step guard on non-finite "
                         "loss/grads")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    mesh = make_local_mesh(data=True)
    cfg = dataclasses.replace(cfg, remat=not args.smoke)
    geom = {k: v for k, v in (("bm", args.bm), ("bk", args.bk), ("bn", args.bn)) if v}
    if args.smoke and not geom and (
        args.backend != "dense" or args.dynamic_sparsity is not None
    ):
        # MXU-sized blocks don't divide smoke shapes (and would clamp a
        # dynamic-sparsity mask to one block per weight — no granularity)
        geom = {"bm": 8, "bk": 16, "bn": 16}
    policy = ShardingPolicy(mesh=mesh)
    rt = rtm.Runtime(backend=args.backend, sharding=policy,
                     geometry=args.geometry, **geom)
    rt.kernel.check_platform()  # fail fast (e.g. pallas on CPU) vs silent dense fallback

    log = ResilienceLog()
    fp = FaultPlan.parse(args.inject_faults, seed=args.fault_seed)
    guard_nonfinite = not args.no_nonfinite_guard

    with mesh, rtm.use(rt), rlog.use_log(log), rfaults.inject(fp), \
            capture_warnings(log):
        params = init_sharded_params(cfg, policy)
        opt = init_opt_state(params)
        data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
        ocfg = OptConfig(total_steps=max(args.steps, 100))
        ctrl = masks = None
        if args.dynamic_sparsity is not None:
            from repro.sparse_train import (
                DynamicSparsityConfig, DynamicSparsityController,
            )

            dkw = dict(args.dynamic_sparsity)
            dkw.setdefault("end", args.steps)
            ctrl = DynamicSparsityController(DynamicSparsityConfig(**dkw), params)
            masks = ctrl.masks()
            print(
                f"dynamic sparsity: {len(ctrl.units)} weight(s), "
                f"target {ctrl.cfg.target:.0%} by step {ctrl.cfg.end}, "
                f"refresh every {ctrl.cfg.update_every}"
            )
        step_fn = jax.jit(make_train_step(
            cfg, ocfg, microbatches=args.microbatches,
            sparsity_taps=args.sparsity_taps, dynamic_sparsity=ctrl,
            guard_nonfinite=guard_nonfinite,
        ), donate_argnums=(0, 1))
        guard = PreemptionGuard()

        start = 0
        if args.ckpt_dir:
            s, state = restore_latest(
                args.ckpt_dir, {"params": params, "opt": opt}
            )
            if s is not None:
                params, opt, start = state["params"], state["opt"], s
                print(f"resumed at step {s}")

        consecutive_faults = 0
        history: list = []
        step_args: tuple = ()
        kw: dict = {}

        def lower():  # reads the last step's step_args / kw when called
            with mesh, rtm.use(rt):
                return step_fn.lower(*step_args, **kw)

        for i in range(start, args.steps):
            for _ in fp.fires("preempt", i):
                signal.raise_signal(signal.SIGTERM)
            t0 = time.time()
            rfaults.stall(fp, "step_stall", i)
            kw = {}
            if guard_nonfinite:
                kw["poison"] = jnp.int32(rfaults.train_poison(fp, i))
            step_args = (params, opt, data.batch_at(i))
            if ctrl is not None:
                step_args += (masks,)
            params, opt, m = step_fn(*step_args, **kw)
            step_args = (params, opt) + step_args[2:]
            m = jax.device_get(m)
            dt = time.time() - t0
            history.append({"step": i, "seconds": dt, **{
                k: float(v) for k, v in m.items() if np.ndim(v) == 0}})
            if guard_nonfinite and int(m.get("nonfinite", 0)):
                consecutive_faults += 1
                log.record("nonfinite", "train.step", "skip-step",
                           step=i, consecutive=consecutive_faults)
                print(f"step {i}: non-finite loss/grads — update skipped "
                      f"({consecutive_faults}/{args.max_faults} consecutive)")
                if consecutive_faults >= args.max_faults:
                    if args.ckpt_dir:
                        save(args.ckpt_dir, i + 1,
                             {"params": params, "opt": opt})
                    log.record("nonfinite", "train.loop", "checkpoint-abort",
                               step=i, consecutive=consecutive_faults)
                    print(f"{consecutive_faults} consecutive non-finite "
                          "steps: checkpointed, aborting")
                    print(log.summary())
                    sys.exit(3)
                time.sleep(min(
                    args.fault_backoff * 2 ** (consecutive_faults - 1), 30.0
                ))
            else:
                consecutive_faults = 0
            if ctrl is not None and ctrl.should_update(i):
                rep = ctrl.update(i, m["dst_w_scores"], m["dst_g_scores"])
                masks = ctrl.masks()
                print(
                    f"dst refresh step {rep['step']:5d} "
                    f"sparsity {rep['sparsity']:.3f} "
                    f"(target {rep['target_sparsity']:.3f}) "
                    f"pruned {rep['pruned']} regrown {rep['regrown']} "
                    f"plan-edit {rep['edit_ms']:.2f}ms"
                )
            # the first executed step pays trace+compile; a deadline sized
            # for steady-state steps must not count that against it
            if dt > args.step_deadline and i != start:
                print(f"step {i} exceeded deadline ({dt:.0f}s): checkpoint + abort")
                log.record("deadline", "train.step", "checkpoint-abort",
                           step=i, seconds=round(dt, 3))
                if args.ckpt_dir:
                    save(args.ckpt_dir, i + 1, {"params": params, "opt": opt})
                print(log.summary())
                sys.exit(4)
            if (i + 1) % 5 == 0 or i == start:
                line = f"step {i+1:5d} loss {float(m['loss']):.4f} gnorm {float(m['grad_norm']):.2f} {dt:.2f}s"
                if ctrl is not None:
                    line += f" Wdens={float(m['dst_density']):.2f}"
                if args.sparsity_taps:
                    from repro.train.step import modeled_speedup

                    sim = modeled_speedup(m, cfg, max_t=64, sample_groups=1)
                    line += (
                        f" A={float(np.mean(m['A_density'])):.2f}"
                        f" G={float(np.mean(m['G_density'])):.2f}"
                        f" ideal={float(m['modeled_speedup']):.2f}x"
                        f" modeled={sim['overall']:.2f}x"
                    )
                print(line)
            if args.ckpt_dir and ((i + 1) % args.ckpt_every == 0 or guard.should_save):
                save(args.ckpt_dir, i + 1, {"params": params, "opt": opt})
                if guard.should_save:
                    log.record("preempt", "train.loop", "checkpoint-exit",
                               step=i)
                    print("preemption: saved, exiting")
                    print(log.summary())
                    return TrainRun(history, lower)
    # per-device balance report: how evenly each cached plan's ragged-grid
    # work would deal across the policy's row-parallel shards
    n_shards = policy.spmm_axes("M")[1]
    for ps in rt.plan_cache.plan_stats(shards=n_shards):
        line = (f"plan key={ps['key']!r} side={ps['side']} "
                f"total_work={ps['total_work']}/{ps['blocks']} blocks "
                f"skipped={ps['skipped_fraction']:.0%}")
        if "imbalance" in ps:
            line += f" imbalance={ps['imbalance']:.2f}x over {n_shards} devices"
        print(line)
    if len(log):
        print(log.summary())
    print("done")
    return TrainRun(history, lower)


if __name__ == "__main__":
    main()
