"""Continuous-batching serving launcher: replay a request arrival stream
through the :class:`repro.serve.engine.ServeEngine` and report latency /
throughput.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --requests 16 --slots 8 --new 8 --backend interpret --rate 0

Without ``--smoke`` the config runs at its published widths, tensor
parallel on a ``model`` mesh over the devices present.  ``--rate``
requests/second shapes the arrival stream (0 = all requests
arrive at t=0, a pure throughput run); prompt lengths and decode budgets are
jittered per request so the engine's slot backfill actually exercises.  One
``repro.runtime.Runtime`` carries the whole execution policy (kernel
backend, block geometry, mesh, plan cache); the decode loop is one jitted
``lax.scan`` program whose trace count and plan-cache hit rates are printed
alongside the latency percentiles.

Resilience: ``--inject-faults`` replays a seeded
:class:`repro.resilience.FaultPlan` (``nan_logits@1:slot=0`` ...) through
the exact production serve loop; ``--ttl``/``--max-pending``/
``--work-budget`` exercise deadlines, bounded admission, and plan-aware
load shedding.  Finish-reason counts and the
:class:`repro.resilience.ResilienceLog` summary are printed with the
report; the replay exits non-zero when *no* request finishes cleanly.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro import runtime as rtm
from repro.configs import get_config, reduce_config
from repro.launch.mesh import (
    enable_compile_cache, init_sharded_params, make_local_mesh,
)
from repro.parallel.sharding import ShardingPolicy
from repro.resilience import FaultPlan, ResilienceLog, capture_warnings
from repro.resilience import faults as rfaults
from repro.resilience import log as rlog
from repro.serve import engine as serve_engine
from repro.serve.engine import QueueFull, ServeEngine


def _pct(xs, q):
    """Percentile, or ``None`` for an empty sample (an all-failed replay
    has no finished requests — report n/a, never a NaN latency)."""
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def _ms(x):
    return f"{x * 1e3:.0f}ms" if x is not None else "n/a"


def make_traffic(rng, vocab_size: int, *, requests: int, prompt_len: int,
                 new: int, rate: float):
    """``(prompts, budgets, arrivals)`` for one replay.  Lengths are
    jittered over ``[prompt_len // 2, prompt_len]`` and ``[new // 2, new]``
    so slots finish at different times and backfill runs; arrivals are
    Poisson at ``rate`` requests/second (all at 0 when ``rate <= 0``),
    relative to the start of the replay."""
    plens = rng.integers(max(prompt_len // 2, 1), prompt_len + 1, size=requests)
    budgets = rng.integers(max(new // 2, 1), new + 1, size=requests)
    prompts = [rng.integers(0, vocab_size, size=int(s)).astype(np.int32)
               for s in plens]
    arrivals = (np.zeros(requests) if rate <= 0
                else np.cumsum(rng.exponential(1.0 / rate, size=requests)))
    return prompts, budgets, arrivals


def replay(eng: ServeEngine, prompts, budgets, arrivals, *,
           ttl: float | None = None) -> float:
    """Submit each request at its arrival and step the engine until every
    request is done; returns the wall seconds.  Arrivals are scheduled on
    the engine clock, so latency percentiles measure from the modeled
    arrival — queueing delay (a request waiting out an in-flight decode
    chunk) is charged to the request, not hidden."""
    arrivals = np.asarray(arrivals) + eng.now()
    t_start = time.monotonic()
    submitted = 0
    while submitted < len(prompts) or eng.sched.has_work:
        now = eng.now()
        while submitted < len(prompts) and arrivals[submitted] <= now:
            try:
                eng.submit(prompts[submitted],
                           max_new=int(budgets[submitted]),
                           arrival=float(arrivals[submitted]), ttl=ttl)
                submitted += 1
            except QueueFull:
                break  # drain a chunk below, then retry this submit
        if not eng.sched.has_work:
            # idle before the next arrival: wait it out
            time.sleep(min(max(arrivals[submitted] - now, 0.0), 0.05))
            continue
        eng.step()
    return time.monotonic() - t_start


def finish_reasons(eng: ServeEngine) -> dict[str, int]:
    """Count of every submitted request's finish reason."""
    reasons: dict[str, int] = {}
    for r in eng._requests.values():
        key = r.finish_reason or "unfinished"
        reasons[key] = reasons.get(key, 0) + 1
    return reasons


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent batch slots (the packed decode batch)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps fused per jitted scan call")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrival rate, requests/sec (0 = all at t=0)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="dense", choices=rtm.available_backends())
    ap.add_argument("--block", type=int, nargs=3, metavar=("BM", "BK", "BN"),
                    default=None, help="block geometry override")
    ap.add_argument("--geometry", default="explicit", choices=rtm.GEOMETRIES,
                    help="'auto' resolves tile geometry / grid family per "
                         "call site from the TuningDB (python -m repro.tune)")
    ap.add_argument("--inject-faults", default="", metavar="SPEC",
                    help="seeded fault replay, e.g. 'nan_logits@1:slot=0' "
                         "(repro.resilience.FaultPlan grammar)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request deadline (seconds after submit)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bounded admission queue (QueueFull beyond this)")
    ap.add_argument("--work-budget", type=float, default=None,
                    help="plan-aware load shedding: max outstanding decode "
                         "work (cached-plan total_work units)")
    ap.add_argument("--no-watchdog", action="store_true",
                    help="disable the in-graph non-finite logits watchdog")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    geom = dict(zip(("bm", "bk", "bn"), args.block)) if args.block else {}
    policy = ShardingPolicy(mesh=make_local_mesh())
    rt = rtm.Runtime(backend=args.backend, sharding=policy,
                     geometry=args.geometry, **geom)
    rt.kernel.check_platform()  # fail fast (e.g. pallas on CPU)

    params = init_sharded_params(cfg, policy)
    prompts, budgets, arrivals = make_traffic(
        np.random.default_rng(args.seed), cfg.vocab_size,
        requests=args.requests, prompt_len=args.prompt_len, new=args.new,
        rate=args.rate,
    )

    log = ResilienceLog()
    fp = FaultPlan.parse(args.inject_faults, seed=args.fault_seed)

    max_len = args.max_len or (args.prompt_len + args.new)
    eng = ServeEngine(
        params, cfg, slots=args.slots, max_len=max_len, rt=rt,
        temperature=args.temperature, seed=args.seed, chunk=args.chunk,
        max_pending=args.max_pending, work_budget=args.work_budget,
        watchdog=not args.no_watchdog, fault_plan=fp if fp else None,
        log=log,
    )
    with rlog.use_log(log), rfaults.inject(fp), capture_warnings(log):
        dt = replay(eng, prompts, budgets, arrivals, ttl=args.ttl)

    reqs = list(eng._requests.values())
    ok = [r for r in reqs if r.ok]
    ttft = [r.t_first - r.arrival for r in reqs if r.t_first > 0.0]
    queue = [r.t_admit - r.arrival for r in reqs if r.t_admit > 0.0]
    e2e = [r.t_finish - r.arrival for r in ok]
    st = eng.stats()
    pc = st["plan_cache"]
    print(f"arch={cfg.name} backend={rt.backend} slots={args.slots} "
          f"chunk={args.chunk} requests={args.requests}")
    print(f"served {st['tokens_out']} tokens in {dt:.2f}s "
          f"({st['tokens_out']/dt:.1f} tok/s); decode program traced "
          f"{st['decode_traces']}x, prefill program traced "
          f"{st['prefill_traces']}x, {st['chunks_run']} chunks")
    print(f"admitted {st['admitted']} requests in {st['prefill_groups']} "
          f"prefill groups ({st['prefill_tokens']} prompt tokens); "
          f"queue wait p50={_ms(_pct(queue,50))} p95={_ms(_pct(queue,95))}")
    print(f"latency  ttft p50={_ms(_pct(ttft,50))} p95={_ms(_pct(ttft,95))}"
          f"   e2e p50={_ms(_pct(e2e,50))} p95={_ms(_pct(e2e,95))}")
    print("finish reasons: " + ", ".join(
        f"{k}={v}" for k, v in sorted(finish_reasons(eng).items())))
    print(f"plan cache: {pc['hits']} hits / {pc['misses']} misses / "
          f"{pc['traced']} traced-in-program")
    # per-plan skew report: total_work is the exact v3 ragged-grid step
    # count per output-column block — alongside the skipped fraction it
    # makes row-density skew (the thing v3's work queue absorbs and v2's
    # max(nnz) bound could not) observable in production traces
    n_shards = policy.spmm_axes("M")[1]
    for ps in rt.plan_cache.plan_stats(shards=n_shards):
        line = (f"  plan key={ps['key']!r} side={ps['side']} "
                f"shape={tuple(ps['shape'])} block={ps['block']} "
                f"total_work={ps['total_work']}/{ps['blocks']} blocks "
                f"skipped={ps['skipped_fraction']:.0%}")
        if "imbalance" in ps:
            # max/mean per-device ragged-grid steps under the serpentine deal
            line += f" imbalance={ps['imbalance']:.2f}x over {n_shards} devices"
        print(line)
    if len(log):
        print(log.summary())
    if not ok:
        print("ERROR: no request finished cleanly", file=sys.stderr)
        sys.exit(2)


if __name__ == "__main__":
    main()
