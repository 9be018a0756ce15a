"""Serving cells: an open loop of generated requests through the program's
``ServeEngine``, each request timed from the moment it was due.

Set-up makes the weights on the device from the seed, builds the engine and
warms every program the cell's traffic forms: the prefill of each prompt
length for every group size up to ``warm_groups`` (the engine prefills
same-length prompts admitted together as one group), the cache growth and
slot writes, and the decode chunk.  The window then submits each request
when it is due and steps the engine; after the window closes, the requests
due in it are drained, a sample of them is checked against the plain
reference, and the metrics are read.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from harness import bench, flops, reference, trace as trace_mod
from harness import traffic as gen


@dataclasses.dataclass
class ServeRun:
    """What one serving run hands the per-layer metric readers."""

    peak: dict
    window_s: float
    lags: list  # seconds each request was submitted after it was due
    quiet_step_walls: list  # wall of each eng.step() that admitted nothing
    chunk: int
    slots: int
    tokens: int  # output tokens produced in the window
    prefill_tokens: int  # of those, first tokens (from a prefill)
    steps: int  # decode steps run in the window
    ops: float  # model operations of the window's tokens
    compiles: int  # executables built inside the window
    head: dict  # the LM head's shape and nonzero elements
    ttft_s: list  # each request's first token less its due time
    tpot_s: list  # each finished request's time per output token
    trace: "trace_mod.Reduction | None" = None
    kernel_pattern: str = ""
    kind: str = "serve"


def build(cell, seed: int, devices):
    """The engine, with weights made on the device from ``seed``."""
    from repro import runtime as rtm
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import init_sharded_params, make_local_mesh
    from repro.parallel.sharding import ShardingPolicy
    from repro.serve.engine import ServeEngine

    tr = cell.traffic
    cfg = ModelConfig(**cell.config["program"])
    policy = ShardingPolicy(mesh=make_local_mesh(devices=devices))
    rt = rtm.Runtime(backend=tr["backend"], sharding=policy,
                     **tr.get("block", {}))
    rt.kernel.check_platform()
    params = init_sharded_params(cfg, policy, seed)
    eng = ServeEngine(params, cfg, slots=tr["slots"], max_len=tr["max_len"],
                      rt=rt, seed=seed, chunk=tr["chunk"])
    head = params["lm_head"]
    info = {"k": int(head.shape[0]), "n": int(head.shape[1]),
            "nonzero_elems": flops.nonzero_block_elems(head.T)}
    return eng, info


def warm(eng, tr: dict, vocab: int) -> None:
    """Build every program the traffic forms before the window opens."""
    rng = np.random.default_rng(0)
    lengths = tr["prompt_lengths"]
    for s in lengths:
        for g in range(1, tr["warm_groups"] + 1):
            for _ in range(g):
                eng.submit(rng.integers(0, vocab, s).astype(np.int32), max_new=1)
            eng.step()
    # every slot decodes: groups of at most warm_groups per prompt length
    per = -(-eng.sched.num_slots // len(lengths))
    if per > tr["warm_groups"]:
        raise ValueError("warm_groups is too small to fill every slot")
    for i in range(eng.sched.num_slots):
        s = lengths[i // per]
        eng.submit(rng.integers(0, vocab, s).astype(np.int32),
                   max_new=tr["chunk"] + 1)
    while eng.sched.has_work:
        eng.step()


def _request_ops(program: dict, prompt: int, first: int, last: int) -> float:
    if program["family"] in ("dense", "moe"):
        return flops.dense_request_ops(program, prompt, first, last)
    raise ValueError(f"no serving count for family {program['family']!r}")


def window(eng, reqs, seconds: float, tr: dict, counter, traced: bool):
    """Run the open loop for ``seconds``; returns the window's records and
    ``{request index: engine rid}``."""
    import jax

    program = eng_program(eng)
    base = eng.now() + 0.001
    end = base + seconds
    trace_from = end - tr["trace_seconds"] if traced else None
    tracing = False
    rids, live = {}, []
    lags, quiet = [], []
    tok0, steps0 = eng.tokens_out, eng.steps_run
    first_tokens, ops = 0, 0.0
    nxt = 0
    win_span = None
    counter.armed = True
    while True:
        now = eng.now()
        if now >= end:
            break
        if traced and not tracing and now >= trace_from:
            bench.TRACE_DIR.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(bench.TRACE_DIR))
            win_span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            win_span.__enter__()
            tracing = True
        with bench.span("bench.submit", tracing):
            while nxt < len(reqs) and base + reqs[nxt].arrival <= now:
                r = reqs[nxt]
                due = base + r.arrival
                rid = eng.submit(r.prompt, max_new=r.max_new, arrival=due)
                lags.append(eng.now() - due)
                rids[r.index] = rid
                live.append(eng._requests[rid])
                nxt += 1
        if not eng.sched.has_work:
            wait = base + reqs[nxt].arrival - eng.now() if nxt < len(reqs) else end - eng.now()
            with bench.span("bench.wait", tracing):
                time.sleep(min(max(wait, 0.0), 0.01))
            continue
        lens = [len(r.tokens) for r in live]
        pending = len(eng.sched.pending)
        t = time.perf_counter()
        with bench.span("bench.step", tracing):
            eng.step()
        wall = time.perf_counter() - t
        if pending == len(eng.sched.pending):
            quiet.append(wall)
        still = []
        for r, old in zip(live, lens):
            new = len(r.tokens)
            if new > old:
                ops += _request_ops(program, int(r.prompt.shape[0]), old, new)
                first_tokens += old == 0
            if not r.finished:
                still.append(r)
        live = still
    counter.armed = False
    window_s = eng.now() - base
    if tracing:
        win_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    rec = dict(window_s=window_s, lags=lags, quiet_step_walls=quiet,
               tokens=eng.tokens_out - tok0, prefill_tokens=first_tokens,
               steps=eng.steps_run - steps0, ops=ops, compiles=counter.count)
    # requests due in the window that the loop had not submitted yet
    while nxt < len(reqs):
        r = reqs[nxt]
        rids[r.index] = eng.submit(r.prompt, max_new=r.max_new,
                                   arrival=base + r.arrival)
        lags.append(eng.now() - (base + r.arrival))
        nxt += 1
    return rec, rids


def eng_program(eng) -> dict:
    """The engine's model configuration as the dict ``flops`` reads."""
    return dataclasses.asdict(eng.cfg) | {"head_dim": eng.cfg.resolved_head_dim}


def drain(eng, rids: dict, limit_s: float) -> float:
    """Step until every request due in the window is finished, at most
    ``limit_s`` seconds; returns the engine clock at the end."""
    stop = eng.now() + limit_s
    pending = [eng._requests[rid] for rid in rids.values()]
    while eng.now() < stop and not all(r.finished for r in pending):
        eng.step()
    return eng.now()


def run(cell, seed: int, seconds: float, traced: bool, t0: float, devices,
        engine_hook=None):
    """One run of a serving cell: ``(result, checks)``."""
    import jax

    tr, program = cell.traffic, cell.config["program"]
    counter = bench.CompileCounter().install()
    eng, head = build(cell, seed, devices)
    if engine_hook is not None:
        engine_hook(eng)
    warm(eng, tr, program["vocab_size"])
    reqs = gen.serve_requests(tr, seed, seconds, program["vocab_size"])
    jax.block_until_ready(eng.caches)
    setup_s = time.monotonic() - t0

    rec, rids = window(eng, reqs, seconds, tr, counter, traced)
    counter.report()
    t_close = drain(eng, rids, tr["drain_seconds"])
    done = [eng._requests[rids[r.index]] for r in reqs]
    failed = [r for r in done if not r.ok]
    ttft = [(r.t_first if r.t_first > 0 else t_close) - r.arrival for r in done]
    tpot = [(r.t_finish - r.t_first) / (len(r.tokens) - 1)
            for r in done if r.ok and len(r.tokens) > 1]
    device = bench.device_record(devices)

    metrics = {}
    if traced:
        peak = bench.load_peaks(device["kind"])
        red = trace_mod.reduce(trace_mod.latest_xplane(bench.TRACE_DIR),
                               keep_stats=cell.config["kernels"]["head"])
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        sr = ServeRun(peak=peak, head=head, chunk=tr["chunk"], slots=tr["slots"],
                      ttft_s=ttft, tpot_s=tpot, trace=red,
                      kernel_pattern=cell.config["kernels"]["head"], **rec)
        metrics = bench.read_per_layer(cell, sr)
        breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.top_idle()}
    else:
        values = {"setup_s": setup_s,
                  "serve_tokens_per_s": rec["tokens"] / rec["window_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    sample = reference.sample_requests(
        [r for r in done if r.ok], seed, tr["check"]["sample_requests"])
    seqs = [(np.asarray(r.prompt), np.asarray(r.tokens, np.int32), r.max_new)
            for r in sample]
    # the reference runs in what the engine held; the runtime outlives the
    # engine in the jit caches, and its plan cache holds the head
    eng.params = eng.caches = None
    eng.rt.plan_cache.clear()
    del eng, done, sample
    gc.collect()
    checks = reference.check_served(cell, seed, seqs)
    result = {
        "correct": reference.correct(checks),
        "attempted": len(reqs),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = breakdown
    return result, {k: {kk: v for kk, v in c.items() if kk != "ok"}
                    for k, c in checks.items()}
