#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate at which the
backlog does not grow and 90% of requests meet both latency limits.

    python3 benchmarks/chip/sweep.py --workload qwen3-4b.serve.saturated \
        --seed 5 --seconds 51 --rates 0.5,2,3,4,5,6 [--then-seeds 4]

One process builds the cell's engine once and runs the cell's traffic at
each rate in turn (an open loop for ``--seconds``, then a drain).  The
lowest rate is taken as unloaded: the limits are ``--ttft-x`` times its
95th-percentile time to first token and ``--tpot-x`` times its
95th-percentile time per output token.  Prints one JSON line per rate and
the limits; the cell's rate is then set by hand to about 4/5 of the knee.
With ``--then-seeds k`` the traffic then runs at 4/5 of the knee on ``k``
more seeds, and the spread of each tail over them is printed (quartile
distance over the median, as ``statistics.quantiles`` gives them).
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ttft-x", type=float, default=3.0)
    ap.add_argument("--tpot-x", type=float, default=1.5)
    ap.add_argument("--then-seeds", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from harness import bench

    cell = bench.load_cell(args.workload)
    bench.prepare_jax()
    devices = bench.require_chips(cell.chips)
    import numpy as np
    from harness import serve
    from harness import traffic as gen

    tr, program = cell.traffic, cell.config["program"]
    counter = bench.CompileCounter().install()
    eng, _ = serve.build(cell, args.seed, devices)
    serve.warm(eng, tr, program["vocab_size"])
    print(json.dumps({"setup_s": time.monotonic() - T0}), flush=True)
    groups = []  # size of every prefill group admitted
    admit = eng._admit_group

    def counted(placements):
        groups.append(len(placements))
        return admit(placements)

    eng._admit_group = counted

    def window(rate, seed):
        mix = dict(tr, rate_per_s=rate)
        reqs = gen.serve_requests(mix, seed, args.seconds, program["vocab_size"])
        counter.count, counter.names, counter.seconds = 0, [], []
        groups.clear()
        rec, rids = serve.window(eng, reqs, args.seconds, mix, counter, False)
        backlog = len(eng.sched.pending)
        t_close = serve.drain(eng, rids, tr["drain_seconds"])
        done = [eng._requests[rids[r.index]] for r in reqs]
        ttft = np.asarray([(r.t_first if r.t_first > 0 else t_close) - r.arrival
                           for r in done])
        tpot = np.asarray([(r.t_finish - r.t_first) / max(len(r.tokens) - 1, 1)
                           if r.ok else np.inf for r in done])
        return {
            "rate": rate, "seed": seed, "requests": len(reqs),
            "backlog_at_close": backlog,
            "tokens_per_s": rec["tokens"] / rec["window_s"],
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "tpot_p50_ms": float(np.percentile(tpot, 50) * 1e3),
            "tpot_p95_ms": float(np.percentile(tpot, 95) * 1e3),
            "compiles": rec["compiles"],
            "slowest_build_s": max(counter.seconds, default=0.0),
            "largest_group": max(groups, default=0),
            "lag_p95_ms": float(np.percentile(rec["lags"], 95) * 1e3),
        }, ttft, tpot

    limits, rows = None, []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row, ttft, tpot = window(rate, args.seed + i)
        if limits is None:
            limits = {"ttft_s": args.ttft_x * float(np.percentile(ttft, 95)),
                      "tpot_s": args.tpot_x * float(np.percentile(tpot, 95))}
            print(json.dumps({"limits": limits}), flush=True)
        row["met_both"] = float(np.mean((ttft <= limits["ttft_s"])
                                        & (tpot <= limits["tpot_s"])))
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = max((r["rate"] for r in rows if r["met_both"] >= 0.9
                and r["backlog_at_close"] <= 1), default=None)
    print(json.dumps({"knee_rate": knee}), flush=True)
    if args.then_seeds and knee:
        rate = round(0.8 * knee, 2)
        runs = [window(rate, args.seed + 1000 + j)[0]
                for j in range(args.then_seeds)]
        for r in runs:
            print(json.dumps(r), flush=True)
        spread = {}
        for key in ("ttft_p95_ms", "tpot_p95_ms", "tokens_per_s"):
            v = [r[key] for r in runs]
            q1, q2, q3 = statistics.quantiles(v, n=4)
            spread[key] = (q3 - q1) / q2
        print(json.dumps({"rate": rate, "spread": spread}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
