#!/usr/bin/env python3
"""Readings that a serving cell's correctness limit is set from.

    python3 benchmarks/chip/calibrate.py --workload qwen3-4b.serve.saturated \
        --seeds 101,102,103 --seconds 20 [--control] [--faults]

For each seed, in one process: a short window at the cell's own traffic,
then the widest logit gap of a seeded sample of finished requests against
the plain reference, as a run computes it.  With ``--control`` also the
reference computed in float8 put in the program's place; with ``--faults``
a token altered where it is produced (each sampled request's first token).
Prints one JSON line per seed, saying whether the program, the control and
the fault each come out ``correct`` through the comparison a run makes.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def serve_seed(cell, seed, seconds, devices, control, faults, counter):
    import numpy as np
    from harness import reference, serve
    from harness import traffic as gen

    tr, program = cell.traffic, cell.config["program"]
    eng, _ = serve.build(cell, seed, devices)
    serve.warm(eng, tr, program["vocab_size"])
    reqs = gen.serve_requests(tr, seed, seconds, program["vocab_size"])
    _, rids = serve.window(eng, reqs, seconds, tr, counter, False)
    serve.drain(eng, rids, tr["drain_seconds"])
    done = [eng._requests[rids[r.index]] for r in reqs]
    sample = reference.sample_requests([r for r in done if r.ok], seed,
                                       tr["check"]["sample_requests"])
    seqs = [(np.asarray(r.prompt), np.asarray(r.tokens, np.int32), r.max_new)
            for r in sample]
    eng.params = eng.caches = None
    eng.rt.plan_cache.clear()
    del eng, done, sample
    gc.collect()
    ref = reference.load_module(cell.config["reference"]["module"])
    got = ref.served_gaps(cell.config, seed, [(p, t) for p, t, _ in seqs],
                          control=control)
    row = {"seed": seed, "requests": len(reqs), **got}
    runs = {"program": reference.served_checks(cell, got, seqs)}
    if control:
        runs["control"] = reference.served_checks(cell, got, seqs,
                                                  "control_widest_gap")
    if faults:  # a token altered where it is produced: each first token
        vocab = program["vocab_size"]
        bad = [(p, np.concatenate([[(t[0] + 1) % vocab], t[1:]]).astype(np.int32), n)
               for p, t, n in seqs]
        runs["token_altered"] = reference.check_served(cell, seed, bad)
    for name, checks in runs.items():
        row[name] = {"correct": reference.correct(checks),
                     **{k: c["value"] for k, c in checks.items()}}
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from harness import bench

    cell = bench.load_cell(args.workload)
    bench.prepare_jax()
    devices = bench.require_chips(cell.chips)
    counter = bench.CompileCounter().install()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        row = serve_seed(cell, seed, args.seconds, devices, args.control,
                         args.faults, counter)
        row["seconds"] = time.monotonic() - t
        print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
