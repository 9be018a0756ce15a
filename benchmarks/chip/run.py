#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload qwen3-4b.serve.saturated \
        --seed 7 --seconds 45 --trace 0

The cell is a workload of ``BENCHMARK.json``; its configuration, traffic,
per-layer metric readers, reference and limits are files under
``benchmarks/chip`` found by name.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, then
``breakdown`` when traced and ``checks`` last); the compared numbers and
their limits are also the last lines of standard error.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.
"""
import time

T0 = time.monotonic()  # set-up is timed from here, before any import

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from harness import bench

    cell = bench.load_cell(args.workload)
    bench.prepare_jax()
    try:
        devices = bench.require_chips(cell.chips)
    except bench.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    from harness import serve, train

    drivers = {"serve": serve, "train": train}
    if cell.traffic["kind"] not in drivers:
        raise SystemExit(f"unknown traffic kind {cell.traffic['kind']!r}")
    driver = drivers[cell.traffic["kind"]]
    if args.trace:
        import shutil

        shutil.rmtree(bench.TRACE_DIR, ignore_errors=True)
    result, checks = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                                T0, devices)
    bench.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
