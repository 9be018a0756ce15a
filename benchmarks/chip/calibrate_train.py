#!/usr/bin/env python3
"""Readings that a training cell's correctness limits are set from.

    python3 benchmarks/chip/calibrate_train.py \\
        --workload mamba2-780m.train.2x2048 --seeds 101,102,103 \\
        [--control] [--faults] [--witness] [--out chiprun_out/calibrate]

For each seed, in one process: the program built and driven through its
warm-up steps and the first window step by the run's own functions, then
the plain reference over the same batches, and the numbers a run compares,
with the directional gap of every leaf's first gradient.  With
``--control`` also the reference computed at the control's precision
(``precision="chip"``) put in the program's place; with ``--faults`` the
program fed half of each batch (the mean taken over the rest), each
parameter leaf in turn scaled by 1.01 after the warm-up (read from the same
run), and a state left unchanged (read from the reference: every step's
loss at the seed's weights; the first gradient and the change read 1 by
their measure); with ``--witness`` the reference with bfloat16 activations
(``precision="bf16"``), rounding alone at the program's precision.  Prints one JSON line per seed;
``--out`` also keeps every leaf's readings, one file per seed.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _scaled_change_norms(prog, seed):
    """Each parameter leaf's change norm with that leaf scaled by 1.01."""
    import jax
    import jax.numpy as jnp
    from harness import train
    from repro.launch.mesh import init_sharded_params

    p0 = init_sharded_params(prog.cfg, prog.policy, seed)
    norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            (1.01 * x.astype(jnp.float32)).astype(x.dtype).astype(jnp.float32)
            - y.astype(jnp.float32)))), a, b))
    out = train._named(norms(prog.params, p0))
    del p0
    return out


def program_run(cell, seed, devices, hook=None, scaled=False):
    """The program's readings in the reference's terms, taken as a run
    takes them (``train.start``, the first window step, then
    ``train.program_readings``); with ``scaled`` also each leaf's change
    norm with that leaf scaled by 1.01 after the warm-up."""
    from harness import train

    tr = cell.traffic
    prog, stream, got = train.start(cell, seed, devices, hook)
    scaled_norms = _scaled_change_norms(prog, seed) if scaled else None
    first_loss = float(prog.step(stream.batch(tr["warm_steps"]))["loss"])
    out = train.program_readings(prog, got, first_loss, tr["optimizer"]["beta1"])
    del prog
    gc.collect()
    if scaled:
        out["scaled_change_norms"] = scaled_norms
    return out


def _half(prog):
    """Every step fed the first half of its rows."""
    whole = prog.feed
    prog.feed = lambda batch: whole({k: v[: len(v) // 2] for k, v in batch.items()})


def seed_row(cell, seed, devices, control, faults, witness=False):
    from harness import reference, train
    from harness import traffic as gen

    tr = cell.traffic
    ref_mod = reference.load_module(cell.config["reference"]["module"])
    run_ = program_run(cell, seed, devices, scaled=faults)
    t = time.monotonic()
    ref = train.reference_readings(cell, seed)
    row = {"seed": seed, "reference_s": time.monotonic() - t}
    moved = train.reference_moves(ref)
    raw = {}

    def read(name, got):
        row[name] = train.readings(got, ref)
        raw[name] = {k: v for k, v in got.items() if k != "grads"}
        raw[name]["misalignment"] = train.misalignment(got["grads"], ref["grads"], moved)

    read("program", run_)
    if control:
        t = time.monotonic()
        read("control", train.reference_readings(cell, seed, precision="chip"))
        row["control_s"] = time.monotonic() - t
    if witness:
        read("witness_bf16", train.reference_readings(cell, seed, precision="bf16"))
    if faults:
        # a state left unchanged: every step's loss at the seed's weights,
        # no gradient in the first moment, no change
        tr_ref = ref_mod.Trainer(cell.config, tr["optimizer"])
        W0 = ref_mod.make_weights(ref_mod.param_tree(cell.config), seed)
        stream = gen.TrainStream(tr, seed)
        losses = [tr_ref.loss(W0, stream.batch(i)) for i in range(tr["warm_steps"] + 1)]
        del W0, tr_ref
        zero = np.float32(0)
        read("state_unchanged", {
            "losses": losses, "grads": dict.fromkeys(ref["grads"], zero),
            "grad_norms": dict.fromkeys(ref["grad_norms"], 0.0),
            "change_norms": dict.fromkeys(ref["change_norms"], 0.0)})
        read("half_batch", program_run(cell, seed, devices, hook=_half))
        scaled = run_["scaled_change_norms"]
        want = {n: ref["change_norms"][n] for n in moved}
        row["leaf_scaled"] = {
            n: train.gap(dict({m: run_["change_norms"][m] for m in moved},
                              **{n: scaled[n]}), want)
            for n in moved}
    raw["reference"] = {k: v for k, v in ref.items() if k != "grads"}
    for name in ("program", "control", "witness_bf16", "state_unchanged", "half_batch"):
        if name in row:
            checks = train.checks_of(cell, row[name])
            row[name] = dict(row[name], correct=reference.correct(checks))
    return row, raw


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from harness import bench

    cell = bench.load_cell(args.workload)
    bench.prepare_jax()
    devices = bench.require_chips(cell.chips)
    out = pathlib.Path(args.out) if args.out else None
    if out is not None:
        out.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        row, raw = seed_row(cell, seed, devices, args.control, args.faults, args.witness)
        row["seconds"] = time.monotonic() - t
        print(json.dumps(row), flush=True)
        if out is not None:
            (out / f"{seed}.json").write_text(json.dumps(dict(row, raw=raw)))
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
