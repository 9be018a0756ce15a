"""Training cells: whole steps of the program's jitted train step, back to
back, for the window.

Set-up builds one object, :class:`Program`: the step as ``launch/train.py``
builds it (``make_train_step`` with the non-finite guard, jitted with the
parameters and the optimizer state donated), the parameters made on the
device from the seed and a zero AdamW state.  It then drives that object
through the mix's ``warm_steps`` steps, by the call and the feed the window
uses, on batches of the seed's stream: the first builds (or loads from the
compile cache) the step program, and the readings the check compares are
taken between them (the first gradient from the first moment after step
1, fetched to the host; each parameter's change after the last).  The
window then runs whole steps for its seconds, each on a new batch made on
the host and placed on the chip, its loss fetched as the launcher fetches
it.  Once the window has
closed and the memory peak is read, the program's arrays are freed and the
plain reference replays the same batches from the seed's weights.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from harness import bench, flops, reference, trace as trace_mod
from harness import traffic as gen

#: the numbers a training cell compares, each against its limit
NUMBERS = ("loss_gap", "first_grad_gap", "change_gap", "grad_dir_median")


@dataclasses.dataclass
class TrainRun:
    """What one training run hands the per-layer metric readers."""

    peak: dict  # one chip's
    window_s: float
    ops: float  # model operations of the window's steps (flops.train_step_ops)
    compiles: int  # executables built inside the window
    head: dict  # the LM head's k, n, tokens per step and nonzero fraction
    chips: int = 1  # the chips the step runs on, each with its share of the rows
    trace: "trace_mod.Reduction | None" = None
    kernel_pattern: str = ""
    kind: str = "train"


class Program:
    """The program's train step with its state, built once."""

    def __init__(self, cell, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro import runtime as rtm
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import init_sharded_params, make_local_mesh
        from repro.optim.adamw import init_opt_state
        from repro.parallel.sharding import ShardingPolicy

        tr = cell.traffic
        self.cfg = ModelConfig(**cell.config["program"])
        self.mesh = make_local_mesh(data=True, devices=devices)
        self.policy = ShardingPolicy(mesh=self.mesh)
        self.rt = rtm.Runtime(backend=tr["backend"], sharding=self.policy)
        self.rt.kernel.check_platform()
        self.seed = seed
        self.batch_sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        with self.use():
            self.params = init_sharded_params(self.cfg, self.policy, seed)
            self.opt = init_opt_state(self.params)
            self.raw_step, self.step_fn = make_step(self.cfg, tr)
        self.poison = jnp.zeros((), jnp.int32)
        self._change = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))

    def use(self):
        import contextlib

        from repro import runtime as rtm

        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(rtm.use(self.rt))
        return stack

    def feed(self, batch: dict) -> dict:
        """A host batch placed on the chips."""
        import jax

        return {k: jax.device_put(v, self.batch_sharding) for k, v in batch.items()}

    def step(self, batch: dict) -> dict:
        """One whole step; returns its loss, guard flag and gradient norm,
        fetched to the host."""
        import jax

        with self.use():
            self.params, self.opt, m = self.step_fn(self.params, self.opt,
                                                    self.feed(batch), poison=self.poison)
        return jax.device_get({k: m[k] for k in ("loss", "nonfinite", "grad_norm")})

    def first_moment(self) -> dict:
        """Every leaf of AdamW's first moment, float32 on the host."""
        import jax

        return _named(jax.device_get(self.opt.m), np.asarray)

    def change_norms(self) -> dict:
        """Norm of every parameter leaf's change since the seed's weights
        (made again by the program's own initializer, then freed)."""
        from repro.launch.mesh import init_sharded_params

        with self.use():
            p0 = init_sharded_params(self.cfg, self.policy, self.seed)
        out = _named(self._change(self.params, p0))
        del p0
        return out

    def head_info(self, tokens_per_step: int) -> dict:
        head = self.params["lm_head"]
        k, n = int(head.shape[0]), int(head.shape[1])
        return {"k": k, "n": n, "m": tokens_per_step,
                "density": flops.nonzero_block_elems(head.T) / (k * n)}

    def free(self) -> None:
        self.params = self.opt = None
        self.rt.plan_cache.clear()


def make_step(cfg, tr: dict):
    """The train step as ``launch/train.py`` builds it, with the non-finite
    guard: ``(step, jitted step)``, the parameters and optimizer state
    donated.  Called under the program's mesh and runtime."""
    import jax
    from repro.optim.adamw import OptConfig
    from repro.train.step import make_train_step

    raw = make_train_step(cfg, OptConfig(**tr["optimizer"]),
                          microbatches=tr["microbatches"], guard_nonfinite=True)
    return raw, jax.jit(raw, donate_argnums=(0, 1))


def _named(tree, value=float) -> dict:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in p): value(v) for p, v in paths}


def _norm(x) -> float:
    return float(np.linalg.norm(np.ravel(x)))


def gap(got: dict, want: dict) -> float:
    """Worst leaf of ``|got - want|`` over the larger of ``want``'s norm of
    that leaf and of its median leaf."""
    med = float(np.median(list(want.values())))
    return max(abs(got[n] - w) / max(w, med) for n, w in want.items())


def loss_gap(got: list, want: list) -> float:
    """Worst step of the relative gap between losses."""
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


def as_reference(got: dict, beta1: float) -> dict:
    """The program's readings in the reference's terms: the first raw
    gradient of each leaf from AdamW's first moment after step 1 and the
    clip scale that step applied (``m = (1 - beta1) * scale * g``), with
    its norm."""
    f = (1 - beta1) * got["clip_scale"]
    grads = {n: (m / np.float32(f)).astype(np.float32) for n, m in got["first_moment"].items()}
    return {"losses": got["losses"], "change_norms": got["change_norms"], "grads": grads,
            "grad_norms": {n: _norm(g) for n, g in grads.items()}}


def misalignment(got: dict, want: dict, names) -> dict:
    """``1 - cos`` of the angle between each named leaf's first gradient and
    the reference's: where it points, as a gap of norms reads its length.
    Taken from the norms of the two and of their difference, which keeps
    its digits where the two nearly agree; a leaf with no gradient reads 1."""
    out = {}
    for n in names:
        a, b = _norm(got[n]), _norm(want[n])
        d = _norm(np.subtract(got[n], want[n], dtype=np.float32))
        out[n] = (d * d - (a - b) ** 2) / (2 * a * b) if a > 0 and b > 0 else 1.0
    return out


def readings(run: dict, ref: dict) -> dict:
    """The numbers compared, of a run (the program's, or the control's put
    in its place) against the reference, both in the reference's terms."""
    moved = reference_moves(ref)
    return {
        "loss_gap": loss_gap(run["losses"], ref["losses"]),
        "first_grad_gap": gap(run["grad_norms"], ref["grad_norms"]),
        "change_gap": gap({n: run["change_norms"][n] for n in moved},
                          {n: ref["change_norms"][n] for n in moved}),
        **dir_readings(misalignment(run["grads"], ref["grads"], moved)),
    }


def dir_readings(mis: dict) -> dict:
    """The median leaf of :func:`misalignment`, which is compared, and the
    worst, which is not: the worst is one of the SSD's small leaves (its
    B and C convolutions, ``in_c``, ``d_skip``), whose direction swings from
    seed to seed."""
    return {"grad_dir_median": float(np.median(list(mis.values()))),
            "grad_dir_worst": max(mis.values())}


def reference_moves(ref: dict) -> list:
    """Leaves whose first gradient in the reference is not nought to
    rounding: at least a thousandth of the median leaf's."""
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    return [n for n, v in g.items() if v >= 1e-3 * med]


def checks_of(cell, got: dict) -> dict:
    """Each compared number beside its limit (a number without one fails)."""
    return {n: reference._check(got[n], cell.limits.get(n), "at most") for n in NUMBERS}


def warm(prog: Program, stream, tr: dict) -> dict:
    """The mix's warm-up steps through the window's call and feed, and the
    program's readings taken between them."""
    losses, first, scale = [], None, None
    for i in range(tr["warm_steps"]):
        m = prog.step(stream.batch(i))
        losses.append(float(m["loss"]))
        if i == 0:
            first = prog.first_moment()
            scale = min(1.0, tr["optimizer"]["clip_norm"] / (float(m["grad_norm"]) + 1e-9))
    return {"losses": losses, "first_moment": first, "clip_scale": scale,
            "change_norms": prog.change_norms()}


def window(prog: Program, stream, seconds: float, tr: dict, counter, traced: bool):
    """Whole steps until ``seconds`` have passed; the last step is waited
    for (its loss is fetched).  Prints the steps' walls and the time the
    host spent in Python's garbage collector to standard error."""
    import jax

    start = tr["warm_steps"]
    steps, failed, first_loss = 0, 0, None
    walls, in_gc, gc_from = [], [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_from[0] = time.perf_counter()
        else:
            in_gc.append(time.perf_counter() - gc_from[0])

    gc.callbacks.append(on_gc)
    t0 = time.perf_counter()
    end = t0 + seconds
    trace_from = end - tr["trace_seconds"] if traced else None
    tracing, win_span = False, None
    counter.armed = True
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if traced and not tracing and now >= trace_from:
            bench.TRACE_DIR.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(bench.TRACE_DIR))
            win_span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            win_span.__enter__()
            tracing = True
        with bench.span("bench.batch", tracing):
            batch = stream.batch(start + steps)
        with bench.span("bench.step", tracing):
            m = prog.step(batch)
        walls.append(time.perf_counter() - now)
        if first_loss is None:
            first_loss = float(m["loss"])
        failed += int(m["nonfinite"])
        steps += 1
    window_s = time.perf_counter() - t0
    counter.armed = False
    gc.callbacks.remove(on_gc)
    print(f"window: {steps} steps, wall median {np.median(walls):.4f} s, slowest "
          f"{[round(w, 4) for w in sorted(walls)[-3:]]}; {len(in_gc)} garbage "
          f"collections, {sum(in_gc):.4f} s", file=sys.stderr)
    if tracing:
        win_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return dict(window_s=window_s, steps=steps, failed=failed, first_loss=first_loss,
                compiles=counter.count)


def start(cell, seed: int, devices, program_hook=None):
    """A run's set-up: the program built, ``program_hook`` (for the
    self-tests and the calibration's faults) given it to change, and driven
    through the warm-up steps.  ``(prog, stream, got)``."""
    prog = Program(cell, seed, devices)
    if program_hook is not None:
        program_hook(prog)
    stream = gen.TrainStream(cell.traffic, seed)
    return prog, stream, warm(prog, stream, cell.traffic)


def program_readings(prog: Program, got: dict, first_loss: float, beta1: float) -> dict:
    """Once the window has closed: the first window step's loss added, the
    program's arrays freed (the reference runs in what they held), and the
    readings in the reference's terms."""
    got["losses"].append(first_loss)
    prog.free()
    gc.collect()
    return as_reference(got, beta1)


def reference_readings(cell, seed: int, precision: str = "float32") -> dict:
    """The plain reference replaying the seed's warm-up batches and the
    first window step's from the seed's weights; ``precision="chip"`` is
    the control."""
    tr = cell.traffic
    stream = gen.TrainStream(tr, seed)
    batches = [stream.batch(i) for i in range(tr["warm_steps"] + 1)]
    return reference.load_module(cell.config["reference"]["module"]).train_readings(
        cell.config, tr["optimizer"], seed, batches, precision=precision)


def run(cell, seed: int, seconds: float, traced: bool, t0: float, devices,
        program_hook=None):
    """One run of a training cell: ``(result, checks)``.  ``program_hook``,
    for the self-tests, may change the built program before it runs."""
    tr, config = cell.traffic, cell.config
    counter = bench.CompileCounter().install()
    prog, stream, got = start(cell, seed, devices, program_hook)
    tokens_per_step = tr["batch"] * tr["seq"]
    head = prog.head_info(tokens_per_step)
    setup_s = time.monotonic() - t0

    rec = window(prog, stream, seconds, tr, counter, traced)
    counter.report()
    device = bench.device_record(devices)
    tokens = rec["steps"] * tokens_per_step
    ops = rec["steps"] * flops.train_step_ops(
        config["program"], tr["batch"], tr["seq"],
        reference.load_module(config["reference"]["module"]))

    metrics = {}
    if traced:
        red = trace_mod.reduce(trace_mod.latest_xplane(bench.TRACE_DIR),
                               keep_stats=config["kernels"]["head"])
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        run_ = TrainRun(peak=bench.load_peaks(device["kind"]), window_s=rec["window_s"],
                        ops=ops, compiles=rec["compiles"], head=head, chips=len(devices),
                        trace=red,
                        kernel_pattern=config["kernels"]["head"])
        metrics = bench.read_per_layer(cell, run_)
        breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.top_idle()}
    else:
        values = {"setup_s": setup_s, "train_tokens_per_s": tokens / rec["window_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    got = program_readings(prog, got, rec["first_loss"], tr["optimizer"]["beta1"])
    del prog
    checks = checks_of(cell, readings(got, reference_readings(cell, seed)))
    result = {
        "correct": reference.correct(checks),
        "attempted": rec["steps"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = breakdown
    return result, {k: {kk: v for kk, v in c.items() if kk != "ok"}
                    for k, c in checks.items()}
