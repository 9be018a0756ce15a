import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with no device allocation (ShapeDtypeStruct inputs).

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k --mesh pod

Results (memory analysis, cost analysis, roofline terms, collective
breakdown) are cached incrementally in ``results/dryrun.json``.

NOTE: the XLA_FLAGS line above MUST run before any other import — jax locks
the device count at first init.  Everything below the flag is ordinary code.
"""
import argparse
import json
import time
import traceback

import jax

from repro import runtime as rtm
from repro.configs import ALL_ARCHS, SHAPES, cells, get_config, input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes, roofline_terms
from repro.models import model as M
from repro.models.common import Spec, abstract_params
from repro.optim.adamw import OptConfig, OptState, init_opt_state
from repro.parallel.sharding import (
    ShardingPolicy,
    batch_pspecs,
    cache_pspecs,
    param_pspecs,
)
from repro.train.step import make_train_step

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun.json")


def _with_shardings(abstract, pspecs, mesh):
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, p)),
        abstract,
        pspecs,
    )


def _layer_period(cfg) -> int:
    """Smallest homogeneous group of scanned layers."""
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.local_global_alternate:
        return 2
    return 1


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *, extra_cfg=None, extrapolate: bool = True):
    """Lower + compile one cell.

    XLA's ``cost_analysis`` counts while-loop bodies once, so scanned layer
    stacks would be undercounted; fully unrolling 60-90 layer models is
    compile-time-prohibitive on one CPU core.  Since scanned layers are
    homogeneous by construction, exact counts come from THREE compiles:

      1. the production (scan) program — proves the cell compiles on the
         mesh and provides the per-device memory analysis;
      2. a truncated model with ``first_dense + period`` layers, unrolled;
      3. one more layer-group, unrolled: (3) - (2) is the exact per-group
         FLOP/byte/collective count, extrapolated linearly to full depth.
    """
    import dataclasses

    cfg = get_config(arch)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    rec = _compile_once(cfg, arch, shape_name, multi_pod, full=True)
    # exact roofline via layer-group extrapolation (single-pod only: the
    # multi-pod pass proves the `pod` axis shards; §Roofline is per-pod)
    p = _layer_period(cfg)
    fd = cfg.first_dense_layers
    n1, n2 = fd + p, fd + 2 * p
    if extrapolate and cfg.num_layers > n2:
        ra = _compile_once(
            dataclasses.replace(cfg, num_layers=n1, unroll=True),
            arch, shape_name, multi_pod, full=False,
        )
        rb = _compile_once(
            dataclasses.replace(cfg, num_layers=n2, unroll=True),
            arch, shape_name, multi_pod, full=False,
        )
        groups_extra = (cfg.num_layers - n1) // p
        def extrap(key):
            a, b = ra["roofline"][key], rb["roofline"][key]
            return a + (b - a) * groups_extra

        flops = extrap("flops")
        hbm = extrap("hbm_bytes")
        coll = extrap("coll_bytes")
        chips = rec["chips"]
        from repro.launch.roofline import RooflineTerms

        terms = RooflineTerms(flops=flops, hbm_bytes=hbm, coll_bytes=coll, chips=chips)
        rec["roofline"] = terms.as_dict()
        rec["collectives"] = {
            k: ra["collectives"][k] + (rb["collectives"][k] - ra["collectives"][k]) * groups_extra
            for k in ra["collectives"]
        }
        rec["useful_flops_ratio"] = rec["model_flops"] / flops if flops else None
        rec["extrapolated_from"] = [n1, n2]
    else:
        # scan-counted program: while bodies count once -> flops/bytes are
        # lower bounds, and the useful ratio is meaningless; null it out
        rec["useful_flops_ratio"] = None
        rec["note"] = "scan-counted (compile-proof cell; no extrapolation)"
    return rec


def _compile_once(cfg, arch: str, shape_name: str, multi_pod: bool, *, full: bool):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    specs = M.param_specs(cfg)
    aparams = abstract_params(specs)
    ppspecs = param_pspecs(specs, mesh)
    aparams = _with_shardings(aparams, ppspecs, mesh)

    t0 = time.time()
    # the dry-run lowers on the dense backend (CPU cannot lower TPU Pallas);
    # the ambient Runtime supplies the mesh to every model entry point
    with mesh, rtm.use(rtm.Runtime(backend="dense", sharding=ShardingPolicy(mesh=mesh))):
        if shape.kind == "train":
            abatch = input_specs(cfg, shape)
            bps = batch_pspecs(cfg, shape, mesh)
            abatch = _with_shardings(abatch, bps, mesh)
            aopt = jax.eval_shape(init_opt_state, aparams)
            opt_ps = OptState(step=jax.sharding.PartitionSpec(), m=ppspecs, v=ppspecs)
            aopt = _with_shardings(aopt, opt_ps, mesh)
            step = make_train_step(cfg, OptConfig())  # mesh: ambient runtime
            from jax.sharding import NamedSharding

            out_sh = (
                jax.tree.map(lambda p: NamedSharding(mesh, p), ppspecs),
                OptState(
                    step=NamedSharding(mesh, jax.sharding.PartitionSpec()),
                    m=jax.tree.map(lambda p: NamedSharding(mesh, p), ppspecs),
                    v=jax.tree.map(lambda p: NamedSharding(mesh, p), ppspecs),
                ),
                None,
            )
            fn = jax.jit(step, out_shardings=out_sh)
            lowered = fn.lower(aparams, aopt, abatch)
        elif shape.kind == "prefill":
            abatch = input_specs(cfg, shape)
            bps = batch_pspecs(cfg, shape, mesh)
            abatch = _with_shardings(abatch, bps, mesh)
            fn = jax.jit(lambda p, b: M.prefill(p, cfg, b, mesh=mesh))
            lowered = fn.lower(aparams, abatch)
        else:  # decode
            full = input_specs(cfg, shape)
            acache = full.pop("cache")
            apos = full.pop("pos")
            cps = cache_pspecs(cfg, shape, mesh, acache)
            acache = _with_shardings(acache, cps, mesh)
            bps = batch_pspecs(cfg, shape, mesh)
            astep = _with_shardings(full, {k: bps[k] for k in full}, mesh)
            from jax.sharding import NamedSharding

            cache_out = jax.tree.map(lambda p: NamedSharding(mesh, p), cps)
            fn = jax.jit(
                lambda p, c, b, pos: M.decode_step(p, cfg, c, b, pos, mesh=mesh),
                out_shardings=(None, cache_out),
            )
            lowered = fn.lower(aparams, acache, astep, apos)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    terms = roofline_terms(compiled, chips)
    colls = collective_bytes(compiled.as_text())
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch  # one new token each
        model_flops = 2.0 * n_active * tokens

    # Buffer-based HBM traffic estimate: arguments read + outputs written +
    # temps written-and-read.  XLA-CPU's 'bytes accessed' counts every
    # unfused op's I/O and overstates TPU traffic (TPU fuses elementwise
    # chains); both are recorded, EXPERIMENTS.md reports the comparison.
    adj_bytes = None
    try:
        adj_bytes = (
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + 2 * mem.temp_size_in_bytes
        ) * chips
    except AttributeError:
        pass

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hbm_bytes_adj": adj_bytes,
        "memory_adj_s": (adj_bytes / (chips * 819e9)) if adj_bytes else None,
        "mem": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None)
            if hasattr(mem, "peak_memory_in_bytes")
            else None,
        },
        "roofline": terms.as_dict(),
        "collectives": {k: v * chips for k, v in colls.items()},
        "model_flops": model_flops,
        "params": n_params,
        "active_params": n_active,
        "useful_flops_ratio": model_flops / terms.flops if terms.flops else None,
        "ok": True,
    }
    return rec


def load_results(path: str) -> dict:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS))
    args = ap.parse_args()

    results = load_results(args.out)
    archs = ALL_ARCHS if args.arch is None else [args.arch]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        cfg = get_config(arch)
        shapes = cells(cfg) if args.shape is None else [args.shape]
        for shape_name in shapes:
            for mp in meshes:
                key = f"{arch}|{shape_name}|{'multipod' if mp else 'pod'}"
                if key in results and results[key].get("ok") and not args.force:
                    print(f"[skip] {key}")
                    continue
                print(f"[run ] {key}", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, mp, extrapolate=not mp)
                    r = rec["roofline"]
                    print(
                        f"   ok: compile={rec['compile_s']}s"
                        f" compute={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s"
                        f" coll={r['collective_s']:.4f}s dom={r['dominant']}"
                        f" useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}",
                        flush=True,
                    )
                except Exception as e:  # record failures: they are bugs
                    rec = {
                        "arch": arch,
                        "shape": shape_name,
                        "mesh": "2x16x16" if mp else "16x16",
                        "ok": False,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    print(f"   FAIL {type(e).__name__}: {e}", flush=True)
                results[key] = rec
                save_results(args.out, results)

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"done: {n_ok}/{len(results)} cells ok -> {args.out}")


if __name__ == "__main__":
    main()
