#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e chip, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_check.py [--workload NAME]

For a serving cell: the decode chunk at the cell's slots, chunk and
``max_len``, and the prefill of the longest prompt at the largest group the
cell warms.  For a training cell: the train step at the cell's batch and
sequence, with its parameters and optimizer state donated, as the run
builds it.  Each is compiled by the TPU compiler for one chip of a
``v5e:2x2`` topology (which refuses what the chip would refuse) and its
``memory_analysis()`` and ``tpu_custom_call`` count are printed.  Exits 1
when a program holds no kernel or does not fit the chip's memory (16 GiB,
``hbm_bytes`` in ``harness/peaks.json``).  A compile that passes here is
not a chip run.
"""
import argparse
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
KIND = "TPU v5 lite"  # the described chip's device_kind in harness/peaks.json


def _report(name: str, compiled, hbm: float) -> bool:
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(json.dumps({
        "program": name, "tpu_custom_call": kernels,
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes, "total_bytes": total,
        "hbm_bytes": hbm, "fits": total < hbm}), flush=True)
    return kernels > 0 and total < hbm


def serve_programs(cell, device):
    import jax
    import jax.numpy as jnp
    from repro import runtime as rtm
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.models.common import abstract_params
    from repro.parallel.sharding import ShardingPolicy
    from repro.serve import engine

    tr = cell.traffic
    cfg = ModelConfig(**cell.config["program"])
    policy = ShardingPolicy(mesh=make_local_mesh(devices=[device]))
    rt = rtm.Runtime(backend=tr["backend"], sharding=policy)
    one = jax.sharding.SingleDeviceSharding(device)
    put = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    params = put(abstract_params(M.param_specs(cfg)))
    slots = tr["slots"]
    caches = put(M.abstract_cache(cfg, slots, tr["max_len"]))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=one)
    keys = jax.ShapeDtypeStruct((slots, 2), jnp.uint32, sharding=one)
    lowered = engine._decode_chunk.lower(
        params, caches, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32), keys, vec(jnp.int32), cfg=cfg, rt=rt, steps=tr["chunk"],
        temperature=0.0, eos_id=None, pad_id=0, watchdog=True)
    yield "decode_chunk", lowered.compile()
    g, s = tr["warm_groups"], max(tr["prompt_lengths"])
    tokens = jax.ShapeDtypeStruct((g, s), jnp.int32, sharding=one)

    def prefill(p, t):
        with rtm.use(rt):
            return M.prefill(p, cfg, {"tokens": t})

    yield f"prefill_{g}x{s}", jax.jit(prefill).lower(params, tokens).compile()


def train_programs(cell, device):
    import jax
    import jax.numpy as jnp
    from harness import train
    from repro import runtime as rtm
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.models.common import abstract_params
    from repro.optim.adamw import init_opt_state
    from repro.parallel.sharding import ShardingPolicy

    tr = cell.traffic
    cfg = ModelConfig(**cell.config["program"])
    mesh = make_local_mesh(data=True, devices=[device])
    rt = rtm.Runtime(backend=tr["backend"], sharding=ShardingPolicy(mesh=mesh))
    one = jax.sharding.SingleDeviceSharding(device)
    put = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    params = put(abstract_params(M.param_specs(cfg)))
    opt = put(jax.eval_shape(init_opt_state, params))
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32, sharding=one)
    poison = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    with mesh, rtm.use(rt):
        lowered = train.make_step(cfg, tr)[1].lower(
            params, opt, {"tokens": tokens, "labels": tokens}, poison=poison)
    yield f"train_step_{tr['batch']}x{tr['seq']}", lowered.compile()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    from jax.experimental import topologies
    from harness import bench
    from repro.runtime.backends import PallasBackend

    # a compile for a described chip cannot be read back from the persistent
    # cache without one; and the kernel backend's own check sees this CPU
    jax.config.update("jax_enable_compilation_cache", False)
    PallasBackend.check_platform = lambda self: None
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    hbm = bench.load_peaks(KIND)["hbm_bytes"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = args.workload or [w["name"] for w in spec["workloads"]]
    ok = True
    for name in names:
        cell = bench.load_cell(name)
        programs = {"serve": serve_programs, "train": train_programs}[cell.traffic["kind"]]
        for prog, compiled in programs(cell, topo.devices[0]):
            ok &= _report(f"{name}:{prog}", compiled, hbm)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
