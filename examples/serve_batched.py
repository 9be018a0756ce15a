"""Continuous-batching serving example: a request stream with mixed prompt
lengths and decode budgets through a fixed-capacity slot array.

  PYTHONPATH=src python examples/serve_batched.py --arch qwen3-4b \
      --requests 8 --slots 4 --backend dense

Execution policy (kernel backend, block geometry, plan cache) is one
``repro.runtime.Runtime``; the decode loop is a single jitted ``lax.scan``
program, traced once and replayed as the scheduler admits, finishes and
backfills requests, and admission prefills through one jitted program per
(group size, prompt length).  Under a sparse backend the LM-head
SparsityPlan is computed at the first admission and replayed (cache hits)
for every later one.
"""
import argparse
import time

import jax
import numpy as np

from repro import runtime as rtm
from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.models.common import init_params
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--backend", default="dense", choices=rtm.available_backends())
    args = ap.parse_args()

    cfg = reduce_config(get_config(args.arch))  # reduced config on CPU
    rt = rtm.Runtime(backend=args.backend, bm=args.slots, bk=16, bn=16)
    params = init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)

    eng = ServeEngine(
        params, cfg, slots=args.slots, max_len=args.prompt_len + args.new,
        rt=rt, temperature=args.temperature, chunk=args.chunk,
    )
    t0 = time.time()
    rids = []
    for _ in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        rids.append(eng.submit(prompt, max_new=int(rng.integers(2, args.new + 1))))
    out = eng.run()
    dt = time.time() - t0

    st = eng.stats()
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests}")
    print(f"served {st['tokens_out']} tokens in {dt:.2f}s "
          f"({st['tokens_out']/dt:.1f} tok/s on 1 CPU core); "
          f"decode program traced {st['decode_traces']}x for {st['chunks_run']} chunks")
    pc = st["plan_cache"]
    print(f"backend={rt.backend} plan cache: {pc['hits']} hits / "
          f"{pc['misses']} misses / {pc['traced']} traced-in-program")
    for rid in rids[: min(len(rids), 2)]:
        print(f"  req{rid}: {out[rid]}")


if __name__ == "__main__":
    main()
