"""Fault-tolerant checkpointing: atomic, keep-k, elastic reshard-on-load.

* Atomic: write to ``<dir>/tmp.<step>`` then ``rename`` — a preemption
  mid-write never corrupts the latest checkpoint.
* keep-k: older checkpoints garbage-collected after a successful save.
* Elastic: arrays are stored logically-global (npz) with their tree paths;
  ``restore(..., shardings=...)`` re-device_puts onto *any* mesh — restart on
  a different pod count / mesh shape just works.
* Preemption: ``PreemptionGuard`` installs a SIGTERM handler; the train loop
  polls ``should_save`` and checkpoints before exit (straggler/maintenance
  evictions on large fleets).
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import threading

import jax
import ml_dtypes
import numpy as np

_EXTENDED = {"bfloat16": ml_dtypes.bfloat16, "float8_e4m3fn": ml_dtypes.float8_e4m3fn}

__all__ = ["save", "restore", "restore_latest", "latest_step", "PreemptionGuard"]

_SEP = "/"


def _flatten(tree):
    flat = jax.tree.flatten_with_path(tree)[0]

    def name(path):
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        return _SEP.join(parts)

    return {name(path): leaf for path, leaf in flat}


def save(directory: str, step: int, tree, *, keep: int = 3) -> str:
    """Atomically write checkpoint ``step``; prune to ``keep`` newest."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"tmp.{step}")
    final = os.path.join(directory, f"step_{step:012d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    flat = _flatten(tree)
    arrays = {k: np.asarray(jax.device_get(v)) for k, v in flat.items()}
    # npz cannot store extended dtypes (bf16 etc.): view as uint16/uint8 with
    # a sidecar dtype map
    dtypes = {}
    for k, v in list(arrays.items()):
        name = str(v.dtype)
        if name in _EXTENDED:
            dtypes[k] = name
            arrays[k] = v.view(np.uint16 if v.dtype.itemsize == 2 else np.uint8)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump({"step": step, "keys": sorted(arrays), "dtypes": dtypes}, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    # prune
    steps = sorted(all_steps(directory))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s:012d}"), ignore_errors=True)
    return final


def all_steps(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for n in os.listdir(directory):
        if n.startswith("step_") and os.path.exists(os.path.join(directory, n, "meta.json")):
            out.append(int(n[len("step_") :]))
    return sorted(out)


def latest_step(directory: str) -> int | None:
    steps = all_steps(directory)
    return steps[-1] if steps else None


def restore(directory: str, step: int, like, *, shardings=None):
    """Load checkpoint ``step`` into the structure of ``like``.

    ``shardings`` (same tree structure) re-places every array on the current
    mesh — elastic restart across mesh shapes.
    """
    base = os.path.join(directory, f"step_{step:012d}")
    data = dict(np.load(os.path.join(base, "arrays.npz")))
    with open(os.path.join(base, "meta.json")) as f:
        meta = json.load(f)
    for k, name in meta.get("dtypes", {}).items():
        data[k] = data[k].view(_EXTENDED[name])
    flat_names = _flatten(like)
    leaves, treedef = jax.tree.flatten(like)
    names = list(_flatten(like).keys())
    assert len(names) == len(leaves)
    restored = [data[n] for n in names]
    if shardings is not None:
        shard_leaves = jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec") or x is None
        )
        restored = [
            jax.device_put(a, s) if s is not None else jax.numpy.asarray(a)
            for a, s in zip(restored, shard_leaves)
        ]
    else:
        restored = [jax.numpy.asarray(a) for a in restored]
    del flat_names
    return jax.tree.unflatten(treedef, restored)


def restore_latest(directory: str, like, *, shardings=None):
    """Load the newest *readable* checkpoint: ``(step, tree)``.

    Graceful degradation for on-disk corruption (a torn write that somehow
    survived the atomic rename, bit rot, a truncated copy): a checkpoint
    that fails to load is skipped — loudly, with a warning and a
    ``ResilienceLog`` event — and the next-older one is tried.  Returns
    ``(None, None)`` when no checkpoint is readable (callers start fresh).
    """
    import warnings

    from repro.resilience.log import record as _record

    for step in reversed(all_steps(directory)):
        try:
            return step, restore(directory, step, like, shardings=shardings)
        except Exception as e:  # np.load/json/KeyError zoo — skip, try older
            warnings.warn(
                f"checkpoint step {step} in {directory!r} is unreadable "
                f"({type(e).__name__}: {e}); trying an older checkpoint",
                RuntimeWarning, stacklevel=2,
            )
            _record("checkpoint", "checkpoint.restore_latest", "skip-corrupt",
                    step=step, error=f"{type(e).__name__}: {e}")
    return None, None


class PreemptionGuard:
    """SIGTERM-aware save trigger for preemptible fleets."""

    def __init__(self):
        self._flag = threading.Event()
        try:
            signal.signal(signal.SIGTERM, self._handler)
        except ValueError:
            pass  # not in main thread (tests)

    def _handler(self, signum, frame):
        self._flag.set()

    @property
    def should_save(self) -> bool:
        return self._flag.is_set()
