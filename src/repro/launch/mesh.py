"""Device setup shared by the launchers and ``chip_smoke.py``: meshes,
parameter placement and the persistent compilation cache.

Every mesh has ``Auto`` axis types: shardings propagate through XLA's
partitioner and the models pin layouts with ``with_sharding_constraint``
(``parallel/sharding.constrain``).  ``jax.make_mesh`` defaults to
``Explicit`` axes, on which gathers such as the embedding lookup raise
``ShardingTypeError``.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh",
    "make_local_mesh",
    "make_production_mesh",
    "init_sharded_params",
    "enable_compile_cache",
]

#: the checkout's root (``src/repro/launch/mesh.py`` -> ``.``)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (over ``devices`` if given)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_local_mesh(*, data: bool = False, devices=None):
    """A ``("data", "model")`` mesh over the devices that exist (or
    ``devices``): all of them on ``model`` (tensor parallel, the serving
    layout) or, with ``data``, all of them on ``data`` (batch parallel plus
    FSDP, the training layout)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    return make_mesh((n, 1) if data else (1, n), ("data", "model"),
                     devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) — the
    dry run's target (``launch/dryrun.py``), never built by the launchers."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def init_sharded_params(cfg, policy, seed: int = 0):
    """``cfg``'s random parameters from ``seed``, materialized by one jitted
    program straight into ``policy``'s parameter shardings (never whole on
    one device first)."""
    from repro.models import model as M  # local: keep this module light
    from repro.models.common import init_params

    specs = M.param_specs(cfg)
    return jax.jit(
        lambda k: init_params(specs, k),
        out_shardings=policy.param_shardings(specs),
    )(jax.random.PRNGKey(seed))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the path is part of every entry's key, so a
    directory named after a temp dir, a pid or the time would never hit.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
