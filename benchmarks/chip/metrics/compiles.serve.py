"""Executables JAX built inside the window: backend compiles and loads from
the persistent cache, counted by a ``jax.monitoring`` listener.  Every one
is a stall that set-up should have paid."""


def read(run):
    return float(run.compiles) if run.kind == "serve" else None
