"""Environment of host-only child processes.

A chip belongs to one process at a time, and the parent of these
children (a server, a benchmark, the edge half of a demo) may hold it.
Every child that only runs host code -- socket decoders, cloud workers,
entropy-coder pools -- is pinned to JAX's CPU backend, so it neither
fails on the accelerator's lock nor quietly takes another device.
"""

from __future__ import annotations

import os


def cpu_env() -> dict:
    """``os.environ`` with ``JAX_PLATFORMS=cpu``: the ``env`` of a
    host-only ``subprocess.Popen`` child."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def pin_cpu() -> None:
    """First call of a host-only ``multiprocessing`` child (or a pool
    initializer): pin JAX to the CPU before anything initializes a
    backend.  Importing this package initializes none."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
