"""JAX's persistent compilation cache for the command-line entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py`` and the
``repro.twin.server`` / ``repro.robust.chaos`` / ``repro.obs.report`` CLIs)
call :func:`enable_compile_cache` first thing; library code never does, so
importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache, at the checkout root.  A fixed path: the directory
#: is part of what lets a later process find the programs again.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Keep compiled programs across processes; returns the directory.

    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
      is set here.
    * Otherwise, on a TPU: :data:`DEFAULT_DIR`.
    * Otherwise (CPU): no cache, and None.  XLA:CPU entries are cheap to
      rebuild and are tied to the host's instruction set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
