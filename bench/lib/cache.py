"""JAX's persistent compilation cache, at a fixed path inside the checkout.

The directory is part of what lets a later process find a program again,
so it never moves: ``<checkout>/.bench_cache/jax``.  Every program is
cached, however short its compile, so that a run after the first loads
everything and its set-up stays steady.
"""
from __future__ import annotations

from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".bench_cache" / "jax"


def enable_compile_cache() -> str:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
