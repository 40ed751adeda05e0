"""Process-level jax runtime tuning shared by the bench/train entrypoints.

``enable_compilation_cache`` turns on jax's persistent compilation cache so
repeated invocations of the same programs (the superstep scan, the sharded
round, the chip smoke run) deserialize executables instead of rebuilding
them.

The cache directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise one fixed directory inside the checkout (``<repo>/.jax_cache``,
git-ignored).  The path is part of what makes an entry findable again, so
it never depends on the temp dir, the user, the pid or the time.
Thresholds are dropped to zero so even the small smoke programs cache (the
defaults skip sub-second compiles, which is most of a CPU CI run).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compilation_cache", "DEFAULT_CACHE_DIR"]

# src/repro/launch/runtime.py -> the checkout root.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
