"""Where this process keeps JAX's persistent compilation cache.

Each entry point that compiles (server main, chip_smoke.py's children)
calls `configure_compile_cache()`
once before its first jit — never at package import, so library users and
the test suite are untouched.

The cache key includes the directory, so a directory that moves never
hits: where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and this
function sets no directory; where it is not, the directory is the fixed
path `<checkout>/.jax_cache` (git-ignored) — never a temporary name, a pid
or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: parseable_tpu/utils/compile_cache.py -> parents[2]
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return it.

    Thresholds are lowered to "store everything": the engine's programs
    include many sub-second compiles (one slice + bitcast per column of a
    packed block, `_transfer`) that JAX's default 1 s floor would skip,
    and a cold start pays for each of them again."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
