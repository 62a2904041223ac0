"""Where XLA's persistent compile cache lives.

One grower program at the Higgs shape is minutes of compile, and a chip
machine may keep nothing between runs except a directory somebody
places. So the cache directory is placeable from outside and otherwise
fixed: the cache's key includes its own path, so a directory that
moves (tempfile, pid, time) never hits.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["configure_compile_cache", "DEFAULT_CACHE_DIRNAME"]

DEFAULT_CACHE_DIRNAME = ".jax_cache"


def configure_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory. Call before the first JAX op (chip_smoke.py,
    bench.py, bench_serve.py and cli.main do; nothing else in the repo
    sets a cache directory).

    JAX_COMPILATION_CACHE_DIR set: JAX reads it by itself, so nothing
    is set here. Unset: ``<checkout>/.jax_cache``, derived from where
    this package sits, and every program is kept however quickly it
    compiled so a second run against the same directory compiles
    nothing. A process pinned to the CPU platform gets no cache (None):
    XLA:CPU compiles in seconds, and its stored executables are tied to
    the CPU features of the host that wrote them."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, DEFAULT_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
