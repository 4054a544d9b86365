"""Where compiled programs are kept between processes.

The one place in the tree that names a compilation-cache directory.
Every entry point that compiles for the accelerator (``chip_smoke.py``,
the ``dl4j-tpu`` CLI, ``benchmark/run.py``, ``scripts/*_bench.py``) calls
:func:`enable_compile_cache` before its first trace, so a second
process — a fleet replica, a rerun, the next benchmark run — loads
executables instead of recompiling them.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache``: derived from this file's own location and
#: nothing else. The directory is part of the cache key, so a path built
#: from a temporary name, a process id or the clock would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment JAX has
    already read it, and this sets nothing — the caller placed the
    cache. Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
