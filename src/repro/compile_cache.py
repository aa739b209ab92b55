"""Where compiled programs persist between runs.

Every entry point (``chip_smoke.py``, the examples, the benchmark scripts,
the mesh workers) calls ``enable_compile_cache`` once at start-up.
Importing ``repro`` enables nothing, so library users and the tests stay
cache-free.

JAX keys its persistent cache by the directory, among other things, so
the directory must not move between runs: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it and nothing here overrides it; otherwise the cache
lives at a fixed ``.jax_cache/`` in the checkout root.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """The directory holding ``src/repro`` (the repository checkout)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def cache_dir() -> str:
    """The cache directory ``enable_compile_cache`` uses."""
    return os.environ.get(CACHE_ENV) or os.path.join(checkout_root(),
                                                     ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
