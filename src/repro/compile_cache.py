"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call :func:`setup_compile_cache` once at start;
nothing calls it at import, so importing the library never changes
JAX's configuration.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# The fallback location, fixed on purpose: a cache only hits where the
# next run looks for it, so the path carries no temp name, pid or time.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and no other directory is set here.  Otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR`` (``<repo>/.jax_cache``).  Either way the
    minimum compile time and entry size drop to nothing, so the
    second-long Pallas kernel compiles are cached with the large
    programs.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
