"""JAX's persistent compilation cache, placeable from outside.

One rule, shared by every entry point (``cli.main``, ``bench.py::main``,
``chip_smoke.py``): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself
reads it and this module names no directory; otherwise the cache lives at one
fixed path inside the checkout. The path is part of the cache key's
neighbourhood — a directory that moves between runs never hits — so it is
never derived from a pid, a time or a temporary name.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    Programs that compile in under half a second are not worth a file; the
    train step and the serve tick programs all take longer."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir
