"""JAX's persistent compilation cache, placed from outside.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory holds the
cache and no other path is set in code.  Otherwise the cache lives at
one fixed path inside the checkout, ``<repo>/.jax_cache/`` (gitignored):
the path is part of what a cache entry is found by, so it must be the
same on every run.
"""
from __future__ import annotations

import os

#: the in-checkout default when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory.  Call after `import jax` and before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    # 0 = cache every compile: a chip call starts with no compiled code,
    # so even quick programs are worth finding again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
