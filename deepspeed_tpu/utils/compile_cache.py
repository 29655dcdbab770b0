"""Placement of JAX's persistent compilation cache.

The directory is part of the cache's key, so one that moves never hits.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set here;
where it is not, the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
Call before the first compile.  This is the only place in the repo that sets
``jax_compilation_cache_dir``.
"""

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR_NAME = ".jax_cache"


def place_compile_cache(checkout_dir: str) -> Optional[str]:
    """Returns the directory set in code, or None when the environment places
    the cache (JAX then reads the variable itself)."""
    import jax
    if os.environ.get(CACHE_DIR_ENV):
        return None
    path = os.path.join(os.path.abspath(checkout_dir), CACHE_DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
