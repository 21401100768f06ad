"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call :func:`enable` before they compile anything;
library modules never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and no directory is set in code.  Otherwise the cache lives
at ``<checkout>/.jax_cache``: a fixed path, because the directory is part
of what a later process must find again."""
from __future__ import annotations

import os
from typing import Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable(root: Optional[str] = None) -> str:
    """Turn on the persistent compilation cache; returns its directory.
    ``root`` replaces the checkout as the parent of ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    path = os.path.join(root or CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()  # re-read the directory if JAX cached it
    return path
