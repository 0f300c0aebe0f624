"""The one persistent compile cache of the program's entry points.

``chip_smoke.py`` and ``bench.py`` call :func:`use_compile_cache` before
their first compile.  Nothing calls it on library import or in tests.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here.  Otherwise the cache goes to the fixed, gitignored
    ``<checkout>/.jax_cache``: a directory that moved between runs would
    never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
