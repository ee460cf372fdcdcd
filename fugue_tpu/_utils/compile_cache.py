"""Where entry-point scripts keep JAX's persistent compilation cache.

Importing the library sets no cache: tests and users' own settings stay
untouched. Scripts that drive the chip (``chip_smoke.py``, ``bench.py``)
call :func:`use_compile_cache` before their first compile.
"""

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REPO_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is
    changed here. Unset: the cache goes to ``<repo>/.jax_cache``, a fixed
    path, because the path is part of what a later process must find."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
