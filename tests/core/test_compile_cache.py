"""Where entry-point scripts place JAX's persistent compilation cache
(``fugue_tpu/_utils/compile_cache.py``)."""

import os

import jax
import pytest

from fugue_tpu._utils.compile_cache import REPO_CACHE_DIR, use_compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax_cache", None])
def test_use_compile_cache(monkeypatch, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        placed = use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert placed == after == REPO_CACHE_DIR == os.path.join(_REPO, ".jax_cache")
    else:
        # JAX reads the variable itself: the helper changes nothing
        assert placed == env_dir and after == before
