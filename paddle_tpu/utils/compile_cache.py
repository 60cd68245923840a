"""Persistent XLA compile cache, placed from outside.

The cache directory is part of the cache key, so it must never move between
runs: when the environment names one (``JAX_COMPILATION_CACHE_DIR``, which
JAX reads by itself) nothing is set in code; otherwise it is the fixed
``<checkout>/.jax_cache`` (listed in .gitignore) — never a tempdir, a pid
or a timestamp. Entry points call this (chip_smoke.py, bench.py, the
launcher's worker bootstrap); ``import paddle_tpu`` does not.
"""
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Turn the persistent compile cache on; returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
