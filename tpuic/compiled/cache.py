"""The one place that says where JAX's persistent compilation cache lives.

Rule: ``JAX_COMPILATION_CACHE_DIR``, when set, is the location — JAX reads
it itself and nothing here touches the directory setting. Unset, the cache
is one fixed path inside the checkout (the path is part of every cache
key, so a directory that moves never hits). Every entry point — train,
serve, score, the benches, the tests and the scripts — calls
:func:`enable_compile_cache` and sets no directory of its own.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tests", ".jax_cache")


def enable_compile_cache(default_dir: str = "") -> str:
    """Turn the persistent cache on and return the directory in use.

    ``default_dir`` replaces the in-checkout path when the environment
    names none (``python -m tpuic.serve --compile-cache-dir``); the
    environment always wins. Call before the first compile."""
    import jax
    cache = os.environ.get(CACHE_ENV, "")
    if not cache:
        cache = os.path.expanduser(default_dir) if default_dir \
            else DEFAULT_CACHE_DIR
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache
