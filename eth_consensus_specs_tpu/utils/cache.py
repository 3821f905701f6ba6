"""Persistent XLA compilation cache.

The unrolled SHA-256/limb kernels trade compile time for runtime; caching
compiled executables across processes makes that cost one-time per machine
instead of one-time per run (``VerifyService``, ``benchmark/run.py`` and
``chip_smoke.py`` turn it on before their first compile)."""

from __future__ import annotations

import os

_enabled = False


_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """The fixed fallback location, ``<checkout>/.jax_cache``: the
    directory is part of every cache key's lookup, so it is never built
    from a temporary name, a pid or the time."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, ".jax_cache")


def cache_dir_path() -> str:
    """Where this process keeps compiled executables: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it
    itself; nothing here overrides it), else :func:`default_cache_dir`."""
    return os.environ.get(_ENV_VAR) or default_cache_dir()


def enable_persistent_cache() -> str | None:
    """Accelerator backends only. XLA:CPU cache entries are AOT executables
    pinned to the compiling host's machine features (avx512 etc.); loading
    one on a different CPU is accepted with a warning and then executes
    garbage (observed: infinite hang). TPU executables are
    topology-portable, and that's also where recompiles actually hurt.

    Returns the directory in use, or None on the cpu backend. A backend
    that fails to start raises here: the caller asked for a device."""
    global _enabled
    import jax

    if jax.default_backend() == "cpu":
        return None
    cache_dir = cache_dir_path()
    if not _enabled:
        os.makedirs(cache_dir, exist_ok=True)
        if not os.environ.get(_ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        _enabled = True
    return cache_dir
