"""Persistent XLA compilation cache.

The unrolled SHA-256/limb kernels trade compile time for runtime; caching
compiled executables across processes makes that cost one-time per machine
instead of one-time per run (bench and test drivers call this first)."""

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
    itself; nothing here overrides it), else :func:`default_cache_dir`.
    The warm sentinels live beside the executables they vouch for."""
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


def warm_sentinel(stage: str, backend: str) -> str:
    """Marker file recording that a device chain (`pairing`, `h2c`, ...)
    compiled AND executed to completion for `backend` with the entries
    persisted in the cache.  Lets the bench attempt a device stage only
    when a warm start is plausible — a cold compile of these chains can
    exceed a whole section budget (round-3 lesson: never let one slow
    compile strand a measurement).  The filename is built HERE only, so
    producers (the kernels' mark_warm) and consumers (bench) can never
    drift apart."""
    return os.path.join(cache_dir_path(), f"device_{stage}_warm.{backend}")


def pairing_warm_sentinel(backend: str) -> str:
    return warm_sentinel("pairing", backend)


def mark_warm(stage: str) -> None:
    """Write the warm sentinel for `stage` — call strictly AFTER the
    chain's results have been materialized on host (a sentinel written
    before a runtime failure would keep steering later runs into the
    broken path).  No-op without the persistent cache or on cpu."""
    try:
        if not _enabled:
            return
        import jax

        backend = jax.default_backend()
        if backend == "cpu":
            return
        with open(warm_sentinel(stage, backend), "w") as fh:
            fh.write("ok\n")
    except Exception:
        pass
