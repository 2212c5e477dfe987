"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmark/run.py``,
the tools, the test harness): if ``JAX_COMPILATION_CACHE_DIR`` is set, jax
reads it itself and nothing is set in code; otherwise the cache is
``<checkout>/.jax_cache``. The path is part of how a run finds an earlier
run's entries, so it never derives from a temp dir, a pid or a clock.
"""

from __future__ import annotations

import os

__all__ = ["cache_dir", "enable"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The cache directory in force: the env var's, else the checkout's."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for this process and the children it
    spawns; returns the directory in force. Call before the first compile.

    Every compile is cached (jax's default skips those under 1 s, which
    would make a warm run recompile them) unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
    """
    import jax
    path = cache_dir()
    if not os.environ.get(_ENV):
        os.environ[_ENV] = path          # children inherit the same place
        jax.config.update("jax_compilation_cache_dir", path)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
