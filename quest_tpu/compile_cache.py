"""Where JAX's persistent compilation cache lives.

One rule for the whole repository (bench.py, the probe tools, conftest.py,
chip_smoke.py, the serving engine): where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX itself reads it and nothing here sets another directory; where it
is not, the cache is the one fixed path ``<checkout>/.jax_cache``. The
directory is part of the cache key, so a path that moves never hits.
"""

from __future__ import annotations

import os

#: JAX's own variable; when set it is the only say on the directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that directory. With ``JAX_COMPILATION_CACHE_DIR`` set the directory is
    left to JAX (which read the variable at import)."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return path
