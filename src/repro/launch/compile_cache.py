"""Where the entry points keep JAX's persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
itself and this module sets nothing.  Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache`` (git-ignored).  The path is part of what
a cached program is found by, so it is never built from a temporary
name, a pid or the time.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()          # before the first compile
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package runs from (``src/repro/launch`` -> root)
CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The cache directory the entry points use."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at ``compile_cache_dir()``; returns the directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
