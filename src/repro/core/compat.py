"""Where a process of this repo meets its environment.

The repo targets one installation (jax 0.9.0) and calls its APIs
directly.  What is left here is the environment the
program reads at startup, in the one module the lint allows to read it
(``raw-environ-read-outside-compat``):

``enable_compile_cache``  places JAX's persistent compilation cache.  An
                          entry point calls it once at startup, never a
                          library module at import.
"""
from __future__ import annotations

import os
import pathlib

#: The checkout root (``src/repro/core/compat.py`` -> three levels up).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Put JAX's persistent compilation cache where the environment says,
    else at ``<checkout>/.jax_cache``; return the directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing.  The fallback is a fixed path because the cache key
    includes it: a temp, pid or time path would never hit."""
    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    import jax
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


__all__ = ["CHECKOUT", "COMPILE_CACHE_ENV", "enable_compile_cache"]
