"""Where the entry points keep JAX's persistent compilation cache.

A cold 24-layer model compiles for minutes; the persistent cache lets the
next process on the same machine skip that.  Its directory is part of the
cache key, so it is a fixed path, never one named after a pid, a time or
a temporary directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``CHECKOUT_CACHE``.
    Call it at the start of an entry point's ``main()``, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
