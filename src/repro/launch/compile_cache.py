"""Where JAX keeps its persistent compilation cache.

The entry points (``launch/train.py``, ``launch/serve.py`` and
``chip_smoke.py``) call :func:`enable_compile_cache` once, before they
compile anything.  Tests do not: a test that compiles for a described
chip would write entries that cannot be read back without one.
"""

from __future__ import annotations

import os

import jax

#: Fixed cache path inside the checkout.  The path is part of each
#: entry's key, so it must not move between runs.
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives at
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
