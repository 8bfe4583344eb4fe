"""JAX's persistent compilation cache, placed from outside or at a fixed
path inside the checkout.

Entry points that compile full-size programs (``launch/train.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` before their first
compile, so a second run in the same checkout skips recompilation.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code and that directory is the cache.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The
  path is fixed, never built from a temp name, a pid or the time: the
  directory is part of where entries are found again, so a moving
  directory would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root (src/repro/runtime/ -> three levels up)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
