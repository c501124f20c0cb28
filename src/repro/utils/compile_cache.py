"""JAX's persistent compilation cache, placed from outside or in the
checkout.

Called from the ``main()`` of each entry point (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``), never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so that one checkout's runs find each other's entries: the
# directory is part of what a run looks up
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
