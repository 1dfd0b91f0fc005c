"""JAX's persistent compilation cache for the entry-point scripts.

Call ``enable()`` from a script's entry point before its first compile
(JAX fixes the cache directory when it first compiles).  Never call it
at library import or from tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
``enable()`` sets no other directory.  Otherwise the cache lives at
``.jax_cache`` in the root of the checkout: a fixed path, so a later run
from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
