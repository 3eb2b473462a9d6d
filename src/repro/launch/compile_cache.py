"""Where launchers keep JAX's persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing. Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path (the path is part of what a cached entry
is found by) that ``.gitignore`` lists. Launchers call
:func:`use_checkout_cache` from their ``main``; importing this module sets
nothing, and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_cache() -> str:
    """Turn on the persistent compile cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
