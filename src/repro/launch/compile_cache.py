"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing here
overrides it. Where it is not, the cache goes to ``<checkout>/.jax_cache``: a
fixed path (the path is part of what a later run must find again), never a temp,
pid- or time-derived one. ``chip_smoke.py`` and ``repro.launch.serve`` call this
before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on at its directory and return that directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
