"""Where JAX keeps its persistent compilation cache.

Call :func:`enable` once, before the first compile, from every entry point
that runs on the chip.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the
directory and JAX reads it on its own; nothing here overrides it.  When it
is not set the cache goes to ``.jax_cache`` at the root of the checkout: a
fixed path, because the path is part of what a later process looks up, so
a directory named after a pid, a temporary name or the time never hits.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
