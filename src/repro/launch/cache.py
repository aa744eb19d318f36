"""Where the entry points keep JAX's persistent compilation cache.

Called by the command-line mains and ``chip_smoke.py``, never when a library
module is imported.  A directory that moves never hits (the path is part of
how a run finds its entries again), so the fallback is one fixed path in the
checkout, not a temporary, per-process or timestamped one.
"""
from __future__ import annotations

import os

import jax

#: fallback cache directory: ``<repo root>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache")


def use_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone; otherwise the cache goes to `DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
