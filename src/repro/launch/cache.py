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
    alone; otherwise the cache goes to `DEFAULT_DIR`.

    Entries are keyed on the program's metadata too.  JAX's key leaves it
    out by default, so a program whose ``jax.named_scope``s changed and
    nothing else would be served the executable compiled before, whose ops
    carry the old names, and a profile would name them wrongly.  The
    metadata keeps each op's ``op_name`` (its scopes) but no Python frames,
    which would tie an entry to the caller's stack and the lines of every
    file on it: the same program traced from another caller (a ``.lower()``
    for its HLO text) then finds the same entry."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
