"""JAX's persistent compilation cache for the programs that run on a device.

:func:`enable_compile_cache` places the cache:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX caches in that directory
  and this module sets no other;
* otherwise the cache lives at ``<checkout>/.jax_cache``, one fixed path (the
  path is part of the cache key, so a directory that moved would never hit).

JAX's one-second floor for what it writes stays.  On a TPU v5e host a small
dense layer compiles in about 0.07 s, but writing its entry cost about
0.85 s, so caching every layer shape of a campaign made compiling 13 times
slower; the programs above the floor (a model's prefill and decode steps,
tens of seconds each) are the ones worth keeping.

Entry points call it before their first compile; importing a module never
does, so tests and library users keep JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed cache directory inside the checkout (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
