"""JAX's persistent compilation cache at a fixed path.

A cold run compiles every program it uses; a whole-model step program at
published widths takes tens of seconds to compile.  The cache lets a later
process on the same machine load those programs instead.  The directory is
part of what makes an entry findable again, so it never moves: either the
one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or
``<checkout>/.jax_cache``.

Entry points call :func:`enable_compilation_cache` before their first
compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
