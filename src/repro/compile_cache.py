"""Persistent XLA compile cache placement for the command-line entry points.

Entry points (``chip_smoke.py``, ``python -m benchmarks.*``) call
:func:`configure_compile_cache` once before their first compilation; the
library never configures a cache on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and nothing
else is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, so a second process on the same checkout finds the entries
the first one wrote.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point jax's persistent compile cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
