"""Where JAX's persistent compilation cache lives.

The entry points (``chip_smoke.py``, ``examples/inference/runner.py``, the
training examples' ``setup_example``) call
:func:`place_compile_cache` once, before their first compile. Placement
belongs to whoever runs the program: when ``JAX_COMPILATION_CACHE_DIR`` is
exported JAX already reads it and nothing is set here. Otherwise the cache
goes to one fixed directory inside the checkout — the path is part of the
cache key, so a directory named after a pid, a time or a temp file would
never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_compile_cache, listed in .gitignore
_IN_CHECKOUT = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def place_compile_cache() -> str:
    """Make sure a cache directory is configured; returns the one in use."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    return str(_IN_CHECKOUT)
