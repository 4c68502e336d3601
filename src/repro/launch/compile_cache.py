"""JAX persistent compilation cache for the entry points.

One rule, applied from ``main()`` of each entry point (never at import):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  configured here.
* unset: the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
  because the directory is part of what a later run must find again (a
  temp-, pid- or time-derived path would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["CACHE_ENV", "CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's persistent cache at the checkout unless the environment
    already names one. Returns the directory configured here, or ``None``
    when ``JAX_COMPILATION_CACHE_DIR`` is in charge."""
    if os.environ.get(CACHE_ENV):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return CHECKOUT_CACHE_DIR
