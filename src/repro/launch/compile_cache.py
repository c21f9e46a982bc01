"""Persistent XLA compilation cache, placed the same way by every entry point.

A cold start of the served decode step compiles for tens of seconds; the
cache turns every later start with the same programs into a lookup.  Its
key includes the directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing else
  is configured, so whoever placed the cache keeps control of it;
* otherwise — ``<checkout>/.jax_cache`` (ignored by git), the same path on
  every call.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
