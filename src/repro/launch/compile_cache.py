"""Where compiled programs persist between runs.

JAX's persistent compilation cache keys entries by the cache path among
other things, so it only pays off at a fixed location.  The entry points
(``launch.train``, ``launch.serve``, ``chip_smoke.py``) call
:func:`enable` once at start-up; library code and the tests never do, so
the tests run with the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): src/repro/launch/ -> checkout root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured here; otherwise the cache lives at the
    fixed ``REPO_CACHE_DIR`` inside the checkout."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
