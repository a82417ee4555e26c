"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache()`` once, before their first compile; importing the
library never touches the cache.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing else is
  configured, so the cache lands there and nowhere else;
* otherwise the cache goes to ``<repo>/.jax_cache``, a fixed path (the path
  is part of each entry's key, so a directory that moves never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
