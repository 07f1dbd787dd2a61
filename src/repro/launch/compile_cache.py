"""The one persistent-compile-cache rule for the entry points
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``).

XLA's persistent cache keys on the cache path as well as the program, so
a directory that moves between runs never hits. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  else is set;
* otherwise: the fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Library modules never call this; importing them leaves JAX's cache
settings alone.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Apply the rule above and return the cache directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
