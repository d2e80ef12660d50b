"""JAX's persistent compilation cache, for the entry points.

Entry points (``chip_smoke.py``, ``examples/mine_t10.py``,
``python -m repro.launch.serve``, the ``launch.multihost`` worker and the
chip benchmark's ``bench/harness.py``) call :func:`enable_compile_cache` once
before their first compile; importing the package never does.  A compiled
program is found again only under the same cache path, so the path is fixed.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_compile_cache — this file is <checkout>/src/repro/launch/.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at ``DEFAULT_DIR``,
    which ``.gitignore`` lists.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
