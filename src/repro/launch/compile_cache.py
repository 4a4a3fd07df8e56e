"""JAX's persistent compilation cache, as every entry point sets it up.

``chip_smoke.py``, ``launch.serve`` and ``launch.train`` call
:func:`enable_compile_cache` before their first compile, so a second run in
the same checkout reads its compiled programs back instead of compiling
again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache is that
directory and no other.  Otherwise the cache lives at one
fixed path, ``.jax_cache/`` at the repository root: a directory derived from
a temporary name, a pid or the time would never be found again.
"""

from __future__ import annotations

import os

import jax

#: the repository root (this file is src/repro/launch/compile_cache.py)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Every compile is cached, however quick: the generated Pallas kernels
    compile in well under JAX's default one-second threshold, and a run
    compiles dozens of them.
    """
    path = os.environ.get(ENV_VAR) or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
