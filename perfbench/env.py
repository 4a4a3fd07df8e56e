"""Where a run keeps what it caches: inside the checkout, at fixed paths.

Compiled programs go to ``.perfbench/jax_cache`` and the program's kernel
choices (its autotune cache and plan DB) to ``.perfbench/``, so that a
machine's home directory or its own compile cache changes nothing that
runs, and only the first run in a checkout compiles.
"""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".perfbench")


def prepare() -> None:
    """Point the caches into the checkout; call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax_cache")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(CACHE, "autotune.json")
    os.environ["REPRO_PLAN_DB"] = os.path.join(CACHE, "plans.json")


def configure_jax(jax) -> None:
    """Cache every compile, however quick."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
