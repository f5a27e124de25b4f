"""On-disk state of a run stays inside the checkout.

Called first by every entry point (launchers, benchmarks, chip_smoke.py),
before JAX initialises a backend; tests never call it, so their compiles
stay in memory.

* Persistent XLA compile cache.  A published-width step compiles for a
  minute or more, so a run reuses whatever an earlier run compiled.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
  overrides it.  Otherwise the cache lives at one fixed directory,
  ``<repo>/.jax_cache``: the path is part of what an entry is found by, so
  it must not move between runs.
* libtpu's logs go to ``<repo>/.tpu_logs`` unless ``TPU_LOG_DIR`` is set
  (its own default is under /tmp).

Both directories are git-ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_DIR = Path(__file__).resolve().parents[3]


def use_repo_dirs() -> str:
    """Apply the rules above; returns the compile-cache directory."""
    os.environ.setdefault("TPU_LOG_DIR", str(REPO_DIR / ".tpu_logs"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    cache = str(REPO_DIR / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    return cache
