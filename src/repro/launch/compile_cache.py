"""Persistent JAX compile cache for the entry points.

Called by ``launch/serve.py``, ``launch/train.py``, ``launch/shard_host.py``,
``chip_smoke.py`` and ``benchmarks/run.py`` before their first compile;
never on package import, so a library caller keeps its own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, so every run of this checkout finds
# what an earlier one compiled (a temporary directory never hits)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on jax's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already keeps the
    cache there and this sets no other path; otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the IoU kernel compiles once per box count,
    # each in well under the default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
