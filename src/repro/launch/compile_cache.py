"""Where the chip entry points keep jax's persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and wins:
nothing here overrides it.  Otherwise the entry points (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks/run.py``) keep the cache at
``<repo>/.jax_cache`` — a fixed path, so a second run of the same
programs loads them instead of compiling again.  Called from ``main()``
only: importing a module never changes compiler state.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
