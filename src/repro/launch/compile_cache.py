"""Where JAX's persistent compilation cache lives — decided in one place.

Entry points (``serve.main``, ``train.main``, ``chip_smoke.py``) call
:func:`setup_compile_cache` before their first compile.  A set
``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and wins: nothing is
changed.  Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout (git-ignored) — a fixed path, so each run finds what an earlier
run of the same checkout compiled.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
