"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, the serve and train launchers and the
benchmark harness) call ``enable_compile_cache`` before their first
compile; library modules and tests never do.  An operator who sets
``JAX_COMPILATION_CACHE_DIR`` owns the location: JAX reads that variable
itself and nothing is set here.  Otherwise the cache lives in one fixed,
git-ignored directory of the checkout, the same on every run, so a later
run finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory compiled programs go to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
