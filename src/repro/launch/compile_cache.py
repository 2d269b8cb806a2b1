"""Where JAX keeps its persistent compilation cache for this checkout.

Called by entry points (``launch/train.py::main``, ``chip_smoke.py``),
never at import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is changed here.  Otherwise the cache goes to
``.jax_cache/`` at the checkout root: a fixed path, since the directory is
part of what makes a later run find the entries (git ignores it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
