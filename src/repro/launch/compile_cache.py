"""Where JAX keeps its persistent compilation cache for the launchers.

A full-width round takes tens of seconds to compile for the TPU, and the
cache is keyed by its directory, so the directory must not move between
runs. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
and nothing here overrides it. Otherwise the cache lives at one fixed
path inside the checkout (``CACHE_DIR``, ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Turn the persistent compilation cache on."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
