"""JAX's persistent compilation cache for the repo's entry points.

A compiled program is written to disk and found again by the next process
that compiles the same program, so a second solve of one shape skips the
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins: nothing is set in code. Otherwise the cache lives at a fixed path
inside the checkout (``.jax_cache/``, git-ignored) — the path is part of the
cache's key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default: <repo>/.jax_cache (this file is
#: <repo>/src/repro/launch/compile_cache.py).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for this process and return its
    directory: the environment's when ``JAX_COMPILATION_CACHE_DIR`` is set
    (then this sets nothing), else :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
