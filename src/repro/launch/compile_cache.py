"""Persistent XLA compile cache at a fixed place.

A cold run of the serving path compiles every prefill bucket and the
paged decode program; a second run in the same checkout should find them
again.  JAX keys cache entries by program, and looks them up in one
directory, so the directory must not move between runs: it is either
the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, so nothing is set in code) or ``.jax_cache/`` at the root of the
checkout.

Entry points call :func:`setup_compile_cache` once, before compiling.
Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "setup_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``.jax_cache/`` at the checkout root (``src/repro/launch`` → root)."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache(env: Optional[Mapping[str, str]] = None
                        ) -> Optional[Path]:
    """Point JAX's persistent compile cache at :func:`default_cache_dir`
    unless ``JAX_COMPILATION_CACHE_DIR`` is set in ``env`` (default: the
    process environment), in which case nothing is touched.  Returns the
    directory set, or ``None`` when the environment decides."""
    env = os.environ if env is None else env
    if env.get(CACHE_ENV):
        return None
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
