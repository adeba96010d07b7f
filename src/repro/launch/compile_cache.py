"""Persistent XLA compilation cache placement for the repo's entry points.

JAX keys its persistent cache on the program and the cache directory, so a
directory that moves between runs never hits. ``setup_compile_cache``:

  * leaves the cache where ``JAX_COMPILATION_CACHE_DIR`` says when that is
    set (JAX reads the variable itself; nothing is set in code);
  * otherwise points ``jax_compilation_cache_dir`` at ``<repo>/.jax_cache``,
    a fixed path inside the checkout (listed in ``.gitignore``).

It touches no device and sets no ``LIBTPU_INIT_ARGS`` or ``XLA_FLAGS``.
Call it at the start of an entry point, before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "setup_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Enable the persistent compile cache; return the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
