"""Where JAX keeps compiled programs between processes.

A step of a model at published widths takes minutes to compile; the
persistent cache lets the next process (a resumed job, the next
benchmark phase) load it instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, because the cache key includes it
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
    it itself and nothing is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
