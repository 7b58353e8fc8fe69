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
    ``<checkout>/.jax_cache``.

    The key includes the program's metadata: the ``op_name`` of every
    operation, which holds the step's named scopes
    (``repro.utils.scopes``).  Without it two programs that differ only
    in their scopes share one entry, and the executable one of them
    loads names the other's scopes, which a profiler trace is read
    by.  It is set for every run, traced or not, because a process
    cannot tell at start-up whether a later one will profile what it
    caches.  The cost: the metadata holds source file names and line
    numbers, so an edit that moves a traced line recompiles the step
    (50 to 110 s for a model at published widths on a v5e) even where
    the compiled code is the same."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
