"""Persistent XLA compilation cache for the command-line entry points.

Every ``main()`` under ``repro.launch`` and ``chip_smoke.py`` calls
:func:`enable_compile_cache` once, before its first compile, so a rerun
reads compiled programs back instead of compiling them again.  Library
import never calls it: tests compile for described (absent) chips, and
such entries cannot be read back without the chip.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX already
reads it.  Otherwise the cache is ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of what a later run must find.
"""

from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
