"""Where JAX's persistent compilation cache lives, for every entry point.

Entry points call :func:`enable_compile_cache` at the start of ``main()``;
importing this module touches neither JAX's configuration nor a device.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is ``<root>/src/repro/launch/compile_cache.py``)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<root>/.jax_cache``:
    a fixed path, since the directory is part of what a later run must find.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
