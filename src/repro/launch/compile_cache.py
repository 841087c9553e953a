"""JAX's persistent compilation cache for the chip entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile, so a second run on the same machine reads back what
the first compiled.  Library code and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable(root: str | os.PathLike) -> str:
    """Turn the persistent cache on; return the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<root>/.jax_cache``: a fixed path, never built from a temporary
    name, a pid or the time, so a later run finds the entries of an
    earlier one.
    """
    if not os.environ.get(ENV_VAR):
        path = Path(root).resolve() / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(path))
    return jax.config.jax_compilation_cache_dir
