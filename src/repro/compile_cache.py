"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
once, before their first compile. Library code never calls it, and importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Directory, inside the checkout, that holds the cache when the
#: environment names none. Listed in ``.gitignore``.
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no directory of its own. Otherwise the cache goes to
    ``<root>/.jax_cache``: a fixed path, so that a later run from the same
    checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
