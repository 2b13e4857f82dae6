"""JAX's persistent compilation cache, placed from outside.

``enable_compile_cache()`` is called once at start-up by the launchers and
``chip_smoke.py``, before anything compiles.  Where the environment sets
``JAX_COMPILATION_CACHE_DIR`` JAX already reads it, and no other directory
is set in code.  Otherwise the cache goes to ``<repo root>/.jax_cache``,
resolved from this package's location: the path is part of what makes a
later process find the entries, so it never depends on a temporary name,
a process id or the time.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def resolve_cache_dir(environ: Optional[Mapping[str, str]] = None
                      ) -> pathlib.Path:
    """The directory the cache lives in: the environment variable when it
    is set and non-empty, else the fixed directory inside the checkout."""
    env = os.environ if environ is None else environ
    return pathlib.Path(env[ENV_VAR]) if env.get(ENV_VAR) else DEFAULT_DIR


def enable_compile_cache() -> pathlib.Path:
    import jax

    path = resolve_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
