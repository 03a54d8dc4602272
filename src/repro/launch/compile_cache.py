"""Where JAX's persistent compilation cache lives for the entry points.

``python chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m repro.launch.train`` call :func:`configure_compile_cache` before
their first compile, so a later process of the same checkout reuses the
compiled programs instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: ``.jax_cache/`` at the root of the checkout (``src/repro/launch/`` is
#: three levels below it).  The path is fixed because it is part of what a
#: later process must find again.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the compilation cache; returns the directory in use.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
