"""Where JAX keeps compiled programs between runs — one fixed place.

The persistent compilation cache is keyed partly by its directory, so
a directory built from a temp name, a pid or the time never hits.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the BFS
examples) call `enable` before their first compile; importing the
library sets nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fallback cache directory: ``.jax_cache`` at the checkout root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads the variable itself, so nothing else is
    set here), else at `DEFAULT_DIR`.  Returns the directory in use."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
