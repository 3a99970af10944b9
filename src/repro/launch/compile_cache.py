"""Where JAX's persistent compilation cache lives, for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and no
other directory is set here. Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout, a fixed path (the path is part of the cache's
key, so a directory that moves never hits) that ``.gitignore`` lists.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
their first compile. Library modules and the test bootstrap never do.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent compilation cache on."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
