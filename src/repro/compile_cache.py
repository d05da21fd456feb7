"""Where JAX keeps compiled programs between runs.

Every entry point (the CLIs, ``examples/``, ``benchmarks/run.py`` and
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is changed here.  Otherwise the cache lives in
``.jax_cache`` at the root of the checkout: a fixed path, because the
directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
