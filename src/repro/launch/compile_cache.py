"""The persistent XLA compilation cache shared by every entry point.

``chip_smoke.py``, ``benchmarks/run.py`` and ``python -m
repro.launch.serve_tc`` call :func:`enable_compile_cache` before their
first compile, so a second run of any of them in the same checkout
reads its programs back instead of compiling them cold.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at one fixed,
git-ignored directory inside the checkout (``<repo>/.jax_cache``): the
directory is part of what makes an entry found again, so it is never
derived from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache directory used when the environment names none
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
