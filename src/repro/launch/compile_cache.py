"""Where JAX's persistent compilation cache lives.

One helper for every entry point that compiles a full-width step
(``launch/serve.py``, ``chip_smoke.py``, ``benchmarks/run.py``): a cold
compile of a published-width step costs tens of seconds, and the cache
turns the second run's compiles into reads.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``
(gitignored) — a fixed path, because the directory is part of what a
later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
