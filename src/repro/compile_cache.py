"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (the mining and streaming CLIs, the benchmark harness and
``chip_smoke.py``) call :func:`use_compile_cache` once at start-up; library
code and tests never do, so importing ``repro`` changes no JAX setting.

A directory named by ``JAX_COMPILATION_CACHE_DIR`` wins, and JAX already
reads it from the environment, so nothing else is set then. Otherwise the
cache lives at the fixed path ``<checkout>/.jax_cache``: the directory is
part of what a later run must find again, so it never depends on a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]
