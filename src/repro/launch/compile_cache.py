"""Where JAX keeps its persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, so
    nothing is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, so a later run from the same checkout finds what an
    earlier one compiled.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
