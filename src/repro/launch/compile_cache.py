"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and no other directory is set in code),
otherwise the fixed path ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(checkout) -> str:
    """The cache directory for a run from ``checkout``."""
    return os.environ.get(ENV_VAR) or str(Path(checkout).resolve()
                                          / ".jax_cache")


def enable_compile_cache(checkout) -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir(checkout)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
