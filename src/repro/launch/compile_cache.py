"""Where the persistent XLA compilation cache lives for chip runs.

JAX keys its persistent cache on the directory path, so the directory
must be the same from one run to the next. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
set in code; otherwise the cache goes to `.jax_cache/` at the repository
root (git-ignored). Entry points that run on the chip call
`enable_compile_cache()` once, before their first compile; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(environ=None) -> str:
    """The directory the persistent cache uses under `environ`."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
