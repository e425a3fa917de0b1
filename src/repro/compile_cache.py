"""JAX's persistent compilation cache, as the entry points set it up.

Every plan variant (scheme, rung, slack, chunk size, owners) is its own
compiled program, and a cold process compiles each from scratch.  The
entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``)
call :func:`setup_compile_cache` once, after parsing their arguments —
never at import, so library users keep JAX's own default.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is changed.  Otherwise the cache goes to the fixed
directory ``<repo root>/.jax_cache`` (listed in ``.gitignore``).  The
path is fixed on purpose: a run finds entries only in the directory
where an earlier run left them.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
