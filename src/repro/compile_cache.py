"""JAX's persistent compilation cache, placed from outside.

Paper-scale simulator programs take a minute each to compile for a TPU, so
entry points that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call `enable()` once at start-up. Library code and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
# fixed, so that every run of this checkout finds what an earlier one built;
# a per-run name would start empty every time
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and no other is set here; otherwise the cache lives at
    ``<repo>/.jax_cache``. Every compile is cached, however short, so a
    warm run compiles next to nothing.

    Source locations keep only the innermost frame. A Pallas kernel's body
    is serialized into its custom call with its locations, which the cache
    key does not strip; with the whole Python stack there, an edit that
    moved any line of a caller, or another entry script, changed the key
    of every program holding the kernel."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = os.environ.get(ENV_DIR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
