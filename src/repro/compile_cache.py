"""Where JAX keeps its persistent compilation cache.

A cold 30-layer serving step takes seconds to compile on a TPU, and each
trace family (prefill buckets, decode, verify) compiles its own program. The persistent cache keeps those programs between processes
that share a directory. Its path is part of what makes an entry hit, so
it never holds a temp name, a pid or a time.

``enable()`` is called by ``chip_smoke.py`` and the benchmark entry
points before anything compiles. ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and nothing here overrides it. Otherwise
the cache lives at ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
