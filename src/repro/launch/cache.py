"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

A cold call on the chip compiles every program (a 30-layer decode step, the
prefill buckets, the fleet program); the persistent cache lets processes and
runs that share a directory reuse what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

#: fixed in-checkout default: cache entries are found again only where the
#: directory stays put, so never a temporary name, a pid or the time
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing is set here. Otherwise the cache goes to
    ``<repo>/.jax_cache`` (listed in ``.gitignore``). Call it from a
    program's entry point, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
