"""Where JAX keeps its persistent compilation cache.

Call :func:`init_compile_cache` once per process, before the first
compile.  A run that finds a program it compiled before loads it instead
of compiling again, which matters most on a chip where one step program
takes tens of seconds to build.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout (gitignored): the cache is only
# found again under the same path, so no temporary name, pid or time
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
