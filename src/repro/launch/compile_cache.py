"""Where a process started from this checkout keeps compiled programs.

JAX's persistent compilation cache lets a second run skip the compiles of
the first.  The entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
``examples/`` mains) call :func:`use_compile_cache` before they compile
anything; importing ``repro`` never does, so a library import does not
decide where a host application caches its programs.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, so every run of this checkout finds
# what the previous one wrote (gitignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` set in the environment is JAX's own
    setting and is left alone.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
