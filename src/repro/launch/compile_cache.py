"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point (``chip_smoke.py``, the ``repro.launch.*`` programs,
``benchmarks/run.py``) calls ``enable_compile_cache()`` before it compiles
anything, so a second run of the same shapes reads its executables back
instead of compiling them again.

* When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
  this sets nothing.
* Otherwise the cache lives in ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed on purpose: it holds no temporary
  directory, process id or time, so every run of a checkout finds it.

Importing this module changes nothing; only the call does.
"""

from __future__ import annotations

import os
from pathlib import Path


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    # src/repro/launch/compile_cache.py: the checkout root is parents[3]
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
