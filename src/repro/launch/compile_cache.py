"""Where JAX keeps its persistent compilation cache.

One helper, called by every entry point that compiles (the launchers and
``chip_smoke.py``) before its first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
  helper sets no other directory;
* otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path.  The
  path is part of nothing else: no temp name, process id or time, so a
  later process in the same checkout finds what an earlier one wrote.

Kernels compile in a second or two, so every compile is cached (minimum
compile time 0).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return directory
