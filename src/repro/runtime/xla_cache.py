"""JAX's persistent compilation cache, placed by the entry points.

``chip_smoke.py``, ``repro.launch.cluster_serve`` and the ``benchmarks/``
mains call ``use_persistent_cache()`` once, before their first compile.
Importing the library never does, and neither do the tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
directory is set here. Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path (never a temporary name, a pid or
a timestamp), because the directory is what a later run has to find.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_persistent_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every compile: a cold run pays each one, and the Pallas
    # kernels compile in well under JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
