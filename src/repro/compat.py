"""Process-level JAX settings shared by the simulator and its entry points.

* :func:`scoped_x64` — the float64 context every StreamSim kernel and
  device program runs under, leaving the process-global default at x32.
* :func:`enable_compile_cache` — JAX's persistent compilation cache at a
  stable path.  Only entry points (``chip_smoke.py``,
  ``benchmarks/run.py``) call it; importing a library module never turns
  the cache on.
"""

from __future__ import annotations

import os
from typing import Any

#: directory name of the default compile cache, inside the checkout
COMPILE_CACHE_DIRNAME = ".jax_cache"


def scoped_x64() -> Any:
    """Context manager enabling float64 for the calls made inside it."""
    import jax
    return jax.enable_x64(True)


def enable_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing
    else is configured (JAX reads the variable itself).  Otherwise the
    cache lives at ``<checkout>/.jax_cache``: a fixed path, because the
    path is part of what a later run must find again."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), COMPILE_CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
