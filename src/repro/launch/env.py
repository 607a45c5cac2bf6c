"""Process-level setup for the entry points (``chip_smoke.py``, train,
serve, the benchmarks, the autotuner): JAX's persistent compilation
cache.

One call, before the first compile::

    from repro.launch.env import setup_environment
    setup_environment()

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
leaves it alone: the cache goes there and nowhere else. Otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout. The path is
part of each entry's key, so it is fixed: a second run from the same
checkout finds the first run's programs and skips their compiles.
``.gitignore`` lists the directory.
"""
from __future__ import annotations

import os
from typing import Dict

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def setup_environment() -> Dict[str, str]:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring). Returns ``{"compilation_cache_dir": path}``
    for logging."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return {"compilation_cache_dir": path}
