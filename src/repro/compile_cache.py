"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (the launchers, ``chip_smoke.py``, the
test suite): ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it —
JAX reads that variable itself, so nothing else is set — and otherwise
``.jax_cache`` at the root of this checkout (git-ignored).  The path is
fixed because it is part of the cache key: a directory that moves never
hits.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.  The
    default directory is also exported through ``ENV`` so that child
    processes (simulator workers, subprocess tests) share it."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    os.environ[ENV] = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
