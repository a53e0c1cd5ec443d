"""Tier-1 test configuration.

 1. Keep tier-1 fast: tests marked ``@pytest.mark.slow`` (multi-minute
    subprocess/integration runs) are skipped unless ``--runslow`` is given
    or an explicit ``-m slow`` selection asks for them.
 2. Compile once: XLA's backend optimization is turned down and jax's
    persistent compilation cache is on (``repro.compile_cache``).
"""
import os

import pytest

# --- shave XLA compile time -----------------------------------------------
# Tier-1 is compile-bound (dozens of tiny-model jits); backend optimization
# level 0 cuts compile ~30% with no effect on what the tests assert.  Set
# REPRO_FULL_XLA_OPT=1 to opt out.  Must run before jax initializes.
if not os.environ.get("REPRO_FULL_XLA_OPT"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_backend_optimization_level" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_backend_optimization_level=0").strip()

# --- persistent jax compilation cache --------------------------------------
# The remaining tier-1 cost is the per-arch value_and_grad compiles; the
# cache makes re-runs skip them.  Same rule as every entry point
# (``repro.compile_cache``): $JAX_COMPILATION_CACHE_DIR when set, else the
# checkout's git-ignored ``.jax_cache``; subprocess tests inherit it.  Opt
# out with REPRO_NO_JAX_CACHE=1.
if not os.environ.get("REPRO_NO_JAX_CACHE"):
    # default min-compile-time is 1 s; at 0.5 s the mid-sized jits (sim
    # numeric rounds, compressor roundtrips) get cached too.  Set before
    # ``compile_cache`` imports jax, which reads it once, at import
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0.5")
    from repro import compile_cache
    compile_cache.enable()


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked slow (multi-minute integration)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-minute integration test; deselected from "
                   "tier-1 unless --runslow (or -m slow) is given")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    if "slow" in (config.getoption("-m") or ""):
        return      # explicit -m selection wins
    skip_slow = pytest.mark.skip(reason="slow: use --runslow or -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
