"""Opt-in ``jax.profiler`` capture, gated on ``REPRO_PROFILE=dir``.

With the env var unset ``capture`` is a no-op (jax is never imported
from here — this module must stay importable in the jax-free timing-only
proc workers).  With ``REPRO_PROFILE=/some/dir``, ``capture(name)``
wraps a region in ``jax.profiler.trace``, writing a TensorBoard-loadable
profile to ``$REPRO_PROFILE/<name>``.

What the capture shows needs nothing from here: the jitted steps name
their regions with plain ``jax.named_scope``s (``model.*``, ``train.*``,
``outer.*``, ``decode.*``), always on, and ``obs.Tracer`` spans enter
``jax.profiler.TraceAnnotation`` once jax is imported.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional


def profile_dir() -> Optional[str]:
    d = os.environ.get("REPRO_PROFILE", "").strip()
    return d or None


@contextlib.contextmanager
def capture(name: str):
    """Profile a region into ``$REPRO_PROFILE/<name>`` (no-op if unset)."""
    d = profile_dir()
    if d is None:
        yield
        return
    import jax
    path = os.path.join(d, name)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield
