"""Unified telemetry layer: span tracing, metrics, overlap ledger.

Everything here is strictly read-only off the numeric path — the modules
*consume* ``Timeline``/``RoundEvent`` data (or wall-clock measurements the
backends already take) and never feed anything back into the round math,
so the proc ≡ in-process bitwise gates are untouched by tracing.

 - ``obs.trace``   — Chrome-trace-event / Perfetto JSON export of the
   per-round phase spans both sim backends record (modeled on the
   in-process backend, measured wall clock on proc), plus a schema
   validator and a wall-clock ``Tracer`` for the launchers (its spans
   also go into the profiler's host timeline once jax is imported).
 - ``obs.metrics`` — counters/gauges/histograms populated from
   ``RoundEvent`` fields, with a JSONL sink and Prometheus text
   exposition.
 - ``obs.ledger``  — the §2.3 overlap claim as numbers: per-round
   hidden/exposed comm seconds, overlap efficiency, modeled-vs-measured
   drift on the proc backend.
 - ``obs.log``     — structured logger replacing ad-hoc ``print()``
   paths (human-readable stream + optional JSON lines).
 - ``obs.profile`` — opt-in ``jax.profiler`` capture
   (``REPRO_PROFILE=dir``), importing jax lazily.  The jitted steps name
   their regions with plain ``jax.named_scope``s, always on.

``import repro.obs`` stays jax-free: the proc backend's timing-only
workers must keep spawning without a jax import.
"""
from repro.obs.ledger import LedgerRow, OverlapLedger
from repro.obs.log import configure as configure_logging
from repro.obs.log import get_logger
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import (Tracer, timeline_trace, trace_fingerprint,
                             validate_chrome_trace)

__all__ = [
    "LedgerRow", "OverlapLedger", "MetricsRegistry", "Tracer",
    "configure_logging", "get_logger", "timeline_trace",
    "trace_fingerprint", "validate_chrome_trace",
]
