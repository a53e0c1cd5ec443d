"""The chips a run uses, their peaks and their memory."""
from __future__ import annotations

import json
import os
import sys

from harness.cell import BENCH_DIR


def require(chips: int):
    """The first ``chips`` TPU devices; where they are not there, exit
    non-zero: the benchmark never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"this cell needs {chips} TPU chip(s); jax sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def describe() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``; 0 where the backend
    keeps no count (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).  A
    device that is not in the table is an error, not a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
