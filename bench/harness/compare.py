"""The numbers that decide ``correct``, and their limits.

Every number is a gap between what the timed path produced and what the
plain reference computes from the same seed; each is held to the limit
that ``limits/<cell>.json`` gives it, and printed beside it.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, List, Sequence

import numpy as np


def leaf_norm_gap(prog: Sequence[float], ref: Sequence[float],
                  ref_grad: Sequence[float]) -> float:
    """Worst leaf of |norm_prog - norm_ref| / max(norm_ref, median leaf's
    norm_ref).  Leaves whose reference gradient is under a thousandth of
    the median leaf's are left out: rounding alone moves them."""
    prog, ref, g = (np.asarray(x, np.float64) for x in (prog, ref, ref_grad))
    keep = g >= 1e-3 * np.median(g)
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)
    return float(gaps.max())


def rel_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> (bool, Dict[str, dict]):
    """(correct, {name: {"value", "limit"}}).  A number that is not
    finite fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        out[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, out


def print_checks(checks: Dict[str, dict]) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
