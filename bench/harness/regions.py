"""Device time by the program's own region names, and device idle time by
host span, from a profile's ``.xplane.pb``.

``tracing.reduce`` gives each program's and each leaf op's device time.
This reads what it leaves out.  Each XLA op's ``tf_op`` (read by
``harness/xspace.py``) is JAX's op path, which holds every
``jax.named_scope`` around the op; the leaf ops' device time (the same
leaf rule as ``tracing.reduce``) is summed by program and by innermost
region, a path component named ``<part>.<region>`` (``model.attn``,
``decode.layers``).  Ops whose path names no region count as ``(none)``,
ops without a path (what the compiler added, such as hoisted casts and
copies) as ``(no metadata)``.  The first device's idle time in the window
is split across the host spans over it by overlap, the innermost taking
each instant.

    python bench/harness/regions.py FILE.xplane.pb[.gz] [--window NAME]
        [--spans a,b,...] [--launch SPAN]

prints both.  The benchmark's runs do not call it: their per-layer
readers see only ``tracing.Trace`` (PERF.md section 7).
"""
from __future__ import annotations

import argparse
import dataclasses
import heapq
import os
import re
import sys
from typing import Collection, Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import tracing, xspace  # noqa: E402

NO_REGION = "(none)"
NO_METADATA = "(no metadata)"
DEVICE = r"/device:(TPU|GPU):\d+$"
_REGION = re.compile(r"[A-Za-z_]\w*\.[A-Za-z_]\w*")


def region_of(tf_op: str) -> str:
    """The innermost region named in an op path such as
    ``jit(step)/vmap(transpose(jvp()))/model.layers/while/body/
    checkpoint/model.attn/dot_general:`` (of the first of merged paths
    that names one); ``(none)`` where it names none, ``(no metadata)``
    where there is no path."""
    if not tf_op:
        return NO_METADATA
    # ops the compiler merged carry their paths joined by ";"
    for path in tf_op.split(";"):
        for part in reversed(path.split("/")):
            for word in reversed(re.findall(r"[\w.]+", part)):
                if _REGION.fullmatch(word):
                    return word
    return NO_REGION


def split_idle(idle, events) -> Dict[str, float]:
    """Seconds of the ``idle`` intervals [(a, b)] (ns, sorted, disjoint)
    under each host event's name: at each instant the shortest event over
    it, which for nested spans is the innermost; ``none`` where no event
    is over it."""
    pts = sorted({x for a, b in idle for x in (a, b)}
                 | {x for a, b, _ in events for x in (a, b)})
    evs = sorted(events)
    out: Dict[str, float] = {}
    heap, k, g = [], 0, 0
    for p, q in zip(pts, pts[1:]):
        while g < len(idle) and idle[g][1] <= p:
            g += 1
        if g == len(idle):
            break
        while k < len(evs) and evs[k][0] <= p:
            a, b, n = evs[k]
            heapq.heappush(heap, (b - a, b, n))
            k += 1
        if idle[g][0] > p:
            continue
        while heap and heap[0][1] <= p:
            heapq.heappop(heap)
        name = heap[0][2] if heap else "none"
        out[name] = out.get(name, 0.0) + (q - p) / 1e9
    return out


@dataclasses.dataclass
class Regions:
    # program -> innermost region -> seconds of its leaf ops
    regions: Dict[str, Dict[str, float]]
    calls: Dict[str, int]                  # program -> runs in the window
    op_paths: Dict[str, str]               # leaf op label -> tf_op or ""
    op_seconds: Dict[str, float]           # leaf op label -> seconds
    idle: List[Tuple[int, int]]            # first device's gaps (ns)
    runs: List[Tuple[int, int]]            # first device's program runs
    host: List[Tuple[int, int, str]]       # host events over the window

    def region_s(self, names: Collection[str],
                 program: Optional[str] = None) -> float:
        """Device seconds of the leaf ops whose innermost region is one
        of ``names``, in ``program`` or in all."""
        progs = [self.regions.get(program, {})] if program else \
            self.regions.values()
        return float(sum(r.get(n, 0.0) for r in progs for n in names))

    def launch_lag_ns(self, launch: str) -> int:
        """How far the device's timeline runs early against the host's,
        for an engine that launches one program run at a time: the
        largest lead of the k-th ``launch`` span's start over the k-th
        run's start (a run cannot start before its launch); 0 where none
        leads."""
        starts = sorted(a for a, _, n in self.host if n == launch)
        return max([0] + [s - a for s, (a, _) in zip(starts, self.runs)])

    def idle_by_span(self, names: Optional[Collection[str]] = None,
                     launch: Optional[str] = None) -> Dict[str, float]:
        """Idle seconds of the first device by the innermost host event
        over them, among those named in ``names`` (all where None);
        ``none`` where none of them is.  With ``launch`` the host events
        first move onto the device's clock (``launch_lag_ns``)."""
        lag = self.launch_lag_ns(launch) if launch else 0
        events = [(a - lag, b - lag, n) for a, b, n in self.host
                  if names is None or n in names]
        return split_idle(self.idle, events)


def _program(module: str) -> Tuple[str, Optional[int]]:
    """``jit_step(1323...)`` -> (``jit_step``, 1323...)."""
    name, _, rest = module.partition("(")
    pid = rest.rstrip(")")
    return name, int(pid) if pid.isdigit() else None


def _tf_ops(entries) -> Dict[object, str]:
    """An op's ``tf_op`` ("" where it has none) by (program id, op text),
    and by op text alone for ops found in no program."""
    out: Dict[object, str] = {}
    for name, stats in entries:
        tf_op, key = stats.get("tf_op", ""), (stats.get("program_id"), name)
        out[key] = out.get(key) or tf_op
        if tf_op:
            out.setdefault(name, tf_op)
    return out


def read(path: str, window: str = tracing.WINDOW) -> Optional[Regions]:
    """The ``Regions`` of the first host span named ``window``; None where
    there is no such span or no device.  ``path`` may be gzipped."""
    import bisect
    import gzip

    import jax
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    meta = xspace.event_metadata(raw, DEVICE)
    host, devices = [], []
    for plane in jax.profiler.ProfileData.from_serialized_xspace(raw).planes:
        lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events]
                 for line in plane.lines}
        if plane.name.startswith("/host:"):
            host += [e for events in lines.values() for e in events]
        elif re.match(DEVICE, plane.name):
            devices.append((lines, _tf_ops(meta.get(plane.name, []))))
    marks = [e for e in host if e[2] == window]
    if not marks or not devices:
        return None
    t0, t1 = marks[0][0], marks[0][1]
    out = Regions(regions={}, calls={}, op_paths={}, op_seconds={},
                  idle=[], runs=[],
                  host=[e for e in host
                        if e[2] != window and e[0] < t1 and e[1] > t0])
    for d, (lines, tf_op) in enumerate(devices):
        runs = sorted(lines.get("XLA Modules", []))
        starts = [r[0] for r in runs]
        ops = [e for e in lines.get("XLA Ops", []) if t0 <= e[0] < t1]
        for a, b, n in tracing._leaves(ops):
            i = bisect.bisect_right(starts, a) - 1
            prog, pid = (_program(runs[i][2]) if i >= 0 and a < runs[i][1]
                         else ("(no program)", None))
            k = tracing.op_label(n)
            out.op_paths[k] = tf_op[(pid, n)] if (pid, n) in tf_op \
                else tf_op.get(n, "")
            out.op_seconds[k] = out.op_seconds.get(k, 0.0) + (b - a) / 1e9
            r = out.regions.setdefault(prog, {})
            region = region_of(out.op_paths[k])
            r[region] = r.get(region, 0.0) + (b - a) / 1e9
        inside = [r for r in runs if t0 <= r[0] < t1]
        for a, b, n in inside:
            prog = _program(n)[0]
            out.calls[prog] = out.calls.get(prog, 0) + 1
        if d == 0:
            busy = tracing._union([(a, min(b, t1)) for a, b, _ in ops])
            edges = [t0] + [x for ab in busy for x in ab] + [t1]
            out.idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                        if b > a]
            out.runs = [(a, b) for a, b, _ in inside]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--window", default=tracing.WINDOW)
    ap.add_argument("--spans", default="",
                    help="comma-separated host spans to split idle time "
                         "by (default: every host event)")
    ap.add_argument("--launch", default="",
                    help="the host span that launches each program run")
    args = ap.parse_args(argv)
    r = read(args.path, args.window)
    if r is None:
        print(f"no span {args.window!r} with device time in {args.path}")
        return 1
    for prog, regs in sorted(r.regions.items()):
        calls, total = r.calls.get(prog, 1) or 1, sum(regs.values())
        print(f"{prog}: {calls} runs, leaf ops {1e3 * total / calls:.3f} "
              f"ms a run")
        for k, s in sorted(regs.items(), key=lambda kv: -kv[1]):
            print(f"  {k:28s} {1e3 * s / calls:10.3f} ms  "
                  f"{100 * s / total:6.2f}%")
    bare = sorted(((s, k) for k, s in r.op_seconds.items()
                   if not r.op_paths[k]), reverse=True)[:5]
    print("largest ops without metadata (ms over the window):")
    for s, k in bare:
        print(f"  {1e3 * s:10.3f}  {k}")
    spans = [s for s in args.spans.split(",") if s] or None
    idle = r.idle_by_span(spans, args.launch or None)
    lag = r.launch_lag_ns(args.launch) if args.launch else 0
    print(f"idle by span (ms over the window; host moved {lag / 1e6:.3f} "
          f"ms onto the device's clock):")
    for k, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {k:28s} {1e3 * s:10.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
