"""Host spans, the profiler capture, and the reduction of a device trace.

``Spans`` records named host intervals on ``time.perf_counter``; with
``annotate`` each also goes into the profiler's host timeline, so the
trace can say what the host was doing during a device gap.

``reduce`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
returns a ``Trace``: over the host span that marks the measured window,
the busy time of each device (union of its op intervals), the device time
of each jitted program, of each leaf operation, and the idle gaps with the
host span in which each fell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str, **_):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [b - a for n, a, b in self.records if n == name]

    def within(self, name: str, outer: str) -> List[List[float]]:
        """For each ``outer`` span, the durations of the ``name`` spans
        that lie inside it."""
        inner = [(a, b) for n, a, b in self.records if n == name]
        return [[b - a for a, b in inner if t0 <= a and b <= t1]
                for n, t0, t1 in self.records if n == outer]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed region into ``log_dir``: device and host
    timelines, no Python-function tracing (it would dwarf the rest)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            yield


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(name: str) -> str:
    """An HLO op event's text without layouts and operands, e.g.
    ``%convert.14 = bf16[24,2048,8192] convert``."""
    return _LAYOUT.sub("", name).split("(", 1)[0].strip()[:120]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _leaves(events):
    """Events that contain no other event of their line (a while loop's
    op spans its body's ops)."""
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves, stack = [], []
    for e in ev:
        while stack and stack[-1][1] <= e[0]:
            leaves.append(stack.pop()) if not stack[-1][3] else stack.pop()
        if stack:
            stack[-1][3] = True
        stack.append([e[0], e[1], e[2], False])
    while stack:
        top = stack.pop()
        if not top[3]:
            leaves.append(top)
    return [(a, b, n) for a, b, n, _ in leaves]


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float                          # mean over devices
    modules: Dict[str, List[float]]        # jit name -> seconds per call
    ops: Dict[str, List[float]]            # leaf op label -> seconds
    op_names: Dict[str, str]               # leaf op label -> full text
    gaps: List[Tuple[str, float]]          # (host span, seconds), longest first

    def op_seconds(self, pattern: str) -> Tuple[int, float]:
        """(calls, seconds) of leaf ops whose full text matches."""
        rx = re.compile(pattern)
        hits = [sum(v) for k, v in self.ops.items()
                if rx.search(self.op_names[k])]
        return (sum(len(self.ops[k]) for k in self.ops
                    if rx.search(self.op_names[k])), float(sum(hits)))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(((k, sum(v)) for k, v in self.ops.items()),
                     key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, s] for k, s in top],
                "idle_gaps": [[k, s] for k, s in self.gaps[:n]]}


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{len(files)}")
    return files[0]


def reduce(path: str, window: str = WINDOW) -> Optional[Trace]:
    """The ``Trace`` of the first host span named ``window``; None where
    the file holds no device events in it.  ``path`` may be gzipped."""
    import gzip
    import jax
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_lines += [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events] for line in plane.lines]
        elif re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in plane.lines}
            devices.append(lines)
    marks = [e for line in host_lines for e in line if e[2] == window]
    if not marks or not devices:
        return None
    t0, t1 = marks[0][0], marks[0][1]
    inside = lambda e: t0 <= e[0] < t1
    busy, modules, ops, names = [], {}, {}, {}
    first_union = None
    for lines in devices:
        op_ev = [e for e in lines.get("XLA Ops", []) if inside(e)]
        u = _union([(a, min(b, t1)) for a, b, _ in op_ev])
        busy.append(sum(b - a for a, b in u))
        if first_union is None:
            first_union = u
        for a, b, n in lines.get("XLA Modules", []):
            if inside((a, b, n)):
                modules.setdefault(n.split("(")[0], []).append((b - a) / 1e9)
        for a, b, n in _leaves(op_ev):
            k = op_label(n)
            ops.setdefault(k, []).append((b - a) / 1e9)
            names[k] = n
    if not any(busy):
        return None
    # idle gaps of the first device, named by the innermost host event
    # (the benchmark's spans and the runtime's own) over each gap's middle
    host = [e for line in host_lines for e in line if e[2] != window]
    edges = [t0] + [x for ab in first_union for x in ab] + [t1]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        over = [e for e in host if e[0] <= mid < e[1]]
        label = min(over, key=lambda e: e[1] - e[0])[2] if over else "none"
        gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Trace(window_s=(t1 - t0) / 1e9,
                 busy_s=sum(busy) / len(busy) / 1e9,
                 modules=modules, ops=ops, op_names=names, gaps=gaps)
