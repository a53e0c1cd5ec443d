"""Chrome-trace-event / Perfetto export of per-round phase spans.

Both sim backends attach a flat span list to every ``RoundEvent``
(``RoundEvent.spans``): tuples ``(name, cluster, start_s, dur_s)`` with
``start_s`` relative to the round's own start.  The in-process simulator
records **modeled** spans (derived from the same
``topology/accounting.compute_leg`` arithmetic that fills the timing
fields); proc workers time their **measured** phases with
``time.monotonic`` and ship the records inside the existing round-report
payload.  This module is a pure consumer: it lays the spans out on a
global clock (cumulative ``t_round_s`` offsets) and emits the Chrome
trace-event JSON that ``chrome://tracing`` / https://ui.perfetto.dev
load directly.

Span taxonomy (one lane pair per cluster):

  ===========  =====  =================================================
  name         lane   meaning
  ===========  =====  =================================================
  inner        0      H local AdamW steps (the compute leg)
  idle         0      barrier wait after own compute (straggler waste)
  stale_wait   0      bounded_stale: staleness-gate wait after the leg
                      (the async replacement for barrier ``idle``)
  leg          0      bounded_stale: per-cluster leg envelope (compute
                      + gate wait); carries the commit's ``staleness``
                      and ``round_clock`` in its ``args``
  compress     1      compressor round-trip on the outgoing delta
  wire         1      payload on the wire (socket send / p2p exchange);
                      in bounded_stale mode the publish is emitted as a
                      ``b``/``e`` async pair because it legitimately
                      overlaps the gate wait and the next leg (§2.3
                      generalized)
  mix          1      applying the returned average / neighbor mixing
  outer        1      EF + outer Nesterov + param hash
  gather       1      coordinator-side gather phase (pid = coordinator)
  round        0      barrier mode: per-round envelope (pid =
                      coordinator row); its ``args`` carry the round's
                      comm accounting
  ===========  =====  =================================================

Lane 0 holds compute-side spans and lane 1 comm-side spans, so spans
nest without overlap within a ``(pid, tid)`` row even in delay mode
(where the comm thread genuinely runs concurrently with compute).

Clock layout depends on the outer-sync policy.  Barrier timelines place
round ``r`` at the cumulative ``t_round_s`` offset and wrap it in a
coordinator-row ``round`` envelope.  Bounded-stale timelines have no
global round — each event is one cluster's commit, placed at its own
``RoundEvent.t_start_s`` on the cluster's row, so Perfetto shows the
per-cluster round clocks drifting apart and re-converging; the ``leg``
span is the envelope and there is no coordinator round row.

``trace_fingerprint`` hashes the *structural* shape of a trace — event
names/categories/rows/round tags, never ``ts``/``dur`` — so identical-
seed runs compare equal even when wall clock differs.
"""
from __future__ import annotations

import hashlib
import json
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional

# pid of the coordinator/global row (clusters use their own id)
COORD_PID = 9999

_LANES = {"inner": 0, "idle": 0, "round": 0, "stale_wait": 0, "leg": 0,
          "compress": 1, "wire": 1, "mix": 1, "outer": 1, "gather": 1}


def _meta(kind: str, pid: int, name: str, tid: int = 0) -> Dict[str, Any]:
    return {"name": kind, "ph": "M", "ts": 0, "dur": 0, "pid": pid,
            "tid": tid, "args": {"name": name}}


def timeline_trace(tl: Any) -> Dict[str, Any]:
    """Convert a ``Timeline`` (either backend) to a Chrome trace dict.

    Every complete event carries ``args.round``; the per-round ``round``
    envelope on the coordinator row additionally carries the round's comm
    accounting (``t_comm_s`` / ``hidden_comm_s`` / ``exposed_comm_s`` /
    ``wire_bytes``) so the trace is self-describing in Perfetto.
    """
    scenario = tl.scenario if isinstance(tl.scenario, dict) else {}
    backend = scenario.get("backend", "model")
    cat = "measured" if backend == "proc" else "modeled"
    events: List[Dict[str, Any]] = []
    pids_seen: Dict[int, set] = {}

    def emit(name: str, pid: int, start_s: float, dur_s: float,
             args: Dict[str, Any]) -> None:
        tid = _LANES.get(name, 1)
        events.append({"name": name, "cat": cat, "ph": "X",
                       "ts": round(start_s * 1e6, 3),
                       "dur": round(max(0.0, dur_s) * 1e6, 3),
                       "pid": pid, "tid": tid, "args": args})
        pids_seen.setdefault(pid, set()).add(tid)

    def emit_pub(pid: int, start_s: float, dur_s: float, rnd: int) -> None:
        # async publish: a b/e pair (Chrome async events MAY overlap,
        # complete events in a row must nest — and an in-flight send
        # genuinely overlaps the gate wait and the next leg)
        base = {"name": "wire", "cat": cat, "pid": pid, "tid": 1,
                "id": int(rnd), "args": {"round": int(rnd)}}
        events.append({**base, "ph": "b", "ts": round(start_s * 1e6, 3)})
        events.append({**base, "ph": "e",
                       "ts": round((start_s + max(0.0, dur_s)) * 1e6, 3)})
        pids_seen.setdefault(pid, set()).add(1)

    is_async = any(e.t_start_s is not None for e in tl.events)
    offset = 0.0
    for e in tl.events:
        hidden = max(0.0, e.t_comm_s - e.exposed_comm_s)
        if is_async:
            # per-cluster round clocks: place the commit at its own leg
            # start; the cluster-row "leg" span is the envelope (there is
            # no global round, so no coordinator round row)
            off = float(e.t_start_s or 0.0)
        else:
            off = offset
            emit("round", COORD_PID, off, e.t_round_s,
                 {"round": e.round, "t_comm_s": round(e.t_comm_s, 6),
                  "hidden_comm_s": round(hidden, 6),
                  "exposed_comm_s": round(e.exposed_comm_s, 6),
                  "wire_bytes": e.wire_bytes})
            offset += e.t_round_s
        for span in (e.spans or ()):
            name, cluster, start_s, dur_s = span
            pid = COORD_PID if int(cluster) < 0 else int(cluster)
            if is_async and str(name) == "wire":
                emit_pub(pid, off + float(start_s), float(dur_s), e.round)
                continue
            args: Dict[str, Any] = {"round": e.round}
            if is_async and str(name) == "leg":
                args.update(
                    cluster=e.cluster,
                    staleness={int(p): int(s)
                               for p, s in (e.staleness or ())},
                    round_clock=list(e.round_clock or ()))
            emit(str(name), pid, off + float(start_s), float(dur_s), args)

    meta: List[Dict[str, Any]] = []
    for pid in sorted(pids_seen):
        pname = ("coordinator" if pid == COORD_PID else f"cluster {pid}")
        meta.append(_meta("process_name", pid, pname))
        for tid in sorted(pids_seen[pid]):
            if pid == COORD_PID:
                tname = "rounds" if tid == 0 else "gather"
            else:
                tname = "compute" if tid == 0 else "comm"
            meta.append(_meta("thread_name", pid, tname, tid))

    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "otherData": {"backend": backend, "category": cat,
                          "n_rounds": len(tl.events)}}


def validate_chrome_trace(trace: Any) -> List[str]:
    """Schema check; returns a list of error strings (empty = valid).

    Checks: the dict serializes to JSON, ``traceEvents`` is a list of
    objects each carrying ``name``/``ph``/``ts``/``pid``/``tid`` (plus a
    non-negative ``dur`` for complete events), and within every
    ``(pid, tid)`` row the complete events nest without partial overlap.
    """
    errs: List[str] = []
    if not isinstance(trace, dict):
        return ["trace is not a JSON object"]
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as e:
        errs.append(f"trace is not JSON-serializable: {e}")
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return errs + ["traceEvents missing or not a list"]

    lanes: Dict[Any, List] = {}
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            errs.append(f"event {i}: not an object")
            continue
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in ev:
                errs.append(f"event {i}: missing {k!r}")
        if ev.get("ph") == "X":
            if not isinstance(ev.get("dur"), (int, float)):
                errs.append(f"event {i}: complete event needs numeric "
                            f"'dur' (got {ev.get('dur')!r})")
            elif ev["dur"] < 0:
                errs.append(f"event {i}: negative dur")
            elif isinstance(ev.get("ts"), (int, float)):
                lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append(
                    (float(ev["ts"]), float(ev["dur"]), i))
            else:
                errs.append(f"event {i}: non-numeric ts")

    eps = 1.0  # µs of float-rounding slack
    for (pid, tid), rows in lanes.items():
        rows.sort(key=lambda t: (t[0], -t[1]))
        stack: List[float] = []          # open span end times
        for ts, dur, i in rows:
            while stack and ts >= stack[-1] - eps:
                stack.pop()
            if stack and ts + dur > stack[-1] + eps:
                errs.append(f"event {i}: span overlaps (not nested in) "
                            f"the enclosing span in row pid={pid} "
                            f"tid={tid}")
                continue
            stack.append(ts + dur)
    return errs


def trace_fingerprint(trace: Dict[str, Any]) -> str:
    """Structural hash of a trace: event names, phases, categories, rows,
    and round tags — never ``ts``/``dur`` or any other wall-clock field.
    Identical-seed runs must produce identical structural fingerprints on
    the in-process backend; proc runs are wall-clock-noisy but keep the
    same row/name structure for a deterministic scenario."""
    rows = [[ev.get("ph"), ev.get("name"), ev.get("cat"), ev.get("pid"),
             ev.get("tid"), (ev.get("args") or {}).get("round")]
            for ev in trace.get("traceEvents", [])
            if isinstance(ev, dict)]
    blob = json.dumps(rows, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()


def save(trace: Dict[str, Any], path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f, indent=1)


class Tracer:
    """Wall-clock span recorder for driver code (``launch/train.py``):
    ``with tracer.span("outer"): ...`` records a measured complete event.
    Threads map to tids in first-seen order, so concurrent spans land on
    separate rows and the nesting invariant holds per row.

    Once jax is imported (by the caller: this module never imports it)
    each span also enters ``jax.profiler.TraceAnnotation``, so a profiler
    capture shows these spans on the device timeline's clock."""

    def __init__(self, process: str = "driver", pid: int = 0):
        self.pid = pid
        self.process = process
        self._t0 = time.monotonic()
        self._tids: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []

    def _tid(self) -> int:
        key = threading.get_ident()
        with self._lock:
            return self._tids.setdefault(key, len(self._tids))

    @contextmanager
    def span(self, name: str, **args: Any):
        jax = sys.modules.get("jax")
        ann = (jax.profiler.TraceAnnotation(name) if jax is not None
               else nullcontext())
        start = time.monotonic()
        try:
            with ann:
                yield
        finally:
            end = time.monotonic()
            ev = {"name": name, "cat": "measured", "ph": "X",
                  "ts": round((start - self._t0) * 1e6, 3),
                  "dur": round((end - start) * 1e6, 3),
                  "pid": self.pid, "tid": self._tid()}
            if args:
                ev["args"] = args
            with self._lock:
                self.events.append(ev)

    def trace(self) -> Dict[str, Any]:
        meta = [_meta("process_name", self.pid, self.process)]
        for tid in sorted(self._tids.values()):
            meta.append(_meta("thread_name", self.pid,
                              f"thread {tid}", tid))
        return {"traceEvents": meta + list(self.events),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        save(self.trace(), path)


def main(argv: Optional[List[str]] = None) -> None:
    """CLI validator: ``python -m repro.obs.trace FILE...`` exits non-zero
    if any file fails the Chrome-trace schema check."""
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m repro.obs.trace TRACE.json [...]",
              file=sys.stderr)
        sys.exit(2)
    bad = 0
    for path in argv:
        try:
            with open(path) as f:
                trace = json.load(f)
        except (OSError, ValueError) as e:
            print(f"{path}: unreadable ({e})")
            bad += 1
            continue
        errs = validate_chrome_trace(trace)
        if errs:
            bad += 1
            print(f"{path}: INVALID ({len(errs)} errors)")
            for e in errs[:20]:
                print(f"  - {e}")
        else:
            n = sum(1 for ev in trace.get("traceEvents", [])
                    if ev.get("ph") == "X")
            print(f"{path}: ok ({n} spans, fingerprint "
                  f"{trace_fingerprint(trace)[:16]})")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
