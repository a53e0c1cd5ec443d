"""Coordinator for the multi-process backend: spawns one OS process per
virtual cluster, drives the outer rounds, and realizes the outer sync for
the scenario's topology.

Gather kinds (star/full): implements the hub outer sync as
``core.membership.masked_cluster_mean`` over the *live* connections — the
coordinator gathers each worker's compressed pseudo-gradient, masks out
dead/crashed members, and broadcasts the mean.  Both the §2.3 delayed round
and the synchronous (``delay=False``) round are supported: the protocol is
identical (round → delta → avg → done); a sync worker simply trains before
shipping.

Gossip kinds (ring/torus/random): the coordinator does NOT touch payloads.
Workers exchange compressed deltas directly over ``PeerMesh`` p2p links
along the topology's edges and mix them through their row of the masked
Metropolis-Hastings matrix; the coordinator only orchestrates membership
and faults — it hands out each round's peer addresses (+ spawn epochs, so
respawned neighbors are re-dialed), mixing-matrix rows, and modeled
rate/latency/compute targets, then collects per-replica ``done`` reports.
Per-cluster outer params legitimately diverge under gossip, so the round's
``param_hash`` is ``combine_row_hashes`` over the alive replicas' row
hashes, and a rejoiner bootstraps from the masked *mean* of the survivors'
(params, outer momentum) — the same arithmetic the in-process simulator
uses, keeping the two backends bit-for-bit comparable.

Per round it:
 1. applies the ``FaultSchedule`` membership events — ``Leave`` kills the
    worker process (SIGKILL, abrupt), ``Join`` respawns a fresh process;
 2. derives each worker's modeled targets (straggler-inflated compute
    seconds, token-bucket rate from the degraded/jittered link, and the
    topology's wire charge: ring all-gather ``(n_alive−1)·wire`` for
    gather, ``deg·wire`` on the own uplink for gossip) from the *same*
    deterministic arithmetic the in-process simulator uses
    (``repro.topology.accounting``);
 3. records a measured ``RoundEvent`` next to the deterministic structural
    fields that ``Timeline.structural_fingerprint()`` covers.

Unexpected worker death (socket EOF mid-round) is tolerated: the member is
masked out exactly like a scheduled ``Leave`` and the round completes with
the survivors — tagged ``crash(cN)`` on the timeline (gossip neighbors mix
zeros for the silent peer that round, tagged ``p2pmiss``).
"""
from __future__ import annotations

import json
import os
import queue
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import comm
from repro.sim.engine import BoundedStaleEngine, run_barrier
from repro.sim.scenario import Scenario
from repro.sim.timeline import (RoundEvent, Timeline, combine_row_hashes,
                                tree_hash)

# repro.core.compression (-> jax) is imported inside run_proc: the worker
# module executes this package's __init__ on spawn, and timing-only workers
# must not pay a jax import for it.


def _src_root() -> str:
    import repro
    pkg_dir = (os.path.dirname(repro.__file__) if repro.__file__
               else list(repro.__path__)[0])      # namespace package
    return os.path.dirname(os.path.abspath(pkg_dir))


class WorkerDied(Exception):
    pass


# deterministic ordering for the merged per-round span list (wall-clock
# starts are noisy, so sorting by start alone would make the trace's event
# order nondeterministic across runs of the same scenario)
_SPAN_ORDER = {"gather": 0, "inner": 1, "idle": 2, "compress": 3,
               "wire": 4, "mix": 5, "outer": 6}


class _Handle:
    """One worker: process, connection, and a reader thread that turns the
    socket into a message queue (so the coordinator never blocks on one
    member while another is ready)."""

    def __init__(self, cluster: int, proc: subprocess.Popen):
        self.cluster = cluster
        self.proc = proc
        self.conn: Optional[socket.socket] = None
        self.p2p_port: Optional[int] = None
        self.q: "queue.Queue[Any]" = queue.Queue()
        self.dead = False

    def attach(self, conn: socket.socket) -> None:
        self.conn = conn
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        t = threading.Thread(target=self._reader, daemon=True)
        t.start()

    def _reader(self) -> None:
        from repro.sim.proc.transport import recv_frame
        try:
            while True:
                self.q.put(recv_frame(self.conn))
        except (ConnectionError, OSError, ValueError, EOFError):
            self.q.put({"type": "_eof"})

    def send(self, obj: Any) -> bool:
        from repro.sim.proc.transport import send_frame
        if self.dead or self.conn is None:
            return False
        try:
            send_frame(self.conn, obj)
            return True
        except OSError:
            self.dead = True
            return False

    def get(self, want: str, timeout: float) -> Optional[Dict[str, Any]]:
        """Next message of type ``want``; None if the worker died/timed out
        first (marks the handle dead)."""
        if self.dead:
            return None
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.q.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                self.dead = True
                return None
            if msg.get("type") == "_eof":
                self.dead = True
                return None
            if msg.get("type") == want:
                return msg
            # unexpected type: drop (stale frame from a killed round)

    def kill(self) -> None:
        self.dead = True
        try:
            self.proc.kill()
        except OSError:
            pass
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass


def _spawn(cluster: int, port: int, sc: Scenario, problem, gossip: bool,
           epoch: int, crash_at: Optional[Dict[int, int]]) -> subprocess.Popen:
    cfg = {
        "host": "127.0.0.1",
        "port": port,
        "cluster": cluster,
        "n_clusters": sc.n_clusters,
        "problem": problem.to_dict() if problem is not None else None,
        "compressor": {"name": sc.compressor, "kw": dict(sc.compressor_kw)},
        "rank": sc.rank,
        # adaptive compression: the coordinator broadcasts the controller's
        # per-round decision in the round header; workers compress with it
        # (and, in spectral modes, report their pending delta back as the
        # controller's rank signal)
        "adaptive_rank": (sc.adaptive is not None
                          and sc.adaptive.mode != "off"),
        "report_pending": (sc.adaptive is not None
                           and sc.adaptive.needs_spectral),
        "warm_rank": (None if sc.adaptive is None
                      else sc.adaptive.r1),
        # heterogeneous local-step scheduling: the coordinator broadcasts
        # each worker's per-round H in the round header; numeric workers
        # compile the masked fixed-length inner scan once (H traced)
        "dynamic_h": (sc.h_spec is not None and sc.h_spec.active),
        # bounded-stale async workers run the synchronous gather arm with
        # classic compressor-local EF: publish-at-finish overlap is modeled
        # by the engine, not by the worker's §2.3 comm thread
        "delay": sc.delay and sc.sync != "bounded_stale",
        "gossip": gossip,
        "classic_ef": sc.sync == "bounded_stale",
        "epoch": epoch,
        "crash_at_round": (crash_at or {}).get(cluster),
    }
    env = os.environ.copy()
    src = _src_root()
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    # the process backend is a CPU model of a WAN fleet: workers run on the
    # host's CPU, never on an accelerator the parent may hold (one process
    # per chip), and the coordinator's own numeric work is pinned likewise
    # (``run_proc``)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "repro.sim.proc.worker", json.dumps(cfg)],
        env=env)


def _stack_rows(rows: List[Any]):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                        *rows)


def run_proc(sc: Scenario, problem=None, *,
             crash_at: Optional[Dict[int, int]] = None,
             spawn_timeout_s: float = 300.0,
             round_timeout_s: float = 300.0,
             p2p_timeout_s: float = 30.0) -> Timeline:
    """Run the scenario on real processes + sockets; returns a Timeline
    whose seconds are *measured* wall clock and whose structural fields
    (participants, wire accounting, per-round param hashes) are
    deterministic and bit-comparable with ``simulate()``.

    ``problem`` is a ``sim.quadratic.QuadraticSpec`` (or None for
    timing-only workers, which skip jax entirely).  ``crash_at`` maps
    cluster -> round for injected hard crashes (``os._exit`` before the
    delta send — the membership-recovery test hook).

    Everything runs on the CPU: the workers by ``_spawn``'s environment,
    the coordinator's gather-mean under a CPU default device.
    """
    kw = dict(crash_at=crash_at, spawn_timeout_s=spawn_timeout_s,
              round_timeout_s=round_timeout_s, p2p_timeout_s=p2p_timeout_s)
    if problem is None:
        return _run_proc(sc, None, **kw)
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return _run_proc(sc, problem, **kw)


def _run_proc(sc: Scenario, problem, *, crash_at, spawn_timeout_s,
              round_timeout_s, p2p_timeout_s) -> Timeline:
    from repro.core import adaptive as _ada
    from repro.core.compression import make_compressor
    from repro.sim.simulator import _jitter_factors
    from repro.topology import (MixingMatrix, compute_leg, gossip_round_comm,
                                round_wire_total)

    if sc.allreduce_per_step:
        raise NotImplementedError(
            "backend='proc' implements the outer-round syncs (gather and "
            "gossip), not per-step allreduce baselines")
    if sc.sync == "bounded_stale":
        return _run_proc_bounded_stale(
            sc, problem, crash_at=crash_at,
            spawn_timeout_s=spawn_timeout_s,
            round_timeout_s=round_timeout_s)
    from repro.sim.faults import Byzantine
    if any(isinstance(e, Byzantine) for e in sc.faults.events):
        # mirror simulate()'s validation: a barrier round has no publish
        # step to corrupt, so silently ignoring the attack here would let
        # the two backends diverge on what the scenario even means
        raise ValueError(
            "Byzantine faults model corrupt *published* deltas, which only "
            "exist under sync='bounded_stale' (the barrier round mixes "
            "inside one jitted program with no publish step to corrupt)")
    topo = sc.topo()
    gossip = topo.is_gossip

    # dynamic time-varying topology: a fresh random graph (and mixing
    # matrix) per round, cached by seed — same key scheme as simulate()'s
    # topo_at/mm_at, so round r communicates over the identical graph on
    # both backends.  PeerMesh.set_peers reconciles each round's peer
    # dict (stale links closed, new ones dialed), so the workers re-dial
    # to the new neighbor sets transparently.
    _topo_cache: Dict[int, Any] = {}

    def topo_at(rnd: int):
        if sc.topology_seed_schedule is None:
            return topo
        key = rnd % len(sc.topology_seed_schedule)
        if key not in _topo_cache:
            _topo_cache[key] = sc.topo(rnd)
        return _topo_cache[key]

    _mm_cache: Dict[int, Any] = {}

    def mm_at(rnd: int, topo_r):
        if not gossip:
            return None
        if sc.topology_seed_schedule is None:
            key = -1
        else:
            key = rnd % len(sc.topology_seed_schedule)
        if key not in _mm_cache:
            _mm_cache[key] = MixingMatrix.metropolis(topo_r)
        return _mm_cache[key]
    h_active = sc.h_spec is not None and sc.h_spec.active
    numeric = problem is not None
    if numeric and problem.n_clusters != sc.n_clusters:
        raise ValueError("problem.n_clusters != scenario.n_clusters")
    if numeric:
        # mirror the in-process simulator's inner-engine validation: the
        # declared Scenario.inner_engine must match the problem's engine,
        # and the pp engine is gather-only (a gossip worker would need a
        # stacked pp program — a different compiled computation)
        engine = getattr(problem, "engine", "scalar")
        if engine != sc.inner_engine:
            raise ValueError(
                f"Scenario.inner_engine={sc.inner_engine!r} but the "
                f"problem was built for engine {engine!r}")
        if engine == "pp" and gossip:
            raise NotImplementedError(
                "backend='proc' runs inner_engine='pp' over gather "
                "topologies only (see simulate()'s matching check)")

    C = sc.n_clusters
    compressor = make_compressor(sc.compressor, **sc.compressor_kw)
    shapes = sc.shapes()
    ctrl = (sc.adaptive.controller(compressor)
            if sc.adaptive is not None else None)
    if ctrl is not None and ctrl.needs_spectral:
        # mirror the in-process simulator's validation exactly
        if not numeric:
            raise ValueError(
                f"adaptive mode {sc.adaptive.mode!r} needs a numeric "
                "problem (the spectral rank signal comes from realized "
                "deltas); timing-only runs can use mode='bandwidth'")
        if not sc.delay:
            raise ValueError(
                f"adaptive mode {sc.adaptive.mode!r} reads the pending "
                "pseudo-gradient, which only delay=True rounds carry; "
                "use mode='bandwidth' for synchronous rounds")
    wire = int(compressor.wire_bytes(shapes, rank=sc.rank))
    alive = (np.ones(C, bool) if sc.initial_alive is None
             else np.asarray(sc.initial_alive, bool).copy())
    epochs = {c: 0 for c in range(C)}

    if numeric:
        import jax
        import jax.numpy as jnp

        from repro.core.membership import masked_cluster_mean
        mean_j = jax.jit(masked_cluster_mean)
        zeros_row = jax.tree.map(
            lambda x: np.zeros(np.shape(x), np.float32),
            problem.init_params())
        # compile the gather-mean before round 0 so it isn't measured
        jax.block_until_ready(mean_j(_stack_rows([zeros_row] * C),
                                     jnp.ones((C,), jnp.float32)))

    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.bind(("127.0.0.1", 0))
    server.listen(C + 2)
    port = server.getsockname()[1]

    handles: Dict[int, _Handle] = {}

    def accept_one(expect: int, timeout: float) -> None:
        """Accept until the worker for cluster ``expect`` says hello."""
        from repro.sim.proc.transport import recv_frame
        deadline = time.monotonic() + timeout
        while handles[expect].conn is None:
            server.settimeout(max(0.1, deadline - time.monotonic()))
            conn, _ = server.accept()
            hello = recv_frame(conn, timeout=30.0)
            h = handles[int(hello["cluster"])]
            h.p2p_port = hello.get("p2p_port")
            h.attach(conn)

    def spawn(c: int) -> None:
        epochs[c] += 1
        handles[c] = _Handle(c, _spawn(c, port, sc, problem, gossip,
                                       epochs[c], crash_at))

    def bootstrap(c: int, state: Optional[Dict[str, Any]]) -> None:
        handles[c].send({"type": "bootstrap",
                         "params": None if state is None
                         else state["params"],
                         "outer_opt": None if state is None
                         else state["outer_opt"]})

    def dump_one(c: int) -> Optional[Dict[str, Any]]:
        h = handles.get(c)
        if h is None or h.dead or not h.send({"type": "dump"}):
            return None
        return h.get("state", round_timeout_s)

    def dump_state() -> Dict[str, Any]:
        """Gather mode: every worker replicates the outer state — fetch it
        from the lowest live one."""
        for c in sorted(handles):
            if alive[c] and not handles[c].dead:
                st = dump_one(c)
                if st is not None:
                    return st
        raise WorkerDied("no live worker to bootstrap a rejoin from")

    def consensus_state(alive_prev: np.ndarray) -> Dict[str, Any]:
        """Gossip mode: per-replica params differ, so a rejoiner restarts
        from the masked MEAN of the survivors' (params, outer momentum) —
        zeros-padded rows through the same jitted ``masked_cluster_mean``
        the in-process simulator's consensus bootstrap uses."""
        rows_p, rows_m, step = [], [], None
        states = {c: dump_one(c) for c in np.flatnonzero(alive_prev)}
        for c in range(C):
            st = states.get(c)
            if st is not None and st.get("params") is not None:
                rows_p.append(st["params"])
                rows_m.append(st["outer_opt"]["momentum"])
                step = st["outer_opt"]["step"]
            else:
                rows_p.append(zeros_row)
                rows_m.append(zeros_row)
        if step is None:
            raise WorkerDied("no live worker to bootstrap a rejoin from")
        m = jnp.asarray(
            [1.0 if states.get(c) is not None else 0.0 for c in range(C)],
            jnp.float32)
        params = jax.tree.map(np.asarray, mean_j(_stack_rows(rows_p), m))
        mom = jax.tree.map(np.asarray, mean_j(_stack_rows(rows_m), m))
        return {"params": params,
                "outer_opt": {"step": step, "momentum": mom}}

    events: List[RoundEvent] = []
    final_params = None
    try:
        for c in np.flatnonzero(alive):
            spawn(int(c))
        for c in sorted(handles):
            if handles[c].conn is None:
                accept_one(c, spawn_timeout_s)
        for c in sorted(handles):
            bootstrap(c, None)

        def _barrier_round(r: int) -> None:
            # The pre-engine per-round body, verbatim — run_barrier drives
            # it in the same index order, so the proc barrier path (and
            # with it every proc≡in-process equivalence gate) stays
            # bit-for-bit identical through the engine refactor.
            nonlocal alive
            prev_alive = alive.copy()
            alive, rejoined = sc.faults.membership(r, alive)
            crash_tags: List[str] = []
            topo_r = topo_at(r)
            mm_r = mm_at(r, topo_r)

            # --- membership enforcement: kill leavers, respawn joiners ----
            for c in range(C):
                if not alive[c] and c in handles and not handles[c].dead:
                    handles[c].kill()
            if rejoined.any():
                # one bootstrap state serves every rejoiner this round
                # (the survivors' consensus doesn't depend on which
                # rejoiner asks) — matches the in-process simulator's
                # single consensus_bootstrap call
                if numeric:
                    state = (consensus_state(prev_alive & alive) if gossip
                             else dump_state())
                else:
                    state = None
                for c in np.flatnonzero(rejoined):
                    c = int(c)
                    spawn(c)
                    accept_one(c, spawn_timeout_s)
                    bootstrap(c, state)

            alive_ids = [int(i) for i in np.flatnonzero(alive)]
            n_alive = len(alive_ids)
            if n_alive == 0:
                if numeric:
                    raise WorkerDied(
                        "all clusters dead in numeric mode: the proc "
                        "backend has no replica left to carry the outer "
                        "state (the in-process simulator keeps applying "
                        "momentum-only rounds; run that instead)")
                rank0 = (ctrl.executed()[0] if ctrl is not None else sc.rank)
                events.append(RoundEvent(
                    round=r, alive=(), rejoined=(), h_steps=sc.h_steps,
                    rank=rank0, t_compute_s=0.0, t_comm_s=0.0,
                    exposed_comm_s=0.0, t_round_s=0.0,
                    wire_bytes=int(compressor.wire_bytes(shapes, rank=rank0)),
                    slowest_cluster=-1, bottleneck_cluster=-1, tokens=0.0,
                    faults=sc.faults.active(r), wire_bytes_total=0))
                return

            # --- modeled targets: same arithmetic as simulate() -----------
            h_t = sc.h_steps
            step_j = _jitter_factors(sc.seed, r, C, sc.link.jitter, salt=1)
            t_steps = np.array([sc.t_step_s * sc.faults.step_multiplier(c, r)
                                * step_j[c] for c in range(C)])
            # per-cluster local-step schedule: same plan_h host arithmetic
            # (and, under gossip, the same spectral-gap clamp on the same
            # masked matrix) as the in-process simulator — the broadcast H
            # schedule cannot drift from the modeled one
            gap = (mm_r.masked(alive).spectral_gap(alive)
                   if (gossip and h_active) else None)
            h_map = _ada.plan_h(sc.h_spec, h_t, t_steps, alive,
                                spectral_gap=gap)
            leg = compute_leg(h_map, t_steps, alive)
            slowest = leg.slowest_cluster
            bw_j = _jitter_factors(sc.seed, r, C, sc.link.jitter, salt=2)
            bws = np.array([sc.link.bytes_per_s
                            * sc.faults.bandwidth_factor(c, r) * bw_j[c]
                            for c in range(C)])

            # --- adaptive rank decision: identical inputs (modeled bws /
            # barrier compute) and identical host arithmetic as the
            # in-process simulator, so the broadcast schedule matches it
            rank_t = sc.rank
            ranks_map = None
            wire_r = wire
            if ctrl is not None:
                rank_t, ranks_map = ctrl.decide(
                    compressor, shapes, topo_r, alive, bws,
                    sc.link.latency_s, leg.t_barrier_s, gossip)
                wire_r = int(compressor.wire_bytes(shapes, rank=rank_t))
            ranks_tuple = (tuple(ranks_map[c] for c in alive_ids)
                           if ranks_map is not None else None)

            if gossip:
                wire_by = (compressor.wire_bytes_per_edge(shapes, ranks_map)
                           if ranks_map is not None else None)
                gc = gossip_round_comm(topo_r, alive, wire_r, bws,
                                       sc.link.latency_s,
                                       wire_by_cluster=wire_by)
                bottleneck = gc.bottleneck_cluster
                wire_total = gc.wire_bytes_total
                W_r = (mm_r.masked(alive).W if numeric else None)
            elif n_alive >= 2:
                bottleneck = int(min(alive_ids, key=lambda c: bws[c]))
                wire_total = round_wire_total("gather", n_alive, wire_r)
            else:
                bottleneck, wire_total = -1, 0

            # --- drive the round ------------------------------------------
            t0 = time.monotonic()
            for c in alive_ids:
                rmsg: Dict[str, Any] = {
                    "type": "round", "round": r,
                    "compute_target_s": float(leg.t_by[c]),
                    "latency_s": float(sc.link.latency_s),
                }
                if h_active and any(h_map[j] != h_t for j in alive_ids):
                    # heterogeneous round: broadcast this worker's
                    # local-step count (the numeric worker masks its
                    # fixed-length scan with it).  Uniform-at-budget
                    # rounds deliberately OMIT the key so every worker
                    # runs the plain scalar-H program — the same dispatch
                    # the in-process simulator makes on the same h_map
                    rmsg["h_steps"] = int(h_map[c])
                if ctrl is not None:
                    # broadcast the controller decision: this worker's send
                    # rank for the round (gossip: its own per-edge rank)
                    rmsg["rank"] = int(ranks_map[c] if ranks_map is not None
                                       else rank_t)
                if gossip:
                    nbrs = topo_r.alive_neighbors(c, alive)
                    wire_c = (wire_by[c] if ranks_map is not None else wire_r)
                    rmsg.update({
                        "charge_bytes": float(wire_c) if nbrs else None,
                        "rate_bytes_per_s": (float(bws[c]) if nbrs
                                             else None),
                        "peers": {int(j): ("127.0.0.1",
                                           handles[j].p2p_port,
                                           epochs[j]) for j in nbrs},
                        "w_row": (np.asarray(W_r[c], np.float32)
                                  if W_r is not None else None),
                        "p2p_timeout_s": float(p2p_timeout_s),
                    })
                else:
                    charge = (n_alive - 1) * wire_r if n_alive >= 2 else 0
                    rmsg.update({
                        "charge_bytes": float(charge),
                        "rate_bytes_per_s": (float(bws[c]) if charge
                                             else None),
                        "latency_s": float((n_alive - 1)
                                           * sc.link.latency_s),
                    })
                if not handles[c].send(rmsg):
                    alive[c] = False
                    crash_tags.append(f"crash(c{c})")

            if not gossip:
                # central gather -> masked mean -> broadcast
                hats: Dict[int, Any] = {}
                for c in list(alive_ids):
                    if not alive[c]:
                        continue
                    msg = handles[c].get("delta", round_timeout_s)
                    if msg is None:
                        alive[c] = False
                        crash_tags.append(f"crash(c{c})")
                        handles[c].kill()
                    else:
                        hats[c] = msg["hat"]
                t_gather_meas = time.monotonic() - t0

                contributors = [int(i) for i in np.flatnonzero(alive)]
                delta_np = None
                if numeric:
                    if not contributors:
                        raise WorkerDied("every worker crashed mid-round")
                    stacked = _stack_rows([hats.get(c, zeros_row)
                                           for c in range(C)])
                    Delta = mean_j(stacked, jnp.asarray(alive, jnp.float32))
                    delta_np = jax.tree.map(lambda x: np.asarray(x), Delta)
                for c in contributors:
                    if not handles[c].send({"type": "avg",
                                            "delta": delta_np}):
                        alive[c] = False
                        crash_tags.append(f"crash(c{c})")
            else:
                contributors = list(alive_ids)

            # --- collect round-done reports -------------------------------
            t_compute_meas, t_comm_workers = 0.0, 0.0
            losses, hash_rows, miss_tags = [], [], []
            pend_rows: Dict[int, Any] = {}
            t_comp_by: Dict[int, float] = {}
            span_rows: List[Tuple[str, int, float, float]] = []
            if not gossip:
                # the hub's own gather phase (round start -> every delta in)
                span_rows.append(("gather", -1, 0.0,
                                  round(t_gather_meas, 6)))
            for c in list(contributors):
                if not alive[c]:
                    continue
                msg = handles[c].get("done", round_timeout_s)
                if msg is None:
                    alive[c] = False
                    crash_tags.append(f"crash(c{c})")
                    handles[c].kill()
                    continue
                t_comp_by[c] = float(msg["t_compute"])
                t_compute_meas = max(t_compute_meas,
                                     float(msg["t_compute"]))
                t_comm_workers = max(t_comm_workers,
                                     float(msg.get("t_comm", 0.0)))
                if msg.get("loss") is not None:
                    losses.append(float(msg["loss"]))
                if msg.get("param_hash") is not None:
                    hash_rows.append((c, msg["param_hash"]))
                if msg.get("pending") is not None:
                    pend_rows[c] = msg["pending"]
                for s in msg.get("spans") or []:
                    span_rows.append((str(s[0]), int(s[1]),
                                      float(s[2]), float(s[3])))
                for j in msg.get("missing", []):
                    miss_tags.append(f"p2pmiss(c{c}<-c{j})")
            t_round_meas = time.monotonic() - t0

            if ctrl is not None and ctrl.needs_spectral:
                # spectral feedback: masked mean of the workers' reported
                # post-round pending deltas through the same jitted mean
                # the in-process simulator uses — identical r' signal,
                # identical next-round rank
                stacked = _stack_rows([pend_rows.get(c, zeros_row)
                                       for c in range(C)])
                ctrl.observe(mean_j(stacked, jnp.asarray(alive, jnp.float32)))

            # measured comm time: the central gather phase for the
            # overlapped hub round; otherwise the slowest worker's own
            # comm leg (sync trains first; gossip never routes through us)
            t_comm_meas = (t_gather_meas if (not gossip and sc.delay)
                           else t_comm_workers)

            param_hash = None
            if numeric and hash_rows:
                if gossip:
                    param_hash = combine_row_hashes(hash_rows)
                else:
                    uniq = sorted({h for _, h in hash_rows})
                    if len(uniq) > 1:
                        raise WorkerDied(
                            f"replica divergence at round {r}: param "
                            f"hashes {uniq}")
                    param_hash = uniq[0]

            survivors = [int(i) for i in np.flatnonzero(alive)]
            tokens = (sc.tokens_per_step
                      * sum(h_map[c] for c in survivors) / max(C, 1))
            events.append(RoundEvent(
                round=r, alive=tuple(survivors),
                rejoined=tuple(int(i) for i in np.flatnonzero(rejoined)),
                h_steps=h_t, rank=rank_t,
                t_compute_s=t_compute_meas, t_comm_s=t_comm_meas,
                exposed_comm_s=max(0.0, t_round_meas - t_compute_meas),
                t_round_s=t_round_meas, wire_bytes=wire_r,
                slowest_cluster=slowest, bottleneck_cluster=bottleneck,
                tokens=tokens,
                faults=(sc.faults.active(r) + tuple(crash_tags)
                        + tuple(sorted(miss_tags))),
                loss=(float(np.mean(losses)) if losses else None),
                param_hash=param_hash, wire_bytes_total=wire_total,
                ranks=ranks_tuple,
                h_by=(tuple(h_map[c] for c in survivors)
                      if h_active and survivors else None),
                t_compute_by=(tuple(t_comp_by.get(c, 0.0)
                                    for c in survivors)
                              if survivors else None),
                idle_by=(tuple(t_compute_meas - t_comp_by.get(c, 0.0)
                               for c in survivors)
                         if survivors else None),
                spans=(tuple(sorted(
                    span_rows,
                    key=lambda s: (s[1], _SPAN_ORDER.get(s[0], 99), s[2])))
                    if span_rows else None)))

        run_barrier(sc.rounds, _barrier_round)

        if numeric and alive.any():
            if gossip:
                final_params = {}
                for c in np.flatnonzero(alive):
                    st = dump_one(int(c))
                    if st is not None and st.get("params") is not None:
                        final_params[int(c)] = st["params"]
            else:
                final_params = dump_state()["params"]
    finally:
        for h in handles.values():
            h.send({"type": "stop"})
        time.sleep(0.05)
        for h in handles.values():
            h.kill()
        server.close()
        for h in handles.values():
            try:
                h.proc.wait(timeout=10.0)
            except Exception:
                pass

    tl = Timeline(scenario={**sc.meta(), "backend": "proc"}, events=events)
    if final_params is not None:
        tl.final_params = final_params
    return tl


def _run_proc_bounded_stale(sc: Scenario, problem=None, *,
                            crash_at: Optional[Dict[int, int]] = None,
                            spawn_timeout_s: float = 300.0,
                            round_timeout_s: float = 300.0) -> Timeline:
    """Bounded-stale async rounds on real processes: the coordinator stops
    being a lockstep gather hub and becomes a membership/clock service over
    the SAME :class:`BoundedStaleEngine` the in-process backend drives.

    The engine runs on modeled time (``async_modeled_times`` — the one
    shared definition), so its commit sequence, staleness records, and
    round-clock vectors are bit-identical to ``simulate()``'s; each commit
    is realized as one serial round-trip with the owning worker (round →
    delta → weighted avg → done).  Workers run flat-out (no compute-target
    sleep, unthrottled links): wall clock never feeds a structural field,
    which is what makes the CI run-to-run drift gate and the cross-backend
    structural/param-hash comparison exact.

    Membership is event-driven: ``on_leave`` SIGKILLs the worker at its
    local leg start; ``on_join`` respawns it when the fleet frontier
    reaches the join round and bootstraps it from the survivors' consensus
    (masked mean of params + outer momentum — the in-process
    ``_AsyncNumeric.on_join`` arithmetic).
    """
    from repro.core.compression import make_compressor
    from repro.sim.simulator import async_modeled_times
    from repro.topology import async_mix_weights

    if crash_at:
        raise NotImplementedError(
            "crash_at is a barrier-round test hook; bounded_stale models "
            "churn through Leave/Join engine events")
    if sc.topology_seed_schedule is not None:
        raise ValueError(
            "sync='bounded_stale' gates on a FIXED peer set per cluster; "
            "run dynamic topologies under barrier")
    numeric = problem is not None
    if numeric and problem.n_clusters != sc.n_clusters:
        raise ValueError("problem.n_clusters != scenario.n_clusters")

    C = sc.n_clusters
    topo = sc.topo()
    compressor = make_compressor(sc.compressor, **sc.compressor_kw)
    wire = int(compressor.wire_bytes(sc.shapes(), rank=sc.rank))
    W_base = async_mix_weights(topo)
    peers = [tuple(p for p in range(C) if p != c and W_base[c, p] > 0.0)
             for c in range(C)]
    leg_seconds, send_seconds, sends = async_modeled_times(sc, wire, topo)
    trimmed = sc.aggregation == "trimmed_mean"
    alive = (np.ones(C, bool) if sc.initial_alive is None
             else np.asarray(sc.initial_alive, bool).copy())
    epochs = {c: 0 for c in range(C)}

    if numeric:
        import jax
        import jax.numpy as jnp

        from repro.core.diloco import staleness_weights
        from repro.core.membership import (masked_cluster_mean,
                                           trimmed_cluster_mean)
        mean_j = jax.jit(masked_cluster_mean)
        trim_j = jax.jit(
            lambda t, m: trimmed_cluster_mean(t, m, sc.trim_k))
        corrupt_j = jax.jit(lambda t, s: jax.tree.map(
            lambda x: (s * x.astype(jnp.float32)).astype(x.dtype), t))
        zeros_row = jax.tree.map(
            lambda x: np.zeros(np.shape(x), np.float32),
            problem.init_params())
        jax.block_until_ready(mean_j(_stack_rows([zeros_row] * C),
                                     jnp.ones((C,), jnp.float32)))

    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.bind(("127.0.0.1", 0))
    server.listen(C + 2)
    port = server.getsockname()[1]
    handles: Dict[int, _Handle] = {}

    def accept_one(expect: int, timeout: float) -> None:
        from repro.sim.proc.transport import recv_frame
        deadline = time.monotonic() + timeout
        while handles[expect].conn is None:
            server.settimeout(max(0.1, deadline - time.monotonic()))
            conn, _ = server.accept()
            hello = recv_frame(conn, timeout=30.0)
            h = handles[int(hello["cluster"])]
            h.p2p_port = hello.get("p2p_port")
            h.attach(conn)

    def spawn(c: int) -> None:
        epochs[c] += 1
        # gossip=False even on ring/torus: async mixing happens in the
        # coordinator's weighted mean over the versioned delta store, not
        # over p2p links (there is no synchronized peer round to exchange
        # with) — the topology enters through W_base/peers instead
        handles[c] = _Handle(c, _spawn(c, port, sc, problem, False,
                                       epochs[c], None))

    def dump_one(c: int) -> Optional[Dict[str, Any]]:
        h = handles.get(c)
        if h is None or h.dead or not h.send({"type": "dump"}):
            return None
        return h.get("state", round_timeout_s)

    def consensus_state() -> Dict[str, Any]:
        """Masked mean of the SURVIVORS' (params, outer momentum) — the
        same zero-padded rows through the same jitted mean as
        ``_AsyncNumeric.on_join``, hence a bit-identical bootstrap."""
        states = {c: dump_one(c) for c in range(C)
                  if alive[c] and not handles[c].dead}
        rows_p, rows_m, mask, step = [], [], [], None
        for c in range(C):
            st = states.get(c)
            if st is not None and st.get("params") is not None:
                rows_p.append(st["params"])
                rows_m.append(st["outer_opt"]["momentum"])
                step = st["outer_opt"]["step"]
                mask.append(1.0)
            else:
                rows_p.append(zeros_row)
                rows_m.append(zeros_row)
                mask.append(0.0)
        if step is None:
            raise WorkerDied("no live worker to bootstrap a rejoin from")
        m = jnp.asarray(mask, jnp.float32)
        params = jax.tree.map(np.asarray, mean_j(_stack_rows(rows_p), m))
        mom = jax.tree.map(np.asarray, mean_j(_stack_rows(rows_m), m))
        # the rejoiner's outer step counter restarts at 0, exactly like
        # _AsyncNumeric.on_join — NOT a survivor's counter: nesterov.update
        # ignores step today, but the documented bootstrap is bit-identical
        # and must stay so if step ever enters the update (e.g. a schedule)
        return {"params": params,
                "outer_opt": {"step": np.zeros((), np.int32),
                              "momentum": mom}}

    store: List[Dict[int, Any]] = [dict() for _ in range(C)]
    events: List[RoundEvent] = []
    final_params = None

    def publish_cb(c: int, k: int, t: float) -> None:
        """Engine ``on_publish``: drive the worker's leg (round → delta)
        and materialize the published version the instant the engine says
        it exists — the worker then parks awaiting its ``avg`` (it serves
        ``dump``/``stop`` while parked), so a gate-blocked publisher's
        delta is already in the store for every peer that commits against
        it."""
        h = handles[c]
        if not h.send({"type": "round", "round": k,
                       "compute_target_s": 0.0, "latency_s": 0.0,
                       "charge_bytes": None, "rate_bytes_per_s": None}):
            raise WorkerDied(f"worker c{c} died before async round {k}")
        msg = h.get("delta", round_timeout_s)
        if msg is None:
            raise WorkerDied(f"worker c{c} died in async round {k}")
        if numeric:
            hat = msg["hat"]
            scale = sc.faults.byzantine_scale(c, k)
            pub = (hat if scale is None
                   else jax.tree.map(np.asarray, corrupt_j(
                       hat, jnp.asarray(scale, jnp.float32))))
            store[c][k] = pub

    def commit_cb(ev) -> None:
        c, k = ev.cluster, ev.round
        h = handles[c]
        delta_np = None
        if numeric:
            used = dict(ev.used)
            rows = []
            for p in range(C):
                if p not in used:
                    rows.append(zeros_row)     # weight/mask 0 anyway
                elif used[p] in store[p]:
                    rows.append(store[p][used[p]])
                else:
                    raise WorkerDied(
                        f"bounded-stale store miss: commit (c{c}, k{k}) "
                        f"uses version (c{p}, k{used[p]}) which was never "
                        f"materialized — engine publish/commit contract "
                        f"broken")
            stacked = _stack_rows(rows)
            if trimmed:
                mask = np.array([1.0 if p in used else 0.0
                                 for p in range(C)], np.float32)
                Delta = trim_j(stacked, jnp.asarray(mask))
            else:
                stal = np.full((C,), -1, np.int64)
                for p, s_p in ev.staleness:
                    stal[p] = s_p
                w = staleness_weights(W_base[c], stal, sc.max_staleness)
                Delta = mean_j(stacked, jnp.asarray(w))
            delta_np = jax.tree.map(lambda x: np.asarray(x), Delta)
            # GC: avail watermarks are monotone (per epoch) — versions
            # below avail[p] can never be referenced again
            for p in range(C):
                for old in [v for v in store[p] if v < ev.avail[p]]:
                    del store[p][old]
        if not h.send({"type": "avg", "delta": delta_np}):
            raise WorkerDied(f"worker c{c} died in async round {k}")
        done = h.get("done", round_timeout_s)
        if done is None:
            raise WorkerDied(f"worker c{c} died in async round {k}")
        span_rows = [(str(s[0]), int(s[1]), float(s[2]), float(s[3]))
                     for s in done.get("spans") or []]
        t_comp, wait, t_send = (float(ev.t_compute), float(ev.wait),
                                float(ev.t_send))
        events.append(RoundEvent(
            round=k, alive=ev.alive, rejoined=ev.rejoined,
            h_steps=sc.h_steps, rank=sc.rank,
            t_compute_s=t_comp, t_comm_s=t_send, exposed_comm_s=wait,
            t_round_s=t_comp + wait, wire_bytes=wire,
            slowest_cluster=c, bottleneck_cluster=c,
            tokens=sc.tokens_per_step * sc.h_steps / max(C, 1),
            faults=sc.faults.active(k),
            loss=done.get("loss"), param_hash=done.get("param_hash"),
            wire_bytes_total=wire * sends[c],
            t_compute_by=(t_comp,), idle_by=(wait,),
            spans=(tuple(sorted(
                span_rows,
                key=lambda s: (s[1], _SPAN_ORDER.get(s[0], 99), s[2])))
                if span_rows else None),
            cluster=c, staleness=ev.staleness,
            round_clock=ev.round_clock, t_start_s=float(ev.t_start)))

    def on_leave(c: int, k: int, t: float) -> None:
        alive[c] = False
        if c in handles and not handles[c].dead:
            handles[c].kill()

    def on_join(c: int, k: int, t: float) -> None:
        state = consensus_state() if numeric else None
        spawn(c)
        accept_one(c, spawn_timeout_s)
        handles[c].send({"type": "bootstrap",
                         "params": None if state is None
                         else state["params"],
                         "outer_opt": None if state is None
                         else state["outer_opt"]})
        store[c].clear()
        alive[c] = True

    try:
        for c in np.flatnonzero(alive):
            spawn(int(c))
        for c in sorted(handles):
            if handles[c].conn is None:
                accept_one(c, spawn_timeout_s)
        for c in sorted(handles):
            handles[c].send({"type": "bootstrap", "params": None,
                             "outer_opt": None})

        engine = BoundedStaleEngine(
            n_clusters=C, rounds=sc.rounds,
            max_staleness=sc.max_staleness, peers=peers,
            leg_seconds=leg_seconds, send_seconds=send_seconds,
            commit=commit_cb, on_publish=publish_cb,
            leaves=sc.faults.leave_events(),
            joins=sc.faults.join_events(),
            initial_alive=[int(i) for i in np.flatnonzero(alive)],
            on_leave=on_leave, on_join=on_join)
        engine.run()

        if numeric and alive.any():
            final_params = {}
            for c in np.flatnonzero(alive):
                st = dump_one(int(c))
                if st is not None and st.get("params") is not None:
                    final_params[int(c)] = st["params"]
    finally:
        for h in handles.values():
            h.send({"type": "stop"})
        time.sleep(0.05)
        for h in handles.values():
            h.kill()
        server.close()
        for h in handles.values():
            try:
                h.proc.wait(timeout=10.0)
            except Exception:
                pass

    tl = Timeline(scenario={**sc.meta(), "backend": "proc"}, events=events)
    if final_params is not None:
        tl.final_params = final_params
    return tl
