"""Traffic kind ``train_rounds_mesh``: DiLoCoX rounds on a mesh of several
chips, clusters x data x model ranks, with a reference that is spread over
the same chips.

The program's round is ``train_rounds.Program`` (the launcher's GSPMD
pieces on a ``(clusters, data, model)`` mesh), driven as ``train_rounds``
drives it, and judged by the same ``numbers``.  What differs:

- the plain reference's round loop (``reference_rounds``) is this
  module's own copy of ``train_rounds.reference_rounds``, the same
  mathematics over ``reference.py``, with every array placed over the
  cell's chips by a plain ``NamedSharding`` (``placement``): at
  Phi-3-medium width two clusters' reference state is ~27 GB, more than
  one chip holds;
- ``outer_work`` is each chip's share of ``workcount.outer_step_work``, so
  that ``outer_step_roofline`` holds a chip's time to a chip's work;
- a traced run also passes ``collective_ms`` and ``cross_cluster_ms``:
  the device time of the collective ops in each program, and of those
  between clusters, per call and chip (``collectives``);
- set-up prints the bytes the compiled outer step moves across clusters,
  by dtype (``exchange_bytes``), beside the int4 payload the compressor
  means to send.

Traffic keys are ``train_rounds``'s.
"""
from __future__ import annotations

import functools
import math
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Sequence

import numpy as np

from harness import reference, train_rounds, weights, workcount
from harness.cell import Cell

# A collective in a device trace, as the TPU compiler emits it: an op whose
# opcode is a collective (``-start`` and ``-done`` included), a fusion that
# calls one (``calls=%all-reduce-scatter.2``), or the start or end of an
# async collective (``%async-collective-start.3 = ... fusion(...)``).
_KINDS = (r"(?:all-reduce|all-gather|reduce-scatter|all-to-all"
          r"|collective-permute)")
COLLECTIVE = re.compile(
    rf"\s{_KINDS}(?:-start|-done)?\(|calls=%{_KINDS}|^%async-collective-")
PROGRAMS = ("jit_train_step", "jit_outer_step")

# A collective's line, in HLO text or a trace op's name: its shape (one
# array, or a tuple where the compiler combined several), opcode, phase and
# the attributes that name the devices it runs between.
LINE = re.compile(rf"= (\(.*?\)|\S+) ({_KINDS})(-start|-done)?\(([^\n]*)")
ARRAY = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
GROUPS = re.compile(r"replica_groups=(?:\{(\{[\d,{}]*\})\}|"
                    r"\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?)")
PAIRS = re.compile(r"source_target_pairs=\{([\d,{}]*)\}")
BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
         "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
         "pred": 1}


def crosses(attrs: str, cluster_size: int) -> bool:
    """Whether a collective runs between devices of different clusters
    (cluster c holds devices [c * cluster_size, (c + 1) * cluster_size) of
    the mesh).  A collective that names no devices is taken to cross none."""
    m = GROUPS.search(attrs)
    if m and m.group(1):
        groups = [[int(d) for d in g.split(",") if d]
                  for g in re.findall(r"\{([\d,]*)\}", m.group(1))]
    elif m:
        n, size = int(m.group(2)), int(m.group(3))
        ids = np.arange(n * size).reshape(
            [int(d) for d in m.group(4).split(",")])
        if m.group(5):
            ids = ids.transpose([int(d) for d in m.group(5).split(",")])
        groups = ids.reshape(n, size).tolist()
    else:
        m = PAIRS.search(attrs)
        groups = ([[int(a), int(b)] for a, b in
                   re.findall(r"\{(\d+),(\d+)\}", m.group(1))] if m else [])
    return any(len({d // cluster_size for d in g}) > 1 for g in groups)


def _items(shape: str):
    """The top-level items of a tuple shape ``(a, (b, c), d)``."""
    items, depth, start = [], 0, 1
    for i, ch in enumerate(shape):
        depth += (ch in "([{") - (ch in ")]}")
        if (ch == "," and depth == 1) or (ch == ")" and depth == 0):
            items.append(shape[start:i].strip())
            start = i + 1
    return items


def exchange_bytes(hlo_text: str, cluster_size: int) -> Dict[str, int]:
    """Bytes by dtype of what each device receives from the collectives of
    ``hlo_text`` (a compiled, partitioned module) that ``crosses`` clusters:
    every array of a combined collective's result, an async pair once, by
    the results in its ``-start`` (an all-gather's or a permute's start
    holds its operands first)."""
    out: Dict[str, int] = {}
    for m in LINE.finditer(hlo_text):
        shape, kind, phase, attrs = m.groups()
        if phase == "-done" or not crosses(attrs, cluster_size):
            continue
        if phase == "-start" and kind != "all-reduce":
            shape = _items(shape)[1]
        for dt, dims in ARRAY.findall(shape):
            out[dt] = out.get(dt, 0) + BYTES.get(dt, 4) * math.prod(
                int(d) for d in dims.split(",") if d)
    return out


# ---------------------------------------------------------------------------
# the reference, placed over the chips
# ---------------------------------------------------------------------------

def placement(tree, mesh):
    """A ``NamedSharding`` for each array (or shape) of ``tree`` on a one-axis
    mesh: its largest dimension that the chips divide is split over them,
    and an array with none is replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, (axis,) = mesh.size, mesh.axis_names

    def one(x):
        dims = [None] * len(x.shape)
        fits = [(s, i) for i, s in enumerate(x.shape) if s % n == 0]
        if fits:
            dims[max(fits)[1]] = axis
        return NamedSharding(mesh, P(*dims))
    return jax.tree.map(one, tree)


def reference_fns(cell: Cell, mesh, *, low: str = "",
                  half_batch: bool = False, no_exchange: bool = False):
    """The jitted pieces of ``reference_rounds`` with their placement on
    ``mesh``: ``init`` (weights and token pool from the key), ``batch``
    (a step's batch, its second half left out under ``half_batch``),
    ``step`` (one AdamW step of one cluster), ``outer`` and
    ``next_anchor`` (which average cluster 0's pseudo-gradient alone, as if
    nothing crossed between clusters, under ``no_exchange``).  Built apart
    from the loop so that they can be compiled for chips that are only
    described."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    tr, m = cell.traffic, cell.model
    C, B = tr["clusters"], tr["batch"]
    opt = tr["adamw"]
    layout = weights.layout(m)
    psh = placement(layout, mesh)
    rep = NamedSharding(mesh, P())
    qsh = placement(jax.eval_shape(lambda p: reference.warm_start(
        p, rank=tr["rank"], min_dim=tr["lowrank_min_dim"]), layout), mesh)
    lower = lambda t: jax.tree.map(lambda x: reference.store(x, low), t)
    outer_kw = dict(lr=tr["outer_lr"], momentum=tr["outer_momentum"])
    sent = (lambda t: [t[0]] * len(t)) if no_exchange else (lambda t: t)

    def init(key):
        wkey = jax.random.fold_in(key, 0)
        return (lower(weights.make(layout, wkey)),
                train_rounds.token_pool(tr, m, key))

    def step(p, st, toks):
        lval, g = jax.value_and_grad(
            lambda p: reference.loss(m, p, toks, low))(p)
        p, st, g = reference.adamw(
            p, g, st, lr=tr["inner_lr"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"],
            grad_clip=opt["grad_clip"], low=low)
        return p, st, lval, train_rounds._tree_norms(g)

    def outer(anchor, v, pending, qs, params):
        delta, qs = reference.compressed_mean(
            sent(pending), qs, block=tr["quant_block"], low=low)
        pending = [lower(jax.tree.map(
            lambda a, p, d, D: a - p + (d - D), anchor, p, d, delta))
            for p, d in zip(params, pending)]
        anchor, v = reference.nesterov(anchor, v, delta, **outer_kw)
        return lower(anchor), lower(v), pending, qs

    def next_anchor(anchor, v, pending, qs):
        delta, _ = reference.compressed_mean(
            sent(pending), qs, block=tr["quant_block"], low=low)
        return lower(reference.nesterov(anchor, v, delta, **outer_kw)[0])

    # one executable for every step of a round and for the half batch (the
    # batch is cut before the step): the first step reads the anchor,
    # which outlives it, so a step gives up only the optimizer's moments
    return {
        "init": jax.jit(init, out_shardings=(psh, rep)),
        "step": jax.jit(step, donate_argnums=(1,),
                        out_shardings=(psh, (psh, psh, rep), rep, rep)),
        "batch": jax.jit((lambda t: jnp.concatenate([t[:B // 2]] * 2))
                         if half_batch else (lambda t: t),
                         out_shardings=rep),
        "outer": jax.jit(outer, donate_argnums=(1, 2, 4),
                         out_shardings=(psh, psh, [psh] * C, [qsh] * C)),
        "next_anchor": jax.jit(next_anchor, out_shardings=psh),
        "zeros": jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                         out_shardings=psh),
        "warm_start": jax.jit(functools.partial(
            reference.warm_start, rank=tr["rank"],
            min_dim=tr["lowrank_min_dim"]), out_shardings=qsh),
        "move_norms": jax.jit(lambda a, b: train_rounds._tree_norms(
            jax.tree.map(lambda x, y: x - y, a, b)), out_shardings=rep),
    }


def reference_rounds(cell: Cell, key, devices, rounds: int = 1, *,
                     low: str = "", half_batch: bool = False,
                     no_exchange: bool = False) -> dict:
    """``train_rounds.reference_rounds`` with its arrays spread over
    ``devices``: every step's loss (mean over clusters), cluster 0's first
    clipped gradient and its move after three steps, and the anchor's move
    from round 1's outer step.  ``low`` names a control
    (``reference.py``); ``half_batch`` leaves out the second half of every
    batch; ``no_exchange`` averages cluster 0's pseudo-gradient alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    tr = cell.traffic
    C, H = tr["clusters"], tr["h"]
    mesh = Mesh(np.asarray(devices), ("chips",))
    with jax.default_matmul_precision("highest"):
        f = reference_fns(cell, mesh, low=low, half_batch=half_batch,
                          no_exchange=no_exchange)
        init, pool = f["init"](key)
        q0 = f["warm_start"](init)
        anchor, v = init, f["zeros"](init)
        pending, qs = [f["zeros"](init) for _ in range(C)], [q0] * C
        # each cluster's optimizer state, made at its first step
        states = [None] * C
        losses = np.zeros((rounds, H))
        out = {}
        for r in range(rounds):
            params = []
            for c in range(C):
                p, st = anchor, states[c]
                states[c] = None
                if st is None:
                    st = (f["zeros"](init), f["zeros"](init),
                          jnp.zeros((), jnp.float32))
                for h in range(H):
                    p, st, lval, g = f["step"](p, st, f["batch"](
                        pool[(r * H + h) % tr["batch_pool"], c]))
                    losses[r, h] += float(lval) / C
                    if (r, c, h) == (0, 0, 0):
                        out["grad"] = np.asarray(g)
                    if (r, c, h) == (0, 0, 2):
                        out["move"] = np.asarray(f["move_norms"](p, init))
                params.append(p)
                states[c] = st
                del p, st
            anchor, v, pending, qs = f["outer"](anchor, v, pending, qs,
                                                params)
            del params
            if r == 0:
                out["outer"] = np.asarray(f["move_norms"](
                    f["next_anchor"](anchor, v, pending, qs), init))
    out = {k: x.tolist() for k, x in out.items()}
    out["losses"] = losses.reshape(-1).tolist()
    return out


def reference_obs(cell: Cell, key, devices, **kw) -> dict:
    """What the program's set-up reads, from the placed reference over one
    round (``train_rounds.reference_obs``)."""
    out = reference_rounds(cell, key, devices, 1, **kw)
    out["losses"] = out["losses"][:3]
    return out


# ---------------------------------------------------------------------------
# collectives in the trace
# ---------------------------------------------------------------------------

def collectives(devices: Sequence[dict], t0: int, t1: int,
                cluster_size: int) -> Dict[str, tuple]:
    """Seconds of collective device time per call and chip of each program
    that ran in [t0, t1): all of it, and the part between clusters
    (``crosses``; an async ``-done`` as its ``-start``).  ``devices`` holds
    one ``{line name: [(start_ns, end_ns, name)]}`` per chip, with the
    lines ``XLA Modules`` and ``XLA Ops``; an op belongs to the program run
    it starts in, and only leaf ops count (``tracing.reduce``'s rule)."""
    import bisect

    from harness import tracing
    seconds: Dict[str, list] = {}
    calls: Dict[str, int] = {}
    for lines in devices:
        runs = sorted(e for e in lines.get("XLA Modules", [])
                      if t0 <= e[0] < t1)
        starts = [r[0] for r in runs]
        for r in runs:
            prog = r[2].split("(")[0]
            calls[prog] = calls.get(prog, 0) + 1
            seconds.setdefault(prog, [0.0, 0.0])
        ops = [e for e in lines.get("XLA Ops", []) if t0 <= e[0] < t1]
        across = set()
        for a, b, name in sorted(tracing._leaves(ops)):
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= runs[i][1] or not COLLECTIVE.search(name):
                continue
            tot = seconds[runs[i][2].split("(")[0]]
            tot[0] += (b - a) / 1e9
            m = LINE.search(name)
            if m and m.group(3) == "-done":
                start = re.search(r"%(\S+-start[.\d]*)\)", name)
                cross = bool(start) and start.group(1) in across
            else:
                cross = bool(m) and crosses(m.group(4), cluster_size)
                if cross:
                    across.add(name.split(" ")[0].lstrip("%"))
            tot[1] += cross * (b - a) / 1e9
    return {p: (s[0] / calls[p], s[1] / calls[p])
            for p, s in seconds.items()}


def trace_collectives(path: str, window: str,
                      cluster_size: int) -> Dict[str, tuple]:
    """``collectives`` over the host span ``window`` of a profile."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    marks, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            marks += [(e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name == window]
        elif re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            devices.append({line.name: [(e.start_ns,
                                         e.start_ns + e.duration_ns, e.name)
                                        for e in line.events]
                            for line in plane.lines})
    if not marks:
        return {}
    return collectives(devices, *marks[0], cluster_size)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def say_exchange(prog: train_rounds.Program, cell: Cell) -> None:
    """Print what the compiled outer step moves across clusters beside the
    int4 payload of one cluster (``mesh_compression.wire_bytes_tree``)."""
    from repro.core import mesh_compression as mc
    tr = cell.traffic
    ccfg = mc.MeshCompressionConfig(rank=tr["rank"], block=tr["quant_block"],
                                    min_dim_for_lowrank=tr["lowrank_min_dim"])
    across = exchange_bytes(prog._outer.as_text(),
                            tr["data"] * tr["model_ranks"])
    print(f"outer step: {across} bytes per chip across clusters "
          f"(compiled); int4 payload "
          f"{mc.wire_bytes_tree(weights.layout(cell.model), ccfg)} bytes "
          f"per cluster (wire_bytes_tree)", file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, ctx) -> dict:
    """One run of a ``train_rounds_mesh`` cell; ``ctx`` is ``run.Context``."""
    from harness import tracing
    from harness.cell import seed_streams

    key, _ = seed_streams(seed)
    prog = train_rounds.Program(cell, key)
    say_exchange(prog, cell)
    obs = prog.warmup()
    setup_s = time.perf_counter() - ctx.t_start
    spans = tracing.Spans(annotate=trace)
    ahead = max(1, math.ceil(train_rounds.AHEAD_S / prog.round_s))
    summary, coll = None, None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with ctx.quiet(), tracing.capture(log_dir):
                win = prog.rounds(spans, count=cell.traffic["trace_rounds"],
                                  ahead=ahead)
            path = tracing.find_xplane(log_dir)
            summary = tracing.reduce(path)
            coll = trace_collectives(
                path, tracing.WINDOW,
                cell.traffic["data"] * cell.traffic["model_ranks"])
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        with ctx.quiet():
            win = prog.rounds(spans, seconds=seconds, ahead=ahead)
    first = " ".join(f"{x:.3f}" for x in win["intervals"][:ahead + 2])
    print(f"window: {len(win['intervals'])} rounds, {ahead} queued ahead "
          f"of the loss read (round 1 took {prog.round_s:.4f} s); the first "
          f"rounds dispatched in {first} s", file=sys.stderr, flush=True)
    ctx.say_intervals("round", win["intervals"])
    memory = ctx.memory_peak()
    prog.free()
    del prog
    ref = reference_obs(cell, key, ctx.devs)
    nums = train_rounds.numbers(obs, ref)
    # a step of the window whose loss is not finite has trained nothing
    nums["nonfinite_losses"] = float(win["steps"] - win["finite"])
    m, tr = cell.model, cell.traffic
    chips = len(ctx.devs)
    flops, nbytes = workcount.outer_step_work(
        m, rank=tr["rank"], clusters=tr["clusters"],
        min_dim=tr["lowrank_min_dim"])
    counters = {"tokens_per_s": win["tokens_per_s"],
                "flops_per_token": workcount.train_flops_per_token(
                    m, tr["seq_len"]),
                "outer_work": (flops / chips, nbytes / chips)}
    if coll:
        counters["collective_ms"] = {p: 1e3 * coll[p][0] for p in PROGRAMS
                                     if p in coll}
        counters["cross_cluster_ms"] = {p: 1e3 * coll[p][1]
                                        for p in PROGRAMS if p in coll}
    e2e = {"train_tokens_per_s": win["tokens_per_s"], "setup_s": setup_s}
    return ctx.result(cell, nums, attempted=win["steps"],
                      failed=win["steps"] - win["finite"], e2e=e2e,
                      counters=counters, spans=spans, trace=summary,
                      memory=memory)
