"""Traffic kind ``train_rounds``: DiLoCoX rounds, H inner AdamW steps and
one outer step, as ``launch/train.py`` composes them for ``--inner gspmd``.

The loop is the benchmark's own (the launcher's loop runs a fixed number
of rounds and compiles inside round 0), over the program's own pieces:
``steps.round_shardings``, ``steps.make_train_step``,
``steps.make_outer_step``, the same jit shardings and donation.  The
weights and the token rows come from the seed (``weights``, ``tokens``).

Set-up builds the state, compiles both steps and drives the state through
rounds 0 and 1 with the window's own calls.  On the way it reads what the
comparison needs: the first three losses, the first gradient (from the
optimizer's first moment after step 1), the parameters' move after step 3
and the anchor's move from the first outer step that averages anything
(round 1's: round 0 averages the zero pending delta).  The window then
runs whole rounds until ``--seconds`` have passed and reads the loss on
the host once per round.

Traffic keys: clusters, data, model_ranks, h, rank, batch, seq_len,
inner_lr, outer_lr, outer_momentum, adamw (the optimizer's constants, for
the reference), batch_pool (distinct batches the feed cycles through),
quant_block and lowrank_min_dim (the compressor's), trace_rounds.  The
configuration may state ``outer_step_precision``, the matmul precision the
outer step is compiled at.  A window with a non-finite loss is not
correct.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import gc
import math
import sys
import time

import numpy as np

from harness import compare, reference, tokens, weights, workcount
from harness.cell import Cell, program_config

# seconds of rounds the window keeps queued on the chip: a host stall
# shorter than this leaves the chip busy
AHEAD_S = 5.0


def _tree_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def token_pool(tr: dict, m: dict, key):
    """(batch_pool, clusters, batch, seq_len) rows, all different."""
    import jax
    C, B, S, n = tr["clusters"], tr["batch"], tr["seq_len"], tr["batch_pool"]
    rows = tokens.markov_rows(jax.random.fold_in(key, 1), n * C * B, S,
                              m["vocab_size"])
    return rows.reshape(n, C, B, S)


class Program:
    """The program's round, built once and driven by set-up and window."""

    def __init__(self, cell: Cell, key):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs.base import ShapeConfig
        from repro.core import mesh_compression as mc
        from repro.launch import steps
        from repro.launch.mesh import launcher_mesh
        from repro.models import model as M
        from repro.optim import adamw
        from repro.parallel import sharding as sh

        tr, m = cell.traffic, cell.model
        self.tr = tr
        C, B, S = tr["clusters"], tr["batch"], tr["seq_len"]
        cfg = program_config(m, cell.config["arch"])
        layout = weights.layout(m)
        weights.check_layout(m, jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
        mesh = launcher_mesh((C, tr["data"], tr["model_ranks"]),
                             ("clusters", "data", "model"))
        M.set_activation_sharder(sh.make_activation_sharder(mesh))
        ccfg = mc.MeshCompressionConfig(
            rank=tr["rank"], block=tr["quant_block"],
            min_dim_for_lowrank=tr["lowrank_min_dim"])
        ps, opt_sh, ost_sh = steps.round_shardings(cfg, mesh, C, ccfg)
        bsh = sh.batch_shardings(
            steps.input_specs(cfg, ShapeConfig("run", S, C * B, "train"),
                              n_clusters=C), mesh, cluster_stacked=True)
        rep = NamedSharding(mesh, P())
        wkey = jax.random.fold_in(key, 0)

        def init_round_state(wkey):
            p1 = weights.make(layout, wkey)
            params = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), p1)
            return (params, jax.vmap(adamw.init)(params),
                    steps.init_outer_state(p1, C, ccfg))

        # keys are arguments, never constants of a program: every seed
        # runs the same executables, which the compile cache holds
        self.params, self.opt, self.ost = jax.jit(
            init_round_state, out_shardings=(ps, opt_sh, ost_sh))(wkey)
        # the feed: each step's batch is an array of its own, laid out as
        # the step takes it, so that a step is one call on the chip
        self.batches = jax.jit(
            lambda k: [{"tokens": b} for b in token_pool(tr, m, k)],
            out_shardings=[bsh] * tr["batch_pool"])(key)
        self._step = jax.jit(
            steps.make_train_step(cfg, inner_lr=tr["inner_lr"]),
            in_shardings=(ps, opt_sh, bsh), out_shardings=(ps, opt_sh, rep),
            donate_argnums=(0, 1))
        self._outer = jax.jit(
            steps.make_outer_step(cfg, ccfg, outer_lr=tr["outer_lr"],
                                  outer_momentum=tr["outer_momentum"]),
            in_shardings=(ps, ost_sh, rep), out_shardings=(ps, ost_sh),
            donate_argnums=(0, 1))
        self.rank = jnp.asarray(tr["rank"], jnp.int32)
        self._step = self._step.lower(self.params, self.opt,
                                      self.batches[0]).compile()
        # the precision the configuration states for the outer step's
        # products (JAX's default where it states none)
        prec = cell.config.get("outer_step_precision")
        with (jax.default_matmul_precision(prec) if prec
              else contextlib.nullcontext()):
            self._outer = self._outer.lower(self.params, self.ost,
                                            self.rank).compile()
        b1 = tr["adamw"]["b1"]
        self._grad_norms = jax.jit(
            lambda mom: _tree_norms(jax.tree.map(lambda x: x[0], mom))
            / (1 - b1))
        move_norms = jax.jit(lambda p, k: _tree_norms(jax.tree.map(
            lambda x, x0: x - x0, p, weights.make(layout, k))))
        self._move_norms = lambda p: move_norms(p, wkey)
        self._row0 = jax.jit(lambda p: jax.tree.map(lambda x: x[0], p))
        self.steps_done = 0

    def inner_step(self):
        i = self.steps_done % self.tr["batch_pool"]
        self.params, self.opt, loss = self._step(
            self.params, self.opt, self.batches[i])
        self.steps_done += 1
        return loss

    def outer_step(self):
        self.params, self.ost = self._outer(self.params, self.ost, self.rank)

    def warmup(self) -> dict:
        """Rounds 0 and 1; returns what the comparison reads, and times
        round 1 (``round_s``)."""
        import jax
        obs, losses = {}, []
        for r in range(2):
            if r == 1:
                jax.block_until_ready(self.params)
                t_round = time.perf_counter()
            for _ in range(self.tr["h"]):
                losses.append(self.inner_step())
                if self.steps_done == 1:
                    obs["grad"] = self._grad_norms(self.opt.m)
                if self.steps_done == 3:
                    obs["move"] = self._move_norms(self._row0(self.params))
            self.outer_step()
            if r == 1:
                jax.block_until_ready(self.params)
                self.round_s = time.perf_counter() - t_round
                obs["outer"] = self._move_norms(self.ost.anchor)
        obs["losses"] = losses[:3]
        obs = jax.device_get(obs)
        jax.block_until_ready((self.params, self.opt, self.ost))
        return {k: np.asarray(v, np.float64).tolist() for k, v in obs.items()}

    def rounds(self, spans, *, seconds: float = 0.0, count: int = 0,
               ahead: int) -> dict:
        """Whole rounds, ``count`` of them or dispatched until ``seconds``
        have passed.  A round's losses are read on the host once ``ahead``
        more rounds have been dispatched, so that the chip has work queued
        while the host waits or stalls.  Once the last round is dispatched
        the window waits for all of them: every round dispatched counts,
        over the time until the last has finished."""
        import jax
        H = self.tr["h"]
        n_rounds, finite, ends, queued = 0, 0, [], collections.deque()
        t0 = time.perf_counter()
        while True:
            with spans.span("bench.round"):
                queued.append([self.inner_step() for _ in range(H)])
                self.outer_step()
            n_rounds += 1
            while len(queued) > ahead:
                with spans.span("bench.loss_read"):
                    losses = jax.device_get(queued.popleft())
                finite += int(np.isfinite(losses).sum())
            ends.append(time.perf_counter())
            if (n_rounds == count if count
                    else ends[-1] - t0 >= seconds):
                break
        while queued:
            finite += int(np.isfinite(jax.device_get(queued.popleft())).sum())
        jax.block_until_ready((self.params, self.opt, self.ost))
        wall = time.perf_counter() - t0
        tr = self.tr
        n_tok = n_rounds * H * tr["clusters"] * tr["batch"] * tr["seq_len"]
        return {"steps": n_rounds * H, "finite": finite, "seconds": wall,
                "tokens_per_s": n_tok / wall,
                "intervals": np.diff([t0] + ends)}

    def free(self):
        from repro.models import model as M
        for name in ("params", "opt", "ost", "batches"):
            for x in __import__("jax").tree.leaves(getattr(self, name)):
                x.delete()
            setattr(self, name, None)
        M.set_activation_sharder(None)
        gc.collect()


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def reference_rounds(cell: Cell, key, rounds: int = 1, *, low: str = "",
                     half_batch: bool = False) -> dict:
    """The plain reference's DiLoCoX loop from the seed: ``rounds`` rounds
    of H AdamW steps per cluster and one outer step (the mean of the
    clusters' compressed pending pseudo-gradients with error feedback, the
    delayed Nesterov step on the anchor, every cluster restarting from
    it).  Returns every step's loss (mean over clusters) and what the
    program's set-up reads: cluster 0's first clipped gradient, its move
    after three steps, and the anchor's move from round 1's outer step,
    which averages round 0's pseudo-gradients (round 1's own steps do not
    enter it, so one round is enough to read it).  ``low`` names a
    control (``reference.py``); ``half_batch`` leaves out the second half
    of every batch (its rows repeat the first half's)."""
    import jax
    import jax.numpy as jnp

    tr, m = cell.traffic, cell.model
    C, H, B = tr["clusters"], tr["h"], tr["batch"]
    opt = tr["adamw"]
    wkey = jax.random.fold_in(key, 0)
    layout = weights.layout(m)
    lower = lambda t: jax.tree.map(lambda x: reference.store(x, low), t)
    outer_kw = dict(lr=tr["outer_lr"], momentum=tr["outer_momentum"])
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda k: lower(weights.make(layout, k)))(wkey)
        pool = jax.jit(lambda k: token_pool(tr, m, k))(key)

        def step(p, st, toks):
            if half_batch:
                toks = jnp.concatenate([toks[:B // 2]] * 2, axis=0)
            lval, g = jax.value_and_grad(
                lambda p: reference.loss(m, p, toks, low))(p)
            p, st, g = reference.adamw(
                p, g, st, lr=tr["inner_lr"], b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], weight_decay=opt["weight_decay"],
                grad_clip=opt["grad_clip"], low=low)
            return p, st, lval, _tree_norms(g)

        # a round's first step reads the anchor, which outlives it; every
        # later step gives up its parameters and the optimizer's moments,
        # so that no more than one copy of each is on the chip
        first_step = jax.jit(step, donate_argnums=(1,))
        next_step = jax.jit(step, donate_argnums=(0, 1))

        @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
        def outer(anchor, v, pending, qs, params):
            delta, qs = reference.compressed_mean(
                pending, qs, block=tr["quant_block"], low=low)
            pending = [lower(jax.tree.map(
                lambda a, p, d, D: a - p + (d - D), anchor, p, d, delta))
                for p, d in zip(params, pending)]
            anchor, v = reference.nesterov(anchor, v, delta, **outer_kw)
            return lower(anchor), lower(v), pending, qs

        @jax.jit
        def next_anchor(anchor, v, pending, qs):
            delta, _ = reference.compressed_mean(
                pending, qs, block=tr["quant_block"], low=low)
            return lower(reference.nesterov(anchor, v, delta, **outer_kw)[0])

        move_norms = jax.jit(lambda a, b: _tree_norms(
            jax.tree.map(lambda x, y: x - y, a, b)))
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        q0 = reference.warm_start(init, rank=tr["rank"],
                                  min_dim=tr["lowrank_min_dim"])
        anchor, v = init, zeros(init)
        pending, qs = [zeros(init)] * C, [q0] * C
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
                    st = (zeros(init), zeros(init),
                          jnp.zeros((), jnp.float32))
                for h in range(H):
                    p, st, lval, g = (next_step if h else first_step)(
                        p, st, pool[(r * H + h) % tr["batch_pool"], c])
                    losses[r, h] += float(lval) / C
                    if (r, c, h) == (0, 0, 0):
                        out["grad"] = np.asarray(g)
                    if (r, c, h) == (0, 0, 2):
                        out["move"] = np.asarray(move_norms(p, init))
                params.append(p)
                states[c] = st
                del p, st
            anchor, v, pending, qs = outer(anchor, v, pending, qs, params)
            del params
            if r == 0:
                out["outer"] = np.asarray(move_norms(
                    next_anchor(anchor, v, pending, qs), init))
    out = {k: x.tolist() for k, x in out.items()}
    out["losses"] = losses.reshape(-1).tolist()
    return out


def reference_obs(cell: Cell, key, **kw) -> dict:
    """What the plain reference reads where the program's set-up reads it
    (``reference_rounds`` over one round): the first three losses, the
    first gradient, the move after three steps, round 1's outer move."""
    out = reference_rounds(cell, key, 1, **kw)
    out["losses"] = out["losses"][:3]
    return out


def numbers(prog: dict, ref: dict) -> dict:
    """The gaps compared with their limits."""
    g = ref["grad"]
    return {
        "loss_gap": compare.rel_gap(prog["losses"], ref["losses"]),
        "grad_gap": compare.leaf_norm_gap(prog["grad"], ref["grad"], g),
        "move_gap": compare.leaf_norm_gap(prog["move"], ref["move"], g),
        "outer_gap": compare.leaf_norm_gap(prog["outer"], ref["outer"], g),
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, ctx) -> dict:
    """One run of a ``train_rounds`` cell; ``ctx`` is ``run.Context``."""
    from harness.cell import seed_streams
    from harness import tracing

    key, _ = seed_streams(seed)
    prog = Program(cell, key)
    obs = prog.warmup()
    setup_s = time.perf_counter() - ctx.t_start
    spans = tracing.Spans(annotate=trace)
    # rounds queued on the chip beyond the one whose losses are read
    ahead = max(1, math.ceil(AHEAD_S / prog.round_s))
    if trace:
        with ctx.quiet(), ctx.traced() as traced:
            win = prog.rounds(spans, count=cell.traffic["trace_rounds"],
                              ahead=ahead)
        summary = traced.trace
    else:
        with ctx.quiet():
            win = prog.rounds(spans, seconds=seconds, ahead=ahead)
        summary = None
    first = " ".join(f"{x:.3f}" for x in win["intervals"][:ahead + 2])
    print(f"window: {len(win['intervals'])} rounds, {ahead} queued ahead "
          f"of the loss read (round 1 took {prog.round_s:.4f} s); the first "
          f"rounds dispatched in {first} s", file=sys.stderr, flush=True)
    ctx.say_intervals("round", win["intervals"])
    memory = ctx.memory_peak()
    prog.free()
    del prog
    ref = reference_obs(cell, key)
    nums = numbers(obs, ref)
    # a step of the window whose loss is not finite has trained nothing
    nums["nonfinite_losses"] = float(win["steps"] - win["finite"])
    m, tr = cell.model, cell.traffic
    flops_tok = workcount.train_flops_per_token(m, tr["seq_len"])
    counters = {"tokens_per_s": win["tokens_per_s"],
                "flops_per_token": flops_tok,
                "outer_work": workcount.outer_step_work(
                    m, rank=tr["rank"], clusters=tr["clusters"],
                    min_dim=tr["lowrank_min_dim"])}
    e2e = {"train_tokens_per_s": win["tokens_per_s"], "setup_s": setup_s}
    return ctx.result(cell, nums, attempted=win["steps"],
                      failed=win["steps"] - win["finite"], e2e=e2e,
                      counters=counters, spans=spans, trace=summary,
                      memory=memory)
