"""Traffic kind ``serve_closed_loop``: ``clients`` clients, each sending
its next request as soon as its previous one has finished, through
``serve.engine.ServeEngine`` (``submit`` / ``step``) with the paged
backend the traffic names.

Every seed serves the same requests' lengths in the same order (drawn
once by the traffic's ``order_seed`` with ``tokens.stratified_lengths``),
so the closed loop's steps hold the same work in every run; ``--seed``
draws the token ids and the weights.  EOS is off, so every answer has
exactly its drawn length.  Each token is stamped on the host clock when the engine
step that produced it returns.

Set-up makes the weights on the device from the seed, compiles the one
decode step, and runs ``warmup_steps`` engine steps of the closed loop.
The window runs whole engine steps until ``--seconds`` have passed; then
no client sends again.  Requests finished by then (in a traced run, whose
window is short, the engine first runs on until ``check_requests`` are)
are sampled from the seed, the longest always in, and every token they
were served is held to the plain reference.  ``attempted`` counts the
finished requests, ``failed`` those whose answer has not the length asked.

Traffic keys: clients, page_size, pages, prompt and answer (lognormal
``median``, ``sigma``, clipped to ``min``..``max``), requests (the drawn
list; the loop must not run out), order_seed, warmup_steps, trace_steps,
check_requests, backend.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from harness import reference, tokens, weights, workcount
from harness.cell import Cell, program_config


def request_list(tr: dict, vocab: int, rng: np.random.Generator):
    """[(prompt ids, answer length)] in the order the clients send them:
    lengths fixed by the traffic, ids drawn by ``rng``."""
    n, order = tr["requests"], np.random.default_rng(tr["order_seed"])
    p_len = tokens.stratified_lengths(tr["prompt"], n, tr["clients"], order)
    a_len = tokens.stratified_lengths(tr["answer"], n, tr["clients"], order)
    return [(rng.integers(0, vocab, int(p)).tolist(), int(a))
            for p, a in zip(p_len, a_len)]


def max_len(tr: dict) -> int:
    return tr["prompt"]["max"] + tr["answer"]["max"]


class Program:
    """The engine under a closed loop of clients."""

    def __init__(self, cell: Cell, key, spans=None):
        import jax

        from repro.models import model as M
        from repro.serve.engine import ServeEngine

        tr, m = cell.traffic, cell.model
        self.tr = tr
        cfg = program_config(m, cell.config["arch"])
        layout = weights.layout(m)
        weights.check_layout(m, jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
        M.set_activation_sharder(None)
        params = jax.jit(lambda k: weights.make(layout, k))(
            jax.random.fold_in(key, 0))
        ps = tr["page_size"]
        self.engine = ServeEngine(
            params, cfg, max_seqs=tr["clients"], page_size=ps,
            n_pages=tr["pages"], max_pages_per_seq=-(-max_len(tr) // ps),
            backend=tr["backend"], eos_id=None,
            span=spans.span if spans is not None else None)
        self.engine.compile()
        self.requests = []
        self.next_request = 0
        self.sent = {}          # rid -> host time of submission
        self.times = {}         # rid -> host time of each served token
        self.live = {}          # rid -> Request
        self.contexts = []      # per step: each active slot's cache length
        self.sending = True

    def send(self, now: float) -> None:
        if self.next_request >= len(self.requests):
            raise RuntimeError("the request list ran out: raise `requests`")
        prompt, n_new = self.requests[self.next_request]
        self.next_request += 1
        req = self.engine.submit(prompt, n_new)
        self.sent[req.rid] = now
        self.times[req.rid] = []
        self.live[req.rid] = req

    def step(self, record_contexts: bool = False) -> None:
        sched = self.engine.sched
        if record_contexts:
            before = [s.fed for s in sched.slots if s is not None]
            admitted = len(sched.admissions)
        self.engine.step()
        now = time.perf_counter()
        if record_contexts:
            new = len(sched.admissions) - admitted
            self.contexts.append([f + 1 for f in before] + [1] * new)
        for rid, req in list(self.live.items()):
            t = self.times[rid]
            t.extend([now] * (len(req.generated) - len(t)))
            if req.state == "DONE":
                del self.live[rid]
                if self.sending:
                    self.send(now)

    def start(self, requests) -> None:
        self.requests = requests
        now = time.perf_counter()
        for _ in range(self.tr["clients"]):
            self.send(now)
        for _ in range(self.tr["warmup_steps"]):
            self.step()

    def window(self, *, seconds: float = 0.0, steps: int = 0,
               spans=None) -> dict:
        """Engine steps until ``seconds`` have passed (or ``steps`` of
        them); returns the window's bounds and step count."""
        import contextlib
        n, ends = 0, []
        t0 = time.perf_counter()
        while True:
            span = spans.span("bench.step") if spans else \
                contextlib.nullcontext()
            with span:
                self.step(record_contexts=bool(steps))
            n += 1
            ends.append(time.perf_counter())
            if (n == steps if steps else ends[-1] - t0 >= seconds):
                break
        return {"t0": t0, "t1": ends[-1], "steps": n,
                "intervals": np.diff([t0] + ends)}

    def finish(self, n: int) -> list:
        """Stop sending; run on until ``n`` requests are done, or none is
        left in flight.  Returns the requests done."""
        self.sending = False
        while self.live and len(self.engine.sched.done) < n:
            self.step()
        return self.engine.sched.done

    def free(self) -> None:
        import jax
        for x in jax.tree.leaves((self.engine.params, self.engine.caches)):
            x.delete()
        self.engine.params = self.engine.caches = None
        gc.collect()


def latency(prog: Program, t0: float, t1: float) -> dict:
    """Tokens per second, the gaps between tokens and the times to the
    first token, over what fell in (t0, t1]."""
    inside = lambda t: t0 < t <= t1
    n_tok, gaps, ttft = 0, [], []
    for rid, ts in prog.times.items():
        n_tok += sum(map(inside, ts))
        gaps += [b - a for a, b in zip(ts, ts[1:]) if inside(b)]
        if ts and inside(ts[0]):
            ttft.append(ts[0] - prog.sent[rid])
    pct = lambda xs, q: 1e3 * float(np.percentile(xs, q)) if xs else \
        float("nan")
    return {"serve_tokens_per_s": n_tok / (t1 - t0),
            "itl_p95_ms": pct(gaps, 95), "ttft_p50_ms": pct(ttft, 50),
            "n_gaps": len(gaps), "n_ttft": len(ttft)}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def sample(done, n: int, rng: np.random.Generator):
    """``n`` finished requests drawn by ``rng``, the longest among them."""
    done = sorted(done, key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.total_len, -r.rid))
    rest = [r for r in done if r is not longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served(reqs, pad_to: int):
    """(tokens (n, pad_to), positions, served ids): each row is a prompt
    and its answer but the last token; position p of row i predicted the
    served token ids[i][k]."""
    rows, where = [], []
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.generated[:-1])
        rows.append(seq + [0] * (pad_to - len(seq)))
        start = len(r.prompt) - 1
        where += [(i, start + k, t) for k, t in enumerate(r.generated)]
    return np.asarray(rows, np.int32), np.asarray(where, np.int64)


def logit_gaps(cell: Cell, key, rows, where, *, low: str = ""
               ) -> np.ndarray:
    """For every served token: how far its float32 reference logit lies
    below the reference's best at that position.  With ``low`` the token
    is the one that control (``reference.py``) puts first there."""
    import jax
    m = cell.model
    i, pos = where[:, 0], where[:, 1]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: weights.make(weights.layout(m), k))(
            jax.random.fold_in(key, 0))
        lg = np.asarray(jax.jit(lambda p, t: reference.logits(m, p, t))(
            params, rows))[i, pos]
        if low:
            tok = np.argmax(np.asarray(jax.jit(lambda p, t: reference.logits(
                m, p, t, low))(params, rows))[i, pos], axis=-1)
        else:
            tok = where[:, 2]
    return lg.max(axis=-1) - lg[np.arange(len(tok)), tok]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, ctx) -> dict:
    from harness import tracing
    from harness.cell import seed_streams

    key, rng = seed_streams(seed)
    tr, m = cell.traffic, cell.model
    spans = tracing.Spans(annotate=True) if trace else None
    prog = Program(cell, key, spans)
    prog.start(request_list(tr, m["vocab_size"], rng))
    setup_s = time.perf_counter() - ctx.t_start
    summary = None
    if trace:
        spans.records.clear()           # the engine's spans from set-up
        with ctx.quiet(), ctx.traced() as traced:
            win = prog.window(steps=tr["trace_steps"], spans=spans)
        summary = traced.trace
    else:
        with ctx.quiet():
            win = prog.window(seconds=seconds)
    lat = latency(prog, win["t0"], win["t1"])
    print(f"window: {win['steps']} engine steps, {lat['n_gaps']} gaps "
          f"between tokens, {lat['n_ttft']} first tokens",
          file=sys.stderr, flush=True)
    ctx.say_intervals("engine step", win["intervals"])
    memory = ctx.memory_peak()
    done = prog.finish(tr["check_requests"])
    short = sum(len(r.generated) != r.max_new for r in done)
    checked = sample(done, tr["check_requests"], rng)
    prog.free()
    rows, where = served(checked, max_len(tr))
    nums = {"logit_gap": float(logit_gaps(cell, key, rows, where).max())}
    counters = {
        "tokens_per_s": lat["serve_tokens_per_s"],
        "step_contexts": prog.contexts,
        "window_s": win["t1"] - win["t0"],
        "flops": sum(workcount.decode_flops(m, c)
                     for step in prog.contexts for c in step),
        "attn_bytes": sum(workcount.paged_attention_bytes(m, step)
                          for step in prog.contexts),
        "attn_flops": sum(workcount.paged_attention_flops(m, step)
                          for step in prog.contexts),
    }
    e2e = {k: lat[k] for k in ("serve_tokens_per_s", "itl_p95_ms",
                               "ttft_p50_ms")}
    e2e["setup_s"] = setup_s
    return ctx.result(cell, nums, attempted=len(done),
                      failed=short, e2e=e2e, counters=counters,
                      spans=spans, trace=summary, memory=memory)
