"""Serve subsystem: page-manager partition invariants (property test),
scheduler state machine / backpressure / determinism (stubbed step, no
jax), and the paged ≡ dense greedy-token equivalence gates."""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.pages import PageManager
from repro.serve.scheduler import (DECODE, DONE, PREFILL, WAITING, Request,
                                   Scheduler)


# ---------------------------------------------------------------------------
# PageManager: free-list + in-use partitions the pool under any op sequence
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_pages=st.integers(4, 40),
       ps=st.integers(1, 8), max_seqs=st.integers(1, 6))
def test_page_manager_partition_invariant(seed, n_pages, ps, max_seqs):
    rng = random.Random(seed)
    max_pp = 6
    pm = PageManager(n_pages, ps, max_seqs, max_pp)
    live = {}          # slot -> [fed, total]
    for _ in range(300):
        op = rng.random()
        free_slots = [i for i in range(max_seqs) if i not in live]
        if op < 0.45 and free_slots:
            total = rng.randint(1, max_pp * ps)
            if pm.can_admit(total):
                slot = rng.choice(free_slots)
                pm.admit(slot, total)
                live[slot] = [0, total]
        elif op < 0.9 and live:
            slot = rng.choice(sorted(live))
            fed, total = live[slot]
            if fed < total:
                pm.ensure(slot, fed)
                live[slot][0] += 1
            else:
                pm.release(slot)
                del live[slot]
        elif live:          # early release (EOS before the length cap)
            slot = rng.choice(sorted(live))
            pm.release(slot)
            del live[slot]
        pm.check_partition()
    for slot in list(live):
        pm.release(slot)
    pm.check_partition()
    assert pm.used_pages == 0
    assert pm.free_pages == pm.n_pages
    assert pm.reserved_pages == 0


def test_page_manager_reservation_guarantees_growth():
    """Admission reserves the worst case, so ensure() can never run dry
    mid-decode even when the pool is exactly full."""
    pm = PageManager(n_pages=4, page_size=2, max_seqs=2,
                     max_pages_per_seq=2)
    pm.admit(0, 4)                       # reserves 2 pages
    pm.admit(1, 4)                       # reserves the other 2
    assert not pm.can_admit(1)           # pool fully reserved
    for pos in range(4):
        pm.ensure(0, pos)
        pm.ensure(1, pos)
    pm.check_partition()
    assert pm.free_pages == 0
    pm.release(0)
    assert pm.can_admit(4)


def test_page_manager_rejects_oversized_and_double_admit():
    pm = PageManager(n_pages=8, page_size=4, max_seqs=2,
                     max_pages_per_seq=2)
    assert not pm.can_admit(9)           # > max_pages_per_seq * ps
    pm.admit(0, 8)
    with pytest.raises(ValueError):
        pm.admit(0, 4)


# ---------------------------------------------------------------------------
# Scheduler: state machine on a stubbed device step (no jax)
# ---------------------------------------------------------------------------

def _drive(sched, next_token_fn, max_steps=2000):
    step = 0
    while sched.has_work():
        assert step < max_steps, "scheduler did not drain"
        sched.admit_ready(step)
        plan = sched.plan_step()
        if plan is not None:
            tokens, lengths, active = plan
            sched.commit(next_token_fn(tokens, lengths, active, step), step)
            sched.pages.check_partition()
        step += 1
    return step


def _mk(pages_kw=None, **kw):
    pages_kw = pages_kw or dict(n_pages=12, page_size=4, max_seqs=3,
                                max_pages_per_seq=4)
    pm = PageManager(**pages_kw)
    return Scheduler(pm, max_seqs=pages_kw["max_seqs"], **kw)


def _const(tok):
    return lambda tokens, lengths, active, step: np.full(len(tokens), tok)


def test_scheduler_runs_all_to_length_cap():
    sched = _mk()
    for rid in range(5):
        sched.submit(Request(rid, prompt=[1, 2, 3], max_new=4,
                             arrival=rid))
    _drive(sched, _const(7))
    assert len(sched.done) == 5
    for r in sched.done:
        assert r.state == DONE and r.finish_reason == "length"
        assert r.generated == [7, 7, 7, 7]
        assert r.first_token_step >= r.admit_step + len(r.prompt) - 1


def test_scheduler_eos_recycles_slot():
    sched = _mk(eos_id=9)

    def fn(tokens, lengths, active, step):
        # request 0 hits EOS on its second generated token
        out = np.full(len(tokens), 5)
        if step == 4:
            out[:] = 9
        return out

    sched.submit(Request(0, prompt=[1, 2, 3], max_new=10, arrival=0))
    sched.submit(Request(1, prompt=[1, 2], max_new=3, arrival=0))
    sched.submit(Request(2, prompt=[1], max_new=2, arrival=0))
    _drive(sched, fn)
    eos_done = [r for r in sched.done if r.finish_reason == "eos"]
    assert eos_done, "no request finished on EOS"
    for r in eos_done:
        assert r.generated[-1] == 9
        assert 9 not in r.generated[:-1]
    # all slots were recycled and the pool fully drained
    assert sched.pages.used_pages == 0


def test_scheduler_backpressure_defers_never_ooms():
    # pool of 2 pages, each request needs 2: strictly one at a time
    sched = _mk(pages_kw=dict(n_pages=2, page_size=2, max_seqs=3,
                              max_pages_per_seq=2))
    for rid in range(4):
        sched.submit(Request(rid, prompt=[1, 2], max_new=2, arrival=0))
    _drive(sched, _const(3))
    assert len(sched.done) == 4
    assert sched.deferred > 0                   # backpressure happened
    assert len(sched.admissions) == 4
    # serialized: at most one admission per step window of 4 tokens
    steps = [t for t, _, _ in sched.admissions]
    assert steps == sorted(steps)


def test_scheduler_static_policy_admits_in_waves():
    def run(policy):
        sched = _mk(policy=policy)
        for rid in range(6):
            sched.submit(Request(rid, prompt=[1, 2], max_new=2 + 4 * (rid % 2),
                                 arrival=0))
        n = _drive(sched, _const(3))
        return sched, n

    stat, n_stat = run("static")
    cont, n_cont = run("continuous")
    assert len(stat.done) == len(cont.done) == 6
    # static admits full waves: admission steps take <= 2 distinct values
    assert len({t for t, _, _ in stat.admissions}) == 2
    assert n_cont < n_stat                      # continuous drains faster


def test_scheduler_admission_fingerprint_deterministic():
    def run():
        sched = _mk()
        for rid in range(5):
            sched.submit(Request(rid, prompt=[1] * (2 + rid % 3),
                                 max_new=3, arrival=rid // 2))
        _drive(sched, _const(3))
        return sched.admission_fingerprint()

    assert run() == run()


# ---------------------------------------------------------------------------
# paged ≡ dense greedy equivalence (ref backend)
# ---------------------------------------------------------------------------

def _dense_greedy(cfg, params, prompts, gen_len, s_max):
    """Legacy dense loop (steps.make_serve_step), equal-length prompts."""
    import jax
    import jax.numpy as jnp

    from repro.launch import steps
    from repro.models import model as M

    B, P = prompts.shape
    state = M.init_decode_state(cfg, B, s_max)
    serve_step = jax.jit(steps.make_serve_step(cfg))
    for t in range(P):
        nxt, state = serve_step(params, state, jnp.asarray(prompts[:, t:t + 1]))
    outs = [np.asarray(nxt)]
    for _ in range(gen_len - 1):
        nxt, state = serve_step(params, state, nxt)
        outs.append(np.asarray(nxt))
    return np.concatenate(outs, axis=1)


def test_paged_equals_dense_greedy_lockstep():
    """Same checkpoint, same prompts, greedy tokens identical: with
    max_pages*page_size == s_max and all slots in lockstep the ref paged
    path is bitwise-identical to the dense cache (serve/README.md)."""
    import jax

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-8b").reduced()    # plain GQA, no window
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    P, G, ps, maxP = 5, 7, 4, 3                   # maxP*ps == s_max == 12
    B = 2
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size))
    dense = _dense_greedy(cfg, params, prompts, G, s_max=maxP * ps)

    eng = ServeEngine(params, cfg, max_seqs=B, page_size=ps,
                      n_pages=B * maxP, max_pages_per_seq=maxP,
                      eos_id=None)
    for b in range(B):
        eng.submit(prompts[b].tolist(), G, arrival=0)
    eng.run()
    done = sorted(eng.sched.done, key=lambda r: r.rid)
    for b in range(B):
        assert done[b].generated == dense[b].tolist(), \
            f"row {b}: paged {done[b].generated} != dense {dense[b].tolist()}"


def test_paged_continuous_staggered_matches_per_seq_dense():
    """Staggered arrivals + unequal prompt lengths: each request's greedy
    tokens match a dedicated B=1 dense decode of the same prompt (the
    paged engine tracks true per-sequence positions)."""
    import jax

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ps, maxP, G = 4, 3, 5
    rng = np.random.default_rng(3)
    reqs = [(0, rng.integers(0, cfg.vocab_size, 3).tolist()),
            (2, rng.integers(0, cfg.vocab_size, 6).tolist()),
            (4, rng.integers(0, cfg.vocab_size, 4).tolist())]

    eng = ServeEngine(params, cfg, max_seqs=2, page_size=ps,
                      n_pages=3 * maxP, max_pages_per_seq=maxP, eos_id=None)
    for arrival, prompt in reqs:
        eng.submit(prompt, G, arrival=arrival)
    eng.run()
    done = sorted(eng.sched.done, key=lambda r: r.rid)
    for (arrival, prompt), req in zip(reqs, done):
        dense = _dense_greedy(cfg, params,
                              np.asarray(prompt)[None, :], G,
                              s_max=maxP * ps)
        assert req.generated == dense[0].tolist(), \
            f"rid {req.rid}: {req.generated} != {dense[0].tolist()}"


def test_paged_engine_kept_logits_match_the_dense_forward():
    """``keep_logits``: every position's logits, staggered arrivals and
    unequal prompts, equal the dense teacher-forced forward over the
    request's own tokens, and the greedy tokens are their argmax."""
    import jax

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ps, maxP, G = 4, 3, 5
    rng = np.random.default_rng(3)
    reqs = [(0, rng.integers(0, cfg.vocab_size, 3).tolist()),
            (2, rng.integers(0, cfg.vocab_size, 6).tolist())]
    eng = ServeEngine(params, cfg, max_seqs=2, page_size=ps,
                      n_pages=2 * maxP, max_pages_per_seq=maxP, eos_id=None,
                      keep_logits=True)
    for arrival, prompt in reqs:
        eng.submit(prompt, G, arrival=arrival)
    eng.run()
    for req in sorted(eng.sched.done, key=lambda r: r.rid):
        seq = np.asarray([req.prompt + req.generated], np.int32)
        h, _ = M.forward_hidden(params, cfg, {"tokens": seq[:, :-1]})
        want = np.asarray(M.logits_fn(params, cfg, h))[0]
        got = np.stack([req_rows for _, req_rows in
                        sorted(eng.logits[req.rid].items())])
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        assert got[len(req.prompt) - 1:].argmax(-1).tolist() == \
            req.generated


def test_paged_engine_eos_and_backpressure_integration():
    """Tiny pool + EOS enabled: requests defer instead of OOMing, every
    request completes, no page leaks."""
    import jax

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, max_seqs=3, page_size=4, n_pages=4,
                      max_pages_per_seq=2)       # pool < 3 full requests
    rng = np.random.default_rng(0)
    for r in range(5):
        eng.submit(rng.integers(0, cfg.vocab_size, 4).tolist(), 4,
                   arrival=0)
    st = eng.run()
    assert st["requests_done"] == 5
    assert eng.pages.used_pages == 0
    eng.pages.check_partition()
    for r in eng.sched.done:
        if r.finish_reason == "eos":
            assert r.generated[-1] == cfg.eos_id
            assert cfg.eos_id not in r.generated[:-1]
