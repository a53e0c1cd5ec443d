"""A run with its timed path broken underneath comes out not correct: once
for each fault a cell of that kind can have, held to that cell's limits.
(The exchange between chips has no cell here to break: every cell runs
one cluster on one chip.)"""
import jax.numpy as jnp
import pytest

import tiny


def test_sound_runs_are_correct():
    for name in (tiny.TRAIN, tiny.SERVE):
        res = tiny.run(tiny.cell(name))
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 0


@pytest.fixture
def broken_train_step(monkeypatch):
    from repro.launch import steps
    make = steps.make_train_step

    def plant(fault):
        def make_broken(cfg, **kw):
            step = make(cfg, **kw)

            def broken(params, opt, batch):
                if fault == "unchanged":
                    return params, opt, step(params, opt, batch)[2]
                if fault == "nonfinite_in_window":
                    # past set-up's two rounds every loss is NaN
                    params, opt, loss = step(params, opt, batch)
                    h = tiny.cell(tiny.TRAIN).traffic["h"]
                    return params, opt, jnp.where(
                        jnp.max(opt.step) > 2 * h, jnp.nan, loss)
                toks = batch["tokens"]
                half = toks[:, :toks.shape[1] // 2]
                return step(params, opt, {"tokens": jnp.concatenate(
                    [half, half], axis=1)})
            return broken
        monkeypatch.setattr(steps, "make_train_step", make_broken)
    return plant


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "nonfinite_in_window"])
def test_train_fault(broken_train_step, fault):
    broken_train_step(fault)
    res = tiny.run(tiny.cell(tiny.TRAIN))
    assert not res["correct"], res["checks"]


def test_serve_token_altered(monkeypatch):
    from repro.serve import engine
    make = engine.make_paged_decode_step

    def make_broken(cfg, **kw):
        step = make(cfg, **kw)

        def broken(*args):
            nxt, *rest = step(*args)
            nxt = nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size)
            return (nxt, *rest)
        return broken
    monkeypatch.setattr(engine, "make_paged_decode_step", make_broken)
    res = tiny.run(tiny.cell(tiny.SERVE))
    assert not res["correct"], res["checks"]


def test_serve_cache_left_unchanged(monkeypatch):
    from repro.serve import attention_paged
    monkeypatch.setattr(attention_paged, "write_kv",
                        lambda cache, *a, **k: cache)
    res = tiny.run(tiny.cell(tiny.SERVE))
    assert not res["correct"], res["checks"]
