"""The controls, the reference put in the program's place with int8
operands or with bfloat16 storage, come out not correct against the cells'
limits: here at a size the CPU holds (on the chip they were read at the
cells' own sizes, PERF.md)."""
import pytest

import tiny
from harness import cell as cells
from harness import compare
from harness import serve_closed_loop as S
from harness import train_rounds as T

SEED = 2 ** 31 + 7


@pytest.mark.parametrize("low", ["int8", "bf16"])
def test_train_control_fails_a_limit(low):
    c = tiny.cell(tiny.TRAIN)
    key, _ = cells.seed_streams(SEED)
    ref = T.reference_obs(c, key)
    ok, checks = compare.judge(
        T.numbers(T.reference_obs(c, key, low=low), ref), c.limits)
    assert not ok, checks
    # and the reference in the program's place passes them
    assert compare.judge(T.numbers(ref, ref), c.limits)[0]


def test_serve_control_fails_the_limit():
    c = tiny.cell(tiny.SERVE)
    c.config = {"arch": "opt-1.3b", "model": dict(
        tiny.MODEL, d_model=512, n_layers=6, vocab_size=4096, d_ff=2048,
        head_dim=64)}
    key, rng = cells.seed_streams(SEED)
    prog = S.Program(c, key)
    prog.start(S.request_list(c.traffic, c.model["vocab_size"], rng))
    prog.window(steps=200)
    checked = S.sample(prog.finish(10 ** 6), 12, rng)
    prog.free()
    rows, where = S.served(checked, S.max_len(c.traffic))
    assert S.logit_gaps(c, key, rows, where).max() <= c.limits["logit_gap"]
    gap = S.logit_gaps(c, key, rows, where, low="int8").max()
    assert gap > c.limits["logit_gap"], gap
