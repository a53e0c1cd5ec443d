"""Traffic drawn from the seed: the same seed gives the same inputs, and
another seed other inputs of the same sizes."""
import json
import os

import numpy as np
import pytest

from harness import cell as cells
from harness import serve_closed_loop as S
from harness import tokens
from harness import train_rounds as T

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 2 ** 31 + 12345        # seeds are larger than 32 signed bits hold


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_seed_streams_take_large_seeds():
    k1, r1 = cells.seed_streams(BIG)
    k2, r2 = cells.seed_streams(BIG)
    k3, _ = cells.seed_streams(BIG + 1)
    assert (np.asarray(k1) == np.asarray(k2)).all()
    assert not (np.asarray(k1) == np.asarray(k3)).all()
    assert r1.integers(1 << 30) == r2.integers(1 << 30)


def test_serve_requests_repeat_per_seed_and_keep_their_sizes():
    tr = _traffic("serve-chat")
    draw = lambda s: S.request_list(tr, 50272, cells.seed_streams(s)[1])
    a, b, c = draw(BIG), draw(BIG), draw(BIG + 1)
    assert a == b
    assert a != c
    for i in (0, 1):
        assert sorted(len(r[0]) if i == 0 else r[1] for r in a) == \
            sorted(len(r[0]) if i == 0 else r[1] for r in c)
    lens = np.array([len(r[0]) for r in a])
    assert lens.min() >= tr["prompt"]["min"]
    assert lens.max() <= tr["prompt"]["max"]


def test_every_block_of_clients_spans_the_distribution():
    spec = {"median": 48, "sigma": 0.8, "min": 16, "max": 128}
    x = tokens.stratified_lengths(spec, 64 * 32, 32,
                                  np.random.default_rng(7))
    blocks = x.reshape(-1, 32).mean(axis=1)
    assert blocks.std() < 0.05 * blocks.mean()


def test_train_token_pool_repeats_per_seed():
    import jax
    tr = dict(_traffic("train-h4"), seq_len=16, batch_pool=4)
    m = {"vocab_size": 512}
    pool = lambda s: np.asarray(jax.jit(lambda: T.token_pool(
        tr, m, cells.seed_streams(s)[0]))())
    a, b, c = pool(BIG), pool(BIG), pool(BIG + 1)
    assert a.shape == (4, 1, 2, 16)
    assert (a == b).all() and not (a == c).all()
    rows = a.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)
