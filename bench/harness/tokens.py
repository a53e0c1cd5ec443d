"""Token streams and request lengths drawn from the seed."""
from __future__ import annotations

import numpy as np


def markov_rows(key, n_rows: int, seq: int, vocab: int,
                branching: int = 4):
    """(n_rows, seq) int32 token rows of a first-order Markov chain whose
    successor table (``branching`` successors per token) is drawn from
    ``key``; first tokens and choices are uniform.  A learnable stream, so
    the loss can fall.  Call it inside ``jit``."""
    import jax
    import jax.numpy as jnp
    kt, k0, k1 = jax.random.split(key, 3)
    table = jax.random.randint(kt, (vocab, branching), 0, vocab, jnp.int32)
    first = jax.random.randint(k0, (n_rows,), 0, vocab, jnp.int32)
    choices = jax.random.randint(k1, (seq - 1, n_rows), 0, branching,
                                 jnp.int32)

    def step(tok, ch):
        nxt = table[tok, ch]
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, choices)
    return jnp.concatenate([first[None], rest], axis=0).T


def stratified_lengths(spec: dict, n: int, strata: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a lognormal (``median``, ``sigma``) clipped to
    [``min``, ``max``]: the same multiset for every seed (the quantiles at
    (i + 1/2)/n), in an order the seed draws so that every run of
    ``strata`` consecutive requests holds one length from each of
    ``strata`` equal slices of the distribution."""
    from statistics import NormalDist
    if n % strata:
        raise ValueError(f"{n} requests are not whole blocks of {strata}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.clip(np.round(spec["median"] * np.exp(spec["sigma"] * z)),
                      spec["min"], spec["max"]).astype(np.int64)
    blocks = n // strata
    by_stratum = lengths.reshape(strata, blocks)
    by_stratum = np.stack([rng.permutation(row) for row in by_stratum])
    order = np.stack([rng.permutation(strata) for _ in range(blocks)])
    return by_stratum[order, np.arange(blocks)[:, None]].reshape(-1)
