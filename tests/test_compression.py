"""Unit + property tests for the DiLoCoX compressor stack (paper §2.4,
Lemma 3.6) — hypothesis drives shapes/ranks/bit-widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import compression as C

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# quantization properties
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(n=st.integers(8, 2000), bits=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 10))
def test_quant_elementwise_bound(n, bits, seed):
    """|dequant(x) - x| <= scale/2 per element, scale = blockmax/qmax."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (n,)))
    block = 256
    out = np.asarray(C.quantize_sim(jnp.asarray(x), bits, block))
    qmax = 2.0 ** (bits - 1) - 1
    pad = (-n) % block
    xp = np.pad(x, (0, pad)).reshape(-1, block)
    scale = np.abs(xp).max(1) / qmax
    bound = np.repeat(np.maximum(scale, 1e-12), block)[:n] / 2 + 1e-6
    assert (np.abs(out - x) <= bound).all()


@settings(max_examples=20, deadline=None)
@given(n=st.integers(8, 500), bits=st.sampled_from([4, 8]),
       seed=st.integers(0, 5))
def test_quant_idempotent(n, bits, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    once = C.quantize_sim(x, bits)
    twice = C.quantize_sim(once, bits)
    np.testing.assert_allclose(np.asarray(once), np.asarray(twice),
                               rtol=0, atol=1e-6)


def test_quant_zero_input():
    x = jnp.zeros((100,))
    assert np.allclose(np.asarray(C.quantize_sim(x, 4)), 0.0)


# ---------------------------------------------------------------------------
# Lemma 3.6: end-to-end compressor error bound
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(m=st.integers(64, 200), n=st.integers(64, 200),
       rank=st.sampled_from([8, 16, 32]), bits=st.sampled_from([4, 8]),
       seed=st.integers(0, 5))
def test_lemma_3_6_error_bound(m, n, rank, bits, seed):
    """E||C(x)-x||^2 <= omega^2 ||x||^2 with omega^2 = 1 - (r/d) 2^{-q}
    (paper Lemma 3.6), for Gaussian inputs."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, n)) / np.sqrt(n)
    comp = C.LowRankQuant(rank=rank, bits=bits)
    state = comp.init_state({"w": x})
    out, _ = comp.roundtrip({"w": x}, state)
    err = float(jnp.sum((out["w"] - x) ** 2))
    nrm = float(jnp.sum(x ** 2))
    d = min(m, n)
    omega2 = 1.0 - (min(rank, d) / d) * (2.0 ** (-bits))
    assert err / nrm <= omega2 + 1e-3, (err / nrm, omega2)


def test_lowrank_exact_at_full_rank():
    """rank >= min(m,n) and high bits => near-exact reconstruction after the
    warm-start iteration converges."""
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 64))
    # build an exactly rank-16 matrix
    u = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    xl = (u @ v) / 16.0
    comp = C.LowRankQuant(rank=16, bits=16, min_dim_for_lowrank=8)
    state = comp.init_state({"w": xl})
    # a few warm-start iterations (PowerSGD subspace converges)
    out = None
    for _ in range(4):
        out, state = comp.roundtrip({"w": xl}, state)
    rel = float(jnp.linalg.norm(out["w"] - xl) / jnp.linalg.norm(xl))
    assert rel < 0.05, rel


def test_rank_mask_matches_true_rank():
    """rank_scalar masking == a compressor built with that smaller rank
    (same warm start), the jit-shape-stable adaptive-rank trick."""
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 128))
    big = C.LowRankQuant(rank=32, bits=16, min_dim_for_lowrank=8)
    st_b = big.init_state({"w": x})
    out_m, _ = big.roundtrip({"w": x}, st_b, rank_scalar=jnp.asarray(8))
    small = C.LowRankQuant(rank=8, bits=16, min_dim_for_lowrank=8)
    st_s = {"w": jax.tree.leaves(st_b)[0][:, :8]}
    out_s, _ = small.roundtrip({"w": x}, st_s)
    np.testing.assert_allclose(np.asarray(out_m["w"]),
                               np.asarray(out_s["w"]), atol=2e-3)


def test_warm_start_follows_the_matrix_not_the_stacking():
    """A stacked leaf's leading dims (pipeline stages x layers per stage)
    merge into one matrix: (2, 1, m, n) and (1, 2, m, n) compress the same
    (2m, n) matrix and must start from the same Q."""
    comp = C.LowRankQuant(rank=8, bits=4, min_dim_for_lowrank=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    a = comp.init_state({"w": x.reshape(2, 1, 16, 32)})["w"]
    b = comp.init_state({"w": x.reshape(1, 2, 16, 32)})["w"]
    assert a.shape == (32, 8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out_a, _ = comp.roundtrip({"w": x.reshape(2, 1, 16, 32)}, {"w": a})
    out_b, _ = comp.roundtrip({"w": x.reshape(1, 2, 16, 32)}, {"w": b})
    np.testing.assert_array_equal(np.asarray(out_a["w"]).reshape(x.shape),
                                  np.asarray(out_b["w"]).reshape(x.shape))


# ---------------------------------------------------------------------------
# wire-bytes accounting (feeds the 357x throughput model)
# ---------------------------------------------------------------------------

def test_wire_bytes_ratios():
    shapes = {"w1": (4096, 4096), "w2": (4096, 16384), "b": (4096,)}
    raw = sum(np.prod(s) for s in shapes.values()) * 4
    lr = C.LowRankQuant(rank=64, bits=4)
    ratio = raw / lr.wire_bytes(shapes)
    assert ratio > 100, ratio   # low-rank+int4 compresses >100x here
    # adaptive rank shrinks the wire
    assert lr.wire_bytes(shapes, rank=16) < lr.wire_bytes(shapes, rank=64)
    # fp16 is exactly 2x
    assert abs(raw / C.FP16().wire_bytes(shapes) - 2.0) < 1e-6


def test_compression_ratio_paper_107b_setting():
    """Paper §4.1.3: rank 2048 on the 107B model ~ 'approximately 2x'
    low-rank compression, int4 ~8x, LocalSGD H=125 amortizes the rest of the
    1000x communication reduction."""
    d = 8192
    shapes = {"w": (d, 4 * d)}
    lr = C.LowRankQuant(rank=2048, bits=4)
    raw = d * 4 * d * 4
    wire = lr.wire_bytes(shapes)
    # (m+n)*r*0.5 bytes vs m*n*4: (8192+32768)*2048 / 2 = 42MB vs 1073MB
    assert 20 < raw / wire < 40, raw / wire


# ---------------------------------------------------------------------------
# baselines sanity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["topk", "random_sparse", "cocktail"])
def test_sparse_compressors_shrink_wire(name):
    comp = C.make_compressor(name)
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (256, 256))}
    state = comp.init_state(tree)
    out, state2 = comp.roundtrip(tree, state)
    assert out["w"].shape == tree["w"].shape
    nz = float((out["w"] != 0).mean())
    assert nz < 0.5
    assert comp.wire_bytes(C.tree_shapes(tree)) < 256 * 256 * 4


def test_random_sparse_unbiased():
    """E[roundtrip(x)] == x for random sparsification (importance-weighted)."""
    comp = C.RandomSparse(ratio=0.25)
    x = {"w": jnp.ones((64, 64))}
    state = comp.init_state(x)
    acc = jnp.zeros((64, 64))
    n = 200
    for _ in range(n):
        out, state = comp.roundtrip(x, state)
        acc = acc + out["w"]
    assert abs(float(acc.mean()) / n - 1.0) < 0.1


# ---------------------------------------------------------------------------
# compressor backend switch (ref vs fused pallas kernels)
# ---------------------------------------------------------------------------

def _backend_pair(**kw):
    kw.setdefault("rank", 8)
    kw.setdefault("min_dim_for_lowrank", 8)
    return (C.LowRankQuant(**kw),
            C.LowRankQuant(backend="pallas", **kw))


def test_backend_validation():
    with pytest.raises(ValueError):
        C.LowRankQuant(backend="cuda")
    with pytest.raises(ValueError):
        C.LowRankQuant(backend="pallas", bits=8)
    assert C.make_compressor(
        "diloco_x", rank=4, backend="pallas").backend == "pallas"


def test_backend_pallas_matches_ref_roundtrip():
    """Same warm start, same wire format: the pallas backend's roundtrip
    tracks the ref chain within quantization-step tolerance over several
    rounds (warm starts drift by reorder ulps, so not bitwise)."""
    cr, cp = _backend_pair()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (48, 32)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (32,))}
    sr, sp = cr.init_state(params), cp.init_state(params)
    for rnd in range(3):
        delta = jax.tree.map(lambda x: x * (0.3 + 0.1 * rnd), params)
        outr, sr = cr.roundtrip(delta, sr)
        outp, sp = cp.roundtrip(delta, sp)
        for k in outr:
            a, b = np.asarray(outr[k]), np.asarray(outp[k])
            assert np.max(np.abs(a - b)) < 5e-2 * max(np.abs(a).max(), 1.0), \
                f"round {rnd} leaf {k}"


def test_backend_pallas_quant_only_bitwise_under_jit():
    """Small/1-D tensors skip low-rank: under jit both backends run the
    identical f32 op sequence, so the values are bitwise equal."""
    cr, cp = _backend_pair()
    x = {"b": jax.random.normal(jax.random.PRNGKey(7), (300,))}
    sr, sp = cr.init_state(x), cp.init_state(x)
    outr = jax.jit(lambda t, s: cr.roundtrip(t, s)[0])(x, sr)
    outp = jax.jit(lambda t, s: cp.roundtrip(t, s)[0])(x, sp)
    np.testing.assert_array_equal(np.asarray(outr["b"]),
                                  np.asarray(outp["b"]))


def test_backend_pallas_jit_rank_traced():
    """One compiled roundtrip serves every adaptive r_t (jit shape
    stability), and masked warm-start columns stay exactly zero."""
    _, cp = _backend_pair()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 48))}
    sp = cp.init_state(params)
    fn = jax.jit(lambda t, s, r: cp.roundtrip(t, s, rank_scalar=r))
    for rt in (8, 5, 2):
        out, s2 = fn(params, sp, jnp.int32(rt))
        assert out["w"].shape == (64, 48)
        assert np.all(np.isfinite(np.asarray(out["w"])))
        if rt < 8:
            assert not np.asarray(s2["w"])[:, rt:].any()
