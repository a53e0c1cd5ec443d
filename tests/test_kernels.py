"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle
(kernels/ref.py), sweeping shapes and dtypes (hypothesis + parametrize)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lowrank_mm import matmul_pallas
from repro.kernels.quant4 import quant4_pack_pallas, quant4_unpack_pallas


# ---------------------------------------------------------------------------
# quant4
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 5000), seed=st.integers(0, 20))
def test_quant4_pack_matches_ref(n, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 3.0
    p_ref, s_ref, _ = ref.quant4_pack_ref(x)
    p_pl, s_pl = quant4_pack_pallas(x)
    np.testing.assert_array_equal(np.asarray(p_pl), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 5000), seed=st.integers(0, 20))
def test_quant4_roundtrip_pallas(n, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 2.0
    p, s = quant4_pack_pallas(x)
    out = quant4_unpack_pallas(p, s, n)
    expect = ref.quant4_roundtrip_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant4_dtypes(dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 5).astype(dtype)
    p, s = quant4_pack_pallas(x.astype(jnp.float32))
    out = quant4_unpack_pallas(p, s, 1024)
    err = np.abs(np.asarray(out) - np.asarray(x, np.float32))
    scale = np.abs(np.asarray(x, np.float32)).max() / 7
    assert err.max() <= scale / 2 + 1e-5


# ---------------------------------------------------------------------------
# tiled matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (100, 70, 36), (1, 512, 64),
                                   (333, 129, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
    out = matmul_pallas(a, b)
    expect = ref.matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 200), k=st.integers(1, 200), n=st.integers(1, 200),
       seed=st.integers(0, 5))
def test_matmul_property(m, k, n, seed):
    a = jax.random.normal(jax.random.PRNGKey(seed), (m, k))
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (k, n))
    np.testing.assert_allclose(np.asarray(matmul_pallas(a, b, bm=64, bn=64,
                                                        bk=64)),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,d", [
    (1, 256, 4, 4, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA 2:1
    (1, 512, 8, 1, 32),     # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, KV, d, causal):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, d))
    k = jax.random.normal(kk, (B, S, KV, d))
    v = jax.random.normal(kv, (B, S, KV, d))
    out = flash_attention_pallas(q, k, v, causal=causal, bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 2, 64)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64)).astype(dtype)
    out = flash_attention_pallas(q, k, v, bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_long_block_sweep():
    """Block-size sweep at longer sequence (the 32k-prefill configuration,
    scaled down)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1024, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, 1, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1024, 1, 64))
    expect = ref.flash_attention_ref(q, k, v)
    for bq, bk in [(128, 256), (256, 128), (512, 512)]:
        out = flash_attention_pallas(q, k, v, bq=bq, bk=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# fused outer-step compressor (kernels/fused_compress.py)
# ---------------------------------------------------------------------------

# Documented ulp bound for the fused reconstruct vs the oracle recon from
# the SAME payload: the only numeric freedom is matmul accumulation order,
# so |fused - oracle| <= ULP_K * eps * (|Pq| @ |Qq|^T) elementwise.
# ULP_K = 16 is generous (measured 0-2 ulp on CPU) to stay stable across
# both CI jax versions.
ULP_K = 16


def _fused_case(m, n, r, rt, dtype=jnp.float32, row_cap=2048):
    from repro.kernels.fused_compress import fused_compress_ef

    d = (jax.random.normal(jax.random.PRNGKey(0), (m, n)) * 0.3).astype(dtype)
    e = jax.random.normal(jax.random.PRNGKey(1), (m, n)) * 0.05
    q = jax.random.normal(jax.random.PRNGKey(2), (n, r))
    rs = None if rt is None else jnp.int32(rt)
    hat, e_new, q_new, pay = jax.jit(
        lambda d_, e_, q_: fused_compress_ef(d_, e_, q_, rs,
                                             row_cap=row_cap))(d, e, q)
    return d, e, q, hat, e_new, q_new, pay


def _assert_fused_contract(m, n, r, rt, d, e, hat, e_new, q_new, pay):
    """The full fused-kernel contract: wire bytes bit-identical to the ref
    packer, recon/EF within the ulp bound of the payload's own oracle
    recon, decompress dual exact, adaptive-rank columns exactly zero."""
    from repro.kernels.fused_compress import fused_decompress

    # 1) pack bytes bit-identical to ref.quant4_pack_ref on the factors
    pP, sP, _ = ref.quant4_pack_ref(np.asarray(pay.p_factor).reshape(-1))
    pQ, sQ, _ = ref.quant4_pack_ref(np.asarray(pay.q_factor).reshape(-1))
    np.testing.assert_array_equal(np.asarray(pay.packed_p), np.asarray(pP))
    np.testing.assert_array_equal(np.asarray(pay.packed_q), np.asarray(pQ))
    np.testing.assert_allclose(np.asarray(pay.scales_p), np.asarray(sP),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pay.scales_q), np.asarray(sQ),
                               rtol=1e-6)

    # 2) recon within the documented ulp bound of the payload's oracle
    Pq = np.asarray(ref.quant4_unpack_ref(
        pay.packed_p, pay.scales_p, m * r)).reshape(m, r)
    Qq = np.asarray(ref.quant4_unpack_ref(
        pay.packed_q, pay.scales_q, n * r)).reshape(n, r)
    oracle = Pq @ Qq.T
    bound = ULP_K * np.finfo(np.float32).eps * (np.abs(Pq) @ np.abs(Qq).T)
    gap = np.abs(np.asarray(hat, np.float32) - oracle)
    if hat.dtype == jnp.bfloat16:       # cast after recon adds a bf16 ulp
        bound = bound + 0.008 * np.abs(oracle) + 1e-6
    assert np.all(gap <= bound + 1e-30), \
        f"recon gap {gap.max()} exceeds ulp bound {bound.max()}"

    # 3) EF residual: e' = (delta + e) - recon (f32 chain)
    M = np.asarray(d, np.float32) + np.asarray(e, np.float32)
    assert e_new.dtype == jnp.float32 and e_new.shape == (m, n)
    ef_gap = np.abs(np.asarray(e_new) - (M - oracle))
    assert np.all(ef_gap <= bound + 2e-6 * np.abs(M) + 1e-6)

    # 4) decompress dual reproduces the forward kernel's recon exactly
    dec = fused_decompress(pay.packed_p, pay.scales_p, pay.packed_q,
                           pay.scales_q, m, n, r,
                           out_dtype=hat.dtype)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(hat))

    # 5) adaptive rank: masked columns are exactly zero end to end
    if rt is not None:
        assert not np.asarray(pay.p_factor)[:, rt:].any()
        assert not np.asarray(pay.q_factor)[:, rt:].any()
        assert not np.asarray(q_new)[:, rt:].any()


def test_fused_compress_smoke():
    """Fast tier-1 gate: one small aligned case end to end."""
    m, n, r, rt = 64, 48, 8, None
    d, e, q, hat, e_new, q_new, pay = _fused_case(m, n, r, rt)
    _assert_fused_contract(m, n, r, rt, d, e, hat, e_new, q_new, pay)


@pytest.mark.slow
@pytest.mark.parametrize("m,n,r,rt", [
    (256, 256, 32, None),     # tile-aligned
    (257, 129, 8, 5),         # non-tile-multiple rows+cols, adaptive rank
    (128, 128, 64, 32),       # r = half masked
    (33, 500, 12, 7),         # wide, blocks straddle row boundaries
    (300, 200, 16, None),     # padded both dims
    (2048, 512, 64, 48),      # multi-tile rows at default row_cap
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_compress_shapes_dtypes(m, n, r, rt, dtype):
    d, e, q, hat, e_new, q_new, pay = _fused_case(m, n, r, rt, dtype)
    _assert_fused_contract(m, n, r, rt, d, e, hat, e_new, q_new, pay)


@pytest.mark.slow
@pytest.mark.parametrize("row_cap", [128, 512])
def test_fused_compress_small_tiles(row_cap):
    """The multi-grid-step path (k-loop accumulation + tile-boundary
    packing) must honor the same contract as single-tile grids."""
    m, n, r, rt = 384, 320, 16, 10
    d, e, q, hat, e_new, q_new, pay = _fused_case(m, n, r, rt,
                                                  row_cap=row_cap)
    _assert_fused_contract(m, n, r, rt, d, e, hat, e_new, q_new, pay)


@pytest.mark.slow
def test_fused_vs_ref_chain():
    """Chain-vs-chain: the fused pipeline against the independently-run
    unfused ref op-chain.  Scales can differ by 1 ulp between the two
    (XLA's divide-by-constant rewrite), which near a rounding tie can
    flip a single int4 code — so the bound allows one code step per
    factor on top of the reorder ulp bound."""
    from repro.kernels.fused_compress import fused_compress_ef

    for m, n, r, rt in [(128, 96, 16, None), (200, 333, 8, 6)]:
        d = jax.random.normal(jax.random.PRNGKey(3), (m, n)) * 0.3
        e = jax.random.normal(jax.random.PRNGKey(4), (m, n)) * 0.05
        q = jax.random.normal(jax.random.PRNGKey(5), (n, r))
        rs = None if rt is None else jnp.int32(rt)
        hat_f, e_f, qn_f, pay_f = jax.jit(lambda a, b, c: fused_compress_ef(
            a, b, c, rs))(d, e, q)
        hat_r, e_r, qn_r, pay_r = jax.jit(lambda a, b, c: ref.outer_step_ref(
            a, b, c, rs))(d, e, q)
        sP = np.asarray(pay_r.scales_p).max()
        sQ = np.asarray(pay_r.scales_q).max()
        Pq = np.abs(np.asarray(pay_r.p_factor)).max()
        Qq = np.abs(np.asarray(pay_r.q_factor)).max()
        atol = sP * Qq + sQ * Pq            # one int4 step per factor
        np.testing.assert_allclose(np.asarray(hat_f), np.asarray(hat_r),
                                   rtol=0, atol=atol)
        np.testing.assert_allclose(np.asarray(qn_f), np.asarray(qn_r),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_fused_rank_scalar_traced():
    """jit-shape-stable adaptive rank: ONE compiled function serves every
    r_t; masked columns stay exactly zero and smaller r_t reconstructs
    strictly less energy."""
    from repro.kernels.fused_compress import fused_compress_ef

    m, n, r = 96, 128, 16
    d = jax.random.normal(jax.random.PRNGKey(0), (m, n))
    e = jnp.zeros((m, n))
    q = jax.random.normal(jax.random.PRNGKey(2), (n, r))
    fn = jax.jit(lambda d_, e_, q_, rt: fused_compress_ef(d_, e_, q_, rt))
    norms = []
    for rt in (16, 8, 4):
        hat, _, q_new, pay = fn(d, e, q, jnp.int32(rt))
        assert hat.shape == (m, n) and q_new.shape == (n, r)
        if rt < r:
            assert not np.asarray(pay.q_factor)[:, rt:].any()
        norms.append(float(jnp.linalg.norm(hat)))
    assert norms[0] > norms[1] > norms[2] > 0


def test_fused_ops_dispatch(monkeypatch):
    """kernels.ops.fused_outer_step routes by REPRO_USE_PALLAS and both
    routes satisfy the same contract."""
    from repro.kernels import ops

    m, n, r = 48, 64, 8
    d = jax.random.normal(jax.random.PRNGKey(0), (m, n))
    e = jax.random.normal(jax.random.PRNGKey(1), (m, n)) * 0.1
    q = jax.random.normal(jax.random.PRNGKey(2), (n, r))
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    hat_r, e_r, qn_r, pay_r = ops.fused_outer_step(d, e, q)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    hat_p, e_p, qn_p, pay_p = ops.fused_outer_step(d, e, q)
    assert hat_r.shape == hat_p.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(pay_p.packed_p),
                                  np.asarray(ref.quant4_pack_ref(
                                      np.asarray(pay_p.p_factor).reshape(-1)
                                  )[0]))
    np.testing.assert_allclose(np.asarray(hat_p), np.asarray(hat_r),
                               rtol=0, atol=0.3)
    np.testing.assert_allclose(np.asarray(qn_p), np.asarray(qn_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (serve engine): pallas kernel vs ref gather path
# ---------------------------------------------------------------------------

def _paged_fixture(seed, *, S, P, ps, KV, G, dh, fill_frac=0.8):
    """Random page pool + table + lengths; scratch page 0 holds garbage to
    prove the masking contract kills unallocated reads."""
    from repro.serve.pages import PageManager

    rng = np.random.default_rng(seed)
    n_pages = S * P
    pm = PageManager(n_pages, ps, S, P)
    lengths = np.zeros(S, np.int32)
    for s in range(S):
        lengths[s] = rng.integers(1, int(P * ps * fill_frac) + 1)
        pm.admit(s, int(lengths[s]))
        for pos in range(int(lengths[s])):
            pm.ensure(s, pos)
    H = KV * G
    k = rng.normal(size=(1 + n_pages, ps, KV, dh)).astype(np.float32)
    v = rng.normal(size=(1 + n_pages, ps, KV, dh)).astype(np.float32)
    k[0] = 1e3          # scratch-page garbage must never leak into outputs
    v[0] = 1e3
    q = rng.normal(size=(S, 1, H, dh)).astype(np.float32)
    cache = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    return (jnp.asarray(q), cache, jnp.asarray(pm.page_table),
            jnp.asarray(lengths))


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("S,P,ps,KV,G,dh", [
    (3, 4, 4, 2, 2, 8),
    (2, 3, 8, 1, 4, 16),
])
def test_paged_attention_pallas_matches_ref(window, S, P, ps, KV, G, dh):
    from repro.serve import attention_paged as ap

    q, cache, table, lengths = _paged_fixture(0, S=S, P=P, ps=ps, KV=KV,
                                              G=G, dh=dh)
    ref_out = ap.ref_paged_attention(q, cache, table, lengths,
                                     window=window)
    pal_out = ap.pallas_paged_attention(q, cache, table, lengths,
                                        window=window)
    assert not np.isnan(np.asarray(pal_out)).any()
    np.testing.assert_allclose(np.asarray(pal_out), np.asarray(ref_out),
                               rtol=1e-3, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1_000), ps=st.sampled_from([2, 4, 8]),
       g=st.sampled_from([1, 2, 4]))
def test_paged_attention_pallas_property(seed, ps, g):
    from repro.serve import attention_paged as ap

    q, cache, table, lengths = _paged_fixture(seed, S=2, P=3, ps=ps, KV=2,
                                              G=g, dh=8)
    ref_out = ap.ref_paged_attention(q, cache, table, lengths)
    pal_out = ap.pallas_paged_attention(q, cache, table, lengths)
    np.testing.assert_allclose(np.asarray(pal_out), np.asarray(ref_out),
                               rtol=1e-3, atol=1e-5)


def test_paged_write_kv_routes_inactive_to_scratch():
    from repro.serve import attention_paged as ap

    ps, KV, dh = 4, 2, 8
    cache = {"k": jnp.zeros((5, ps, KV, dh)), "v": jnp.zeros((5, ps, KV, dh))}
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([5, 2], jnp.int32)       # row 0 -> page 2 slot 1
    k_new = jnp.ones((2, KV, dh))
    out = ap.write_kv(cache, k_new, k_new, table,
                      lengths, jnp.asarray([True, False]))
    k = np.asarray(out["k"])
    assert k[2, 1].all()                            # active row landed
    assert not k[3].any() and not k[4].any()        # inactive row did not
    assert k[0, 2].all()                            # ... it went to scratch


def test_flash_attention_refuses_a_gradient():
    """No backward kernel: differentiating says so instead of handing
    Mosaic a gradient it cannot compile."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 64))
    loss = lambda q_: flash_attention_pallas(q_, q, q, bq=128, bk=128).sum()
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(loss)(q)
