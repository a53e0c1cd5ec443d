"""Paged-gather decode attention: ref path (bitwise vs ``decode_gqa``) and
a Pallas gather-attention kernel behind ``backend={"ref","pallas"}``.

Shapes (per segment unit):
  q            (S, 1, H, dh)      one query token per slot
  cache k/v    (1 + n_pages, page_size, KV, dh)   page 0 = scratch
  page_tables  (S, max_pages)     int32; 0 = unallocated -> scratch page
  lengths      (S,)               tokens already cached (== query position)
  active       (S,)               bool slot mask

Masking contract (jit-shape-stable — one executable for every occupancy):
the gathered key position is computed from the *table column index*
(``page * page_size + slot``), never from page contents, and the additive
``k_pos <= q_pos`` bias kills every position past ``lengths`` — including
whatever the scratch page holds for unallocated entries (finite garbage;
``exp(-1e30)`` underflows to exactly 0.0, so masked lanes contribute
exact zeros). Inactive slots read the all-zero table row -> scratch page
and their output is discarded by the scheduler.

The ref path gathers each sequence's pages into a contiguous
``(S, max_pages*page_size, KV, dh)`` view and reuses the *exact*
``_mask_bias`` + ``grouped_attend`` that ``attention.decode_gqa`` runs:
with ``max_pages * page_size == s_max`` the two are bitwise-identical,
which is what the paged ≡ dense greedy-equivalence gate asserts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.models import attention as attn

NEG_INF = attn.NEG_INF


# ---------------------------------------------------------------------------
# scatter this step's K/V rows into their page slots
# ---------------------------------------------------------------------------

def write_kv(cache, k_new, v_new, page_tables, lengths, active):
    """cache {"k","v"}: (P, ps, KV, dh); k_new/v_new: (S, KV, dh). Writes
    row i at (table[i, len_i // ps], len_i % ps); inactive rows are routed
    to the scratch page (never read unmasked)."""
    ps = cache["k"].shape[1]
    log_page = lengths // ps
    slot = lengths % ps
    phys = jnp.take_along_axis(page_tables, log_page[:, None], axis=1)[:, 0]
    phys = jnp.where(active, phys, 0)
    return {"k": cache["k"].at[phys, slot].set(
                k_new.astype(cache["k"].dtype)),
            "v": cache["v"].at[phys, slot].set(
                v_new.astype(cache["v"].dtype))}


# ---------------------------------------------------------------------------
# ref backend
# ---------------------------------------------------------------------------

def ref_paged_attention(q, cache, page_tables, lengths, *, window: int = 0):
    """Gather pages -> contiguous per-sequence KV, then the same
    ``_mask_bias`` + ``grouped_attend`` as the dense decode path.
    Returns (S, 1, H, dh) pre-``wo`` attention output."""
    S, P = page_tables.shape
    ps = cache["k"].shape[1]
    k = cache["k"][page_tables].reshape(S, P * ps, *cache["k"].shape[2:])
    v = cache["v"][page_tables].reshape(S, P * ps, *cache["v"].shape[2:])
    pos = lengths[:, None]
    k_pos = jnp.arange(P * ps, dtype=jnp.int32)[None, :]
    bias = attn._mask_bias(pos, k_pos, causal=True, window=window)
    return attn.grouped_attend(q, k, v, bias)


# ---------------------------------------------------------------------------
# pallas backend
# ---------------------------------------------------------------------------

def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, ps: int, n_pages: int, kv: int,
                  g: int, scale: float, window: int):
    """Grid (S, max_pages): one query row streams its pages (online
    softmax, flash recurrence); the page table is a scalar-prefetch input
    so each page's BlockSpec index map gathers the *physical* page."""
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)             # (H, dh)
    k = k_ref[0].astype(jnp.float32)             # (ps, KV, dh)
    v = v_ref[0].astype(jnp.float32)
    dh = q.shape[-1]
    qg = q.reshape(kv, g, dh)
    # scores (KV, G, ps): batch over KV, contract dh
    sc = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32,
                             precision=kernels.F32) * scale
    q_pos = len_ref[s]
    k_pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (kv, g, ps), 2)
    ok = k_pos <= q_pos
    if window > 0:
        ok = ok & (k_pos > q_pos - window)
    sc = jnp.where(ok, sc, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(sc, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    pexp = jnp.exp(sc - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + pexp.sum(axis=-1)
    # (KV, G, ps) @ (ps, KV, dh) batched over KV -> (KV, G, dh)
    pv = jax.lax.dot_general(pexp, v, (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32,
                             precision=kernels.F32)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _flush():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = o.reshape(kv * g, dh).astype(o_ref.dtype)


def pallas_paged_attention(q, cache, page_tables, lengths, *,
                           window: int = 0):
    """Same contract as ``ref_paged_attention`` (ulp-bounded, not bitwise:
    the online-softmax recurrence reassociates the reduction)."""
    S, _, H, dh = q.shape
    P = page_tables.shape[1]
    ps, KV = cache["k"].shape[1], cache["k"].shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda s, p, t, l: (s, 0, 0)),
            pl.BlockSpec((1, ps, KV, dh),
                         lambda s, p, t, l: (t[s, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, KV, dh),
                         lambda s, p, t, l: (t[s, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda s, p, t, l: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),        # running max
            pltpu.VMEM((KV, G), jnp.float32),        # running denom
            pltpu.VMEM((KV, G, dh), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, n_pages=P, kv=KV, g=G,
                          scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, dh), q.dtype),
        interpret=kernels.pallas_interpret(),
    )(page_tables, lengths, q.reshape(S, H, dh), cache["k"], cache["v"])
    return out.reshape(S, 1, H, dh)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def paged_attention(q, cache, page_tables, lengths, *, window: int = 0,
                    backend: str = "ref"):
    if backend == "ref":
        return ref_paged_attention(q, cache, page_tables, lengths,
                                   window=window)
    if backend == "pallas":
        return pallas_paged_attention(q, cache, page_tables, lengths,
                                      window=window)
    raise ValueError(f"unknown paged-attention backend {backend!r}")
