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

The Pallas kernel's work follows the lengths: a slot copies and computes
only the pages holding positions ``max(0, len - window + 1) .. len``
(``page_span``), ``nb`` pages a block (``pages_per_block``: ~64
positions).  Its grid is a work list of blocks, the slots' blocks back to
back (``_work_list``), as long as the most blocks that distinct pool pages
can fill (``N / nb + S`` for a pool of N pages and S slots); the steps
past the last block copy and compute nothing.  The pipeline gathers each
block's pages: ``nb`` K and ``nb`` V page operands, each indexed by a
scalar-prefetched fetch table, so the next block's copies overlap this
block's compute.  (Mosaic refuses an in-kernel DMA slice of a pool whose
minor dim, ``dh`` = 64, is under the 128-lane tile, so the pages are not
copied with ``make_async_copy``.)  Scores are exact float32 on the VPU for
MHA (G = 1) and a batched matmul at ``kernels.F32`` for GQA.
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

BLOCK_POSITIONS = 64          # cached positions one block aims to hold
BLOCK_BUFFER_BYTES = 4 << 20  # VMEM for the K and V pages, both buffers


def pages_per_block(page_size: int, max_pages: int, kv: int, dh: int,
                    itemsize: int) -> int:
    """Pages one kernel block copies: about ``BLOCK_POSITIONS`` positions,
    no more than a table row holds, and the block's K and V pages,
    double-buffered, within ``BLOCK_BUFFER_BYTES`` of VMEM at the TPU's
    (8, 128) tiling of the trailing ``(KV, dh)`` axes."""
    page_bytes = (page_size * -(-kv // 8) * 8 * -(-dh // 128) * 128
                  * itemsize)
    return max(1, min(BLOCK_POSITIONS // page_size, max_pages,
                      BLOCK_BUFFER_BYTES // (4 * page_bytes)))


def page_span(lengths, page_size: int, window: int = 0):
    """First logical page and number of pages the kernel copies for a slot
    whose query sits at ``lengths``: the pages holding positions
    ``max(0, len - window + 1) .. len`` (``0 .. len`` when ``window`` is
    0).  Works on numpy and jax arrays alike."""
    first = 0
    if window > 0:
        start = lengths - window + 1
        first = start * (start > 0) // page_size
    return first, lengths // page_size - first + 1


def _work_list(page_tables, lengths, *, ps: int, nb: int, steps: int,
               window: int):
    """The kernel's grid steps, as scalar-prefetch tables of ``steps``
    rows: the slot and block each step computes (block -1: a step past
    the last block, which computes nothing), and the physical page each of
    the ``nb`` K and V page operands holds there.  The slots' blocks come
    first, in slot order, so each block's copies overlap the block before
    it.  An operand whose page lies past its slot's keeps the page it held
    at the step before (page 0 before any), so the pipeline copies nothing
    for it; the mask weighs its rows 0."""
    S, P = page_tables.shape
    first, n_used = page_span(lengths, ps, window)
    first = first + jnp.zeros_like(lengths)
    n_used = jnp.minimum(n_used, P - first)
    n_blocks = (n_used + nb - 1) // nb
    end = jnp.cumsum(n_blocks)
    w = jnp.arange(steps, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(w[:, None] >= end[None, :], axis=1), S - 1)
    block = w - (end - n_blocks)[slot]
    col = block[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)
    used = (col < n_used[slot, None]) & (w < end[-1])[:, None]
    phys = page_tables[slot[:, None],
                       jnp.clip(first[slot, None] + col, 0, P - 1)]
    last = jax.lax.cummax(jnp.where(used, w[:, None], -1), axis=0)
    held = jnp.take_along_axis(phys, jnp.maximum(last, 0), axis=0)
    return (slot, jnp.where(w < end[-1], block, -1),
            jnp.where(last >= 0, held, 0))


def _scores(q, k, g: int):
    """Block scores.  MHA (``g == 1``): exact float32 products summed over
    ``dh`` on the VPU, ``(T, KV, 1)``.  GQA: a batched matmul over KV at
    ``kernels.F32``, ``(KV, G, T)``."""
    if g == 1:
        return jnp.sum(k * q, axis=-1, keepdims=True)
    kv, dh = k.shape[1], k.shape[2]
    return jax.lax.dot_general(q.reshape(kv, g, dh), k,
                               (((2,), (2,)), ((0,), (1,))),
                               preferred_element_type=jnp.float32,
                               precision=kernels.F32)


def _weighted_values(p, v, g: int):
    """``p`` (the scores' layout) against the block's values: ``(KV, dh)``
    for MHA, ``(KV, G, dh)`` for GQA."""
    if g == 1:
        return jnp.sum(p * v, axis=0)
    return jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                               preferred_element_type=jnp.float32,
                               precision=kernels.F32)


def _paged_kernel(table_ref, len_ref, slot_ref, block_ref, fetch_ref, q_ref,
                  *refs, ps: int, nb: int, n_pages: int, g: int,
                  scale: float, window: int):
    """One grid step per block of ``nb`` pages, the slots' blocks back to
    back (``_work_list``): one query row streams its blocks (online
    softmax, flash recurrence), starting at its first block and writing
    its output at its last.  The ``nb`` K and V page operands are gathered
    by the pipeline from ``fetch_ref``, so the copies follow each slot's
    length (and window) and steps past the last block do nothing."""
    del table_ref            # the work list is derived from it
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * nb:]
    w = pl.program_id(0)
    s, b = slot_ref[w], block_ref[w]
    t_axis = 0 if g == 1 else 2          # the scores' position axis
    q_pos = len_ref[s]
    first, n_used = page_span(q_pos, ps, window)
    n_blocks = (jnp.minimum(n_used, n_pages - first) + nb - 1) // nb

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(b >= 0)
    def _block():
        q = q_ref[0].astype(jnp.float32)             # (H, dh)
        k = jnp.concatenate([r[0] for r in k_refs]).astype(jnp.float32)
        v = jnp.concatenate([r[0] for r in v_refs]).astype(jnp.float32)
        sc = _scores(q, k, g) * scale
        k_pos = (first + b * nb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, t_axis)
        ok = k_pos <= q_pos
        if window > 0:
            ok = ok & (k_pos > q_pos - window)
        sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=t_axis))
        pexp = jnp.exp(sc - jnp.expand_dims(m_new, t_axis))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=t_axis)
        if g > 1:
            alpha = alpha[..., None]
        acc_ref[...] = acc_ref[...] * alpha + _weighted_values(pexp, v, g)
        m_ref[...] = m_new

    @pl.when(b == n_blocks - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / (l if g == 1 else l[..., None])
        o_ref[0] = o.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def pallas_paged_attention(q, cache, page_tables, lengths, *,
                           window: int = 0):
    """Same contract as ``ref_paged_attention`` (ulp-bounded, not bitwise:
    the online-softmax recurrence reassociates the reduction)."""
    S, _, H, dh = q.shape
    P = page_tables.shape[1]
    ps, KV = cache["k"].shape[1], cache["k"].shape[2]
    G = H // KV
    nb = pages_per_block(ps, P, KV, dh, cache["k"].dtype.itemsize)
    # blocks in use, at most: the slots' pages are distinct pool pages
    # (page 0 aside, once a slot), so they number at most N + S of the
    # pool's N, and a slot's last block is the only one not full
    n_pool = cache["k"].shape[0] - 1
    steps = min(S * -(-P // nb), -(-n_pool // nb) + S)
    slot, block, fetch = _work_list(page_tables, lengths, ps=ps, nb=nb,
                                    steps=steps, window=window)

    def page_spec(j):
        return pl.BlockSpec((1, ps, KV, dh),
                            lambda w, t, l, s, b, f: (f[w, j], 0, 0, 0))

    row = pl.BlockSpec((1, H, dh), lambda w, t, l, s, b, f: (s[w], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[row] + [page_spec(j) for j in range(nb)] * 2,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),        # running max
            pltpu.VMEM((KV, G), jnp.float32),        # running denom
            pltpu.VMEM((KV, dh) if G == 1 else (KV, G, dh),
                       jnp.float32),                  # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, nb=nb, n_pages=P, g=G,
                          scale=1.0 / math.sqrt(dh), window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kernels.pallas_interpret(),
        name="paged_attention",
    )(page_tables, lengths, slot, block, fetch, q.reshape(S, H, dh),
      *[cache["k"]] * nb, *[cache["v"]] * nb)
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
