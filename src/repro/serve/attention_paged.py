"""Paged-gather decode attention: ref path (bitwise vs ``decode_gqa``) and
a Pallas gather-attention kernel behind ``backend={"ref","pallas"}``.

Shapes (per segment):
  q            (S, 1, H, dh)      one query token per slot
  cache k/v    (n_units, 1 + n_pages, page_size, KV * dh)   page 0 = scratch
  layer        ()                 int32: the unit whose pool is read/written
  page_tables  (S, max_pages)     int32; 0 = unallocated -> scratch page
  lengths      (S,)               tokens already cached (== query position)
  active       (S,)               bool slot mask

The pool is lane-dense: a page is ``page_size`` rows of the ``KV * dh``
keys (or values) of every KV head, so with ``page_size`` 8 and ``KV * dh``
a multiple of 128 it is a whole number of the TPU's (8, 128) tiles with no
padding.  Every unit of a segment shares one array, which the decode step
carries through its layer loop and updates in place; each call names its
unit with ``layer``.

Masking contract (jit-shape-stable — one executable for every occupancy):
the gathered key position is computed from the *table column index*
(``page * page_size + slot``), never from page contents, and the additive
``k_pos <= q_pos`` bias kills every position past ``lengths`` — including
whatever the scratch page holds for unallocated entries (finite garbage;
``exp(-1e30)`` underflows to exactly 0.0, so masked lanes contribute
exact zeros). Inactive slots read the all-zero table row -> scratch page
and their output is discarded by the scheduler.

The ref path gathers each sequence's pages of the layer into a contiguous
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
block's pages: ``nb`` K and ``nb`` V page operands, each indexed by the
layer and a scalar-prefetched fetch table, so the next block's copies
overlap this block's compute.  A query head's dot product with a key row
is a sum over its ``dh`` lanes of the lane-dense row: float32 products on
the VPU, summed per head by a 0/1 head-indicator matmul at
``kernels.F32``, which is exact up to the float32 sums' order (Mosaic
refuses the reshape that would split the lanes into heads).  The same
indicator spreads each head's softmax weights back over its lanes.
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

def write_kv(cache, layer, k_new, v_new, page_tables, lengths, active):
    """cache {"k","v"}: (U, P, ps, KV * dh); k_new/v_new: (S, KV, dh).
    Writes row i at ``[layer, table[i, len_i // ps], len_i % ps]``;
    inactive rows are routed to the scratch page (never read unmasked).
    Only those S rows change: the pool is updated in place where the
    caller donates or carries it."""
    ps = cache["k"].shape[2]
    log_page = lengths // ps
    slot = lengths % ps
    phys = jnp.take_along_axis(page_tables, log_page[:, None], axis=1)[:, 0]
    phys = jnp.where(active, phys, 0)
    rows = lambda x, c: x.reshape(x.shape[0], -1).astype(c.dtype)
    return {"k": cache["k"].at[layer, phys, slot].set(
                rows(k_new, cache["k"])),
            "v": cache["v"].at[layer, phys, slot].set(
                rows(v_new, cache["v"]))}


# ---------------------------------------------------------------------------
# ref backend
# ---------------------------------------------------------------------------

def ref_paged_attention(q, cache, layer, page_tables, lengths, *,
                        window: int = 0):
    """Gather the layer's pages -> contiguous per-sequence KV, then the
    same ``_mask_bias`` + ``grouped_attend`` as the dense decode path.
    Returns (S, 1, H, dh) pre-``wo`` attention output."""
    S, P = page_tables.shape
    ps, width = cache["k"].shape[2:]
    dh = q.shape[-1]
    gather = lambda c: c[layer, page_tables].reshape(
        S, P * ps, width // dh, dh)
    k, v = gather(cache["k"]), gather(cache["v"])
    pos = lengths[:, None]
    k_pos = jnp.arange(P * ps, dtype=jnp.int32)[None, :]
    bias = attn._mask_bias(pos, k_pos, causal=True, window=window)
    return attn.grouped_attend(q, k, v, bias)


# ---------------------------------------------------------------------------
# pallas backend
# ---------------------------------------------------------------------------

BLOCK_POSITIONS = 64          # cached positions one block aims to hold
BLOCK_BUFFER_BYTES = 4 << 20  # VMEM for the K and V pages, both buffers


def pages_per_block(page_size: int, max_pages: int, width: int,
                    itemsize: int) -> int:
    """Pages one kernel block copies: about ``BLOCK_POSITIONS`` positions,
    no more than a table row holds, and the block's K and V pages,
    double-buffered, within ``BLOCK_BUFFER_BYTES`` of VMEM at the TPU's
    (8, 128) tiling of a page's ``(page_size, KV * dh)`` rows."""
    page_bytes = (-(-page_size // 8) * 8 * -(-width // 128) * 128
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


def _head_indicator(width: int, dh: int, *, lanes_first: bool):
    """Which of the ``width // dh`` heads each of a lane-dense row's
    ``width`` lanes belongs to, as 0/1: ``(width, KV)``, or ``(KV,
    width)`` when not ``lanes_first``."""
    kv = width // dh
    shape, lane_axis = ((width, kv), 0) if lanes_first else ((kv, width), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_axis)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_axis)
    return (lane // dh == head).astype(jnp.float32)


def _head_sums(x, dh: int):
    """``(T, KV * dh) -> (T, KV)``: each head's sum over its ``dh`` lanes,
    a matmul with the head indicator at ``kernels.F32`` (its products are
    exact; only the order of the float32 sums differs from a VPU sum)."""
    return jnp.dot(x, _head_indicator(x.shape[-1], dh, lanes_first=True),
                   preferred_element_type=jnp.float32,
                   precision=kernels.F32)


def _spread_heads(x, width: int):
    """``(T, KV) -> (T, width)``: each head's value on all of its lanes,
    exactly (one product with 1 per lane)."""
    dh = width // x.shape[-1]
    return jnp.dot(x, _head_indicator(width, dh, lanes_first=False),
                   preferred_element_type=jnp.float32,
                   precision=kernels.F32)


def _paged_kernel(table_ref, len_ref, slot_ref, block_ref, fetch_ref,
                  layer_ref, q_ref, *refs, ps: int, nb: int, n_pages: int,
                  g: int, dh: int, scale: float, window: int):
    """One grid step per block of ``nb`` pages, the slots' blocks back to
    back (``_work_list``): one query row streams its blocks (online
    softmax, flash recurrence), starting at its first block and writing
    its output at its last.  The ``nb`` K and V page operands are gathered
    by the pipeline from the layer's pool at ``fetch_ref``, so the copies
    follow each slot's length (and window) and steps past the last block
    do nothing.

    Rows are lane-dense, ``(positions, KV * dh)``; the query is ``(G,
    KV * dh)``, row ``j`` holding heads ``kv * G + j``.  Per query row:
    scores ``(positions, KV)``, softmax state ``(1, KV)`` and the
    accumulator ``(1, KV * dh)``."""
    del table_ref, layer_ref   # the work list is derived from the table
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * nb:]
    w = pl.program_id(0)
    s, b = slot_ref[w], block_ref[w]
    q_pos = len_ref[s]
    first, n_used = page_span(q_pos, ps, window)
    n_blocks = (jnp.minimum(n_used, n_pages - first) + nb - 1) // nb
    width = q_ref.shape[-1]

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(b >= 0)
    def _block():
        k = jnp.concatenate([r[0, 0] for r in k_refs]).astype(jnp.float32)
        v = jnp.concatenate([r[0, 0] for r in v_refs]).astype(jnp.float32)
        k_pos = (first + b * nb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[0], width // dh), 0)
        ok = k_pos <= q_pos
        if window > 0:
            ok = ok & (k_pos > q_pos - window)
        for j in range(g):
            q = q_ref[0, j:j + 1].astype(jnp.float32)         # (1, W)
            sc = jnp.where(ok, _head_sums(k * q, dh) * scale,
                           NEG_INF)                           # (T, KV)
            m_prev = m_ref[j:j + 1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
            pexp = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j:j + 1] = (l_ref[j:j + 1] * alpha
                              + pexp.sum(axis=0, keepdims=True))
            acc_ref[j:j + 1] = (
                acc_ref[j:j + 1] * _spread_heads(alpha, width)
                + jnp.sum(_spread_heads(pexp, width) * v, axis=0,
                          keepdims=True))
            m_ref[j:j + 1] = m_new

    @pl.when(b == n_blocks - 1)
    def _flush():
        l = _spread_heads(jnp.maximum(l_ref[...], 1e-30), width)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def pallas_paged_attention(q, cache, layer, page_tables, lengths, *,
                           window: int = 0):
    """Same contract as ``ref_paged_attention`` (ulp-bounded, not bitwise:
    the online-softmax recurrence reassociates the reduction).  Reads the
    layer's pages from the stacked pool as it is stored: the kernel's
    operands are the whole pool, and ``layer`` picks its unit."""
    S, _, H, dh = q.shape
    P = page_tables.shape[1]
    _, n_pool, ps, width = cache["k"].shape
    KV = width // dh
    G = H // KV
    nb = pages_per_block(ps, P, width, cache["k"].dtype.itemsize)
    # blocks in use, at most: the slots' pages are distinct pool pages
    # (page 0 aside, once a slot), so they number at most N + S of the
    # pool's N, and a slot's last block is the only one not full
    steps = min(S * -(-P // nb), -(-(n_pool - 1) // nb) + S)
    slot, block, fetch = _work_list(page_tables, lengths, ps=ps, nb=nb,
                                    steps=steps, window=window)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    # lane-dense query rows: row j holds heads kv * G + j, each in the
    # lanes of its KV head
    qg = q.reshape(S, KV, G, dh).transpose(0, 2, 1, 3).reshape(S, G, width)

    def page_spec(j):
        return pl.BlockSpec((1, 1, ps, width),
                            lambda w, t, n, s, b, f, u: (u[0], f[w, j], 0, 0))

    row = pl.BlockSpec((1, G, width),
                       lambda w, t, n, s, b, f, u: (s[w], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[row] + [page_spec(j) for j in range(nb)] * 2,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((G, KV), jnp.float32),        # running max
            pltpu.VMEM((G, KV), jnp.float32),        # running denom
            pltpu.VMEM((G, width), jnp.float32),     # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, nb=nb, n_pages=P, g=G,
                          dh=dh, scale=1.0 / math.sqrt(dh), window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kernels.pallas_interpret(),
        name="paged_attention",
    )(page_tables, lengths, slot, block, fetch, layer, qg,
      *[cache["k"]] * nb, *[cache["v"]] * nb)
    return out.reshape(S, G, KV, dh).transpose(0, 2, 1, 3).reshape(
        S, 1, H, dh)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def paged_attention(q, cache, layer, page_tables, lengths, *,
                    window: int = 0, backend: str = "ref"):
    if backend == "ref":
        return ref_paged_attention(q, cache, layer, page_tables, lengths,
                                   window=window)
    if backend == "pallas":
        return pallas_paged_attention(q, cache, layer, page_tables, lengths,
                                      window=window)
    raise ValueError(f"unknown paged-attention backend {backend!r}")
