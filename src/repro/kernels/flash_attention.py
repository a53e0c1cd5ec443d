"""Pallas TPU kernel: causal flash attention (online softmax).

Used for the 32k prefill / 4k train attention hot spot: the (Sq, Sk) score
matrix never leaves VMEM — each (batch*head, q-block) grid cell streams
k/v blocks, maintaining running max/denominator in f32 (Rabe-Staats /
FlashAttention recurrence). GQA is handled by the wrapper (kv heads are
index-mapped, not materialized, via the BlockSpec head mapping).

Forward only: there is no backward kernel, so differentiating through it
raises (``_no_backward``) rather than handing Mosaic a gradient it cannot
compile.  Training takes the XLA attention path (``REPRO_USE_PALLAS``
unset).  Validated against ``ref.flash_attention_ref`` in interpret mode
and on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_kblocks: int, scale: float,
                  causal: bool):
    """Grid: (bh, n_qblocks, n_kblocks); q block fixed per (i,j), k/v block
    varies with kk (innermost)."""
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)                 # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                precision=kernels.F32) * scale

    if causal:
        q_pos = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p, v, preferred_element_type=jnp.float32,
                              precision=kernels.F32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kk == n_kblocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, bq: int = 256,
                           bk: int = 256) -> jnp.ndarray:
    """q: (B,Sq,H,d); k,v: (B,Sk,KV,d). Returns (B,Sq,H,d)."""
    return _flash(q, k, v, causal, bq, bk)


@functools.partial(jax.custom_jvp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, bq, bk):
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(d)
    bq_ = max(1, min(bq, Sq))
    bk_ = max(1, min(bk, Sk))
    assert Sq % bq_ == 0 and Sk % bk_ == 0, (Sq, Sk, bq_, bk_)

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)

    n_q, n_k = Sq // bq_, Sk // bk_
    grid = (B * H, n_q, n_k)

    def q_map(h, j, kk):
        return (h, j, 0)

    def kv_map(h, j, kk):
        # GQA: query head h reads kv head h // G of its batch
        b = h // H
        kvh = (h % H) // G
        return (b * KV + kvh, kk, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq_, bk=bk_, n_kblocks=n_k,
                          scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), q_map),
            pl.BlockSpec((1, bk_, d), kv_map),
            pl.BlockSpec((1, bk_, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),     # running max
            pltpu.VMEM((bq_,), jnp.float32),     # running denom
            pltpu.VMEM((bq_, d), jnp.float32),   # accumulator
        ],
        interpret=kernels.pallas_interpret(),
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)


@_flash.defjvp
def _no_backward(causal, bq, bk, primals, tangents):
    raise NotImplementedError(
        "flash_attention_pallas is forward-only (no backward kernel): "
        "differentiate through the XLA attention path instead, i.e. leave "
        "REPRO_USE_PALLAS unset for training")
