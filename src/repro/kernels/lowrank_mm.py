"""Pallas TPU kernel: tiled matmul with f32 VMEM accumulation.

The PowerSGD projections P = M Q and Q = M^T P are the compute hot spot of
the DiLoCoX compressor at 100B scale (two skinny matmuls per parameter
matrix per outer step). Tiles are MXU-aligned (128 by default); the K loop
is the innermost grid dim with a VMEM accumulator flushed on the last K
step — the standard Pallas matmul pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=kernels.F32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                  bn: int = 128, bk: int = 128) -> jnp.ndarray:
    """(m,k) @ (k,n) -> (m,n), f32 accumulation, MXU-aligned tiles."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    bm_, bn_, bk_ = max(1, min(bm, m)), max(1, min(bn, n)), max(1, min(bk, k))
    ap = _pad_to(a, bm_, bk_)
    bp = _pad_to(b, bk_, bn_)
    gm, gn, gk = ap.shape[0] // bm_, bp.shape[1] // bn_, ap.shape[1] // bk_
    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], bp.shape[1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=kernels.pallas_interpret(),
    )(ap, bp)
    return out[:m, :n]
