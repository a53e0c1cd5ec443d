"""Pallas TPU kernel: block-wise symmetric int4 quantization (pack/unpack).

This is the wire-format hot spot of the DiLoCoX compressor (Alg. 1 step 2):
every outer step quantizes the PowerSGD factors of every parameter matrix.
The kernel streams ``rows_per_tile`` quantization blocks from HBM into
VMEM, computes the per-block scale on the VPU, packs two int4 codes per
byte, and writes the packed payload + scales back out.

Layout: a block's even and odd elements arrive as two (rows, block/2)
operands (a strided slice in the wrapper), so the pack is a lane-wise
``lo | hi << 4`` with no in-kernel lane interleave, which Mosaic cannot
lower; unpack returns the two halves and the wrapper interleaves them.
Scales are (rows, 1) blocks: Mosaic refuses rank-1 blocks that are not a
multiple of 128.  Validated against ``ref.quant4_pack_ref`` (bit-identical
bytes) in interpret mode and compiled for v5e in the TPU compile tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import kernels

# uint8 tiles are (32, 128) on TPU: row tiles stay a multiple of 32
_ROW_UNIT = 32


def _pack_kernel(even_ref, odd_ref, packed_ref, scale_ref):
    even = even_ref[...]                            # (rows, block/2) f32
    odd = odd_ref[...]
    amax = jnp.maximum(jnp.max(jnp.abs(even), axis=1, keepdims=True),
                       jnp.max(jnp.abs(odd), axis=1, keepdims=True))
    scale = jnp.where(amax == 0, 1.0, amax / 7.0)   # qmax = 7

    def code(x):
        return jnp.clip(jnp.round(x / scale), -8, 7).astype(jnp.int32) & 0xF

    packed_ref[...] = (code(even) | (code(odd) << 4)).astype(jnp.uint8)
    scale_ref[...] = scale


def _unpack_kernel(packed_ref, scale_ref, even_ref, odd_ref):
    p = packed_ref[...].astype(jnp.int32)           # (rows, block/2)
    scale = scale_ref[...]                          # (rows, 1)

    def value(c):
        return jnp.where(c >= 8, c - 16, c).astype(jnp.float32) * scale

    even_ref[...] = value(p & 0xF)
    odd_ref[...] = value((p >> 4) & 0xF)


def _row_tile(rows: int, rows_per_tile: int) -> int:
    unit = -(-max(rows_per_tile, 1) // _ROW_UNIT) * _ROW_UNIT
    return min(unit, -(-rows // _ROW_UNIT) * _ROW_UNIT)


def quant4_pack_pallas(x: jnp.ndarray, block: int = 256,
                       rows_per_tile: int = 512):
    """x: flat (n,) -> (packed uint8 (ceil(n/2),), scales f32)."""
    if block % 2:
        raise ValueError(f"block must be even, got {block}")
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, block)
    rows = xp.shape[0]
    rt = _row_tile(rows, rows_per_tile)
    xp = jnp.pad(xp, ((0, (-rows) % rt), (0, 0)))
    half = block // 2
    row_spec = pl.BlockSpec((rt, half), lambda i: (i, 0))
    packed, scales = pl.pallas_call(
        _pack_kernel,
        grid=(xp.shape[0] // rt,),
        in_specs=[row_spec, row_spec],
        out_specs=[row_spec, pl.BlockSpec((rt, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], half), jnp.uint8),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=kernels.pallas_interpret(),
    )(xp[:, 0::2], xp[:, 1::2])
    return packed[:rows].reshape(-1), scales[:rows, 0]


def quant4_unpack_pallas(packed: jnp.ndarray, scales: jnp.ndarray, n: int,
                         block: int = 256,
                         rows_per_tile: int = 512) -> jnp.ndarray:
    rows = scales.shape[0]
    half = block // 2
    rt = _row_tile(rows, rows_per_tile)
    row_pad = (-rows) % rt
    pp = jnp.pad(packed.reshape(rows, half), ((0, row_pad), (0, 0)))
    sc = jnp.pad(scales, (0, row_pad)).reshape(-1, 1)
    row_spec = pl.BlockSpec((rt, half), lambda i: (i, 0))
    even, odd = pl.pallas_call(
        _unpack_kernel,
        grid=(pp.shape[0] // rt,),
        in_specs=[row_spec, pl.BlockSpec((rt, 1), lambda i: (i, 0))],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(pp.shape, jnp.float32)] * 2,
        interpret=kernels.pallas_interpret(),
    )(pp, sc)
    # interleave as a constant-index gather: the stack+reshape spelling
    # takes the TPU compiler ~20 s at (512, 128)
    order = np.stack([np.arange(half), half + np.arange(half)], 1).ravel()
    out = jnp.concatenate([even[:rows], odd[:rows]], axis=1)[:, order]
    return out.reshape(-1)[:n]
