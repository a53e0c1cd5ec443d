"""Jit'd kernel entry points. Each op dispatches to the Pallas TPU kernel
when available/enabled and to the pure-jnp reference otherwise (CPU tests,
and the GSPMD dry-run where the kernel is a per-shard local op).

Set ``REPRO_USE_PALLAS=1`` to route through
``pl.pallas_call`` — interpreted on the CPU, compiled on a TPU
(``kernels.pallas_interpret``); the kernel tests sweep both paths and
assert they agree with ref.py.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _use_pallas() -> bool:
    return os.environ.get("REPRO_USE_PALLAS", "0") == "1"


# ---------------------------------------------------------------------------
# int4 block quantization
# ---------------------------------------------------------------------------

def quant4_pack(x: jnp.ndarray, block: int = 256):
    """x: flat (n,) -> (packed uint8, scales f32). Pads internally."""
    if _use_pallas():
        from repro.kernels.quant4 import quant4_pack_pallas
        return quant4_pack_pallas(x, block)
    packed, scales, _ = ref.quant4_pack_ref(x, block)
    return packed, scales


def quant4_unpack(packed: jnp.ndarray, scales: jnp.ndarray, n: int,
                  block: int = 256) -> jnp.ndarray:
    if _use_pallas():
        from repro.kernels.quant4 import quant4_unpack_pallas
        return quant4_unpack_pallas(packed, scales, n, block)
    return ref.quant4_unpack_ref(packed, scales, n, block)


def quant_dequant(x: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    shape = x.shape
    p, s = quant4_pack(x.reshape(-1), block)
    return quant4_unpack(p, s, x.size, block).reshape(shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused outer-step compressor (EF add + PowerSGD + quant4 pack + recon + EF)
# ---------------------------------------------------------------------------

def fused_outer_step(delta, error, q_prev, rank_scalar=None,
                     block: int = 256):
    """One parameter matrix's full outer-step compression: returns
    ``(delta_hat, e_new, q_new, payload)`` — the fused Pallas pipeline
    under REPRO_USE_PALLAS=1, the unfused jnp op-chain otherwise.  Same
    wire bytes either way; reconstruction agrees within the reorder-ulp
    bound gated in tests/test_kernels.py."""
    from repro.obs import profile as _prof
    if _use_pallas():
        from repro.kernels.fused_compress import fused_compress_ef
        with _prof.scope("fused_outer_step/pallas"):
            return fused_compress_ef(delta, error, q_prev, rank_scalar,
                                     block=block)
    with _prof.scope("fused_outer_step/ref"):
        return ref.outer_step_ref(delta, error, q_prev, rank_scalar, block)


# ---------------------------------------------------------------------------
# matmul (PowerSGD projection hot spot)
# ---------------------------------------------------------------------------

def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    if _use_pallas() and a.ndim == 2 and b.ndim == 2:
        from repro.kernels.lowrank_mm import matmul_pallas
        return matmul_pallas(a, b)
    return ref.matmul_ref(a, b)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True):
    if _use_pallas():
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal)
    return ref.flash_attention_ref(q, k, v, causal=causal)
