"""Gradient/pseudo-gradient compressors (paper §2.4).

The DiLoCoX compressor (Alg. 1) is ``Quantize_q ∘ LowRank_r``:
PowerSGD-style single-iteration subspace projection with a persistent
warm-start Q per 2-D-reshaped parameter, followed by block-wise symmetric
int-q quantization of the two factors. It is gather-compatible (the wire
payload is the packed factors), which is how the outer collective stays at
compressed size in the compiled HLO (DESIGN.md §3).

Baselines from the paper's comparison are here too: Top-K, random
sparsification, CocktailSGD (random ∘ top-k ∘ quant), fp16/no-op
(OpenDiLoCo).

Adaptive rank: to stay jit-shape-stable while Alg. 3 anneals r_t, factors
are allocated at ``r_max`` and columns >= r_t are zero-masked at runtime;
wire-byte accounting uses r_t. Semantics match a true rank-r_t compressor.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# quantization (simulation numerics; kernels/quant4.py is the wire format)
# ---------------------------------------------------------------------------

def quantize_sim(x: jnp.ndarray, bits: int, block: int = 256) -> jnp.ndarray:
    """Symmetric per-block quantize->dequantize (value-faithful simulation of
    the packed wire format; kernels/ops.quant_dequant matches this)."""
    if bits >= 32:
        return x
    if bits == 16:
        return x.astype(jnp.bfloat16).astype(x.dtype)
    orig_shape = x.shape
    n = x.size
    pad = (-n) % block
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad)).reshape(-1, block)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(xf), axis=1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax)
    out = (q * scale).reshape(-1)[:n].reshape(orig_shape)
    return out.astype(x.dtype)


def quant_wire_bytes(n_elems: int, bits: int, block: int = 256) -> int:
    payload = math.ceil(n_elems * bits / 8)
    scales = math.ceil(n_elems / block) * 2          # bf16 scales
    return payload + scales


# ---------------------------------------------------------------------------
# 2-D reshape helpers (PowerSGD operates per-matrix)
# ---------------------------------------------------------------------------

def to_matrix(x: jnp.ndarray) -> jnp.ndarray:
    if x.ndim <= 1:
        return x.reshape(1, -1)
    # merge all leading dims; keep last dim as columns (weights are (in, out))
    return x.reshape(-1, x.shape[-1])


def matrix_shape(shape: Tuple[int, ...]) -> Tuple[int, int]:
    if len(shape) <= 1:
        return (1, math.prod(shape) if shape else 1)
    m = 1
    for s in shape[:-1]:
        m *= s
    return (m, shape[-1])


def _orthonormalize(P: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Cholesky-QR: G = P^T P + eps_rel*I, P <- P L^{-T}. All-matmul (MXU
    friendly) and GSPMD-shardable, unlike Householder QR which gathers the
    tall matrix; zero (rank-masked) columns stay zero.

    eps is RELATIVE to mean(diag(G)): pseudo-gradients are ~1e-2 scale, so
    an absolute 1e-6 ridge dominated P^T P and mangled the reconstruction
    (DiLoCoX training silently stalled — caught by the convergence-ordering
    integration tests)."""
    Pf = P.astype(jnp.float32)
    r = Pf.shape[-1]
    G = Pf.T @ Pf
    scale = jnp.trace(G) / r
    ridge = eps * jnp.maximum(scale, 1e-30) + 1e-30
    L = jnp.linalg.cholesky(G + ridge * jnp.eye(r, dtype=jnp.float32))
    Linv = jax.scipy.linalg.solve_triangular(
        L, jnp.eye(r, dtype=jnp.float32), lower=True)
    out = Pf @ Linv.T
    return jnp.where(jnp.isfinite(out), out, 0.0)


# ---------------------------------------------------------------------------
# compressor base protocol
# ---------------------------------------------------------------------------

class Compressor:
    """compress(tree, state) -> (payload_tree, state); decompress(payload) ->
    tree. ``roundtrip`` fuses both (what the convergence sim uses).
    ``wire_bytes(tree_shapes)`` is the analytic on-the-wire size."""

    name = "identity"

    def init_state(self, params) -> Any:
        return jnp.zeros((), jnp.int32)

    def roundtrip(self, tree, state, rank_scalar=None):
        return tree, state

    def wire_bytes(self, shapes: Dict[str, Tuple[int, ...]],
                   rank: Optional[int] = None) -> int:
        return sum(math.prod(s) * 4 for s in shapes.values())

    def wire_bytes_per_edge(self, shapes: Dict[str, Tuple[int, ...]],
                            ranks: Dict[int, int]) -> Dict[int, int]:
        """Per-sender payload sizes under per-edge adaptive ranks: ``ranks``
        maps cluster id -> the rank that cluster compresses at for its own
        uplink (the bandwidth-aware controller's gossip decision — every
        directed edge carries the sender's payload)."""
        return {c: int(self.wire_bytes(shapes, rank=r))
                for c, r in ranks.items()}


def tree_shapes(tree) -> Dict[str, Tuple[int, ...]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): tuple(x.shape) for p, x in flat}


@dataclass
class Identity(Compressor):
    name: str = "allreduce_fp32"


@dataclass
class FP16(Compressor):
    """OpenDiLoCo's FP16 pseudo-gradient compression."""
    name: str = "fp16"

    def roundtrip(self, tree, state, rank_scalar=None):
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(x.dtype), tree), state

    def wire_bytes(self, shapes, rank=None):
        return sum(math.prod(s) * 2 for s in shapes.values())


@dataclass
class QuantOnly(Compressor):
    bits: int = 4
    block: int = 256
    name: str = "quant"

    def roundtrip(self, tree, state, rank_scalar=None):
        return jax.tree.map(
            lambda x: quantize_sim(x, self.bits, self.block), tree), state

    def wire_bytes(self, shapes, rank=None):
        return sum(quant_wire_bytes(math.prod(s), self.bits,
                                    self.block) for s in shapes.values())


# ---------------------------------------------------------------------------
# DiLoCoX: LowRank r ∘ Quantize q  (Alg. 1)
# ---------------------------------------------------------------------------

@dataclass
class LowRankQuant(Compressor):
    rank: int = 64                 # r_max (adaptive r_t <= rank)
    bits: int = 4
    block: int = 256
    min_dim_for_lowrank: int = 64  # small tensors skip the low-rank stage
    name: str = "diloco_x"
    # "ref": the unfused jnp op-chain below.  "pallas": the fused
    # compress+EF kernel pipeline (kernels/fused_compress.py) — same wire
    # format bit-for-bit, reconstruction within a
    # documented reorder-ulp bound of the ref chain, identical adaptive-
    # rank masking contract.  Threaded through ``per_cluster_compress``
    # unchanged (the backend only changes what ``roundtrip`` dispatches
    # to); the proc/in-process equivalence gates stay bitwise per backend.
    backend: str = "ref"

    def __post_init__(self):
        if self.backend not in ("ref", "pallas"):
            raise ValueError(f"backend must be 'ref' or 'pallas', "
                             f"got {self.backend!r}")
        if self.backend == "pallas" and self.bits != 4:
            raise ValueError("the pallas backend implements the int4 wire "
                             f"format (bits=4); got bits={self.bits}")

    def init_state(self, params) -> Any:
        """Warm-start Q per matrix-shaped param (PowerSGD memory)."""
        def mk(x):
            m, n = matrix_shape(x.shape)
            if min(m, n) < self.min_dim_for_lowrank:
                return jnp.zeros((0,), jnp.float32)
            r = min(self.rank, m, n)
            # keyed by the matrix PowerSGD sees, so a stacked leaf draws the
            # same Q however its leading dims split (pipeline stages x layers)
            key = jax.random.PRNGKey(zlib.crc32(str((m, n)).encode())
                                     % (2 ** 31))
            return jax.random.normal(key, (n, r), jnp.float32)
        return jax.tree.map(mk, params)

    def _quant_only_pallas(self, x):
        """quantize_sim via the quant4 pallas kernels. Same elementwise f32
        op sequence; under jit both paths are bitwise equal. (Eagerly,
        quantize_sim's `amax / 7.0` is an exact IEEE divide while the
        interpreted kernel — always jitted — gets XLA's divide-by-constant
        → reciprocal-multiply rewrite, so scales can differ by 1 ulp.)"""
        from repro.kernels.quant4 import (quant4_pack_pallas,
                                          quant4_unpack_pallas)
        flat = x.reshape(-1).astype(jnp.float32)
        rows = -(-flat.size // self.block)
        p, s = quant4_pack_pallas(flat, self.block,
                                  rows_per_tile=min(rows, 1024))
        out = quant4_unpack_pallas(p, s, flat.size, self.block,
                                   rows_per_tile=min(rows, 1024))
        return out.reshape(x.shape).astype(x.dtype)

    def _one(self, x, q_prev, rank_scalar):
        m, n = matrix_shape(x.shape)
        if q_prev.size == 0:     # quant-only path for small/1-D tensors
            if self.backend == "pallas":
                return self._quant_only_pallas(x), q_prev
            return quantize_sim(x, self.bits, self.block), q_prev
        if self.backend == "pallas":
            from repro.kernels.fused_compress import fused_compress_ef
            M = to_matrix(x).astype(jnp.float32)
            hat, _, q_new, _ = fused_compress_ef(
                M, None, q_prev, rank_scalar, block=self.block,
                compute_error=False)
            return hat.reshape(x.shape).astype(x.dtype), q_new
        M = to_matrix(x).astype(jnp.float32)
        r = q_prev.shape[1]
        # rank mask: columns >= r_t contribute nothing (adaptive rank)
        if rank_scalar is not None:
            col_mask = (jnp.arange(r) < rank_scalar).astype(jnp.float32)
        else:
            col_mask = jnp.ones((r,), jnp.float32)
        P = M @ (q_prev * col_mask)                  # (m, r)
        P = _orthonormalize(P) * col_mask
        Q = M.T @ P                                  # (n, r)
        Pq = quantize_sim(P, self.bits, self.block)
        Qq = quantize_sim(Q, self.bits, self.block)
        out = (Pq @ Qq.T).reshape(x.shape).astype(x.dtype)
        # zero-input guard: with the one-step delay the FIRST pending delta
        # is all-zero; M.T P == 0 would zero the warm start and the
        # compressor never recovers (P = M @ 0 forever). Keep q_prev then.
        q_new = jnp.where(jnp.sum(Q * Q) > 0, Q, q_prev * col_mask)
        return out, q_new        # warm start with *unquantized* Q
    def roundtrip(self, tree, state, rank_scalar=None):
        flat, treedef = jax.tree.flatten(tree)
        flat_q = jax.tree.leaves(state)
        outs, new_q = [], []
        for x, q in zip(flat, flat_q):
            o, nq = self._one(x, q, rank_scalar)
            outs.append(o)
            new_q.append(nq)
        return treedef.unflatten(outs), treedef.unflatten(new_q)

    def wire_bytes(self, shapes, rank=None):
        r_eff = rank if rank is not None else self.rank
        total = 0
        for s in shapes.values():
            m, n = matrix_shape(s)
            if min(m, n) < self.min_dim_for_lowrank:
                total += quant_wire_bytes(m * n, self.bits, self.block)
            else:
                r = min(r_eff, self.rank, m, n)
                total += quant_wire_bytes((m + n) * r, self.bits, self.block)
        return total


# ---------------------------------------------------------------------------
# baselines: top-k / random / CocktailSGD
# ---------------------------------------------------------------------------

@dataclass
class TopK(Compressor):
    ratio: float = 0.01
    name: str = "topk"

    def roundtrip(self, tree, state, rank_scalar=None):
        def one(x):
            flat = x.reshape(-1)
            k = max(1, int(flat.size * self.ratio))
            _, idx = jax.lax.top_k(jnp.abs(flat), k)
            mask = jnp.zeros_like(flat).at[idx].set(1.0)
            return (flat * mask).reshape(x.shape)
        return jax.tree.map(one, tree), state

    def wire_bytes(self, shapes, rank=None):
        total = 0
        for s in shapes.values():
            n = math.prod(s)
            k = max(1, int(n * self.ratio))
            total += k * 4 + k * 4          # values + int32 indices
        return total


@dataclass
class RandomSparse(Compressor):
    ratio: float = 0.1
    seed: int = 0
    name: str = "random_sparse"

    def roundtrip(self, tree, state, rank_scalar=None):
        step = state

        def one(path, x):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), step),
                zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
            mask = (jax.random.uniform(key, x.shape) < self.ratio)
            return jnp.where(mask, x / self.ratio, 0.0).astype(x.dtype)

        out = jax.tree_util.tree_map_with_path(one, tree)
        return out, step + 1

    def wire_bytes(self, shapes, rank=None):
        # seed is free; values are ratio * n
        return sum(int(math.prod(s) * self.ratio) * 4
                   for s in shapes.values())


@dataclass
class CocktailSGD(Compressor):
    """Random sparsify -> Top-K within the sample -> quantize (Wang et al.
    2023). Ratios per the paper's §4.1.3 hyperparameters."""
    random_ratio: float = 0.1
    topk_ratio: float = 0.08
    bits: int = 4
    seed: int = 0
    name: str = "cocktail"

    def roundtrip(self, tree, state, rank_scalar=None):
        step = state

        def one(path, x):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), step),
                zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
            flat = x.reshape(-1)
            rmask = (jax.random.uniform(key, flat.shape) < self.random_ratio)
            sampled = jnp.where(rmask, flat, 0.0)
            k = max(1, int(flat.size * self.random_ratio * self.topk_ratio))
            _, idx = jax.lax.top_k(jnp.abs(sampled), k)
            tmask = jnp.zeros_like(flat).at[idx].set(1.0)
            kept = sampled * tmask
            return quantize_sim(kept, self.bits).reshape(x.shape)

        out = jax.tree_util.tree_map_with_path(one, tree)
        return out, step + 1

    def wire_bytes(self, shapes, rank=None):
        total = 0
        for s in shapes.values():
            n = math.prod(s)
            k = max(1, int(n * self.random_ratio * self.topk_ratio))
            total += quant_wire_bytes(k, self.bits) + k * 4   # + indices
        return total


def make_compressor(name: str, **kw) -> Compressor:
    table = {"identity": Identity, "allreduce_fp32": Identity, "fp16": FP16,
             "quant": QuantOnly, "diloco_x": LowRankQuant,
             "lowrank_quant": LowRankQuant, "topk": TopK,
             "random_sparse": RandomSparse, "cocktail": CocktailSGD}
    return table[name](**kw)
