"""Wire-honest mesh compression for the outer step.

The single-host simulator (core.compression) round-trips values; here the
compiled HLO itself must carry only *compressed* bytes across the cluster
axis, so the roofline parser reads honest numbers. Per 2-D parameter matrix
(per scan unit, per cluster):

    P = M Q_warm ; P <- CholeskyQR(P) ; Q = M^T P          (PowerSGD step)
    payload = (pack_int4(P), scales_P, pack_int4(Q), scales_Q)
    Delta   = mean_over_clusters( unpack(payload) )        <- the only op
                                                              crossing the
                                                              slow axis

The mean over the cluster-stacked payload forces GSPMD to move the uint8
payload (or at worst the same bytes in f32 — verified in the dry-run HLO by
the collective parser). 1-D/small leaves are quantized without low-rank.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import (_orthonormalize, matrix_shape,
                                    quant_wire_bytes)
from repro.kernels import ops as kops


@dataclass(frozen=True)
class MeshCompressionConfig:
    rank: int = 128
    bits: int = 4      # wire format is int4 (kernels/quant4) — Alg. 1's q=4;
                       # `bits` is used by the analytic accounting only
    block: int = 256
    min_dim_for_lowrank: int = 64


def _leaf_matrix_dims(shape: Tuple[int, ...]) -> Tuple[int, int, int]:
    """(n_lead, m, n): leading stacked dims (cluster/scan) are vmapped; the
    trailing 2 dims are the PowerSGD matrix."""
    if len(shape) <= 1:
        return (1, 1, shape[0] if shape else 1)
    m, n = shape[-2], shape[-1]
    lead = math.prod(shape[:-2]) if len(shape) > 2 else 1
    return lead, m, n


def init_q_state(params, cfg: MeshCompressionConfig):
    """Warm-start Q per leaf: (lead..., n, r) or empty for quant-only."""
    import zlib

    def mk(path, x):
        lead, m, n = _leaf_matrix_dims(x.shape)
        if min(m, n) < cfg.min_dim_for_lowrank:
            return jnp.zeros((0,), jnp.float32)
        r = min(cfg.rank, m, n)
        key = jax.random.PRNGKey(
            zlib.crc32(str((x.shape, "q")).encode()) % (2 ** 31))
        q = jax.random.normal(key, (n, r), jnp.float32)
        return jnp.broadcast_to(q, x.shape[:-2] + (n, r)).copy()

    return jax.tree_util.tree_map_with_path(mk, params)


def _compress_leaf_matrix(M, q_prev, rank_scalar, cfg: MeshCompressionConfig):
    """M: (m,n) f32; q_prev: (n,r). Returns (Delta_contrib_payload, Q_new)
    where payload = packed factors."""
    r = q_prev.shape[-1]
    if rank_scalar is not None:
        col_mask = (jnp.arange(r) < rank_scalar).astype(jnp.float32)
    else:
        col_mask = jnp.ones((r,), jnp.float32)
    P = kops.matmul(M, q_prev * col_mask)
    with jax.named_scope("outer.orthonormalize"):
        P = _orthonormalize(P) * col_mask
    Q = kops.matmul(M.T, P)
    with jax.named_scope("outer.quant"):
        pP, sP = kops.quant4_pack(P.reshape(-1), cfg.block)
        pQ, sQ = kops.quant4_pack(Q.reshape(-1), cfg.block)
    # zero-input guard (first delayed round): never zero the warm start
    q_new = jnp.where(jnp.sum(Q * Q) > 0, Q, q_prev * col_mask)
    return (pP, sP, pQ, sQ), q_new


def _decompress_leaf_matrix(payload, m, n, r, cfg: MeshCompressionConfig):
    pP, sP, pQ, sQ = payload
    with jax.named_scope("outer.quant"):
        P = kops.quant4_unpack(pP, sP, m * r, cfg.block).reshape(m, r)
        Q = kops.quant4_unpack(pQ, sQ, n * r, cfg.block).reshape(n, r)
    return kops.matmul(P, Q.T)


@jax.named_scope("outer.compress")
def compress_gather_mean(delta_stacked, q_state, rank_scalar,
                         cfg: MeshCompressionConfig):
    """delta_stacked: cluster-stacked pytree (C, ...). Returns
    (Delta mean tree (...), new q_state). The cross-cluster data movement is
    the packed payload (uint8 + scales).  Region ``outer.compress``, with
    the Cholesky-QR in ``outer.orthonormalize`` and int4 pack/unpack in
    ``outer.quant``."""

    def one(path, d, q):
        C = d.shape[0]
        lead, m, n = _leaf_matrix_dims(d.shape[1:])
        if q.size == 0:
            # quant-only: pack per cluster, unpack all, mean
            flat = d.reshape(C, -1).astype(jnp.float32)
            with jax.named_scope("outer.quant"):
                pk, sc = jax.vmap(
                    lambda v: kops.quant4_pack(v, cfg.block))(flat)
                vals = jax.vmap(
                    lambda p, s: kops.quant4_unpack(p, s, flat.shape[1],
                                                    cfg.block))(pk, sc)
            return vals.mean(0).reshape(d.shape[1:]).astype(d.dtype), q

        r = q.shape[-1]
        dm = d.reshape(C * lead, m, n).astype(jnp.float32)
        qm = q.reshape(C * lead, n, r)
        comp = jax.vmap(
            lambda M, qp: _compress_leaf_matrix(M, qp, rank_scalar, cfg))
        payload, q_new = comp(dm, qm)
        dec = jax.vmap(
            lambda pl: _decompress_leaf_matrix(pl, m, n, r, cfg))(payload)
        Delta = dec.reshape(C, lead, m, n).mean(0).reshape(d.shape[1:])
        return Delta.astype(d.dtype), q_new.reshape(q.shape)

    flat_d, treedef = jax.tree_util.tree_flatten_with_path(delta_stacked)
    flat_q = jax.tree.leaves(q_state)
    outs = [one(p, dd, qq) for (p, dd), qq in zip(flat_d, flat_q)]
    Delta = jax.tree.unflatten(treedef, [o[0] for o in outs])
    q_new = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return Delta, q_new


def wire_bytes_tree(params, cfg: MeshCompressionConfig,
                    rank: Optional[int] = None) -> int:
    """Analytic per-cluster payload bytes (for the comm model)."""
    total = 0
    for x in jax.tree.leaves(params):
        lead, m, n = _leaf_matrix_dims(x.shape)
        if min(m, n) < cfg.min_dim_for_lowrank:
            total += quant_wire_bytes(lead * m * n, cfg.bits, cfg.block)
        else:
            r = min(rank if rank is not None else cfg.rank, m, n)
            total += lead * (quant_wire_bytes(m * r, cfg.bits, cfg.block)
                             + quant_wire_bytes(n * r, cfg.bits, cfg.block))
    return total
