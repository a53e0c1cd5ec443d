"""Operations and bytes of the benchmarked work, counted from the
configuration's shapes.  They belong to the benchmark, so a later kernel
or precision is judged against the same count whatever implements it.

``m`` is a configuration file's ``model`` block.
"""
from __future__ import annotations

import math

from harness import weights

F32 = 4


def layer_matmul_params(m: dict) -> int:
    """Weights of one layer that enter a matrix product."""
    d, H, KV, dh, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    attn = d * H * dh + 2 * d * KV * dh + H * dh * d
    mlp = (2 if m["mlp"] == "gelu" else 3) * d * ff
    return attn + mlp


def matmul_params(m: dict) -> int:
    """Weights that enter a matrix product per token: every layer and the
    head (the embedding is a lookup)."""
    return m["n_layers"] * layer_matmul_params(m) + m["d_model"] * m[
        "vocab_size"]


def param_count(m: dict) -> int:
    """Every weight held: matrices, biases, norms, embedding and head."""
    return sum(math.prod(x.shape) for x in _shapes(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward, no recomputation: 6 per matmul weight, and
    attention's two products over the whole sequence, 12 L H dh S (the
    PaLM count, arXiv:2204.02311 appendix B)."""
    return (6.0 * matmul_params(m)
            + 12.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq)


def decode_flops(m: dict, context: int) -> float:
    """One token through the model with ``context`` keys in its cache."""
    return (2.0 * matmul_params(m)
            + 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context)


def paged_attention_bytes(m: dict, contexts) -> float:
    """What one paged-attention call per layer must read and write for a
    step whose active slots hold ``contexts`` keys each (this step's key
    included): keys and values of those positions, the query and the
    output."""
    kv = 2 * m["n_kv_heads"] * m["head_dim"] * F32
    qo = 2 * m["n_heads"] * m["head_dim"] * F32
    return float(m["n_layers"] * sum(c * kv + qo for c in contexts))


def paged_attention_flops(m: dict, contexts) -> float:
    return float(m["n_layers"] * sum(4 * c * m["n_heads"] * m["head_dim"]
                                     for c in contexts))


def _shapes(m: dict):
    import jax
    return jax.tree.leaves(weights.layout(m))


def outer_step_work(m: dict, *, rank: int, clusters: int,
                    min_dim: int = 64):
    """(FLOPs, bytes) of one outer step for ``clusters`` clusters.

    FLOPs, per low-rank matrix (m x n, rank r) and cluster: P = M Q, Q' =
    M^T P and the reconstruction P Q'^T, 2 m n r each, and Cholesky-QR,
    4 m r^2 + r^3.  Bytes: the float32 round state read and written once:
    each cluster's pseudo-gradient (read), its post-step params (read),
    its error feedback and new pseudo-gradient (written) and its restarted
    params (written); the anchor and the momentum (each read and
    written)."""
    flops = 0.0
    for x in _shapes(m):
        if x.ndim < 2:
            continue
        rows, cols = x.shape[-2:]
        lead = math.prod(x.shape[:-2])
        if min(rows, cols) >= min_dim:
            r = min(rank, rows, cols)
            flops += clusters * lead * (6.0 * rows * cols * r
                                        + 4.0 * rows * r * r + r ** 3)
    n = param_count(m)
    nbytes = F32 * n * (5 * clusters + 4)
    return flops, float(nbytes)
