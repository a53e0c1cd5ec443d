"""Plain references the benchmark holds the program to.

Written from the published descriptions, in straightforward ``jax.numpy``,
with no import of the program: a decoder-only transformer (pre-norm,
rotary positions, causal grouped-query attention, a GELU or SwiGLU MLP,
untied head), AdamW, and one DiLoCoX outer step (PowerSGD with one power
iteration from a seeded warm start, Cholesky-QR, block-256 int4 factors,
the mean over clusters, error feedback and Nesterov momentum).

The weights follow the layout the model keeps them in (``weights.py``):
``embed``, ``final_norm``, ``head`` and a list of layer stacks under
``segments``.  Numbers such as the optimizer's constants come from the
workload's traffic file, never from the program.

Every product runs in float32 at ``highest`` (the caller sets the
precision).  ``low`` names a control, the reference put in the program's
place at a precision below the one the configurations state:

- ``"bf16"``: weights, optimizer moments, the residual stream and the
  keys and values stored in bfloat16, each product taken over bfloat16
  operands with a float32 sum (as the TPU's default precision takes
  float32 products);
- ``"int8"``: each operand of every product rounded to int8 codes with one
  scale per tensor (max |x| / 127), the products summed in float32.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _q8(x):
    """x on the int8 grid of scale max|x| / 127; gradients pass straight
    through (the products' backward sees the rounded operands)."""
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


def store(x, low: str = ""):
    """x as the control ``low`` keeps it in memory: rounded to bfloat16
    under ``"bf16"`` (gradients pass through), as it is otherwise.  The
    rounding is a ``reduce_precision``, which the compiler keeps: a
    float32 -> bfloat16 -> float32 round trip of casts it may drop."""
    if low == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(spec: str, a, b, low: str = ""):
    """``jnp.einsum(spec, a, b)`` over the operands the control ``low``
    takes: int8 codes, or bfloat16 summed in float32."""
    if low == "int8":
        a, b = _q8(a), _q8(b)
    elif low == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b)


def _norm(p, x, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y * p["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, positions, theta: float):
    """Rotate-half rotary embedding; x (B, S, heads, dh)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m: dict, p, x, low: str):
    B, S, _ = x.shape
    H, KV, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _norm(p["ln1"], x, m["norm"], m["norm_eps"])
    a = p["attn"]
    mm = lambda x, w: _mm("bsd,de->bse", x, w, low)
    q = _rope(mm(h, a["wq"]).reshape(B, S, H, dh), pos, m["rope_theta"])
    k = _rope(mm(h, a["wk"]).reshape(B, S, KV, dh), pos, m["rope_theta"])
    v = mm(h, a["wv"]).reshape(B, S, KV, dh)
    k, v = store(k, low), store(v, low)
    # query head i reads key/value head i // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, low) / math.sqrt(dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -1e30)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, low)
    x = store(x + mm(o.reshape(B, S, H * dh), a["wo"]), low)
    h = _norm(p["ln2"], x, m["norm"], m["norm_eps"])
    f = p["mlp"]
    if m["mlp"] == "gelu":
        y = jax.nn.gelu(mm(h, f["w_in"]) + f["b_in"], approximate=True)
        y = mm(y, f["w_out"]) + f["b_out"]
    else:
        y = mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
               f["w_down"])
    return store(x + y, low)


def logits(m: dict, params, tokens, low: str = ""):
    """(B, S, vocab) logits of token rows (B, S)."""
    params = jax.tree.map(lambda w: store(w, low), params)
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(m, p, x, low), None))
    for seg in params["segments"]:
        x, _ = jax.lax.scan(body, x, seg)
    h = _norm(params["final_norm"], x, m["norm"], m["norm_eps"])
    return _mm("bsd,dv->bsv", h, params["head"], low)


def loss(m: dict, params, tokens, low: str = ""):
    """Mean next-token cross-entropy over every position but the last."""
    lg = logits(m, params, tokens, low)[:, :-1]
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def adamw(params, grads, state, *, lr, b1, b2, eps, weight_decay,
          grad_clip, low: str = ""):
    """AdamW with decoupled weight decay after clipping the gradients by
    their global norm; parameters and moments are kept as the control
    ``low`` keeps them.  Returns (params, state, clipped grads)."""
    m, v, t = state
    t = t + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, grad_clip / gnorm),
                         grads)
    m = jax.tree.map(lambda m, g: store(b1 * m + (1 - b1) * g, low),
                     m, grads)
    v = jax.tree.map(lambda v, g: store(b2 * v + (1 - b2) * g * g, low),
                     v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: store(p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                        + weight_decay * p), low),
        params, m, v)
    return params, (m, v, t), grads


# ---------------------------------------------------------------------------
# the outer step
# ---------------------------------------------------------------------------

def _quant4(x, block: int):
    """Symmetric int4 with one scale per block of ``block`` values,
    scale = max|x| / 7, codes rounded to nearest and clipped to [-8, 7];
    returns the dequantized values."""
    n = x.size
    xf = jnp.pad(x.reshape(-1), (0, (-n) % block)).reshape(-1, block)
    scale = jnp.max(jnp.abs(xf), axis=1, keepdims=True) / 7.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale), -8, 7)
    return (q * scale).reshape(-1)[:n].reshape(x.shape)


def _cholesky_qr(P, eps: float = 1e-6):
    """Orthonormal columns of P by Cholesky-QR, with a ridge of ``eps``
    times the mean squared column norm."""
    r = P.shape[-1]
    G = P.T @ P
    ridge = eps * jnp.maximum(jnp.trace(G) / r, 1e-30) + 1e-30
    L = jnp.linalg.cholesky(G + ridge * jnp.eye(r, dtype=P.dtype))
    Linv = jax.scipy.linalg.solve_triangular(
        L, jnp.eye(r, dtype=P.dtype), lower=True)
    return P @ Linv.T


def warm_start(tree, *, rank: int, min_dim: int):
    """The power iteration's first Q for each leaf of ``tree``: for a leaf
    (..., m, n) with m, n >= ``min_dim``, a standard normal (n, r) drawn
    from a key made from the leaf's shape, the same for every leading
    index; an empty array for a leaf that is quantized only."""
    def leaf(x):
        shape = x.shape
        if len(shape) < 2 or min(shape[-2:]) < min_dim:
            return jnp.zeros((0,), jnp.float32)
        n = shape[-1]
        r = min(rank, shape[-2], n)
        key = jax.random.PRNGKey(
            zlib.crc32(str((tuple(shape), "q")).encode()) % (2 ** 31))
        q = jax.random.normal(key, (n, r), jnp.float32)
        return jnp.broadcast_to(q, shape[:-2] + (n, r))
    return jax.tree.map(leaf, tree)


def compressed_mean(deltas, qs, *, block: int, low: str = ""):
    """Mean over clusters of each cluster's compressed pseudo-gradient, and
    each cluster's next warm start.  ``deltas`` and ``qs`` are lists (one
    tree per cluster; ``qs`` as ``warm_start`` makes them).  A leaf with a
    warm start goes through one PowerSGD iteration per matrix (one per
    leading index) with int4 factors, and its next warm start is the new
    Q (the old one where Q is all zero); the others are int4 only."""
    def leaf(d, q):
        if q.size == 0:
            return _quant4(d, block), q
        m, n = d.shape[-2:]

        def one(M, q0):
            P = _cholesky_qr(_mm("mn,nr->mr", M, q0, low))
            Q = _mm("mn,mr->nr", M, P, low)
            Pq = _quant4(P.reshape(-1), block).reshape(P.shape)
            Qq = _quant4(Q.reshape(-1), block).reshape(Q.shape)
            return (_mm("mr,nr->mn", Pq, Qq, low),
                    jnp.where(jnp.sum(Q * Q) > 0, Q, q0))

        out, q_new = jax.vmap(one)(d.reshape(-1, m, n),
                                   q.reshape((-1,) + q.shape[-2:]))
        return out.reshape(d.shape), q_new.reshape(q.shape)

    per = [jax.tree.map(leaf, d, q) for d, q in zip(deltas, qs)]
    is_pair = lambda x: isinstance(x, tuple)
    outs = [jax.tree.map(lambda o: o[0], t, is_leaf=is_pair) for t in per]
    q_new = [jax.tree.map(lambda o: o[1], t, is_leaf=is_pair) for t in per]
    mean = jax.tree.map(lambda *o: sum(o) / len(o), *outs)
    return mean, q_new


def nesterov(anchor, v, delta, *, lr: float, momentum: float):
    """The outer step on the anchor with the averaged pseudo-gradient:
    v <- momentum v + delta; anchor <- anchor - lr (momentum v + delta)."""
    v = jax.tree.map(lambda v, d: momentum * v + d, v, delta)
    anchor = jax.tree.map(lambda a, v, d: a - lr * (momentum * v + d),
                          anchor, v, delta)
    return anchor, v
