"""Weights made by the benchmark from the seed.

One rule for every leaf of the layout, keyed by the leaf's path, so the
program and the reference are given the same numbers and neither makes
them: norm scales 1 + 0.1 N(0, 1), biases 0.02 N(0, 1), the embedding
0.02 N(0, 1), every other matrix N(0, 1) / sqrt(fan-in).  Scales and
biases are not left at 1 and 0, so a program that skipped one would read
differently from the reference.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _leaf(path, shape, key):
    name = jax.tree_util.keystr(path)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
    z = jax.random.normal(k, shape, jnp.float32)
    last = getattr(path[-1], "key", "")
    if last == "scale":
        return 1.0 + 0.1 * z
    if last in ("bias", "b_in", "b_out") or last == "embed":
        return 0.02 * z
    return z / math.sqrt(shape[-2])


def make(layout, key):
    """A tree of float32 weights shaped like ``layout`` (a tree of
    ``ShapeDtypeStruct``), drawn from ``key``.  Call it inside ``jit``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, s: _leaf(path, s.shape, key), layout)


def layout(m: dict):
    """The weights' shapes for a configuration file's ``model`` block, in
    the layout the model keeps them: one stack of ``n_layers`` layers."""
    d, H, KV, dh, ff, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab_size"],
                              m["n_layers"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def norm(*lead):
        out = {"scale": s(*lead, d)}
        if m["norm"] == "layernorm":
            out["bias"] = s(*lead, d)
        return out

    if m["mlp"] == "gelu":
        mlp = {"w_in": s(L, d, ff), "b_in": s(L, ff), "w_out": s(L, ff, d),
               "b_out": s(L, d)}
    else:
        mlp = {"w_gate": s(L, d, ff), "w_up": s(L, d, ff),
               "w_down": s(L, ff, d)}
    layer = {"ln1": norm(L), "ln2": norm(L), "mlp": mlp,
             "attn": {"wq": s(L, d, H * dh), "wk": s(L, d, KV * dh),
                      "wv": s(L, d, KV * dh), "wo": s(L, H * dh, d)}}
    tree = {"embed": s(V, d), "final_norm": norm(), "segments": [layer]}
    if not m.get("tie_embeddings"):
        tree["head"] = s(d, V)
    return tree


def check_layout(m: dict, program_shapes) -> None:
    """Refuse a program whose weights are not laid out as ``layout``."""
    want = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                  layout(m))
    got = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                 program_shapes)
    if want != got:
        raise SystemExit(f"the program's weights are not laid out as the "
                         f"configuration states:\n want {want}\n got {got}")
