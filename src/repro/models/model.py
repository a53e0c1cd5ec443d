"""Unified model definition: every assigned architecture is a list of
*segments* — runs of identical layer "units" executed with ``lax.scan`` over
stacked unit params (keeps HLO small at 40-78 layers and gives the remat
boundary). Heterogeneous stacks (gemma3 5:1 local/global, zamba2 shared
block, xlstm mLSTM/sLSTM, deepseek first-dense) become short segment lists
via run-length encoding of the per-layer spec.

Public API:
    init_params(cfg, rng)                -> params pytree
    loss_fn(params, cfg, batch, rng)     -> (loss, aux)
    init_decode_state(cfg, batch, s_max) -> decode cache pytree
    decode_step(params, cfg, state, tokens) -> (logits, new_state)
    count_params(cfg) / count_active_params(cfg)  (via eval_shape, no alloc)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (apply_norm, apply_swiglu, dense_init,
                                 embed_init, init_norm, init_swiglu, split)


# ---------------------------------------------------------------------------
# segment protocol
# ---------------------------------------------------------------------------

@dataclass
class Segment:
    kind: str
    n: int
    init_unit: Callable          # key -> unit params
    apply_unit: Callable         # (p, x, ctx) -> (x, aux_scalar)
    init_cache: Callable         # (batch, s_max, dtype) -> unit cache (or None)
    decode_unit: Callable        # (p, x1, cache, index, ctx) -> (x1, cache)


def _rle(specs: List[str]) -> List[Tuple[str, int]]:
    out: List[Tuple[str, int]] = []
    for s in specs:
        if out and out[-1][0] == s:
            out[-1] = (s, out[-1][1] + 1)
        else:
            out.append((s, 1))
    return out


# ---------------------------------------------------------------------------
# dense / gqa / mla layer units
# ---------------------------------------------------------------------------

def apply_ffn_unit(p, x, cfg: ModelConfig, *, use_moe: bool = False):
    """FFN half of a transformer unit: ln2 + MLP/MoE dispatch (handles the
    layernorm/gelu family, swiglu, ln2-less and mlp-less variants). Shared
    by the train/decode units here and the paged serve engine
    (repro.serve.engine), which must stay bitwise-identical to this path.
    Returns (ffn_out, aux_scalar)."""
    with jax.named_scope("model.ffn"):
        if use_moe:
            h = apply_norm(p["ln2"], x, cfg.norm)
            return moe_lib.apply_moe(p["moe"], h, cfg)
        if "mlp" not in p:
            return jnp.zeros_like(x), 0.0
        h = apply_norm(p["ln2"], x, cfg.norm) if "ln2" in p else x
        if "w_gate" in p["mlp"]:
            return apply_swiglu(p["mlp"], h), 0.0
        from repro.models.layers import apply_gelu_mlp
        return apply_gelu_mlp(p["mlp"], h), 0.0


def _mk_attn_layer(cfg: ModelConfig, *, window: int, cross: bool = False,
                   causal: bool = True, use_moe: bool = False,
                   dense_ffn: bool = True, shared_after: bool = False,
                   kind: str = "dense"):
    """Builds a Segment unit for one transformer layer."""
    d, dt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    is_mla = cfg.attn_type == "mla"

    def init_unit(key):
        ks = split(key, 8)
        p: Dict[str, Any] = {"ln1": init_norm(cfg.norm, d, dt)}
        if is_mla:
            p["attn"] = attn.init_mla(ks[0], d, cfg.n_heads, cfg.mla, dt)
        else:
            p["attn"] = attn.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                      hd, dt)
        if cross:
            p["ln_x"] = init_norm(cfg.norm, d, dt)
            p["cross"] = attn.init_gqa(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                       hd, dt)
        if use_moe or (dense_ffn and cfg.d_ff > 0):
            p["ln2"] = init_norm(cfg.norm, d, dt)
        if use_moe:
            p["moe"] = moe_lib.init_moe(ks[2], cfg, dt)
        elif dense_ffn and cfg.d_ff > 0:
            if cfg.norm == "layernorm" and cfg.family in ("dense", "audio"):
                from repro.models.layers import init_gelu_mlp
                p["mlp"] = init_gelu_mlp(ks[3], d, cfg.d_ff, dt)
            else:
                p["mlp"] = init_swiglu(ks[3], d, cfg.d_ff, dt)
        return p

    @jax.named_scope("model.attn")
    def _self_attn(p, x, ctx):
        h = apply_norm(p["ln1"], x, cfg.norm)
        if is_mla:
            return attn.apply_mla(
                p["attn"], h, ctx["positions"], n_heads=cfg.n_heads,
                mla=cfg.mla, rope_theta=cfg.rope_theta, chunk=ctx["chunk"])
        return attn.apply_gqa(
            p["attn"], h, ctx["positions"], n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=hd, rope_theta=cfg.rope_theta,
            causal=causal, window=window, chunk=ctx["chunk"],
            mrope_positions=ctx.get("mrope_positions"),
            mrope_sections=cfg.mrope_sections if cfg.mrope else None)

    def _ffn(p, x, ctx):
        return apply_ffn_unit(p, x, cfg, use_moe=use_moe)

    def apply_unit(p, x, ctx):
        if cfg.parallel_residual and not use_moe:
            a = _self_attn(p, x, ctx)
            f, aux = _ffn(p, x, ctx)
            x = x + a + f
        else:
            x = x + _self_attn(p, x, ctx)
            if cross:
                h = apply_norm(p["ln_x"], x, cfg.norm)
                x = x + attn.apply_cross(p["cross"], h, ctx["enc_memory"],
                                         n_heads=cfg.n_heads,
                                         n_kv=cfg.n_kv_heads, head_dim=hd)
            f, aux = _ffn(p, x, ctx)
            x = x + f
        if shared_after:
            x = _apply_shared_block(ctx["shared_params"], x, ctx, cfg)
        return x, aux

    def init_cache(batch, s_max, dtype):
        if is_mla:
            c = {"self": attn.init_mla_cache(batch, s_max, cfg.mla, dtype)}
        else:
            c = {"self": attn.init_gqa_cache(batch, s_max, cfg.n_kv_heads, hd,
                                             window=window, dtype=dtype)}
        if cross:
            c["cross"] = {"k": jnp.zeros((batch, ctx_enc_len(cfg), cfg.n_kv_heads, hd), dtype),
                          "v": jnp.zeros((batch, ctx_enc_len(cfg), cfg.n_kv_heads, hd), dtype)}
        return c

    @jax.named_scope("model.attn")
    def _decode_attn(p, x1, cache, index, ctx):
        h = apply_norm(p["ln1"], x1, cfg.norm)
        if is_mla:
            return attn.decode_mla(p["attn"], h, cache["self"], index,
                                   n_heads=cfg.n_heads, mla=cfg.mla,
                                   rope_theta=cfg.rope_theta)
        if "pos" in cache["self"]:
            return attn.decode_gqa_ring(
                p["attn"], h, cache["self"], index, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=hd, rope_theta=cfg.rope_theta)
        return attn.decode_gqa(
            p["attn"], h, cache["self"], index, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=hd, rope_theta=cfg.rope_theta,
            window=window,
            mrope_positions=ctx.get("mrope_positions"),
            mrope_sections=cfg.mrope_sections if cfg.mrope else None)

    def decode_unit(p, x1, cache, index, ctx):
        a, new_self = _decode_attn(p, x1, cache, index, ctx)
        new_cache = dict(cache)
        new_cache["self"] = new_self
        if cfg.parallel_residual and not use_moe:
            f, _ = _ffn(p, x1, ctx)
            x1 = x1 + a + f
        else:
            x1 = x1 + a
            if cross:
                hx = apply_norm(p["ln_x"], x1, cfg.norm)
                cx = attn.decode_cross(p["cross"], hx, cache["cross"],
                                       n_heads=cfg.n_heads, head_dim=hd)
                x1 = x1 + cx
            f, _ = _ffn(p, x1, ctx)
            x1 = x1 + f
        if shared_after:
            x1 = _apply_shared_block(ctx["shared_params"], x1, ctx, cfg,
                                     decode=True)
        return x1, new_cache

    return Segment(kind, 1, init_unit, apply_unit, init_cache, decode_unit)


def ctx_enc_len(cfg: ModelConfig) -> int:
    return cfg.n_frontend_tokens or 1024


# ---------------------------------------------------------------------------
# zamba2 shared attention block
# ---------------------------------------------------------------------------

def init_shared_block(key, cfg):
    d, dt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    ks = split(key, 5)
    return {
        "in_proj": dense_init(ks[0], 2 * d, d, dt),   # concat(hidden, embed)
        "ln1": init_norm(cfg.norm, d, dt),
        "attn": attn.init_gqa(ks[1], d, cfg.n_heads, cfg.n_kv_heads, hd, dt),
        "ln2": init_norm(cfg.norm, d, dt),
        "mlp": init_swiglu(ks[2], d, cfg.hybrid.shared_d_ff or cfg.d_ff, dt),
        "out_proj": dense_init(ks[3], d, d, dt),
    }


def _apply_shared_block(p, x, ctx, cfg, decode: bool = False):
    hd = cfg.resolved_head_dim
    u = jnp.concatenate([x, ctx["x0"] if not decode else ctx["x0_1"]],
                        axis=-1) @ p["in_proj"]
    h = apply_norm(p["ln1"], u, cfg.norm)
    if decode:
        # shared block re-attends within the running window of its own cache;
        # zamba2's shared block sees the full sequence — we keep a full cache
        # held in ctx (threaded through decode by model-level code).
        a, ctx["shared_cache"] = attn.decode_gqa(
            p["attn"], h, ctx["shared_cache"], ctx["index"],
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta)
    else:
        a = attn.apply_gqa(p["attn"], h, ctx["positions"],
                           n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           head_dim=hd, rope_theta=cfg.rope_theta,
                           causal=True, window=0, chunk=ctx["chunk"])
    u = u + a
    u = u + apply_swiglu(p["mlp"], apply_norm(p["ln2"], u, cfg.norm))
    return x + u @ p["out_proj"]


# ---------------------------------------------------------------------------
# ssm units
# ---------------------------------------------------------------------------

def _mk_mamba_layer(cfg, *, shared_after: bool, kind: str):
    d, dt = cfg.d_model, jnp.dtype(cfg.param_dtype)

    def init_unit(key):
        ks = split(key, 2)
        return {"ln": init_norm(cfg.norm, d, dt),
                "mamba": ssm_lib.init_mamba2(ks[0], d, cfg.ssm, dt)}

    def apply_unit(p, x, ctx):
        h = apply_norm(p["ln"], x, cfg.norm)
        x = x + ssm_lib.apply_mamba2(p["mamba"], h, cfg.ssm, d_model=d)
        if shared_after:
            x = _apply_shared_block(ctx["shared_params"], x, ctx, cfg)
        return x, 0.0

    def init_cache(batch, s_max, dtype):
        return ssm_lib.init_mamba2_state(batch, d, cfg.ssm, dtype)

    def decode_unit(p, x1, cache, index, ctx):
        h = apply_norm(p["ln"], x1, cfg.norm)
        y, cache = ssm_lib.decode_mamba2(p["mamba"], h, cache, cfg.ssm,
                                         d_model=d)
        x1 = x1 + y
        if shared_after:
            x1 = _apply_shared_block(ctx["shared_params"], x1, ctx, cfg,
                                     decode=True)
        return x1, cache

    return Segment(kind, 1, init_unit, apply_unit, init_cache, decode_unit)


def _mk_xlstm_layer(cfg, *, slstm: bool, kind: str):
    d, dt = cfg.d_model, jnp.dtype(cfg.param_dtype)

    def init_unit(key):
        ks = split(key, 2)
        if slstm:
            return {"ln": init_norm(cfg.norm, d, dt),
                    "cell": ssm_lib.init_slstm(ks[0], d, cfg.ssm, dt)}
        return {"ln": init_norm(cfg.norm, d, dt),
                "cell": ssm_lib.init_mlstm(ks[0], d, cfg.ssm, dt)}

    def apply_unit(p, x, ctx):
        h = apply_norm(p["ln"], x, cfg.norm)
        fn = ssm_lib.apply_slstm if slstm else ssm_lib.apply_mlstm
        return x + fn(p["cell"], h, cfg.ssm, d_model=d), 0.0

    def init_cache(batch, s_max, dtype):
        fn = ssm_lib.init_slstm_state if slstm else ssm_lib.init_mlstm_state
        return fn(batch, d, cfg.ssm, dtype)

    def decode_unit(p, x1, cache, index, ctx):
        h = apply_norm(p["ln"], x1, cfg.norm)
        fn = ssm_lib.decode_slstm if slstm else ssm_lib.decode_mlstm
        y, cache = fn(p["cell"], h, cache, cfg.ssm, d_model=d)
        return x1 + y, cache

    return Segment(kind, 1, init_unit, apply_unit, init_cache, decode_unit)


# ---------------------------------------------------------------------------
# per-architecture segment lists
# ---------------------------------------------------------------------------

def build_segments(cfg: ModelConfig, decoder: bool = True) -> List[Segment]:
    """Returns the segment list (decoder stack; encoder handled separately)."""
    segs: List[Segment] = []
    if cfg.family in ("dense", "vlm"):
        specs = []
        for i in range(cfg.n_layers):
            if cfg.global_every and (i % cfg.global_every != cfg.global_every - 1):
                specs.append("local")
            elif cfg.global_every:
                specs.append("global")
            else:
                specs.append("global" if not cfg.sliding_window else "local")
        for kind, n in _rle(specs):
            w = cfg.sliding_window if kind == "local" else 0
            s = _mk_attn_layer(cfg, window=w, kind=kind)
            s.n = n
            segs.append(s)
    elif cfg.family == "audio":
        # decoder stack with cross attention
        s = _mk_attn_layer(cfg, window=0, cross=True, kind="xdec")
        s.n = cfg.n_layers
        segs.append(s)
    elif cfg.family == "moe":
        m = cfg.moe
        if m.first_k_dense:
            s = _mk_attn_layer(cfg, window=0, use_moe=False, kind="dense0")
            s.n = m.first_k_dense
            segs.append(s)
        s = _mk_attn_layer(cfg, window=0, use_moe=True, kind="moe")
        s.n = cfg.n_layers - m.first_k_dense
        segs.append(s)
    elif cfg.family == "hybrid":
        period = cfg.hybrid.shared_attn_period
        specs = ["mamba_shared" if (i % period == period - 1) else "mamba"
                 for i in range(cfg.n_layers)]
        for kind, n in _rle(specs):
            s = _mk_mamba_layer(cfg, shared_after=(kind == "mamba_shared"),
                                kind=kind)
            s.n = n
            segs.append(s)
    elif cfg.family == "ssm":
        unit = cfg.ssm.xlstm_unit
        specs = ["slstm" if (i % unit == unit - 1) else "mlstm"
                 for i in range(cfg.n_layers)]
        for kind, n in _rle(specs):
            s = _mk_xlstm_layer(cfg, slstm=(kind == "slstm"), kind=kind)
            s.n = n
            segs.append(s)
    else:
        raise ValueError(cfg.family)
    return segs


def build_encoder_segments(cfg: ModelConfig) -> List[Segment]:
    s = _mk_attn_layer(cfg, window=0, causal=False, kind="enc")
    s.n = cfg.n_enc_layers
    return [s]


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.param_dtype)
    keys = split(rng, 8)
    segs = build_segments(cfg)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dt)
    seg_keys = split(keys[2], len(segs))
    params["segments"] = [
        jax.vmap(s.init_unit)(jax.random.split(k, s.n))
        for s, k in zip(segs, seg_keys)]
    if cfg.family == "hybrid":
        params["shared_block"] = init_shared_block(keys[3], cfg)
    if cfg.is_encdec:
        enc = build_encoder_segments(cfg)
        enc_keys = split(keys[4], len(enc))
        params["enc_segments"] = [
            jax.vmap(s.init_unit)(jax.random.split(k, s.n))
            for s, k in zip(enc, enc_keys)]
        params["enc_final_norm"] = init_norm(cfg.norm, cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

@jax.named_scope("model.layers")
def _run_segments(segs, seg_params, x, ctx, *, remat: bool = True):
    """The layer stack; its own work (slicing the stacked unit params,
    residual adds) is region ``model.layers``."""
    aux_total = jnp.zeros((), jnp.float32)
    x = shard_act(x, "act")
    for s, sp in zip(segs, seg_params):
        # close over ctx so its static leaves (chunk size) stay python ints
        unit = s.apply_unit
        body = (lambda p, x, _u=unit: _u(p, x, ctx))
        if remat:
            body = jax.checkpoint(body)
        if s.n == 1:
            # unscanned single unit (keeps shared-block ctx access simple)
            p1 = jax.tree.map(lambda a: a[0], sp)
            x, a = body(p1, x)
            aux_total = aux_total + a
            continue

        def scan_fn(carry, p, _body=body):
            x, aux = carry
            x, a = _body(p, x)
            x = shard_act(x, "act")
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(scan_fn, (x, aux_total), sp)
    return x, aux_total


def _positions(B, S):
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))


# activation sharding hook lives in models.layers (leaf module — the SSM/
# MoE blocks use it too); re-exported here for the launchers.
from repro.models.layers import set_activation_sharder, shard_act  # noqa: E402


def _vlm_mrope_positions(cfg, B, S):
    """(3,B,S): vision prefix uses (t=0, h, w) grid; text continues with
    t=h=w = running position (qwen2-vl)."""
    P = cfg.n_frontend_tokens
    gw = max(1, int(P ** 0.5))
    idx = jnp.arange(S, dtype=jnp.int32)
    is_txt = idx >= P
    t = jnp.where(is_txt, idx, 0)
    h = jnp.where(is_txt, idx, idx // gw)
    w = jnp.where(is_txt, idx, idx % gw)
    pos3 = jnp.stack([t, h, w])                   # (3,S)
    return jnp.broadcast_to(pos3[:, None, :], (3, B, S))


def make_ctx(cfg, B, S, params=None, x0=None):
    chunk = 512 if S >= 4096 else 0
    ctx: Dict[str, Any] = {"positions": _positions(B, S), "chunk": chunk}
    if cfg.mrope:
        ctx["mrope_positions"] = _vlm_mrope_positions(cfg, B, S)
    if cfg.family == "hybrid" and params is not None:
        ctx["shared_params"] = params["shared_block"]
        ctx["x0"] = x0
    return ctx


@jax.named_scope("model.embed")
def embed_tokens(params, cfg, tokens):
    cd = jnp.dtype(cfg.compute_dtype)
    return params["embed"].astype(cd)[tokens] * (cfg.d_model ** 0.5 if cfg.name.startswith("gemma") else 1.0)


@jax.named_scope("model.head")
def logits_fn(params, cfg, x):
    h = apply_norm(params["final_norm"], x, cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    w = shard_act(w, "head_w")        # (d, V): V -> "model", d -> "data"
    return shard_act(h @ w.astype(h.dtype), "logits")


def forward_hidden(params, cfg, batch, *, remat: bool = True):
    """Trunk only: returns (final hidden (B,S,d) pre-final-norm, aux)."""
    cd = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.is_encdec:
        mem = batch["frontend"].astype(cd)
        enc_ctx = make_ctx(cfg, B, mem.shape[1])
        mem, _ = _run_segments(build_encoder_segments(cfg),
                               params["enc_segments"], mem, enc_ctx,
                               remat=remat)
        mem = apply_norm(params["enc_final_norm"], mem, cfg.norm)
        x = embed_tokens(params, cfg, tokens)
        ctx = make_ctx(cfg, B, S, params, x)
        ctx["enc_memory"] = mem
        return _run_segments(build_segments(cfg), params["segments"], x,
                             ctx, remat=remat)
    x = embed_tokens(params, cfg, tokens)
    if cfg.modality == "vlm":
        P = batch["frontend"].shape[1]
        x = jnp.concatenate([batch["frontend"].astype(cd), x[:, P:]], axis=1)
    ctx = make_ctx(cfg, B, S, params, x)
    return _run_segments(build_segments(cfg), params["segments"], x, ctx,
                         remat=remat)


def forward(params, cfg, batch, *, remat: bool = True):
    """Full-sequence logits (tests / small models)."""
    x, aux = forward_hidden(params, cfg, batch, remat=remat)
    return logits_fn(params, cfg, x), aux


@jax.named_scope("model.head")
def _ce_from_hidden(params, cfg, h_c, tgt_c, mask_c):
    """CE over one sequence chunk: head matmul + vocab-parallel-friendly
    logsumexp/masked-select (no gather over the sharded vocab dim)."""
    lg = logits_fn(params, cfg, h_c).astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    iota_v = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    tgt_logit = jnp.sum(jnp.where(iota_v == tgt_c[..., None], lg, 0.0),
                        axis=-1)
    nll = (lse - tgt_logit) * mask_c
    return nll.sum(), mask_c.sum()


def loss_fn(params, cfg, batch, *, remat: bool = True,
            loss_chunk: int = 1024):
    """Next-token CE. The head+CE is chunked over the sequence so the
    (B,S,V) f32 logits never materialize (the dominant activation at 100k+
    vocabs); backward recomputes per chunk under remat."""
    h, aux = forward_hidden(params, cfg, batch, remat=remat)
    tokens = batch["tokens"]
    B, S = tokens.shape
    tgt = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    if cfg.modality == "vlm":
        # only text positions (after the patch prefix) carry LM loss
        P = cfg.n_frontend_tokens
        pos = jnp.arange(S)[None, :]
        mask = mask * (pos >= P).astype(jnp.float32)

    if S % loss_chunk == 0 and S > loss_chunk:
        n = S // loss_chunk
        hs = h.reshape(B, n, loss_chunk, -1).transpose(1, 0, 2, 3)
        ts = tgt.reshape(B, n, loss_chunk).transpose(1, 0, 2)
        ms = mask.reshape(B, n, loss_chunk).transpose(1, 0, 2)
        body = jax.checkpoint(
            lambda hc, tc, mc: _ce_from_hidden(params, cfg, hc, tc, mc))
        sums = jax.lax.map(lambda args: body(*args), (hs, ts, ms))
        total, cnt = sums[0].sum(), sums[1].sum()
    else:
        total, cnt = _ce_from_hidden(params, cfg, h, tgt, mask)
    loss = total / jnp.maximum(cnt, 1.0)
    return loss + aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, s_max: int,
                      dtype=None) -> Dict[str, Any]:
    dt = jnp.dtype(dtype or cfg.param_dtype)
    segs = build_segments(cfg)
    caches = [jax.vmap(lambda _ , s=s: s.init_cache(batch, s_max, dt))(
        jnp.arange(s.n)) for s in segs]
    state: Dict[str, Any] = {"caches": caches,
                             "index": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        state["shared_cache"] = attn.init_gqa_cache(
            batch, s_max, cfg.n_kv_heads, cfg.resolved_head_dim, dtype=dt)
    if cfg.is_encdec:
        state["enc_memory"] = jnp.zeros(
            (batch, ctx_enc_len(cfg), cfg.d_model), dt)
    return state


def decode_step(params, cfg: ModelConfig, state, tokens, embeds=None):
    """tokens: (B,1) current token (or ``embeds`` (B,1,d) for frontend
    positions of a VLM prefill-by-decode). Returns (logits, new_state)."""
    cd = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    index = state["index"]
    x1 = embeds.astype(cd) if embeds is not None \
        else embed_tokens(params, cfg, tokens)
    x1 = shard_act(x1, "act")
    ctx: Dict[str, Any] = {"chunk": 0, "index": index,
                           "positions": jnp.full((B, 1), index, jnp.int32)}
    if cfg.mrope:
        # same (t,h,w) mapping as the forward path, evaluated at `index`
        P = cfg.n_frontend_tokens
        gw = max(1, int(P ** 0.5))
        is_txt = index >= P
        t = jnp.where(is_txt, index, 0)
        h = jnp.where(is_txt, index, index // gw)
        w = jnp.where(is_txt, index, index % gw)
        pos3 = jnp.broadcast_to(jnp.stack([t, h, w])[:, None, None], (3, B, 1))
        ctx["mrope_positions"] = pos3.astype(jnp.int32)
    if cfg.family == "hybrid":
        ctx["shared_params"] = params["shared_block"]
        ctx["x0_1"] = x1
        ctx["shared_cache"] = state["shared_cache"]
    if cfg.is_encdec:
        ctx["enc_memory"] = state["enc_memory"]

    segs = build_segments(cfg)
    new_caches = []
    for s, sp, cache in zip(segs, params["segments"], state["caches"]):
        if s.n == 1:
            # unscanned: lets shared-block cache updates thread through ctx
            p1 = jax.tree.map(lambda a: a[0], sp)
            c1 = jax.tree.map(lambda a: a[0], cache)
            x1, nc1 = s.decode_unit(p1, x1, c1, index, ctx)
            new_caches.append(jax.tree.map(lambda a: a[None], nc1))
            continue

        def scan_fn(x1, pc, _s=s):
            p, c = pc
            x1, c = _s.decode_unit(p, x1, c, index, ctx)
            return x1, c

        x1, nc = jax.lax.scan(scan_fn, x1, (sp, cache))
        new_caches.append(nc)
    logits = logits_fn(params, cfg, x1)
    new_state = dict(state)
    new_state["caches"] = new_caches
    new_state["index"] = index + 1
    if cfg.family == "hybrid":
        new_state["shared_cache"] = ctx["shared_cache"]
    return logits, new_state


def prefill_encoder(params, cfg, frontend, *, remat=False):
    """Audio serving: run the encoder once, fill cross-attn caches."""
    cd = jnp.dtype(cfg.compute_dtype)
    mem = frontend.astype(cd)
    enc_ctx = make_ctx(cfg, mem.shape[0], mem.shape[1])
    mem, _ = _run_segments(build_encoder_segments(cfg),
                           params["enc_segments"], mem, enc_ctx, remat=remat)
    return apply_norm(params["enc_final_norm"], mem, cfg.norm)


def fill_cross_caches(params, cfg, state, enc_memory):
    """Precompute cross-attention K/V from encoder memory for every decoder
    layer (stacked over the segment scan dim)."""
    hd = cfg.resolved_head_dim
    segs = build_segments(cfg)
    new_caches = []
    for s, sp, cache in zip(segs, params["segments"], state["caches"]):
        def kv_fn(p):
            return attn.cross_kv(p["cross"], enc_memory,
                                 n_kv=cfg.n_kv_heads, head_dim=hd)
        kv = jax.vmap(kv_fn)(sp)
        c = dict(cache)
        c["cross"] = kv
        new_caches.append(c)
    state = dict(state)
    state["caches"] = new_caches
    state["enc_memory"] = enc_memory
    return state


# ---------------------------------------------------------------------------
# parameter counting (eval_shape — no allocation)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig) -> int:
    import math
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def count_active_params(cfg: ModelConfig) -> int:
    """MoE-aware active-parameter count (routed experts scaled by top_k/E)."""
    import math
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = 0
    scale_paths = ("experts",)

    def visit(path, leaf):
        nonlocal total
        n = math.prod(leaf.shape)
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if cfg.moe and any(k in names for k in scale_paths):
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        total += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total


def count_params_analytic(cfg: ModelConfig) -> int:
    return count_params(cfg)
