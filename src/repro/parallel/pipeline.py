"""Mode B: paper-faithful Pipeline Parallelism (§2.2) as a shard_map over
the "model" axis.

Each device holds one *stage* (layers_per_stage stacked decoder layers);
microbatches flow stage-to-stage via ``lax.ppermute`` in a GPipe-style loop
of n_micro + n_stages - 1 ticks. The whole loop is differentiable (the
transpose of ppermute is the reversed permute; shard_map's VMA tracking
inserts the data-parallel grad psums). Embedding + head run replicated per
stage-column; only stage 0's embedding and the last stage's head feed the
dataflow.

Scope: uniform decoder-only stacks (dense family). MoE/hybrid/enc-dec keep
Mode A (DESIGN.md §Arch-applicability): their stages are either memory-
infeasible without intra-stage tensor sharding (MoE experts) or break the
sequential stage chain (cross-attention).

Layer padding: n_layers is padded up to a multiple of n_stages; padded
slots carry an ``active`` flag and pass activations through untouched (the
waste is layers_pad/n_layers and is reported by the dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.layers import apply_norm, embed_init, dense_init, init_norm, split


@dataclass(frozen=True)
class PipelineConfig:
    n_stages: int = 4
    n_micro: int = 16             # microbatches per (cluster, data) column


def layers_per_stage(cfg: ModelConfig, pcfg: PipelineConfig) -> Tuple[int, int]:
    lps = math.ceil(cfg.n_layers / pcfg.n_stages)
    pad = lps * pcfg.n_stages - cfg.n_layers
    return lps, pad


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_pp_params(cfg: ModelConfig, rng, pcfg: PipelineConfig):
    """{"embed","head","final_norm","stages","active"}; stages leaves are
    (n_stages, layers_per_stage, ...)."""
    assert cfg.family in ("dense", "vlm") and not cfg.global_every, \
        "Mode B supports uniform decoder stacks (DESIGN.md)"
    dt = jnp.dtype(cfg.param_dtype)
    lps, pad = layers_per_stage(cfg, pcfg)
    seg = M.build_segments(cfg)[0]          # uniform => single segment
    keys = split(rng, 4)
    unit_keys = jax.random.split(keys[2], pcfg.n_stages * lps).reshape(
        pcfg.n_stages, lps, -1)
    stages = jax.vmap(jax.vmap(seg.init_unit))(unit_keys)
    # float mask (not bool) so the tree stays jax.grad-able; padded slots
    # contribute exactly zero gradient through the lerp in stage_fn
    active = (jnp.arange(pcfg.n_stages * lps) < cfg.n_layers).reshape(
        pcfg.n_stages, lps).astype(jnp.float32)
    params = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
        "stages": stages,
        "active": active,
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dt)
    return params


def sequential_params(params, cfg: ModelConfig):
    """The unpipelined model's params (``models.model`` layout) holding the
    same weights as one cluster's pipeline params: the stage dim is
    flattened back into the layer stack and padded slots are dropped.  The
    reference side of the pipeline-vs-sequential loss comparisons."""
    stack = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                         params["stages"])
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "segments": [jax.tree.map(lambda x: x[:cfg.n_layers], stack)]}
    if "head" in params:
        out["head"] = params["head"]
    return out


def pp_param_specs(params, mesh: Mesh, *, cluster_stacked: bool):
    """in_specs for shard_map: stage dim -> "model"; everything else
    replicated within the cluster (embed/head/norm live on every stage)."""
    lead = ("clusters",) if cluster_stacked else ()

    def spec(path, x):
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        nlead = len(lead)
        if any(n in ("stages", "active") for n in names):
            return P(*lead, "model", *([None] * (x.ndim - nlead - 1)))
        return P(*lead, *([None] * (x.ndim - nlead)))

    return jax.tree_util.tree_map_with_path(spec, params)


# ---------------------------------------------------------------------------
# the pipelined loss
# ---------------------------------------------------------------------------

def make_pp_loss(cfg: ModelConfig, mesh: Mesh, pcfg: PipelineConfig, *,
                 cluster_stacked: bool = True, loss_scale_clusters: bool = True):
    """Returns loss_fn(params, tokens) running the GPipe loop inside a
    shard_map over (clusters, data, model). tokens: (C, Bc, S) (or (B, S) if
    not cluster_stacked). Loss returned is the SUM over clusters of the
    per-cluster mean NLL (so per-cluster grads match independent training)."""
    seg = M.build_segments(cfg)[0]
    lps, _ = layers_per_stage(cfg, pcfg)
    n_stages = pcfg.n_stages
    axes = ("clusters", "data", "model") if cluster_stacked else \
        ("data", "model")

    def stage_fn(stage_params, active, x, ctx):
        def layer(x, pa):
            p, a = pa
            y, _ = seg.apply_unit(p, x, ctx)
            a = a.astype(y.dtype)
            return y * a + x * (1.0 - a), None

        x, _ = jax.lax.scan(layer, x, (stage_params, active))
        return x

    def per_device(params, tokens):
        # squeeze shard_map's size-1 sharded dims
        sq = (lambda t: jax.tree.map(lambda a: a[0], t))
        if cluster_stacked:
            params = sq(params)
            tokens = tokens[0]
        stage_params = sq({"s": params["stages"]})["s"]   # (lps, ...)
        active = params["active"][0]
        tokens = tokens[0] if False else tokens           # (B_loc, S)

        B, S = tokens.shape
        m = pcfg.n_micro
        assert B % m == 0, (B, m)
        mb = B // m
        stage = jax.lax.axis_index("model")
        cd = jnp.dtype(cfg.compute_dtype)

        x_all = params["embed"].astype(cd)[tokens]        # (B,S,d)
        micro = x_all.reshape(m, mb, S, -1)
        ctx = M.make_ctx(cfg, mb, S)
        chk_stage = jax.checkpoint(
            lambda sp, act, xx: stage_fn(sp, act, xx, ctx))

        perm = [(i, i + 1) for i in range(n_stages - 1)]
        T = m + n_stages - 1

        def tick(carry, t):
            recv = carry
            idx = jnp.clip(t, 0, m - 1)
            first_in = jax.lax.dynamic_index_in_dim(micro, idx, axis=0,
                                                    keepdims=False)
            my_in = jnp.where(stage == 0, first_in, recv)
            out = chk_stage(stage_params, active, my_in)
            recv_next = jax.lax.ppermute(out, "model", perm)
            return recv_next, out

        _, outs = jax.lax.scan(tick, jnp.zeros_like(micro[0]),
                               jnp.arange(T))
        # valid last-stage outputs are ticks [n_stages-1, n_stages-1+m)
        outs_valid = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, m,
                                                  axis=0)
        h = outs_valid.reshape(B, S, -1)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        tgt = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
        msk = jnp.concatenate(
            [jnp.ones((B, S - 1), jnp.float32),
             jnp.zeros((B, 1), jnp.float32)], axis=1)

        # chunked head+CE: the (B,S,V) f32 logits of the replicated head
        # were ~50 GB of temp at vocab 49k (hillclimb C iter 2); per-chunk
        # logits are ~1.6 GB and backward recomputes under checkpoint.
        def ce_chunk(h_c, tgt_c, m_c):
            hc = apply_norm(params["final_norm"], h_c, cfg.norm)
            lg = (hc @ w.astype(hc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            iota_v = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 2)
            tl = jnp.sum(jnp.where(iota_v == tgt_c[..., None], lg, 0.0), -1)
            return jnp.sum((lse - tl) * m_c)

        lc = 512 if S % 512 == 0 and S > 512 else S
        n_ch = S // lc
        hs = h.reshape(B, n_ch, lc, -1).transpose(1, 0, 2, 3)
        ts = tgt.reshape(B, n_ch, lc).transpose(1, 0, 2)
        ms = msk.reshape(B, n_ch, lc).transpose(1, 0, 2)
        ce = jax.checkpoint(ce_chunk)
        sums = jax.lax.map(lambda a: ce(*a), (hs, ts, ms))
        nll_sum = sums.sum()
        cnt = msk.sum()
        # only the last stage's numbers are real
        is_last = (stage == n_stages - 1).astype(jnp.float32)
        nll_sum = nll_sum * is_last
        cnt = cnt * is_last
        # per-cluster mean: reduce over data+model; SUM over clusters
        nll_sum = jax.lax.psum(nll_sum, ("data", "model"))
        cnt = jax.lax.psum(cnt, ("data", "model"))
        loss_c = nll_sum / jnp.maximum(cnt, 1.0)
        if cluster_stacked:
            loss_c = jax.lax.psum(loss_c, "clusters")
        return loss_c

    in_specs = (pp_param_specs(
        jax.eval_shape(lambda: None) if False else _dummy_params_tree(cfg, pcfg),
        mesh, cluster_stacked=cluster_stacked),
        P(*( ("clusters", "data", None) if cluster_stacked
             else ("data", None))))
    # replication checking is off: the per-stage loss masking here is
    # deliberately "unreplicated"
    loss_sm = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                            out_specs=P(), check_vma=False)
    return loss_sm


def _dummy_params_tree(cfg: ModelConfig, pcfg: PipelineConfig):
    """Structure-only params tree for building in_specs (eval_shape)."""
    return jax.eval_shape(
        lambda k: init_pp_params(cfg, k, pcfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
