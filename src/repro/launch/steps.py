"""Mesh-level step functions (Mode A, pjit/GSPMD).

Cluster semantics: params carry a leading ``n_clusters`` dim sharded over
the "clusters" mesh axis; the inner step is vmapped over it, so dataflow
cannot mix clusters during local training (DESIGN.md §3). The outer step is
the only function whose collectives cross the cluster (1 Gbps) boundary,
and they carry the packed int4 payload (core.mesh_compression).

Functions are pure and jit-ready; ``launch/dryrun.py`` lowers them with
ShapeDtypeStructs, ``launch/train.py`` executes them on small meshes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import mesh_compression as mc
from repro.models import model as M
from repro.optim import adamw, nesterov


# ---------------------------------------------------------------------------
# inner train step (per-cluster, vmapped over the cluster dim)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, inner_lr: float = 1e-4,
                    per_cluster_h: bool = False):
    """(params_stacked, opt_stacked, batch_stacked) -> (params', opt', loss).
    One inner AdamW step per cluster; no cross-cluster collectives by
    construction (vmap over the stacked cluster dim).

    ``per_cluster_h=True`` returns the heterogeneous-local-step variant
    ``(params, opt, batch, active) -> (params', opt', loss)``: ``active``
    is a (C,) bool mask and inactive clusters' params/optimizer pass
    through unchanged (bitwise — a select, not an arithmetic no-op), which
    is how the driver realizes a per-cluster H schedule (cluster c sits
    out steps ``h >= h_c`` of the round while the fast ones finish their
    budget); the loss is the mean over active clusters only."""

    def one_cluster(params, opt, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch), has_aux=True)(params)
        params, opt = adamw.update(grads, opt, params, lr=inner_lr)
        return params, opt, loss

    if not per_cluster_h:
        def train_step(params_stacked, opt_stacked, batch_stacked):
            params, opt, loss = jax.vmap(one_cluster)(
                params_stacked, opt_stacked, batch_stacked)
            return params, opt, loss.mean()

        return train_step

    def one_cluster_masked(params, opt, batch, active):
        new_p, new_o, loss = one_cluster(params, opt, batch)
        keep = lambda n, o: jnp.where(active, n, o)
        params = jax.tree.map(keep, new_p, params)
        opt = jax.tree.map(keep, new_o, opt)
        return params, opt, jnp.where(active, loss, 0.0)

    def train_step_h(params_stacked, opt_stacked, batch_stacked, active):
        params, opt, losses = jax.vmap(one_cluster_masked)(
            params_stacked, opt_stacked, batch_stacked, active)
        n = jnp.maximum(active.astype(jnp.float32).sum(), 1.0)
        return params, opt, losses.sum() / n

    return train_step_h


# ---------------------------------------------------------------------------
# outer DiLoCoX step (the cross-cluster sync)
# ---------------------------------------------------------------------------

class OuterState(NamedTuple):
    anchor: Any          # theta^{t-1} (unstacked, global)
    outer_opt: Any       # Nesterov momentum
    delta_pending: Any   # cluster-stacked pseudo-grads (previous round)
    error: Any           # cluster-stacked EF buffers
    q_state: Any         # cluster-stacked PowerSGD warm starts


def init_outer_state(params, n_clusters: int,
                     ccfg: mc.MeshCompressionConfig) -> OuterState:
    stack = lambda tree: jax.tree.map(
        lambda x: jnp.zeros((n_clusters,) + x.shape, jnp.float32), tree)
    q0 = mc.init_q_state(params, ccfg)
    q_stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_clusters,) + x.shape).copy(), q0)
    return OuterState(anchor=params, outer_opt=nesterov.init(params),
                      delta_pending=stack(params), error=stack(params),
                      q_state=q_stacked)


def make_outer_step(cfg: ModelConfig, ccfg: mc.MeshCompressionConfig, *,
                    outer_lr: float = 0.7, outer_momentum: float = 0.9):
    """(params_stacked_postH, outer_state, rank_scalar) ->
    (params_stacked_next, outer_state'). Implements Alg. 2's communicate +
    delayed outer update with the one-step-delay schedule."""

    def outer_step(params_stacked, st: OuterState, rank_scalar):
        # communicate: compress + gather + mean LAST round's pseudo-grads
        Delta, q_new = mc.compress_gather_mean(
            st.delta_pending, st.q_state, rank_scalar, ccfg)
        # Alg. 2 error feedback: e = delta^{t-1} - Delta^{t-1}
        err = jax.tree.map(lambda d, D: d - D[None].astype(d.dtype),
                           st.delta_pending, Delta)
        # next pending: (anchor - theta_inner) + e
        delta_new = jax.tree.map(
            lambda a, p, e: (a.astype(jnp.float32)[None]
                             - p.astype(jnp.float32)) + e,
            st.anchor, params_stacked, err)
        # delayed outer update on the anchor
        params_new, outer_opt = nesterov.update(
            Delta, st.outer_opt, st.anchor,
            lr=outer_lr, momentum=outer_momentum)
        # replicas restart from the outer-updated params
        C = jax.tree.leaves(params_stacked)[0].shape[0]
        params_stacked_new = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (C,) + p.shape).astype(p.dtype),
            params_new)
        return params_stacked_new, OuterState(
            anchor=params_new, outer_opt=outer_opt,
            delta_pending=delta_new, error=err, q_state=q_new)

    return outer_step


# ---------------------------------------------------------------------------
# serving steps (no cluster dim; serving mesh ("data","model"))
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    """Forward over the full sequence, returns last-position logits (the
    inference-prefill workload)."""

    def prefill_step(params, batch):
        h, _ = M.forward_hidden(params, cfg, batch, remat=True)
        return M.logits_fn(params, cfg, h[:, -1:])[:, 0]

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, eos_id: Optional[int] = None):
    """One decode step: token + caches -> next token (greedy) + caches.

    With ``eos_id`` set the returned function takes and returns a
    per-sequence ``finished`` bool mask: rows already finished keep
    emitting ``eos_id`` (so everything past the first EOS is masked in
    the decoded output) and the mask absorbs rows whose new token is EOS.
    Callers must reset the mask across prefill-by-decode steps — those
    outputs are prompt-forced and must not trip EOS."""

    def serve_step(params, state, tokens):
        logits, state = M.decode_step(params, cfg, state, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, state

    if eos_id is None:
        return serve_step

    def serve_step_eos(params, state, tokens, finished):
        nxt, state = serve_step(params, state, tokens)
        nxt = jnp.where(finished[:, None], jnp.int32(eos_id), nxt)
        finished = finished | (nxt[:, 0] == eos_id)
        return nxt, state, finished

    return serve_step_eos


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------

def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *,
                n_clusters: int = 1) -> Dict[str, Any]:
    """ShapeDtypeStructs for every model input of this (arch, shape)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        assert B % n_clusters == 0
        Bc = B // n_clusters
        batch = {"tokens": sds((n_clusters, Bc, S), jnp.int32)}
        if cfg.modality != "text":
            batch["frontend"] = sds(
                (n_clusters, Bc, cfg.n_frontend_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32)}
        if cfg.modality != "text":
            batch["frontend"] = sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
        return batch
    # decode: one new token against an S-long cache
    return {"tokens": sds((B, 1), jnp.int32)}


def decode_state_specs(cfg: ModelConfig, shape: ShapeConfig):
    """eval_shape of init_decode_state (no allocation)."""
    return jax.eval_shape(
        lambda: M.init_decode_state(cfg, shape.global_batch, shape.seq_len,
                                    dtype=jnp.dtype(cfg.compute_dtype)))


def params_specs(cfg: ModelConfig, *, n_clusters: int = 0):
    """eval_shape of init_params (+ optional cluster stacking)."""
    p = jax.eval_shape(lambda k: M.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    if n_clusters:
        p = jax.tree.map(
            lambda x: sds((n_clusters,) + x.shape, x.dtype), p)
    return p


def opt_specs(params_stacked_specs):
    """vmapped init => per-cluster step counters (C,) and stacked m/v."""
    return jax.eval_shape(jax.vmap(adamw.init), params_stacked_specs)


def outer_state_specs(cfg: ModelConfig, n_clusters: int,
                      ccfg: mc.MeshCompressionConfig):
    p = params_specs(cfg)
    return jax.eval_shape(
        lambda pp: init_outer_state(pp, n_clusters, ccfg), p)


# ---------------------------------------------------------------------------
# placement of the round state (shared by the trainer and the dry-run)
# ---------------------------------------------------------------------------

def round_shardings(cfg: ModelConfig, mesh: Mesh, n_clusters: int,
                    ccfg: mc.MeshCompressionConfig):
    """NamedShardings ``(params, opt, outer_state)`` of the GSPMD round
    state on a (clusters, data, model) mesh.  Passing them as both the in-
    and out-shardings of the jitted steps keeps one executable per step
    (and lets donation update the state in place)."""
    from repro.parallel import sharding as sh

    p_specs = params_specs(cfg, n_clusters=n_clusters)
    ps = sh.param_shardings(p_specs, mesh, cluster_stacked=True)
    opt_sh = adamw.AdamWState(step=NamedSharding(mesh, P("clusters")),
                              m=ps, v=ps)
    anchor = sh.param_shardings(params_specs(cfg), mesh,
                                cluster_stacked=False)

    def q_sharding(leaf):
        if leaf.ndim <= 1:
            return NamedSharding(mesh, P())
        dims = [None] * leaf.ndim
        if leaf.shape[0] % mesh.shape["clusters"] == 0:
            dims[0] = "clusters"
        # the n dim (second to last) over data, like the params
        if leaf.ndim >= 3 and leaf.shape[-2] % mesh.shape["data"] == 0:
            dims[-2] = "data"
        return NamedSharding(mesh, P(*dims))

    ost = outer_state_specs(cfg, n_clusters, ccfg)
    ost_sh = OuterState(
        anchor=anchor,
        outer_opt=nesterov.NesterovState(step=NamedSharding(mesh, P()),
                                         momentum=anchor),
        delta_pending=ps, error=ps,
        q_state=jax.tree.map(q_sharding, ost.q_state))
    return ps, opt_sh, ost_sh
