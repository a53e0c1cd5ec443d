"""Sharded pipeline-parallel inner engine: one virtual cluster = one real
jax mesh slice.

The simulator's clusters historically ran *single-replica* inner steps
(``sim/quadratic.py``, ``train/trainer.py``): fine for certifying the outer
DiLoCoX round loop, but the paper's headline result — 107B pre-training
over 1 Gbps — rests on Pipeline Parallelism *inside* each cluster (§2.2)
with the Dual Optimizer and one-step-delay overlap layered on top.  This
module runs the H inner AdamW steps through ``parallel/pipeline.py``'s
shard_map GPipe loss under ``parallel/sharding.py``-style explicit
shardings, on devices faked via ``--xla_force_host_platform_device_count``,
and hands the *gathered* per-cluster pseudo-gradient to the existing outer
compress/mix layer unchanged.

Two mesh flavors:

 - **unit mesh** (``("data", "model")``, one cluster): the canonical
   engine.  The proc backend's ``worker.py`` and the in-process
   simulator's ``inner_fn`` (a python-level unroll over clusters — same
   discipline as ``core.diloco.per_cluster_compress``) execute the *same*
   compiled per-cluster program with the cluster index as a traced arg,
   which is what keeps proc ≡ in-process bitwise (the equivalence gate).
 - **cluster-stacked mesh** (``("clusters", "data", "model")``): the
   ``launch/train.py --inner pp`` production driver, where all clusters
   live in one program and bitwise cross-backend identity is not a goal.

State is held in a ``DiLoCoTrainState`` (the drjax-placements /
DemoYeti-maxtext idiom): params + inner AdamW moments + outer Nesterov
replica + error-feedback residual in one pytree with one sharding rule, so
a single ``jax.device_put`` (or ``in_shardings``) places the whole round
state.

Numerics contract (mirrors the PR 5 masked-dispatch lesson):

 - pp proc ≡ pp in-process: **bitwise** — identical jitted programs per
   cluster on identical unit meshes.
 - pp ≡ scalar (single-replica): **tolerance**, not bitwise — the pipeline
   loss computes the same math as the sequential model through a different
   op schedule (ppermute ticks, chunked CE, sharded reductions), so per
   round the params agree only to the pipeline-equivalence tolerance
   (``tests/test_pipeline.py``: loss 1e-4, grads 1e-3), compounding over
   H steps and rounds.  ``tests/test_inner_engine.py`` states the budget.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import diloco
from repro.optim import adamw, nesterov
from repro.parallel import pipeline as PP


class DiLoCoTrainState(NamedTuple):
    """One cluster's full DiLoCoX round state as a single sharded pytree.

    ``params`` is the *local* (inner-loop) replica; the outer anchor
    θ_anchor is passed separately to ``extract_delta`` because in the
    one-step-delay round it is the previous round's global params, owned
    by the outer layer, not the engine.
    """
    params: Any        # pp param tree {"embed","final_norm","stages",
                       #   "active"[,"head"]}; stages: (n_stages, lps, ...)
    inner_opt: Any     # adamw.AdamWState — moments mirror params' sharding
    outer_opt: Any     # nesterov.NesterovState — fp32 momentum replica
    error: Any         # EF residual, fp32, param-shaped


# ---------------------------------------------------------------------------
# mesh + state construction
# ---------------------------------------------------------------------------

def unit_mesh(pcfg: PP.PipelineConfig, data_parallel: int = 1) -> Mesh:
    """The single-cluster ("data","model") mesh. Requires the process to
    have been started with ``--xla_force_host_platform_device_count >=
    data_parallel * n_stages`` (jax locks the device count at first init)."""
    need = data_parallel * pcfg.n_stages
    if jax.device_count() < need:
        raise RuntimeError(
            f"pp inner engine needs {need} devices "
            f"(data_parallel={data_parallel} x n_stages={pcfg.n_stages}) "
            f"but jax sees {jax.device_count()}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax "
            f"initializes")
    return jax.make_mesh((data_parallel, pcfg.n_stages), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def init_train_state(cfg: ModelConfig, pcfg: PP.PipelineConfig,
                     rng) -> DiLoCoTrainState:
    """Round-0 state for one cluster (unstacked). Error/moments start at
    zero, the outer Nesterov momentum replica at zero — matching
    ``diloco.init_state`` row semantics."""
    params = PP.init_pp_params(cfg, rng, pcfg)
    return DiLoCoTrainState(
        params=params,
        inner_opt=adamw.init(params),
        outer_opt=nesterov.init(params),
        error=jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                           params),
    )


def state_shardings(state: DiLoCoTrainState, mesh: Mesh, *,
                    cluster_stacked: bool = False) -> DiLoCoTrainState:
    """NamedShardings for every leaf of a DiLoCoTrainState: params and all
    param-shaped companions (AdamW m/v, Nesterov momentum, EF residual)
    share ``pp_param_specs`` (stage dim -> "model"); step counters are
    replicated (or "clusters"-sharded when stacked).  This is the "explicit
    shardings" half of the tentpole: the whole round state is placed by
    one tree of rules, so the outer layer's gathered delta is just a
    device_get away."""

    def pshard(tree):
        specs = PP.pp_param_specs(tree, mesh, cluster_stacked=cluster_stacked)
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                            is_leaf=lambda x: isinstance(x, P))

    scalar = NamedSharding(mesh, P("clusters") if cluster_stacked else P())
    return DiLoCoTrainState(
        params=pshard(state.params),
        inner_opt=type(state.inner_opt)(
            step=scalar, m=pshard(state.inner_opt.m),
            v=pshard(state.inner_opt.v)),
        outer_opt=type(state.outer_opt)(
            step=scalar, momentum=pshard(state.outer_opt.momentum)),
        error=pshard(state.error),
    )


# ---------------------------------------------------------------------------
# delta extraction (the outer-layer boundary)
# ---------------------------------------------------------------------------

def extract_delta(anchor, state: DiLoCoTrainState):
    """Gathered per-cluster pseudo-gradient δ = (θ_anchor − θ_local) + e,
    fp32, from the sharded train state (``core.diloco.pseudo_grad`` does
    the arithmetic — one implementation for the scalar and pp engines).

    The ``active`` stage mask is not a trainable parameter: its delta is
    pinned to exactly zero, so it stays zero through compression (zero in
    → zero out in LowRankQuant) and the outer Nesterov momentum row for it
    never moves."""
    delta = diloco.pseudo_grad(anchor, state.params, state.error)
    delta = dict(delta)
    delta["active"] = jnp.zeros_like(delta["active"])
    return delta


def apply_delta(anchor, delta, error=None):
    """Inverse of ``extract_delta`` (up to fp rounding): local params such
    that extraction from them reproduces ``delta``.  θ_local =
    θ_anchor − (δ − e); the ``active`` mask is carried from the anchor
    (it was excluded from the delta).  Used by the round-trip property
    test; exactness is a stated tolerance, not bitwise — ``a − (a − p)``
    re-rounds unless Sterbenz applies."""
    if error is None:
        error = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             anchor)
    local = jax.tree.map(
        lambda a, d, e: (a.astype(jnp.float32) - (d - e)).astype(a.dtype),
        anchor, delta, error)
    local = dict(local)
    local["active"] = anchor["active"]
    return local


# ---------------------------------------------------------------------------
# the inner step / inner loop
# ---------------------------------------------------------------------------

def make_pp_train_step(cfg: ModelConfig, mesh: Mesh,
                       pcfg: PP.PipelineConfig, *, inner_lr: float,
                       cluster_stacked: bool = False) -> Callable:
    """One inner AdamW step through the pipelined loss:
    ``train_step(params, opt, tokens) -> (params', opt', loss)``.

    The ``active`` mask's gradient is zeroed before the update and the
    mask itself carried through unchanged (the dry-run's Mode B pattern) —
    AdamW weight decay would otherwise shrink the mask."""
    loss_fn = PP.make_pp_loss(cfg, mesh, pcfg,
                              cluster_stacked=cluster_stacked)

    from repro.obs import profile as _prof

    def train_step(params, opt, tokens):
        # named scope shows up in REPRO_PROFILE captures / XLA HLO names;
        # a nullcontext when profiling is off (identical trace either way)
        with _prof.scope("pp_train_step"):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            grads = dict(grads)
            grads["active"] = jnp.zeros_like(grads["active"])
            if cluster_stacked:
                new_params, opt = jax.vmap(
                    lambda p_, g_, o_: adamw.update(g_, o_, p_,
                                                    lr=inner_lr))(
                    params, grads, opt)
            else:
                new_params, opt = adamw.update(grads, opt, params,
                                               lr=inner_lr)
            new_params = dict(new_params)
            new_params["active"] = params["active"]
            return new_params, opt, loss

    return train_step


def make_pp_one_cluster(cfg: ModelConfig, pcfg: PP.PipelineConfig,
                        mesh: Mesh, *, inner_lr: float, h_steps: int,
                        batch_fn: Callable) -> Tuple[Callable, Callable]:
    """Per-cluster H-step inner loops on the unit mesh.

    ``batch_fn(c, i) -> tokens (B, S)`` with *traced* cluster index ``c``
    and inner-step index ``i`` — and nothing else.  The proc worker calls
    the returned function with no round index (its contract since PR 2),
    so pp data must be round-invariant; trainers that want per-round data
    fold the round into their own batch_fn closure instead of using this.

    Returns ``(one_cluster, one_cluster_h)``:
      one_cluster(params, opt, c)      -> (params_H, opt', losses[(H,)])
      one_cluster_h(params, opt, c, h) -> (params_H, opt', mean_loss)
    — the exact signatures ``sim/quadratic.QuadraticSpec`` exposes, so the
    worker and simulator wire pp identically to scalar.  ``one_cluster_h``
    is the masked fixed-length scan (``diloco.masked_local_steps``); per
    the PR 5 dispatch rule, uniform-at-budget rounds must route to
    ``one_cluster``."""
    train_step = make_pp_train_step(cfg, mesh, pcfg, inner_lr=inner_lr,
                                    cluster_stacked=False)

    def step_body(carry, i, c):
        params, opt = carry
        tokens = batch_fn(c, i)
        params, opt, loss = train_step(params, opt, tokens)
        return (params, opt), loss

    def one_cluster(params, opt, c):
        (params, opt), losses = jax.lax.scan(
            lambda carry, i: step_body(carry, i, c), (params, opt),
            jnp.arange(h_steps))
        return params, opt, losses

    def one_cluster_h(params, opt, c, h):
        (params, opt), mean_loss = diloco.masked_local_steps(
            lambda carry, i: step_body(carry, i, c), (params, opt),
            h_steps, h)
        return params, opt, mean_loss

    return one_cluster, one_cluster_h


def make_pp_inner_fns(one_cluster: Callable, one_cluster_h: Callable,
                      n_clusters: int) -> Tuple[Callable, Callable]:
    """Lift the per-cluster loops to the ``NumericProblem.inner_fn``
    signature ``(params, inner_opt_stacked, round_idx) -> (params_stacked,
    opt_stacked, aux)`` by a python-level UNROLL over clusters — not vmap.

    vmap would batch the pipeline's matmuls and ppermutes into one program
    whose accumulation order differs from a lone worker's by ~1 ulp (the
    ``per_cluster_compress`` lesson); unrolling executes the identical
    per-cluster op sequence the proc worker jits, which is what the
    bitwise proc≡in-process gate certifies.  The round index is accepted
    and ignored: pp batches are round-invariant (see
    ``make_pp_one_cluster``)."""

    def _stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def inner_fn(params, opt_stacked, t):
        del t
        outs = [one_cluster(params, diloco.take_row(opt_stacked, c),
                            jnp.asarray(c, jnp.int32))
                for c in range(n_clusters)]
        return (_stack([o[0] for o in outs]), _stack([o[1] for o in outs]),
                _stack([o[2] for o in outs]))

    def inner_fn_h(params, opt_stacked, t, h_vec):
        del t
        outs = [one_cluster_h(params, diloco.take_row(opt_stacked, c),
                              jnp.asarray(c, jnp.int32), h_vec[c])
                for c in range(n_clusters)]
        return (_stack([o[0] for o in outs]), _stack([o[1] for o in outs]),
                _stack([o[2] for o in outs]))

    return inner_fn, inner_fn_h
