"""End-to-end DiLoCoX training driver on the devices jax sees.

This is the *executable* counterpart of the dry-run: the same mesh-level
step functions (launch/steps.py), run for real, with the full DiLoCoX
round structure:

    for outer step t:  H x train_step (per-cluster, vmapped)
                       outer_step     (compress -> gather -> Nesterov,
                                       one-step-delay semantics)
                       AdaGradCmp     (Alg. 3 host-side controller)

The mesh is (clusters, data, model) over ``jax.devices()``: on a TPU host
that is its chips, and ``--devices`` (if given) must match their count.
On the CPU, ``--devices N`` asks XLA for N fake host devices — the test
path:
  python -m repro.launch.train --arch granite-3-8b --smoke \
      --devices 8 --clusters 2 --data 2 --model 2 --rounds 8 --h-steps 10

``--inner pp`` switches the inner loop to the sharded pipeline-parallel
engine (parallel/inner_engine.py): the mesh becomes
(clusters, data, --pp-stages), every cluster's H AdamW steps run through
the shard_map GPipe loss, the whole round state lives in one
cluster-stacked ``DiLoCoTrainState`` placed by ``state_shardings``, and
the outer compress -> mean -> Nesterov round consumes the gathered delta
from ``extract_delta`` — the same code path the sim gates certify on the
unit mesh:
  python -m repro.launch.train --arch granite-3-8b --smoke \
      --inner pp --devices 8 --clusters 2 --data 2 --pp-stages 2

``run(args, cfg)`` is the whole driver for an already-built config; it
returns the per-step losses, compile times, step times and the compiled
steps' memory analysis (``chip_smoke.py`` calls it at OPT-1.3B width).
"""
import argparse
import contextlib
import os
import sys
import time


def _setup_obs(args):
    """Logger (stdout, byte-stable lines) + optional wall-clock tracer.
    Returns ``(log, tracer, span)`` where ``span`` is a no-op context
    factory when ``--trace`` is off."""
    from repro.obs import Tracer, configure_logging, get_logger
    configure_logging(stream=sys.stdout,
                      json_stream=(sys.stderr if args.log_json else None))
    log = get_logger("launch.train")
    tracer = Tracer("train-driver") if args.trace else None
    if tracer is not None:
        span = tracer.span
    else:
        def span(name, **kw):
            return contextlib.nullcontext()
    return log, tracer, span


def _finish_obs(args, log, tracer) -> None:
    if tracer is not None:
        tracer.write(args.trace)
        log.info(f"wrote {args.trace}")


def _timed(fn, *args):
    """Call ``fn`` and wait for every output; returns (outputs, seconds)."""
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}


def _compile(name, jitted, args, result, log):
    """AOT-compile ``jitted`` for ``args``, recording its compile time and
    memory analysis under ``name`` in ``result``."""
    t = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    result["compile_s"][name] = time.perf_counter() - t
    result["memory"][name] = mem = _memory(compiled)
    log.info(f"compiled {name} in {result['compile_s'][name]:.1f}s: "
             + " ".join(f"{k}={v / 2**30:.2f}GiB" for k, v in mem.items()))
    return compiled


def _new_result() -> dict:
    return {"step_losses": [], "round_losses": [], "compile_s": {},
            "memory": {}, "inner_step_s": [], "outer_step_s": []}


def _run_pp(args, cfg) -> dict:
    """DiLoCoX rounds with the pipeline-parallel inner engine on a
    cluster-stacked (clusters, data, model) mesh.

    Each cluster row holds its own full replica of the round state — local
    params, inner AdamW moments, outer Nesterov momentum, EF residual —
    exactly as the paper's decentralized clusters do (no parameter
    server); the outer rows stay identical because every cluster applies
    the same averaged delta.  The comm leg here runs sequentially after
    the inner steps (it's a driver, not the overlap-scheduled runtime),
    but the DELAYED round arithmetic matches ``core.diloco.diloco_round``:
    round t averages delta^{t-1} and the outer update lands on the
    anchor."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import diloco
    from repro.core.compression import make_compressor, tree_shapes
    from repro.data.synthetic import SyntheticLM
    from repro.launch.mesh import launcher_mesh
    from repro.optim import nesterov
    from repro.parallel import inner_engine as IE
    from repro.parallel import pipeline as PP

    if args.adaptive or args.h_policy != "global":
        raise SystemExit("--inner pp supports the static round schedule "
                         "only (no --adaptive / --h-policy balance yet)")
    C = args.clusters
    Bc = args.global_batch // C
    if Bc % args.pp_micro:
        raise SystemExit("per-cluster batch (global-batch/clusters) must "
                         "divide --pp-micro")

    mesh = launcher_mesh((C, args.data, args.pp_stages),
                         ("clusters", "data", "model"), args.devices)
    pcfg = PP.PipelineConfig(n_stages=args.pp_stages, n_micro=args.pp_micro)

    compressor = make_compressor("diloco_x", rank=args.rank)

    def init_round_state():
        # one cluster's state, broadcast to a (C,)-stacked DiLoCoTrainState,
        # the anchor (its own buffers: the steps donate the state's), the
        # zero pending delta and the compressor's warm starts
        st1 = IE.init_train_state(cfg, pcfg, jax.random.PRNGKey(0))
        stack = lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), t)
        st = stack(st1)
        return (st, st.params, jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), st.params),
                stack(compressor.init_state(st1.params)))

    # placed by the explicit sharding rules (stage dim -> "model", leading
    # replica dim -> "clusters") and built under them, so no device holds
    # an unsharded copy on the way.  The steps' in- and out-shardings are
    # this one placement: each compiles once and its outputs feed the next
    # call unchanged.  Both donate the round state, updating it in place
    st_shape = jax.eval_shape(init_round_state)[0]
    sst = IE.state_shardings(st_shape, mesh, cluster_stacked=True)
    comp_sh = NamedSharding(mesh, P("clusters"))
    outer_sh = (sst, sst.params, sst.params, comp_sh)
    state, anchor, delta_pending, comp_state = jax.jit(
        init_round_state, out_shardings=outer_sh)()
    tok_sharding = NamedSharding(mesh, P("clusters", "data", None))
    rep = NamedSharding(mesh, P())

    train_step = jax.jit(IE.make_pp_train_step(
        cfg, mesh, pcfg, inner_lr=args.inner_lr, cluster_stacked=True),
        in_shardings=(sst.params, sst.inner_opt, tok_sharding),
        out_shardings=(sst.params, sst.inner_opt, rep),
        donate_argnums=(0, 1))

    def outer_round(state, anchor, delta_pending, comp_state):
        # comm leg: average LAST round's pseudo-grads (one-step delay)
        delta_hat, comp_state = diloco.per_cluster_compress(
            compressor, delta_pending, comp_state,
            jnp.asarray(args.rank, jnp.int32))
        Delta = jax.tree.map(lambda x: x.mean(0), delta_hat)
        Delta_rows = jax.tree.map(
            lambda D, d: jnp.broadcast_to(D[None], d.shape), Delta,
            delta_pending)
        # Alg. 2 error feedback: e = delta - Delta (vs the applied average)
        err = jax.tree.map(lambda d, Dr: d - Dr, delta_pending, Delta_rows)
        # next round's pending delta, gathered from the sharded state
        delta_new = IE.extract_delta(anchor, state._replace(error=err))
        # delayed outer Nesterov on the anchor, applied row-wise (rows
        # stay identical: same Delta everywhere)
        params_new, outer_opt = nesterov.update(
            Delta_rows, state.outer_opt, anchor,
            lr=args.outer_lr, momentum=args.outer_momentum)
        state = IE.DiLoCoTrainState(params=params_new,
                                    inner_opt=state.inner_opt,
                                    outer_opt=outer_opt, error=err)
        return state, params_new, delta_new, comp_state

    outer_jit = jax.jit(outer_round, in_shardings=outer_sh,
                        out_shardings=outer_sh, donate_argnums=(0, 1, 2, 3))

    data = [SyntheticLM(cfg.vocab_size, args.seq_len, Bc, seed=0,
                        data_shard=i) for i in range(C)]
    wire = compressor.wire_bytes(tree_shapes(jax.eval_shape(
        lambda: PP.init_pp_params(cfg, jax.random.PRNGKey(0), pcfg))))

    log, tracer, span = _setup_obs(args)
    from repro.obs import profile as prof
    from repro.checkpoint import checkpoint as ckpt_lib
    result = _new_result()
    with prof.capture("train-pp"):
        for r in range(args.rounds):
            with span("round", round=r):
                losses = []
                with span("inner", round=r):
                    for h in range(args.h_steps):
                        toks = jnp.stack(
                            [d.next_batch()["tokens"] for d in data])
                        toks = jax.device_put(toks, tok_sharding)
                        if r == 0 and h == 0:
                            train_step = _compile(
                                "pp_train_step", train_step,
                                (state.params, state.inner_opt, toks),
                                result, log)
                        (params, inner_opt, loss), dt = _timed(
                            train_step, state.params, state.inner_opt, toks)
                        state = state._replace(params=params,
                                               inner_opt=inner_opt)
                        losses.append(float(loss) / C)
                        result["inner_step_s"].append(dt)
                with span("outer", round=r):
                    if r == 0:
                        outer_jit = _compile(
                            "outer_step", outer_jit,
                            (state, anchor, delta_pending, comp_state),
                            result, log)
                    (state, anchor, delta_pending, comp_state), dt = _timed(
                        outer_jit, state, anchor, delta_pending, comp_state)
                    result["outer_step_s"].append(dt)
            result["step_losses"] += losses
            result["round_losses"].append(float(np.mean(losses)))
            log.info(f"round {r}: mean_loss={np.mean(losses):.4f} "
                     f"H={args.h_steps} wire_per_cluster={wire/1e6:.2f}MB",
                     round=r, mean_loss=float(np.mean(losses)),
                     h_steps=args.h_steps, wire_bytes=wire)
            if args.ckpt_dir:
                ckpt_lib.save(os.path.join(args.ckpt_dir, f"round_{r:04d}"),
                              {"params": state.params}, step=r,
                              meta={"arch": args.arch, "inner": "pp"})
    result["state"] = state.params
    log.info("TRAIN-DRIVER-OK")
    _finish_obs(args, log, tracer)
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--devices", type=int, default=None,
                    help="expected device count (default: every device jax "
                         "sees); on the CPU, the number of fake host "
                         "devices to create")
    ap.add_argument("--clusters", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--h-steps", type=int, default=10)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--inner-lr", type=float, default=1e-3)
    ap.add_argument("--outer-lr", type=float, default=0.5)
    ap.add_argument("--outer-momentum", type=float, default=0.7)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--h-policy", default="global",
                    choices=["global", "balance"],
                    help="per-cluster local-step scheduling: balance gives "
                         "each cluster its own H from --step-times so slow "
                         "sites do fewer local steps per round")
    ap.add_argument("--step-times", default="",
                    help="comma-separated per-cluster step seconds "
                         "(measured on the real sites) for --h-policy "
                         "balance; default: uniform (== global)")
    ap.add_argument("--h-min", type=int, default=1)
    ap.add_argument("--inner", default="gspmd", choices=["gspmd", "pp"],
                    help="inner engine: gspmd = the vmapped cluster-stacked "
                         "step (launch/steps.py); pp = the sharded "
                         "pipeline-parallel engine "
                         "(parallel/inner_engine.py) with --pp-stages "
                         "stages per cluster")
    ap.add_argument("--pp-stages", type=int, default=2)
    ap.add_argument("--pp-micro", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--trace", default="",
                    help="write a wall-clock Chrome-trace JSON of the "
                         "driver's round/inner/outer spans here")
    ap.add_argument("--log-json", action="store_true",
                    help="mirror log lines as JSON objects on stderr")
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from repro.launch.mesh import request_host_devices
    request_host_devices(args.devices)

    from repro import compile_cache
    from repro.configs.base import get_config
    compile_cache.enable()
    cfg = get_config(args.arch)
    run(args, cfg.reduced() if args.smoke else cfg)


def run(args, cfg) -> dict:
    """Train ``cfg`` with the DiLoCoX round loop ``args`` describes.
    Returns ``{"step_losses", "round_losses", "compile_s", "memory",
    "inner_step_s", "outer_step_s", "state"}``: per-step and per-round
    mean losses, AOT compile seconds and ``memory_analysis`` bytes per
    compiled step, wall seconds of every inner and outer step (each ended
    by ``block_until_ready``), and the final cluster-stacked params."""
    if args.inner == "pp":
        return _run_pp(args, cfg)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import ShapeConfig
    from repro.core import adaptive
    from repro.core import mesh_compression as mc
    from repro.data.synthetic import SyntheticLM
    from repro.launch import steps
    from repro.launch.mesh import launcher_mesh
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import sharding as sh

    C = args.clusters
    mesh = launcher_mesh((C, args.data, args.model),
                         ("clusters", "data", "model"), args.devices)
    M.set_activation_sharder(sh.make_activation_sharder(mesh))

    # one placement for the whole round state, used as both the in- and
    # out-shardings of the steps: one executable each, updated in place
    ccfg = mc.MeshCompressionConfig(rank=args.rank)
    ps, opt_sh, ost_sh = steps.round_shardings(cfg, mesh, C, ccfg)
    Bc = args.global_batch // C
    bsh = sh.batch_shardings(
        steps.input_specs(cfg, ShapeConfig("run", args.seq_len,
                                           args.global_batch, "train"),
                          n_clusters=C), mesh, cluster_stacked=True)
    rep = NamedSharding(mesh, P())

    rng = jax.random.PRNGKey(0)

    def init_round_state():
        params1 = M.init_params(cfg, rng)
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), params1)
        return (params, jax.vmap(adamw.init)(params),
                steps.init_outer_state(params1, C, ccfg))

    # built under the round's shardings: no device holds an unsharded copy
    # of the state on the way
    params, opt, outer_state = jax.jit(
        init_round_state, out_shardings=(ps, opt_sh, ost_sh))()
    param_shapes = jax.eval_shape(lambda: M.init_params(cfg, rng))

    balance_h = args.h_policy == "balance"
    step_times = ([float(s) for s in args.step_times.split(",")]
                  if args.step_times else [1.0] * C)
    if len(step_times) != C:
        raise SystemExit("--step-times needs one entry per cluster")

    def plan_round_h(h_budget):
        h_map = adaptive.plan_h(
            adaptive.HSpec(policy="balance", h_min=args.h_min),
            h_budget, np.asarray(step_times), np.ones(C, bool))
        return [h_map[c] for c in range(C)]

    # uniform-at-budget rounds run the plain train step (bitwise the
    # global path); only genuinely heterogeneous rounds use the masked
    # variant — the same dispatch rule the sim backends and trainer apply.
    # Params and optimizer state are donated: the steps update them in
    # place instead of holding input and output copies at once.
    train_step = jax.jit(steps.make_train_step(cfg, inner_lr=args.inner_lr),
                         in_shardings=(ps, opt_sh, bsh),
                         out_shardings=(ps, opt_sh, rep),
                         donate_argnums=(0, 1))
    train_step_h = (jax.jit(steps.make_train_step(
        cfg, inner_lr=args.inner_lr, per_cluster_h=True),
        in_shardings=(ps, opt_sh, bsh, rep),
        out_shardings=(ps, opt_sh, rep),
        donate_argnums=(0, 1)) if balance_h else None)
    outer_step = jax.jit(steps.make_outer_step(
        cfg, ccfg, outer_lr=args.outer_lr,
        outer_momentum=args.outer_momentum),
        in_shardings=(ps, ost_sh, rep), out_shardings=(ps, ost_sh),
        donate_argnums=(0, 1))

    data = [SyntheticLM(cfg.vocab_size, args.seq_len, Bc, seed=0,
                        data_shard=i) for i in range(C)]
    ada_cfg = adaptive.AdaGradCmpConfig(r1=args.rank, h1=args.h_steps,
                                        mode="overlap")
    ada = adaptive.AdaGradCmpState.create(ada_cfg)

    log, tracer, span = _setup_obs(args)
    from repro.obs import profile as prof
    from repro.checkpoint import checkpoint as ckpt_lib
    result = _new_result()
    # static (non-adaptive) budgets have a round-invariant schedule —
    # plan it once outside the loop
    h_vec_static = plan_round_h(args.h_steps) if balance_h else None
    prof_cm = contextlib.ExitStack()
    prof_cm.enter_context(prof.capture("train-gspmd"))
    for r in range(args.rounds):
        # pre-observe controller state = what this round executes (same
        # accounting rule as train/trainer.py: the post-observe state is
        # round r+1's budget and must not be logged as this round's)
        h_t = ada.h_t if args.adaptive else args.h_steps
        r_exec = ada.r_t
        if balance_h:
            h_vec = plan_round_h(h_t) if args.adaptive else h_vec_static
        else:
            h_vec = [h_t] * C
        het_round = any(hc != h_t for hc in h_vec)
        losses = []
        with span("round", round=r):
            with span("inner", round=r):
                for h in range(max(h_vec)):
                    toks = jnp.stack([d.next_batch()["tokens"]
                                      for d in data])
                    batch = {"tokens": jax.device_put(toks, bsh["tokens"])}
                    if cfg.modality != "text":
                        fe = jax.random.normal(
                            jax.random.fold_in(rng, r * 1000 + h),
                            (C, Bc, cfg.n_frontend_tokens,
                             cfg.d_model)) * 0.02
                        batch["frontend"] = jax.device_put(
                            fe, bsh["frontend"])
                    if het_round:
                        active = jnp.asarray([h < hc for hc in h_vec],
                                             bool)
                        (params, opt, loss), dt = _timed(
                            train_step_h, params, opt, batch, active)
                    else:
                        if "train_step" not in result["compile_s"]:
                            train_step = _compile(
                                "train_step", train_step,
                                (params, opt, batch), result, log)
                        (params, opt, loss), dt = _timed(
                            train_step, params, opt, batch)
                    losses.append(float(loss))
                    result["inner_step_s"].append(dt)
            with span("outer", round=r):
                rank_scalar = jnp.asarray(r_exec, jnp.int32)
                if "outer_step" not in result["compile_s"]:
                    outer_step = _compile(
                        "outer_step", outer_step,
                        (params, outer_state, rank_scalar), result, log)
                (params, outer_state), dt = _timed(
                    outer_step, params, outer_state, rank_scalar)
                result["outer_step_s"].append(dt)
        result["step_losses"] += losses
        result["round_losses"].append(float(np.mean(losses)))
        wire = mc.wire_bytes_tree(param_shapes, ccfg,
                                  rank=r_exec if args.adaptive else None)
        h_str = (f"H={h_t}" if not het_round
                 else "H=" + "/".join(str(hc) for hc in h_vec))
        log.info(f"round {r}: mean_loss={np.mean(losses):.4f} "
                 f"{h_str} r={r_exec} wire_per_cluster={wire/1e6:.2f}MB",
                 round=r, mean_loss=float(np.mean(losses)), h=h_vec,
                 rank=int(r_exec), wire_bytes=wire)
        if args.adaptive:
            ada = adaptive.observe_mean_pseudo_grad(
                ada, jax.tree.map(lambda x: x.mean(0),
                                  outer_state.delta_pending), ada_cfg)
        if args.ckpt_dir:
            ckpt_lib.save(os.path.join(args.ckpt_dir, f"round_{r:04d}"),
                          {"params": params, "outer": outer_state._asdict()},
                          step=r, meta={"arch": args.arch})
    prof_cm.close()
    result["state"] = params
    log.info("TRAIN-DRIVER-OK")
    _finish_obs(args, log, tracer)
    return result


if __name__ == "__main__":
    main()
