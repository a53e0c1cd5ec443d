import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run (spec deliverable e).

Lowers + compiles every runnable (architecture x input-shape) combination on
the single-pod (16,16) and multi-pod (2,16,16) production meshes, printing
``memory_analysis()`` and ``cost_analysis()`` and parsing collective bytes
from the compiled HLO — the inputs to EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k [--multi-pod] [--all] [--out results.json]

The XLA_FLAGS line above MUST stay the first statement: jax locks the
device count on first init, and only the dry-run wants 512 host devices.
"""
import argparse
import dataclasses
import json
import math
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, ModelConfig, SHAPES, get_config,
                                get_shape, supports_shape)
from repro.core import mesh_compression as mc
from repro.launch import mesh as mesh_lib
from repro.launch import steps
from repro.models import model as M
from repro.parallel import sharding as sh

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e targets; DESIGN.md §5)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s/link (intra-pod)
DCN_BW = 0.125e9             # 1 Gbps decentralized link (paper's scenario)

COLLECTIVE_RE = re.compile(
    r"= (f8|f16|f32|f64|bf16|u8|s8|u32|s32|pred)\[([\d,]*)\]\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(([^\n]*)")

GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=(\[[\d,]+\])?(?:T\(([\d,]+)\))?")
SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)")
PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def _first_group(attrs: str):
    """First replica group as a list of device ids (literal or iota form)."""
    m = GROUPS_LITERAL_RE.search(attrs)
    if m:
        return [int(x) for x in m.group(1).split(",") if x.strip()]
    m = GROUPS_IOTA_RE.search(attrs)
    if m:
        import numpy as _np
        n_groups, g_size = int(m.group(1)), int(m.group(2))
        n = n_groups * g_size
        ids = _np.arange(n)
        if m.group(3):
            dims = [int(x) for x in m.group(3).strip("[]").split(",")]
            ids = ids.reshape(dims)
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                ids = ids.transpose(perm)
            ids = ids.reshape(-1)
        return list(ids.reshape(n_groups, g_size)[0])
    m = SOURCE_TARGET_RE.search(attrs)
    if m:
        # a permute "crosses" if ANY pair crosses; return the widest pair
        pairs = [(int(a), int(b)) for a, b in PAIR_RE.findall(m.group(1))]
        if pairs:
            widest = max(pairs, key=lambda ab: abs(ab[0] - ab[1]))
            return list(widest)
    return None


def _crosses_cluster(group, cluster_size: int) -> bool:
    if not group:
        return False
    return len({d // cluster_size for d in group}) > 1

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
               "u8": 1, "s8": 1, "u32": 4, "s32": 4, "pred": 1}


def parse_collective_bytes(hlo_text: str,
                           cluster_size: int = 0) -> Dict[str, Any]:
    """Sum output-operand sizes of collective ops in the (post-SPMD) HLO.
    When cluster_size > 0, traffic whose replica groups span clusters is
    reported separately (that is the 1 Gbps decentralized boundary)."""
    out: Dict[str, Any] = {}
    cross = 0
    cross_by_dtype: Dict[str, int] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, kind, attrs = m.group(1), m.group(2), m.group(3), m.group(4)
        n = 1
        if dims.strip():
            for d in dims.split(","):
                n *= int(d)
        nbytes = n * DTYPE_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0) + nbytes
        if cluster_size:
            grp = _first_group(attrs)
            if _crosses_cluster(grp, cluster_size):
                cross += nbytes
                cross_by_dtype[dt] = cross_by_dtype.get(dt, 0) + nbytes
    if cluster_size:
        out["_cross_cluster_bytes"] = cross
        out["_cross_cluster_by_dtype"] = cross_by_dtype
    return out


def production_dtypes(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              rank: int = 128, include_outer: bool = True,
              mode: str = "gspmd", verbose: bool = True) -> Dict[str, Any]:
    cfg = production_dtypes(get_config(arch))
    shape = get_shape(shape_name)
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    base = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    res: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "multi_pod": multi_pod, "mode": mode,
                           "mesh": mesh_lib.describe(base)}
    t0 = time.time()
    try:
        if shape.kind == "train" and mode == "pipeline":
            res.update(_lower_train_pipeline(cfg, shape, base))
        elif shape.kind == "train":
            res.update(_lower_train(cfg, shape, base, rank, include_outer,
                                    mode))
        elif shape.kind == "prefill":
            res.update(_lower_prefill(cfg, shape, base))
        else:
            res.update(_lower_decode(cfg, shape, base))
        res["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        res["status"] = "fail"
        res["error"] = f"{type(e).__name__}: {str(e)[:500]}"
    res["lower_compile_s"] = round(time.time() - t0, 1)
    if verbose:
        print(json.dumps(res)[:2000])
    return res


def _analyze(compiled, n_chips: int, cluster_size: int = 0):
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo, cluster_size)
    cross = coll.pop("_cross_cluster_bytes", 0)
    cross_dt = coll.pop("_cross_cluster_by_dtype", {})
    coll_total = sum(coll.values())
    out = {
        "per_device_memory_bytes": int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll,
        "collective_total_bytes": coll_total,
        "cross_cluster_bytes": cross,
        "cross_cluster_by_dtype": cross_dt,
        # roofline terms (seconds), per device
        "t_compute": flops / PEAK_FLOPS,
        "t_memory": bytes_acc / HBM_BW,
        "t_collective_ici": (coll_total - cross) / ICI_BW,
        "t_collective_dcn_1gbps": cross / DCN_BW,
    }
    return out


def _lower_train(cfg, shape, base, rank, include_outer, mode):
    n_clusters = 2 if base.devices.ndim == 3 else 2
    mesh = mesh_lib.make_cluster_mesh(base, n_clusters=n_clusters)
    n_chips = base.devices.size

    p_specs = steps.params_specs(cfg, n_clusters=n_clusters)
    o_specs = steps.opt_specs(p_specs)
    b_specs = steps.input_specs(cfg, shape, n_clusters=n_clusters)
    ccfg = mc.MeshCompressionConfig(rank=rank)
    ps, opt_sh, ost_sh = steps.round_shardings(cfg, mesh, n_clusters, ccfg)
    bs = sh.batch_shardings(b_specs, mesh, cluster_stacked=True)

    train_step = steps.make_train_step(cfg)
    M.set_activation_sharder(sh.make_activation_sharder(mesh))
    lowered = jax.jit(
        train_step,
        in_shardings=(ps, opt_sh, bs),
        out_shardings=(ps, opt_sh, NamedSharding(mesh, P())),
    ).lower(p_specs, o_specs, b_specs)
    compiled = lowered.compile()
    cluster_size = base.devices.size // n_clusters
    out = {"train": _analyze(compiled, n_chips, cluster_size)}
    print("memory_analysis:", compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print("cost_analysis: flops=%.3e bytes=%.3e"
          % (ca.get("flops", 0), ca.get("bytes accessed", 0)))

    if include_outer:
        ost_specs = steps.outer_state_specs(cfg, n_clusters, ccfg)
        outer_step = steps.make_outer_step(cfg, ccfg)
        lowered_o = jax.jit(
            outer_step,
            in_shardings=(ps, ost_sh, NamedSharding(mesh, P())),
            out_shardings=(ps, ost_sh),
        ).lower(p_specs, ost_specs,
                jax.ShapeDtypeStruct((), jnp.int32))
        compiled_o = lowered_o.compile()
        out["outer"] = _analyze(compiled_o, n_chips, cluster_size)
        print("outer memory_analysis:", compiled_o.memory_analysis())
    return out


def _lower_train_pipeline(cfg, shape, base, n_micro=16):
    """Mode B: paper-faithful PP over the "model" axis (shard_map +
    ppermute GPipe loop), dense decoder archs. One inner step =
    grad(pp_loss) + AdamW."""
    import jax.numpy as jnp
    from repro.optim import adamw
    from repro.parallel import pipeline as PP

    n_clusters = 2
    mesh = mesh_lib.make_cluster_mesh(base, n_clusters=n_clusters)
    n_chips = base.devices.size
    n_stages = mesh.shape["model"]
    pcfg = PP.PipelineConfig(n_stages=n_stages, n_micro=n_micro)
    lps, pad = PP.layers_per_stage(cfg, pcfg)

    p1 = jax.eval_shape(lambda k: PP.init_pp_params(cfg, k, pcfg),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_clusters,) + x.shape, x.dtype), p1)
    o_specs = jax.eval_shape(jax.vmap(adamw.init), p_specs)
    Bc = shape.global_batch // n_clusters
    t_specs = jax.ShapeDtypeStruct((n_clusters, Bc, shape.seq_len),
                                   jnp.int32)
    loss_fn = PP.make_pp_loss(cfg, mesh, pcfg, cluster_stacked=True)

    def train_step(params, opt, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        grads = dict(grads)
        grads["active"] = jnp.zeros_like(grads["active"])
        new_params, opt = jax.vmap(
            lambda p_, g_, o_: adamw.update(g_, o_, p_, lr=1e-4,
                                            weight_decay=0.0))(
            params, grads, opt)
        new_params = dict(new_params)
        new_params["active"] = params["active"]
        return new_params, opt, loss

    specs_in = PP.pp_param_specs(p_specs, mesh, cluster_stacked=False)
    # pp_param_specs built for unstacked; rebuild with the cluster dim
    def to_sharding(tree_specs):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), tree_specs)

    pspec_tree = PP.pp_param_specs(p1, mesh, cluster_stacked=False)
    def add_cluster(sp):
        return P(*(("clusters",) + tuple(sp)))
    pspec_tree = jax.tree.map(add_cluster, pspec_tree,
                              is_leaf=lambda x: isinstance(x, P))
    psh = to_sharding(pspec_tree)
    osh = jax.eval_shape(jax.vmap(adamw.init), p_specs)
    osh = type(o_specs)(
        step=NamedSharding(mesh, P("clusters")),
        m=psh, v=psh)
    tsh = NamedSharding(mesh, P("clusters", "data", None))
    lowered = jax.jit(train_step,
                      in_shardings=(psh, osh, tsh),
                      out_shardings=(psh, osh, NamedSharding(mesh, P()))
                      ).lower(p_specs, o_specs, t_specs)
    compiled = lowered.compile()
    print("memory_analysis:", compiled.memory_analysis())
    out = {"train": _analyze(compiled, n_chips,
                             base.devices.size // n_clusters)}
    out["pipeline"] = {"n_stages": n_stages, "layers_per_stage": lps,
                       "padded_layers": pad, "n_micro": n_micro,
                       "bubble_frac": (n_stages - 1)
                       / (n_micro + n_stages - 1)}
    return out


def pp_inner_smoke(arch: str, *, n_stages: int = 8, data_parallel: int = 1,
                   n_micro: int = 8, batch: int = 16, seq_len: int = 512,
                   verbose: bool = True) -> Dict[str, Any]:
    """``--inner pp``: shape-check the FULL-SIZE model through the sharded
    pipeline-parallel inner engine (parallel/inner_engine.py) on the faked
    devices — pure ``jax.eval_shape``, no lowering or compute, so even
    qwen1.5-107b (78 layers, d_model 8192) passes in seconds.  Certifies
    that one inner train step is a shape fixed-point of the
    ``DiLoCoTrainState`` params, that ``state_shardings`` resolves a
    placement rule for every leaf, and that ``extract_delta`` yields an
    fp32 tree congruent with the params (what the outer compress/mix layer
    consumes)."""
    from repro.parallel import inner_engine as IE
    from repro.parallel import pipeline as PP

    cfg = production_dtypes(get_config(arch))
    res: Dict[str, Any] = {"arch": arch, "shape": f"pp_inner_b{batch}",
                           "multi_pod": False, "mode": "pp_inner",
                           "n_stages": n_stages,
                           "data_parallel": data_parallel}
    t0 = time.time()
    try:
        pcfg = PP.PipelineConfig(n_stages=n_stages, n_micro=n_micro)
        lps, pad = PP.layers_per_stage(cfg, pcfg)
        mesh = IE.unit_mesh(pcfg, data_parallel)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state = jax.eval_shape(lambda k: IE.init_train_state(cfg, pcfg, k),
                               key)
        shardings = IE.state_shardings(state, mesh)
        n_sharded = len(jax.tree.leaves(shardings))
        n_leaves = len(jax.tree.leaves(state))
        assert n_sharded == n_leaves, (n_sharded, n_leaves)

        train_step = IE.make_pp_train_step(cfg, mesh, pcfg, inner_lr=1e-4)
        toks = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
        p2, o2, loss = jax.eval_shape(train_step, state.params,
                                      state.inner_opt, toks)
        sd = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
        assert sd(p2) == sd(state.params), "inner step not a shape fixed-point"
        assert sd(o2) == sd(state.inner_opt)
        assert loss.shape == ()

        delta = jax.eval_shape(IE.extract_delta, state.params, state)
        assert jax.tree.structure(delta) == jax.tree.structure(state.params)
        assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(delta))

        n_params = sum(int(math.prod(x.shape))
                       for x in jax.tree.leaves(state.params))
        res.update({
            "status": "ok", "layers_per_stage": lps, "padded_layers": pad,
            "n_micro": n_micro, "param_count": n_params,
            "state_bytes": sum(
                int(math.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(state)),
            "bubble_frac": (n_stages - 1) / (n_micro + n_stages - 1),
        })
        print(f"PP-INNER-SMOKE-OK arch={arch} stages={n_stages} "
              f"layers_per_stage={lps} params={n_params}")
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        res["status"] = "fail"
        res["error"] = f"{type(e).__name__}: {str(e)[:500]}"
    res["lower_compile_s"] = round(time.time() - t0, 1)
    if verbose:
        print(json.dumps(res)[:2000])
    return res


def _lower_prefill(cfg, shape, base):
    mesh = mesh_lib.make_serving_mesh(base)
    n_chips = base.devices.size
    p_specs = steps.params_specs(cfg)
    b_specs = steps.input_specs(cfg, shape)
    ps = sh.param_shardings(p_specs, mesh, cluster_stacked=False)
    bs = sh.batch_shardings(b_specs, mesh, cluster_stacked=False)
    prefill = steps.make_prefill_step(cfg)
    M.set_activation_sharder(sh.make_activation_sharder(mesh))
    lowered = jax.jit(prefill, in_shardings=(ps, bs)).lower(
        p_specs, b_specs)
    compiled = lowered.compile()
    print("memory_analysis:", compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print("cost_analysis: flops=%.3e bytes=%.3e"
          % (ca.get("flops", 0), ca.get("bytes accessed", 0)))
    return {"prefill": _analyze(compiled, n_chips)}


def _lower_decode(cfg, shape, base):
    import math
    mesh = mesh_lib.make_serving_mesh(base)
    n_chips = base.devices.size
    p_specs = steps.params_specs(cfg)
    s_specs = steps.decode_state_specs(cfg, shape)
    b_specs = steps.input_specs(cfg, shape)
    # [hillclimb D, REFUTED]: TP-only weight sharding for decode predicted
    # killing the 1.2 GB/token all-gathers (assumed FSDP weight gathers).
    # Measured: ICI -1.6% (the gathers are KV-cache/head-layout resharding)
    # and temp memory 0.85 -> 8.7 GB (activations replicated over "data").
    # 2-D weights stay the serving default; flag kept for experiments.
    serve_tp_only = os.environ.get("REPRO_SERVE_TP_ONLY", "0") == "1"
    ps = sh.param_shardings(p_specs, mesh, cluster_stacked=False,
                            serve=serve_tp_only)
    seq_shard = shape.global_batch < mesh.shape["data"]
    ss = sh.decode_state_shardings(s_specs, mesh, seq_shard=seq_shard)
    bs = sh.batch_shardings(b_specs, mesh, cluster_stacked=False)
    serve = steps.make_serve_step(cfg)
    M.set_activation_sharder(sh.make_activation_sharder(mesh))
    lowered = jax.jit(serve, in_shardings=(ps, ss, bs["tokens"]),
                      out_shardings=(bs["tokens"], ss)).lower(
        p_specs, s_specs, b_specs["tokens"])
    compiled = lowered.compile()
    print("memory_analysis:", compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print("cost_analysis: flops=%.3e bytes=%.3e"
          % (ca.get("flops", 0), ca.get("bytes accessed", 0)))
    return {"decode": _analyze(compiled, n_chips),
            "seq_sharded_cache": bool(seq_shard),
            "serve_tp_only_weights": bool(serve_tp_only)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=ARCH_IDS)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="full matrix: every arch x shape x both meshes")
    ap.add_argument("--no-outer", action="store_true")
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--mode", default="gspmd", choices=["gspmd", "pipeline"])
    ap.add_argument("--inner", default="gspmd", choices=["gspmd", "pp"],
                    help="pp: eval_shape the arch through the sharded "
                         "pipeline-parallel inner engine instead of "
                         "lowering the mesh step (fast, no compute)")
    ap.add_argument("--pp-stages", type=int, default=8)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    results = []
    if args.inner == "pp":
        results.append(pp_inner_smoke(args.arch, n_stages=args.pp_stages))
    elif args.all:
        for arch in [a for a in ARCH_IDS
                     if a not in ("opt-1.3b", "qwen1.5-107b")]:
            for shape in SHAPES:
                for mp in (False, True):
                    results.append(lower_one(
                        arch, shape, multi_pod=mp, rank=args.rank,
                        include_outer=(shape == "train_4k"
                                       and not args.no_outer)))
    else:
        results.append(lower_one(args.arch, args.shape,
                                 multi_pod=args.multi_pod, rank=args.rank,
                                 include_outer=not args.no_outer,
                                 mode=args.mode))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"DRYRUN SUMMARY ok={n_ok} skipped={n_skip} fail={n_fail}")
    if n_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
