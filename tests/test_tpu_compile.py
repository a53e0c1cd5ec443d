"""Compile the chip's main paths for a described TPU v5e, with no chip.

The TPU compiler is installed next to jax and compiles for a topology that
is described, not attached.  It refuses what interpret mode accepts: block
shapes off the TPU tiling, kernels over the scoped-VMEM limit, programs
over the device's memory.  So every Pallas kernel of the training and
serving paths is compiled here at the shapes ``chip_smoke.py`` runs, and
the depth-cut OPT-1.3B round steps are held to the chip's 16 GiB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each xdist worker
imports every test module.
"""
import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs.base import ShapeConfig, get_config

V5E_HBM = 16 * 2 ** 30
HEADROOM = 0.85             # of HBM the depth cut may plan for


def _chip_smoke():
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables for a described chip can be written to the persistent
    # cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the kernels pick interpret mode from the default backend (the CPU
    # here); these compiles are for the TPU
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernels, "pallas_interpret", lambda: False)
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_cases(sds):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.fused_compress import (fused_compress_ef,
                                              fused_decompress)
    from repro.kernels.lowrank_mm import matmul_pallas
    from repro.kernels.quant4 import quant4_pack_pallas, quant4_unpack_pallas
    from repro.serve.attention_paged import pallas_paged_attention

    u8, i32 = jnp.uint8, jnp.int32
    S, pages, ps, H, dh = 8, 20, 8, 32, 64          # OPT-1.3B heads
    cases = {
        "paged_attention": (
            lambda q, k, v, t, n: pallas_paged_attention(
                q, {"k": k, "v": v}, t, n),
            sds((S, 1, H, dh)), sds((1 + S * pages, ps, H, dh)),
            sds((1 + S * pages, ps, H, dh)), sds((S, pages), i32),
            sds((S,), i32)),
        # serve-chat's shape: 32 slots of 96 pages of 8 over a 768-page
        # pool, OPT-1.3B's 32 MHA heads
        "paged_attention_serve_chat": (
            lambda q, k, v, t, n: pallas_paged_attention(
                q, {"k": k, "v": v}, t, n),
            sds((32, 1, H, dh)), sds((1 + 768, ps, H, dh)),
            sds((1 + 768, ps, H, dh)), sds((32, 96), i32), sds((32,), i32)),
        "flash_attention": (flash_attention_pallas,
                            *[sds((1, 2048, H, dh))] * 3),
        "quant4_pack": (quant4_pack_pallas, sds((2048 * 64,))),
        "quant4_unpack": (
            lambda p, s: quant4_unpack_pallas(p, s, 2048 * 64),
            sds((2048 * 32,), u8), sds((512,))),
        "lowrank_mm": (matmul_pallas, sds((2048, 8192)), sds((8192, 64))),
    }
    for m, n, r in ((2048, 8192, 64), (2048, 6144, 512)):
        nbp, nbq = m * r // 256, n * r // 256
        cases[f"fused_compress_ef_{m}x{n}_r{r}"] = (
            lambda d, e, q, rt: fused_compress_ef(d, e, q, rt),
            sds((m, n)), sds((m, n)), sds((n, r)), sds((), i32))
        cases[f"fused_decompress_{m}x{n}_r{r}"] = (
            lambda a, b, c, d, m=m, n=n, r=r: fused_decompress(
                a, b, c, d, m, n, r),
            sds((nbp * 128,), u8), sds((nbp,)), sds((nbq * 128,), u8),
            sds((nbq,)))
    return cases


KERNELS = ["paged_attention", "paged_attention_serve_chat", "flash_attention",
           "quant4_pack", "quant4_unpack", "lowrank_mm",
           "fused_compress_ef_2048x8192_r64", "fused_decompress_2048x8192_r64",
           "fused_compress_ef_2048x6144_r512",
           "fused_decompress_2048x6144_r512"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    fn, *shapes = _kernel_cases(sds)[name]
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} compiled without its Pallas kernel"


def test_depth_cut_opt_round_fits_v5e(topo):
    """The train and outer steps of ``chip_smoke.py``'s depth-cut OPT-1.3B
    round, each with the rest of the resident round state beside it, fit
    the chip with headroom."""
    from repro.core import mesh_compression as mc
    from repro.launch import steps
    from repro.models import model as M
    from repro.parallel import sharding as sh

    cs = _chip_smoke()
    cfg = dataclasses.replace(get_config("opt-1.3b"),
                              n_layers=cs.TRAIN_LAYERS)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                ("clusters", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)
    ccfg = mc.MeshCompressionConfig(rank=cs.RANK)
    ps, opt_sh, ost_sh = steps.round_shardings(cfg, mesh, 1, ccfg)
    p = steps.params_specs(cfg, n_clusters=1)
    opt = steps.opt_specs(p)
    ost = steps.outer_state_specs(cfg, 1, ccfg)
    batch = steps.input_specs(cfg, ShapeConfig("smoke", cs.SEQ_LEN,
                                               cs.TRAIN_BATCH, "train"),
                              n_clusters=1)
    rep = NamedSharding(mesh, P())
    M.set_activation_sharder(sh.make_activation_sharder(mesh))
    try:
        train = jax.jit(steps.make_train_step(cfg), donate_argnums=(0, 1),
                        in_shardings=(ps, opt_sh,
                                      sh.batch_shardings(batch, mesh,
                                                         cluster_stacked=True)),
                        out_shardings=(ps, opt_sh, rep)
                        ).lower(p, opt, batch).compile()
        outer = jax.jit(steps.make_outer_step(cfg, ccfg),
                        donate_argnums=(0, 1),
                        in_shardings=(ps, ost_sh, rep),
                        out_shardings=(ps, ost_sh)
                        ).lower(p, ost, jax.ShapeDtypeStruct((), jnp.int32)
                                ).compile()
    finally:
        M.set_activation_sharder(None)

    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))

    def peak(compiled):
        ma = compiled.memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)

    # what stays resident beside each step: the outer state during the
    # inner step, the AdamW moments during the outer step
    during_train = peak(train) + nbytes(ost)
    during_outer = peak(outer) + nbytes((opt.m, opt.v))
    for name, b in (("train", during_train), ("outer", during_outer)):
        assert b <= HEADROOM * V5E_HBM, \
            f"{name} step at {cs.TRAIN_LAYERS} layers needs " \
            f"{b / 2 ** 30:.2f} GiB of the chip's 16"


def test_phi3_medium_round_on_two_clusters_fits_v5e_2x2(topo):
    """The benchmark's Phi-3-medium round (one layer at published widths
    with embedding and head, batch 2 x 2048 per cluster) on 2 clusters x 2
    model ranks: each step with the rest of the resident round state
    beside it fits a chip with headroom, and the inner step sends nothing
    across clusters but the reported loss."""
    from repro.core import mesh_compression as mc
    from repro.launch import steps
    from repro.models import model as M
    from repro.launch.dryrun import parse_collective_bytes
    from repro.parallel import sharding as sh

    C = 2
    cfg = dataclasses.replace(get_config("phi3-medium-14b"), n_layers=1)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(C, 1, 2),
                ("clusters", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)
    ccfg = mc.MeshCompressionConfig(rank=64)
    ps, opt_sh, ost_sh = steps.round_shardings(cfg, mesh, C, ccfg)
    p = steps.params_specs(cfg, n_clusters=C)
    opt = steps.opt_specs(p)
    ost = steps.outer_state_specs(cfg, C, ccfg)
    batch = steps.input_specs(cfg, ShapeConfig("run", 2048, C * 2, "train"),
                              n_clusters=C)
    rep = NamedSharding(mesh, P())
    M.set_activation_sharder(sh.make_activation_sharder(mesh))
    try:
        train = jax.jit(steps.make_train_step(cfg), donate_argnums=(0, 1),
                        in_shardings=(ps, opt_sh,
                                      sh.batch_shardings(batch, mesh,
                                                         cluster_stacked=True)),
                        out_shardings=(ps, opt_sh, rep)
                        ).lower(p, opt, batch).compile()
        with jax.default_matmul_precision("highest"):
            outer = jax.jit(steps.make_outer_step(cfg, ccfg),
                            donate_argnums=(0, 1),
                            in_shardings=(ps, ost_sh, rep),
                            out_shardings=(ps, ost_sh)
                            ).lower(p, ost,
                                    jax.ShapeDtypeStruct((), jnp.int32)
                                    ).compile()
    finally:
        M.set_activation_sharder(None)

    def per_chip(tree, shardings):
        return sum(math.prod(s.shard_shape(x.shape)) * x.dtype.itemsize
                   for x, s in zip(jax.tree.leaves(tree),
                                   jax.tree.leaves(shardings)))

    def peak(compiled):
        ma = compiled.memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)

    # beside the inner step: the outer state; beside the outer step: the
    # AdamW moments.  They read 12.3 GB and 9.7 GB: 2.3 GB under the 14.6
    # GB (85% of 16 GiB) this holds them to, 4.9 GB under the chip's 17.2
    during_train = peak(train) + per_chip(ost, ost_sh)
    during_outer = peak(outer) + per_chip((opt.m, opt.v),
                                          (opt_sh.m, opt_sh.v))
    for name, b in (("train", during_train), ("outer", during_outer)):
        assert b <= HEADROOM * V5E_HBM, \
            f"{name} step needs {b / 2 ** 30:.2f} GiB of the chip's 16"
    # clusters are devices {0, 1} and {2, 3}.  DiLoCoX's inner steps never
    # mix clusters: the one collective across them is the f32 scalar mean
    # of the clusters' losses that the step reports
    inner = parse_collective_bytes(train.as_text(), cluster_size=2)
    assert inner["_cross_cluster_by_dtype"] == {"f32": 4}, inner
