"""Mesh compression (core.mesh_compression): numerics match the
simulator, the payload bytes match the analytic accounting, and the
dry-run's collective parser counts what the compiled exchange moves."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mesh_compression as mc
from repro.core.compression import LowRankQuant
from repro.launch import dryrun


def _tree(C=2):
    k = jax.random.PRNGKey(0)
    one = {"w": jax.random.normal(k, (3, 128, 96)) / 10,   # (units, m, n)
           "b": jax.random.normal(jax.random.fold_in(k, 1), (96,))}
    return jax.tree.map(
        lambda x: jnp.stack([x, x * 0.5]), one)            # stacked clusters


def test_compress_gather_mean_shapes_and_finiteness():
    cfg = mc.MeshCompressionConfig(rank=16, bits=4, min_dim_for_lowrank=64)
    tree = _tree()
    q = mc.init_q_state(jax.tree.map(lambda x: x[0], tree), cfg)
    q = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,) + x.shape).copy(), q)
    Delta, q2 = mc.compress_gather_mean(tree, q, jnp.asarray(16), cfg)
    for a, b in zip(jax.tree.leaves(jax.tree.map(lambda x: x[0], tree)),
                    jax.tree.leaves(Delta)):
        assert a.shape == b.shape
        assert np.isfinite(np.asarray(b)).all()


def test_mesh_compression_reduces_error_with_warm_start():
    """Repeated compression of the same low-rank matrix converges (PowerSGD
    subspace iteration), matching the simulator's behaviour."""
    cfg = mc.MeshCompressionConfig(rank=8, min_dim_for_lowrank=8)
    u = jax.random.normal(jax.random.PRNGKey(1), (128, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (8, 96))
    M = (u @ v) / 8.0
    tree = {"w": jnp.stack([M, M])}          # 2 identical clusters
    q = mc.init_q_state({"w": M}, cfg)
    q = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,) + x.shape).copy(), q)
    errs = []
    for _ in range(4):
        Delta, q = mc.compress_gather_mean(tree, q, None, cfg)
        errs.append(float(jnp.linalg.norm(Delta["w"] - M)
                          / jnp.linalg.norm(M)))
    # int4 factor quantization bounds the floor: |PQ^T - M| ~ 2 * (scale/2)
    # relative ~ 0.15-0.2 for Gaussian factors; the subspace itself locks on
    assert errs[-1] < 0.25, errs
    assert errs[-1] <= errs[0] + 0.02


def test_wire_bytes_scale_with_rank():
    cfg64 = mc.MeshCompressionConfig(rank=64)
    cfg16 = mc.MeshCompressionConfig(rank=16)
    p = {"w": jnp.zeros((4, 1024, 1024))}
    assert mc.wire_bytes_tree(p, cfg16) < mc.wire_bytes_tree(p, cfg64)
    # adaptive rank accounting
    assert mc.wire_bytes_tree(p, cfg64, rank=16) == \
        mc.wire_bytes_tree(p, cfg16, rank=16)


# lines as a TPU v5e 2 x 2 compile of the outer step prints them (2
# clusters x 2 model ranks: clusters are devices {0,1} and {2,3})
COMBINED = ("%all-reduce.31 = (f32[1,2048,256]{1,2,0:T(8,128)}, f32[512]{0}) "
            "all-reduce(%a, %b), channel_id=5, "
            "replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, "
            "to_apply=%add")
WITHIN = ("%all-reduce.47 = f32[1,5120,64]{1,2,0:T(8,128)} all-reduce(%c), "
          "channel_id=6, replica_groups={{0,1},{2,3}}, to_apply=%add")
GATHER = ("%all-gather.250 = u8[1,327680]{1,0:T(4,128)(4,1)} "
          "all-gather(%d), channel_id=7, replica_groups=[2,2]<=[4], "
          "dimensions={1}")


def test_collective_parser_counts_combined_collectives():
    combined = (2048 * 256 + 512) * 4
    out = dryrun.parse_collective_bytes(
        "\n".join([COMBINED, WITHIN, GATHER]), cluster_size=2)
    assert out["all-reduce"] == combined + 5120 * 64 * 4
    assert out["all-gather"] == 327680
    # {0,2},{1,3}: the combined all-reduce is the one that crosses
    assert out["_cross_cluster_bytes"] == combined
    assert out["_cross_cluster_by_dtype"] == {"f32": combined}


def test_collective_parser_counts_an_async_pair_once():
    start = WITHIN.replace("%all-reduce.47", "%all-reduce-start.1").replace(
        " all-reduce(", " all-reduce-start(")
    done = ("%all-reduce-done.1 = f32[1,5120,64]{1,2,0:T(8,128)} "
            "all-reduce-done(%all-reduce-start.1)")
    out = dryrun.parse_collective_bytes("\n".join([start, done]))
    assert out == {"all-reduce": 5120 * 64 * 4}


def test_collective_parser_counts_an_async_start_by_its_results():
    # an all-gather's start holds its operand, then its result; a combined
    # one holds a tuple of each; a permute's start adds two context words
    gather = ("%all-gather-start.3 = (bf16[1,2,1024,5120]{3,2,1,0:T(8,128)"
              "(2,1)}, bf16[1,2,2048,5120]{3,2,1,0:T(8,128)(2,1)}) "
              "all-gather-start(%e), channel_id=8, "
              "replica_groups=[2,2]<=[4], dimensions={2}")
    combined = ("%all-gather-start.4 = ((u8[327680]{0}, f32[1280]{0}), "
                "(u8[655360]{0}, f32[2560]{0})) all-gather-start(%f, %g), "
                "channel_id=9, replica_groups=[2,2]<=[2,2]T(1,0), "
                "dimensions={0}")
    permute = ("%collective-permute-start.1 = (f32[8,64]{1,0}, "
               "f32[8,64]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) "
               "collective-permute-start(%h), channel_id=10, "
               "source_target_pairs={{0,2},{2,0}}")
    out = dryrun.parse_collective_bytes(
        "\n".join([gather, combined, permute]), cluster_size=2)
    ag = 2 * 2048 * 5120 * 2
    assert out["all-gather"] == ag + 655360 + 2560 * 4
    assert out["collective-permute"] == 8 * 64 * 4
    assert out["_cross_cluster_by_dtype"] == {"u8": 655360,
                                              "f32": 2560 * 4 + 8 * 64 * 4}
